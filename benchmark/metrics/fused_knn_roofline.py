"""Percent of the roofline the fused brute-force kernel
(``ops/fused_topk``) reached: the ideal time of the work
``work/fused_knn.py`` counts over the kernel's summed device time in
the traced run."""


def read(w):
    return w.kernel_roofline("fused_knn")
