"""Percent of the roofline the IVF list scan kernel (``ops/ivf_scan``)
reached: the ideal time of the work ``work/ivf_scan.py`` counts over
the kernel's summed device time in the traced run."""


def read(w):
    return w.kernel_roofline("ivf_scan")
