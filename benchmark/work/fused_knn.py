"""Work exact brute-force kNN needs, whatever implements it: the whole
corpus read once per dispatch plus the queries, and ``2 * dim`` per
(query, row) pair, with the real query count (never the bucket)."""

from __future__ import annotations

# the fused kernel's events, as a v5e trace names them (read by hand in
# PR 22): ``%_fused_knn_impl.1 = (f32[16,10]..., s32[16,10]...)
# custom-call(...), custom_call_target="tpu_custom_call"``
TRACE_PATTERNS = (
    r'%_fused_knn_impl(\.\d+)? = .*custom_call_target="tpu_custom_call"',
)


def dispatch(q: int, n: int, dim: int, itemsize: int = 4):
    """``(bytes, flops)`` of one dispatch of ``q`` queries over ``n``
    rows."""
    return n * dim * itemsize + q * dim * 4, 2 * q * n * dim


def totals(inputs: dict, dispatches) -> tuple:
    """Summed ``(bytes, flops)`` over ``dispatches`` (arrays of pool
    rows). ``inputs``: ``n``, ``dim``, ``itemsize``."""
    b = f = 0
    for rows in dispatches:
        db, df = dispatch(len(rows), inputs["n"], inputs["dim"],
                          inputs["itemsize"])
        b += db
        f += df
    return b, f
