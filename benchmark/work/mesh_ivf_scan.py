"""Work the list-sharded IVF-Flat probe scan over byte lists needs,
whatever implements it: ``work/ivf_scan.py``'s counting with
``itemsize`` 1 (the adapter's ``work_inputs``). Bytes: the rows stored
in each dispatch's distinct probed lists times ``dim + 4`` (the byte
vector and its float32 norm), plus the float32 queries. Operations:
``2 * dim`` per probed (query, row) pair. Summed over the chips: each
list lives on one chip, so the total is what the four chips read and
compute together."""

from __future__ import annotations

from benchmark.work import ivf_scan

# the byte list scan's events, as the v5e compiler names them in the
# served rt_dist_ivf_flat_<digest> executable (read from its compiled
# HLO for a described v5e:2x2): the Pallas call inside the shard_map
# body's "scan" scope, one event per chip per dispatch, with the uint8
# (or int8) list plane among its operands, e.g.
# ``%scan.1 = (f32[1032,10]..., s32[1032,10]...) custom-call(...,
# u8[8192,6208,128]{2,1,0:T(8,128)(4,1)} %param.6, ...),
# custom_call_target="tpu_custom_call"``
TRACE_PATTERNS = (
    r'%scan(\.\d+)? = .*custom-call\(.*[su]8\[\d+,\d+,\d+\].*'
    r'custom_call_target="tpu_custom_call"',
)


def totals(inputs: dict, dispatches) -> tuple:
    """Summed ``(bytes, flops)`` over ``dispatches`` (arrays of pool
    rows); ``inputs`` as ``work/ivf_scan.py`` takes them."""
    return ivf_scan.totals(inputs, dispatches)
