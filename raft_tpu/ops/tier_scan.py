"""Tiered (hot/cold) list-major IVF probe scan — the engine family of
:mod:`raft_tpu.neighbors.tiered` (grafttier, the billion-scale tiered
storage subsystem).

Every index so far is HBM-resident, which caps corpus size at device
memory. The tiered formulation splits the dominant plane — the packed
raw-vector tensor — in two: a **hot tier** ``hot_data[n_hot, m, d]``
stays HBM-resident and rides the exact scalar-prefetched BlockSpec
pipeline of :mod:`raft_tpu.ops.ivf_scan`, while a **cold tier**
``cold_data[n_cold, m, d]`` lives in host memory and streams through a
**double-buffered manual-DMA pipeline** (the beam_search/bq_scan
discipline: ``pltpu.make_async_copy`` from an ``ANY``-space operand
into VMEM scratch, prefetching list ``i+1``'s block while list ``i``
scores). TPU-KNN's dual-roofline methodology (PAPERS.md) is the
honest target: hot blocks should saturate HBM bandwidth, cold blocks
the host/PCIe link — and the per-step fetch plan below makes each
stream pay for exactly its own tier's bytes.

The id and norm planes (``indices``/``data_norms`` — ~2% of the bytes
at serving dims) stay fully HBM-resident: membership masking, the
shared-filter id-fold, and graftgauge's probe accounting all keep
riding the existing device path unchanged, and only the heavy vector
plane ever crosses the host link.

Per-step fetch plan (:func:`tier_fetch_plan`, computed on device from
the probed-list union): ``hot_fetch[j]`` steers the hot BlockSpec
index map — on cold steps it HOLDS the previous hot slot, so the
Pallas pipeline's unchanged-block elision skips the redundant HBM
fetch; ``cold_fetch[j]`` is the cold slot to DMA (−1 on hot and
sentinel steps); ``cold_seq[j]`` numbers the cold steps so the two
DMA buffers alternate.

Two parity-locked engines share the formulation (the ivf_scan
contract): ``pallas`` is the dual-source kernel, ``xla`` the same
math as a ``lax.scan`` selecting each block from its tier — the
portable correctness engine for CPU tier-1. Both upcast/score/merge
in exactly the order of their un-tiered ivf_scan counterparts, so a
tiered index's results are **bit-identical** to the all-HBM index per
engine (pinned in ``tests/test_tiered.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_tpu.core.chips import vmem_budget_mb
from raft_tpu.core.validation import expect
from raft_tpu.distance.types import DistanceType
from raft_tpu.ops.fused_topk import _extract_topk
from raft_tpu.ops.ivf_scan import (
    _PALLAS_MAX_K,
    _merge_smallest_id,
    degrade,
    unique_lists,
)

TIER_ENGINES = ("auto", "pallas", "xla")


def resolve_tier_engine(engine: str, *, hot_data=None, cold_data=None,
                        filter_words=None, k=None,
                        vmem_mb: int = 0) -> str:
    """Resolve a tiered ``scan_engine`` param to a concrete engine.

    ``auto`` is the dual-source Pallas kernel on TPU and the tiered
    XLA scan elsewhere. ``pallas`` degrades to ``xla`` when the
    kernel's preconditions fail: per-query (2-D) filter words (the
    id-fold trick needs one shared id plane), non-f32 storage (the
    tiered path is f32-only — the cold DMA scratch and the hot block
    must agree on layout), ``k`` past the unrolled-merge budget,
    compiled-mode layout misalignment, a VMEM budget the hot block
    + the double-buffered cold scratch cannot fit, or a cold plane
    committed to host memory (Mosaic refuses a host-memory operand:
    ``tests/test_tpu_compile.py`` compiles the kernel with the cold
    plane in HBM only)."""
    expect(engine in TIER_ENGINES,
           f"tiered scan_engine must be one of {TIER_ENGINES}, got "
           f"{engine!r}")
    if engine == "auto":
        engine = "pallas" if jax.default_backend() == "tpu" else "xla"
    if engine != "pallas":
        return engine
    if filter_words is not None and getattr(filter_words, "ndim", 1) == 2:
        return degrade("tier_scan", "per-query filter words")
    if k is not None and k > _PALLAS_MAX_K:
        return degrade("tier_scan", f"k > {_PALLAS_MAX_K}")
    if cold_data is not None and getattr(
            cold_data.sharding, "memory_kind", None) == "pinned_host":
        return degrade("tier_scan", "cold plane in host memory")
    if hot_data is not None:
        if hot_data.dtype != jnp.float32:
            return degrade("tier_scan", f"{hot_data.dtype} storage")
        m_pad = -(-hot_data.shape[1] // 8) * 8
        d_pad = -(-hot_data.shape[2] // 128) * 128
        if jax.default_backend() == "tpu" and (
                m_pad != hot_data.shape[1] or d_pad != hot_data.shape[2]):
            # compiled Mosaic would force a whole-tensor jnp.pad per
            # call; interpret mode (CPU CI) keeps the pad path so any
            # test shape is coverable — same contract as ivf_scan
            return degrade("tier_scan", "list layout not tile-aligned")
        if vmem_mb <= 0:
            vmem_mb = vmem_budget_mb()
        fixed, per_q = _tier_vmem_plan(m_pad, d_pad,
                                       k or _PALLAS_MAX_K)
        if fixed + 8 * per_q > vmem_mb << 20:
            return degrade("tier_scan",
                           "list block exceeds the VMEM budget")
    return engine


def resolve_tier_pq_engine(engine: str) -> str:
    """Resolve a tiered-PQ ``scan_engine`` param. The tiered PQ cold
    engine is the LUT union scan with the per-step dual-tier block
    select (graftcast): list-major only — the rank-major PQ scan
    gathers per (query, rank) and has no per-list fetch step to
    steer through the slot maps, so ``rank`` is rejected rather than
    silently served from the wrong tier. ``auto`` is always the XLA
    union scan (there is no Pallas PQ engine, tiered or not)."""
    expect(engine in ("auto", "xla"),
           "tiered PQ scan_engine must be 'auto' or 'xla' — the "
           "rank-major scan has no per-list fetch step to steer "
           f"through the tier slot maps, got {engine!r}")
    return "xla"


def resolve_tier_bq_engine(engine: str) -> str:
    """Resolve a tiered-BQ ``scan_engine`` param. The tiered BQ cold
    engine is the XOR+popcount estimate-then-rerank union scan with
    every per-row plane (codes/corrections/rerank vectors) selected
    from its tier per step. ``auto`` and ``pallas`` both resolve to
    ``xla`` for now: the fused BQ kernel's conditional rerank DMA
    already rides the ANY-operand discipline, but its dual-source
    (hot BlockSpec + cold DMA) variant is the on-chip follow-on
    (ROADMAP) — degrading here keeps the engine choice honest
    instead of serving cold lists from a kernel that cannot reach
    them. ``rank`` is rejected (no per-list fetch step)."""
    expect(engine in ("auto", "pallas", "xla"),
           "tiered BQ scan_engine must be 'auto', 'pallas' or 'xla' "
           f"— got {engine!r}")
    if engine == "pallas":
        return degrade("tier_bq_scan", "no dual-source BQ kernel")
    return "xla"


def tier_slot_pair(hot_slot_map, cold_slot_map, lidc):
    """One step's (hot_slot, cold_slot) pair for clamped list id
    ``lidc`` — computed ONCE per scan step and shared by every
    plane's :func:`tier_block_select`, so a multi-plane family (BQ's
    codes + corrections + rerank vectors) cannot read two planes of
    the same list from different tiers."""
    return (jnp.take(hot_slot_map, lidc),
            jnp.take(cold_slot_map, lidc))


def tier_block_select(hot_plane, cold_plane, hs, cs):
    """THE dual-tier block fetch — the one divergence every tiered
    engine has from its all-HBM twin: step ``j``'s block comes from
    its tier via the slot pair of :func:`tier_slot_pair`. ``lax.cond``
    keeps the cold branch a real conditional (only the probed tier's
    block is read — the cold stream pays for exactly its own bytes);
    the selected values are the stored rows either way, so everything
    downstream is bit-identical to the un-tiered scan. Shared by the
    tiered flat XLA engine and the graftcast PQ/BQ cold engines
    (LUT union scan / XOR+popcount estimate). The cold block moves to
    device memory explicitly: a host-committed plane may only be
    sliced, and XLA turns slice + move into one host-to-device copy
    of exactly that block."""
    return jax.lax.cond(
        cs >= 0,
        lambda: jax.device_put(jax.lax.dynamic_index_in_dim(
            cold_plane, jnp.maximum(cs, 0), 0, False),
            jax.memory.Space.Device),
        lambda: jax.lax.dynamic_index_in_dim(
            hot_plane, jnp.maximum(hs, 0), 0, False),
    )


def _tier_vmem_plan(m_pad: int, d_pad: int, k: int):
    """The tiered kernel's VMEM footprint model, shared by
    :func:`resolve_tier_engine` (the degrade decision) and
    ``_tier_scan_pallas`` (the query-tile sizing). ``fixed``: the
    double-buffered hot block + norm/id strips, PLUS the two cold DMA
    scratch buffers (the manual pipeline's landing zone), plus a
    safety margin; ``per_q``: query row + probe row + ~24 B of
    (m)-wide intermediates + the (k) running state (the ivf_scan
    arithmetic — the compute body is the same)."""
    fixed = (3 * m_pad * (d_pad * 4 + 8)
             + 2 * m_pad * d_pad * 4
             + (2 << 20))
    per_q = 4 * (d_pad + 256) + 24 * m_pad + 16 * k
    return fixed, per_q


def tier_fetch_plan(uniq: jax.Array, hot_slot_map: jax.Array,
                    cold_slot_map: jax.Array, n_lists: int):
    """Translate the probed-list union into the per-step dual-tier
    fetch plan (device-side — the slot maps are tiny resident int32
    planes). Returns ``(hot_fetch, cold_fetch, cold_seq)``, each
    ``(n_steps,)`` int32:

    - ``hot_fetch[j]``: hot slot whose block the BlockSpec index map
      streams at step j. On cold and sentinel steps it HOLDS the most
      recent hot slot (leading steps clamp to 0), so consecutive
      same-index steps let the Pallas pipeline elide the copy — a
      cold step costs no HBM block traffic.
    - ``cold_fetch[j]``: cold slot to DMA at step j, or −1 on
      hot/sentinel steps.
    - ``cold_seq[j]``: exclusive running count of cold steps before
      j — the double-buffer slot is ``cold_seq % 2``.
    """
    lidc = jnp.minimum(uniq, n_lists - 1)
    hot_raw = jnp.where(uniq < n_lists,
                        jnp.take(hot_slot_map, lidc), -1)
    cold_raw = jnp.where(uniq < n_lists,
                         jnp.take(cold_slot_map, lidc), -1)
    # carry the last hot slot forward across cold/sentinel steps
    # (f(a, b) = b if b >= 0 else a — associative, so one log-depth
    # scan instead of a sequential loop)
    carried = jax.lax.associative_scan(
        lambda a, b: jnp.where(b >= 0, b, a), hot_raw)
    hot_fetch = jnp.maximum(carried, 0)
    is_cold = (cold_raw >= 0).astype(jnp.int32)
    cold_seq = jnp.cumsum(is_cold) - is_cold
    return hot_fetch, cold_raw, cold_seq


def tiered_list_major_scan(qf, hot_data, cold_data, hot_slot_map,
                           cold_slot_map, data_norms, indices, probes,
                           filter_words=None, init_d=None, init_i=None,
                           *, k: int, metric: DistanceType,
                           engine: str = "xla",
                           interpret: bool = False):
    """Run the probe scan over a tiered index; returns the pre-epilog
    running top-k ``(best_d, best_i)`` in the ivf_scan convention
    (min-space ``norms − 2 x·y`` for L2 with +inf pads; raw inner
    products for IP with −inf pads), so the caller's metric epilog is
    shared with the un-tiered engines.

    ``hot_data``/``cold_data`` are the split vector planes;
    ``hot_slot_map``/``cold_slot_map`` the (n_lists,) int32 slot
    translation (−1 where a list lives in the other tier — every list
    is in exactly one); ``data_norms``/``indices`` the FULL resident
    planes, indexed by list id exactly like the un-tiered engines.
    Both engines break distance ties by smallest dataset id (the
    ``_extract_topk`` order) and score each block with the same
    shapes and op order as their ivf_scan counterparts, so results
    are bit-identical to the all-HBM index per engine. Probe slots
    carrying the sentinel value ``n_lists`` are masked probes and
    contribute nothing."""
    expect(engine in ("pallas", "xla"),
           f"tiered_list_major_scan engine must be pallas|xla, got "
           f"{engine!r}")
    if engine == "pallas":
        return _tier_scan_pallas(
            qf, hot_data, cold_data, hot_slot_map, cold_slot_map,
            data_norms, indices, probes, filter_words, k=k,
            metric=metric, interpret=interpret)
    return _tier_scan_xla(
        qf, hot_data, cold_data, hot_slot_map, cold_slot_map,
        data_norms, indices, probes, filter_words, init_d, init_i,
        k=k, metric=metric)


# ---------------------------------------------------------------------------
# XLA tiered engine — the portable parity reference
# ---------------------------------------------------------------------------


def _tier_scan_xla(qf, hot_data, cold_data, hot_slot_map, cold_slot_map,
                   data_norms, indices, probes, filter_words,
                   init_d=None, init_i=None, *, k: int,
                   metric: DistanceType):
    from raft_tpu.neighbors.filters import test_filter

    q = qf.shape[0]
    n_lists = indices.shape[0]
    ip_metric = metric == DistanceType.InnerProduct
    uniq = unique_lists(probes, n_lists)

    def step(carry, lid):
        best_d, best_i = carry
        lidc = jnp.minimum(lid, n_lists - 1)      # sentinel-safe index
        hs, cs = tier_slot_pair(hot_slot_map, cold_slot_map, lidc)
        # the ONE tiered divergence from ivf_scan's _scan_xla: the
        # block comes from its tier (see tier_block_select).
        rows = tier_block_select(hot_data, cold_data, hs,
                                 cs).astype(jnp.float32)       # (m, d)
        row_ids = jax.lax.dynamic_index_in_dim(indices, lidc, 0, False)
        ip = jax.lax.dot_general(
            qf, rows, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )                                                      # (q, m)
        if ip_metric:
            dist = -ip
        else:
            row_norms = jax.lax.dynamic_index_in_dim(
                data_norms, lidc, 0, False)
            dist = row_norms[None, :] - 2.0 * ip
        ids_b = jnp.broadcast_to(row_ids[None, :], dist.shape)
        probed = jnp.any(probes == lid, axis=1) & (lid < n_lists)
        ok = (ids_b >= 0) & probed[:, None]
        if filter_words is not None:
            ok = ok & test_filter(filter_words, ids_b)
        dist = jnp.where(ok, dist, jnp.inf)
        return _merge_smallest_id(best_d, best_i, dist, ids_b, k), None

    init = (
        jnp.full((q, k), jnp.inf, jnp.float32) if init_d is None
        else jnp.full_like(init_d, jnp.inf),
        jnp.full((q, k), -1, jnp.int32) if init_i is None
        else jnp.full_like(init_i, -1),
    )
    (best_d, best_i), _ = jax.lax.scan(step, init, uniq)
    if ip_metric:
        best_d = -best_d          # inf (unfilled) -> -inf, ip exact
    return best_d, best_i


# ---------------------------------------------------------------------------
# Pallas tiered engine — hot BlockSpec pipeline + cold manual-DMA pipeline
# ---------------------------------------------------------------------------


def _cold_dma(cold_ref, cbuf, sem, cslot, slot):
    """The (described, not yet started) async copy of cold block
    ``cslot`` into double-buffer ``slot``. The buffer index is
    resolved STATICALLY under two ``pl.when`` branches by the caller
    — semaphore and scratch slices stay compile-time constants."""
    return pltpu.make_async_copy(
        cold_ref.at[pl.ds(cslot, 1)], cbuf.at[pl.ds(slot, 1)],
        sem.at[slot])


def _tier_scan_kernel(u_ref, hf_ref, cf_ref, cs_ref, probes_ref, q_ref,
                      x_ref, xn_ref, ids_ref, cold_ref, outd_ref,
                      outi_ref, bestd, besti, cbuf, sem, *, k: int,
                      n_steps: int, n_lists: int, ip_metric: bool):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        bestd[:] = jnp.full_like(bestd, jnp.inf)
        besti[:] = jnp.full_like(besti, -1)

    lid = u_ref[j]                        # scalar-prefetched list id
    cslot = cf_ref[j]                     # cold slot, or -1 on hot steps
    is_cold = cslot >= 0
    slot = cs_ref[j] % 2                  # this step's double-buffer slot

    # warm-up: the first step of each query tile must fetch its own
    # cold block — there was no previous step to prefetch it
    @pl.when((j == 0) & is_cold)
    def _():
        for s in (0, 1):
            @pl.when(slot == s)
            def _(s=s):
                _cold_dma(cold_ref, cbuf, sem,
                          jnp.maximum(cslot, 0), s).start()

    # prefetch the NEXT step's cold block while this step scores —
    # the double-buffer discipline: its landing slot is the one this
    # step is NOT reading, and every started copy is waited exactly
    # once (at its own step, below)
    nxt = jnp.minimum(j + 1, n_steps - 1)
    nxt_cold = cf_ref[nxt]
    nxt_slot = cs_ref[nxt] % 2

    @pl.when((j + 1 < n_steps) & (nxt_cold >= 0))
    def _():
        for s in (0, 1):
            @pl.when(nxt_slot == s)
            def _(s=s):
                _cold_dma(cold_ref, cbuf, sem,
                          jnp.maximum(nxt_cold, 0), s).start()

    # wait for this step's cold block (started at step j-1, or just
    # above when j == 0)
    @pl.when(is_cold)
    def _():
        for s in (0, 1):
            @pl.when(slot == s)
            def _(s=s):
                _cold_dma(cold_ref, cbuf, sem,
                          jnp.maximum(cslot, 0), s).wait()

    # block source select: the hot BlockSpec block (hf held the
    # previous hot slot on cold steps, so the pipeline elided its
    # copy) or the cold DMA landing buffer. Both are f32 VMEM reads;
    # the selected values are the stored rows either way, so the
    # contraction below is bit-identical to _ivf_scan_kernel's.
    cold_blk = jnp.where(slot == 0, cbuf[0], cbuf[1])      # (m, d)
    xt = jnp.where(is_cold, cold_blk, x_ref[0])
    ip = jax.lax.dot_general(
        q_ref[:], xt, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )                                     # (q_tile, m)
    dist = -ip if ip_metric else xn_ref[0] - 2.0 * ip
    ids = ids_ref[0]                      # (1, m) — -1 marks pad/filtered
    probed = jnp.any(probes_ref[:] == lid, axis=1, keepdims=True)
    probed = jnp.logical_and(probed, lid < n_lists)
    dist = jnp.where((ids >= 0) & probed, dist, jnp.inf)

    kth = bestd[:, k - 1 : k]
    any_better = jnp.any(dist < kth)

    @pl.when(any_better)
    def _():
        cat_d = jnp.concatenate([bestd[:], dist], axis=1)
        cat_i = jnp.concatenate(
            [besti[:], jnp.broadcast_to(ids, dist.shape)], axis=1)
        new_d, new_i = _extract_topk(cat_d, cat_i, k)
        bestd[:] = new_d
        besti[:] = new_i

    @pl.when(j == n_steps - 1)
    def _():
        outd_ref[:] = -bestd[:] if ip_metric else bestd[:]
        outi_ref[:] = besti[:]


def _tier_scan_pallas(qf, hot_data, cold_data, hot_slot_map,
                      cold_slot_map, data_norms, indices, probes,
                      filter_words, *, k: int, metric: DistanceType,
                      interpret: bool, vmem_mb: int = 0):
    from raft_tpu.neighbors.filters import test_filter

    q, d = qf.shape
    n_lists = indices.shape[0]
    m = hot_data.shape[1]
    ip_metric = metric == DistanceType.InnerProduct
    if vmem_mb <= 0:
        vmem_mb = vmem_budget_mb()
    expect(hot_data.dtype == jnp.float32
           and cold_data.dtype == jnp.float32,
           "the tiered Pallas engine is f32-only — use engine='xla' "
           "for other storage dtypes")
    expect(filter_words is None
           or getattr(filter_words, "ndim", 1) == 1,
           "the tiered Pallas engine supports shared (1-D) filters "
           "only — use engine='xla' for per-query filter words")

    uniq = unique_lists(probes, n_lists)
    n_steps = uniq.shape[0]
    hot_fetch, cold_fetch, cold_seq = tier_fetch_plan(
        uniq, hot_slot_map, cold_slot_map, n_lists)

    # gathered id planes + shared-filter fold, exactly like ivf_scan
    # (the id/norm planes are fully resident, so the fold never
    # touches the cold tier)
    uc = jnp.minimum(uniq, n_lists - 1)
    ids_g = jnp.take(indices, uc, axis=0)
    xn_g = jnp.take(data_norms, uc, axis=0)
    if filter_words is not None:
        bits = test_filter(filter_words, ids_g)
        ids_g = jnp.where(bits & (ids_g >= 0), ids_g, -1)

    # lane/sublane alignment; no-ops on aligned serving layouts
    # (resolve_tier_engine degrades misaligned compiled runs — the
    # pad path is interpret mode's any-test-shape coverage)
    m_pad = -(-m // 8) * 8
    d_pad = -(-d // 128) * 128
    if m_pad != m or d_pad != d:
        hot_data = jnp.pad(hot_data,
                           ((0, 0), (0, m_pad - m), (0, d_pad - d)))
        cold_data = jnp.pad(cold_data,
                            ((0, 0), (0, m_pad - m), (0, d_pad - d)))
        xn_g = jnp.pad(xn_g, ((0, 0), (0, m_pad - m)),
                       constant_values=jnp.inf)
        ids_g = jnp.pad(ids_g, ((0, 0), (0, m_pad - m)),
                        constant_values=-1)
    # a unit middle axis: Mosaic wants a block's last two dims divisible
    # by (8, 128) or equal to the array's, and one list row is (1, m)
    xn_g = xn_g[:, None, :]
    ids_g = ids_g[:, None, :]
    p = probes.shape[1]
    p_pad = -(-p // 128) * 128

    fixed, per_q = _tier_vmem_plan(m_pad, d_pad, k)
    budget = (vmem_mb << 20) - fixed
    q_tile = min(max(8, (budget // per_q) // 8 * 8), -(-q // 8) * 8)
    q_pad = -(-q // q_tile) * q_tile

    qs = jnp.pad(qf.astype(jnp.float32),
                 ((0, q_pad - q), (0, d_pad - d)))
    probes_p = jnp.pad(probes.astype(jnp.int32),
                       ((0, q_pad - q), (0, p_pad - p)),
                       constant_values=-1)

    kernel = functools.partial(_tier_scan_kernel, k=k, n_steps=n_steps,
                               n_lists=n_lists, ip_metric=ip_metric)
    hot_clamp = max(hot_data.shape[0] - 1, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(q_pad // q_tile, n_steps),
        in_specs=[
            pl.BlockSpec((q_tile, p_pad),
                         lambda i, j, u, hf, cf, cs: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((q_tile, d_pad),
                         lambda i, j, u, hf, cf, cs: (i, 0),
                         memory_space=pltpu.VMEM),
            # the hot tier rides the scalar-prefetched dynamic index
            # map: step j streams hot slot hf[j]; cold steps HOLD the
            # previous value, so the pipeline elides their copy
            pl.BlockSpec((1, m_pad, d_pad),
                         lambda i, j, u, hf, cf, cs: (
                             jnp.minimum(hf[j], hot_clamp), 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, m_pad),
                         lambda i, j, u, hf, cf, cs: (j, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, m_pad),
                         lambda i, j, u, hf, cf, cs: (j, 0, 0),
                         memory_space=pltpu.VMEM),
            # the cold tier stays put (host memory on TPU): the
            # kernel DMAs one list block at a time into the
            # double-buffered VMEM scratch — the only reads the host
            # link ever serves are probed cold blocks
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=(
            pl.BlockSpec((q_tile, k),
                         lambda i, j, u, hf, cf, cs: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((q_tile, k),
                         lambda i, j, u, hf, cf, cs: (i, 0),
                         memory_space=pltpu.VMEM),
        ),
        scratch_shapes=[
            pltpu.VMEM((q_tile, k), jnp.float32),
            pltpu.VMEM((q_tile, k), jnp.int32),
            pltpu.VMEM((2, m_pad, d_pad), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    outd, outi = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((q_pad, k), jnp.float32),
            jax.ShapeDtypeStruct((q_pad, k), jnp.int32),
        ),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_mb << 20),
        interpret=interpret,
    )(uniq, hot_fetch, cold_fetch, cold_seq, probes_p, qs, hot_data,
      xn_g, ids_g, cold_data)
    return outd[:q], outi[:q]
