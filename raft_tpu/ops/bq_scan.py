"""Fused estimate-then-rerank BQ probe scan — the list-major engine
family of :mod:`raft_tpu.neighbors.ivf_bq` (IVF-RaBitQ, PAPERS.md
arXiv 2602.23999, in the :mod:`raft_tpu.ops.ivf_scan` formulation).

The estimate-only BQ search pays twice: a calibrated over-fetch
multiplies the candidate traffic, and the exact re-rank is a SECOND
pass over rows the estimate pass just touched. The TPU-KNN roofline
methodology (PAPERS.md) says a bandwidth-bound scan that reads its
data twice is leaving half the machine idle — so this module fuses
the two stages into ONE list-major stream:

- grid over the probed-list union (:func:`raft_tpu.ops.ivf_scan
  .unique_lists` — the scalar-prefetched block index map of Ragged
  Paged Attention steering each step's HBM→VMEM DMA);
- **estimate** the whole query tile against the block's packed sign
  words by XOR+popcount: the rotated query quantizes to
  ``_QUERY_BITS`` uniform levels per (query, list), its bit-planes
  pack into int32 lane words, and each plane scores against the code
  words as ``⟨u_j, s⟩ = popcount(c) − popcount(u_j XOR c)`` — integer
  VPU work on 1/32nd the bytes of the raw vectors;
- **prune** with the RaBitQ error bound: a row whose estimate minus
  :func:`raft_tpu.neighbors.ivf_bq.estimator_margin` cannot beat the
  running k-th *exact* distance is finished — its raw vector is never
  read;
- **re-rank** the survivors against the raw-vector plane of the SAME
  list, DMA'd into VMEM scratch *only when the block has survivors*
  (``pl.when`` + manual async copy): one exact f32 MXU GEMM, merged
  into the VMEM running top-k via the ``_extract_topk`` network.

Each probed block therefore costs one stream of codes + corrections
(+ the raw vectors only when it still holds candidates) instead of a
full estimate pass plus a full gather-refine pass. The running top-k
warms itself: the first blocks re-rank everything, later blocks prune
almost everything.

Two parity-locked engines share the formulation (the ivf_scan
contract): ``pallas`` is the fused kernel, ``xla`` the same math as a
``lax.scan`` over the union (reads every block's vectors — the
portable correctness engine for CPU tier-1 and interpret-mode
coverage). Both use identical integer estimate math and identical
f32 assembly order, so their output ids are bit-identical."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_tpu.core.chips import vmem_budget_mb
from raft_tpu.core.validation import expect
from raft_tpu.distance.types import DistanceType
from raft_tpu.neighbors.ivf_bq import estimator_margin
from raft_tpu.ops.fused_topk import _extract_topk
from raft_tpu.ops.ivf_scan import (
    _PALLAS_MAX_K,
    SCAN_ENGINES,
    _merge_smallest_id,
    degrade,
    unique_lists,
)

# uniform quantization levels of the rotated query inside the scan
# (RaBitQ's asymmetric query treatment): 4 bits keeps the
# quantization-noise term of the margin well under the rotation term
_QUERY_BITS = 4


def auto_query_bits(bits: int) -> int:
    """Query quantization width matched to the code-ladder width.

    At ``bits < 3`` the 4-bit query grid's noise term is already well under
    the code's own quantization error; at 3+ code bits the code estimate is
    sharp enough that the query grid becomes the dominant noise source, so
    widen it to 8 bits (the widest grid the i32 cross-term accumulators
    admit without overflow headroom changes).
    """
    return 4 if bits < 3 else 8


def resolve_bq_engine(engine: str, *, data=None, filter_words=None,
                      k=None, dim_ext: int = 0, bits: int = 1,
                      n_probes: int = 0, vmem_mb: int = 0) -> str:
    """Resolve an ivf_bq ``scan_engine`` param to a concrete engine.

    ``auto`` is the fused Pallas kernel on TPU and the fused XLA scan
    elsewhere — *when the index carries the raw-vector rerank plane*
    (``data``); a codes-only index (streaming build) always runs the
    legacy rank-major estimate scan. ``pallas`` degrades to ``xla``
    when the kernel's preconditions fail: per-query (2-D) filter words
    (the id-fold trick needs one shared id plane), non-f32 vector
    storage (the exact-rerank contract), ``k`` past the
    unrolled-merge budget, compiled-mode layout misalignment, or a
    VMEM budget the resident block + vector scratch cannot fit."""
    expect(engine in SCAN_ENGINES,
           f"scan_engine must be one of {SCAN_ENGINES}, got {engine!r}")
    if engine == "auto":
        engine = "pallas" if jax.default_backend() == "tpu" else "xla"
    if engine == "rank":
        return engine
    if data is None:
        # no rerank plane — the fused engines have nothing to re-rank
        return "rank"
    if engine != "pallas":
        return engine
    if filter_words is not None and getattr(filter_words, "ndim", 1) == 2:
        return degrade("bq_scan", "per-query filter words")
    if k is not None and k > _PALLAS_MAX_K:
        return degrade("bq_scan", f"k > {_PALLAS_MAX_K}")
    if data.dtype != jnp.float32:
        return degrade("bq_scan", f"{data.dtype} rerank vectors")
    m_pad = -(-data.shape[1] // 8) * 8
    d_pad = -(-data.shape[2] // 128) * 128
    de_pad = -(-max(dim_ext, 1) // 128) * 128
    if jax.default_backend() == "tpu" and (
            m_pad != data.shape[1] or d_pad != data.shape[2]
            or de_pad != dim_ext):
        # compiled Mosaic would force a whole-tensor jnp.pad per call —
        # a full HBM read+write dwarfing the scan. Interpret mode (CPU
        # CI) keeps the pad path so any test shape is coverable.
        return degrade("bq_scan", "list layout not tile-aligned")
    if vmem_mb <= 0:
        vmem_mb = vmem_budget_mb()
    # THE kernel's own budget arithmetic (shared helper): the
    # double-buffered code/correction blocks + the raw-vector scratch
    # + margin must leave room for at least one minimal (8-row) query
    # tile. The probe-row term uses the kernel's p_pad when the caller
    # says n_probes (256 covers the unknown case only up to that
    # width).
    p_pad = -(-max(n_probes, 1) // 128) * 128 if n_probes else 256
    fixed, per_q = _vmem_plan(
        m_pad, d_pad, de_pad, p_pad, bits * max(dim_ext, 32) // 32,
        bits, k or _PALLAS_MAX_K)
    if fixed + 8 * per_q > vmem_mb << 20:
        return degrade("bq_scan", "list block exceeds the VMEM budget")
    return engine


def _vmem_plan(m_pad: int, d_pad: int, de_pad: int, p_pad: int,
               words: int, bits: int, k: int):
    """The fused kernel's VMEM footprint model — ONE implementation
    shared by :func:`resolve_bq_engine` (the degrade decision) and
    ``_bq_scan_pallas`` (the query-tile sizing), so the two can never
    drift apart. ``fixed``: double-buffered code/correction blocks +
    the raw-vector scratch + a safety margin; ``per_q``: per query
    row the kernel keeps the rotated+raw query rows, the probe row,
    ~8 (m)-wide f32/int32 intermediates (est, margin, cand,
    xor/popcount planes, exact, merge concat) and the (k) running
    state."""
    fixed = (4 * m_pad * d_pad
             + 3 * m_pad * (4 * words + 4 * (bits + 3))
             + (2 << 20))
    per_q = 4 * (de_pad + d_pad + p_pad) + 32 * m_pad + 16 * k
    return fixed, per_q


def _popcount32(v):
    """Element-wise population count of int32 lanes by the SWAR ladder
    — add/shift/and only, so it lowers on the VPU and in every XLA
    backend identically (``lax.population_count`` has no Mosaic
    lowering guarantee)."""
    v = v - ((v >> 1) & 0x55555555)
    v = (v & 0x33333333) + ((v >> 2) & 0x33333333)
    v = (v + (v >> 4)) & 0x0F0F0F0F
    # byte-sum via multiply; counts ≤ 32 keep the sign bit clear
    return (v * 0x01010101) >> 24


def _estimate_block(qt, codes_wb, rnorm_row, cfac_t, *, dim_ext: int,
                    bits: int, query_bits: int):
    """Popcount estimate of the cross term ``Σ_l a_l·⟨q̃, s_l⟩`` for
    one list block — THE shared math of both engines (one function ⇒
    identical op order ⇒ bit-identical estimates, hence identical
    prune decisions).

    ``qt`` (q, ≥dim_ext) is the rotated query side (``q̃ = Rq − Rc``
    for L2, ``Rq`` for IP; lanes past ``dim_ext`` are padding and are
    masked). ``codes_wb`` (m, bits·W) are the block's packed sign
    words, ``rnorm_row`` (1, m) and ``cfac_t`` (bits, m) the
    correction factors. Returns ``(cross (q, m) f32, delta (q, 1))``
    — ``delta`` is the query-quantization step the margin prices.

    Math: with ``q̃_i = lo + Δ·u_i + ε_i`` (uniform levels) and sign
    words ``s``: ``⟨q̃, s⟩ = Δ·⟨u, s⟩ + lo·Σs + ⟨ε, s⟩`` where
    ``⟨u, s⟩ = Σ_j 2^j·(popcount(c) − popcount(u_j XOR c))`` summed
    over lane words and ``Σs = 2·popcount(c) − D`` — exact integers;
    only the ``⟨ε, s⟩`` rounding noise survives into the margin."""
    w_cnt = dim_ext // 32
    lane = jax.lax.broadcasted_iota(jnp.int32, qt.shape, 1)
    inb = lane < dim_ext
    lo = jnp.min(jnp.where(inb, qt, jnp.inf), axis=1, keepdims=True)
    hi = jnp.max(jnp.where(inb, qt, -jnp.inf), axis=1, keepdims=True)
    levels = (1 << query_bits) - 1
    delta = jnp.maximum((hi - lo) / levels, 1e-30)
    u = jnp.round((qt - lo) / delta).astype(jnp.int32)
    u = jnp.clip(jnp.where(inb, u, 0), 0, levels)
    word = lane // 32
    shift = lane - word * 32
    # packed query bit-planes: one int32 lane word per (plane, word)
    uw = []
    for jbit in range(query_bits):
        sh = ((u >> jbit) & 1) << shift
        uw.append([jnp.sum(jnp.where(word == w, sh, 0), axis=1,
                           keepdims=True, dtype=jnp.int32)
                   for w in range(w_cnt)])
    m = codes_wb.shape[0]
    ct = jnp.transpose(codes_wb)                  # (bits·W, m)
    cross = jnp.zeros((qt.shape[0], m), jnp.float32)
    for lev in range(bits):
        pcc = jnp.zeros((1, m), jnp.int32)
        for w in range(w_cnt):
            pcc = pcc + _popcount32(
                ct[lev * w_cnt + w : lev * w_cnt + w + 1, :])
        ius = jnp.zeros((qt.shape[0], m), jnp.int32)
        for jbit in range(query_bits):
            acc = jnp.zeros((qt.shape[0], m), jnp.int32)
            for w in range(w_cnt):
                cw = ct[lev * w_cnt + w : lev * w_cnt + w + 1, :]
                acc = acc + _popcount32(
                    jnp.bitwise_xor(uw[jbit][w], cw))
            ius = ius + ((pcc - acc) << jbit)
        ssum = (2 * pcc - dim_ext).astype(jnp.float32)
        qs = delta * ius.astype(jnp.float32) + lo * ssum
        a = rnorm_row * cfac_t[lev : lev + 1, :]
        cross = cross + a * qs
    return cross, delta


def _block_estimate(qrot, crot, rnorm_row, errw_row, cfac_t, codes_wb,
                    *, dim_ext: int, bits: int, query_bits: int,
                    epsilon: float, ip_metric: bool):
    """Min-space estimate + margin for one block, shared by both
    engines. ``crot`` is the (1, D) rotated center row. Returns
    ``(est (q, m), margin (q, m))``."""
    if ip_metric:
        qt = qrot
        base_ip = jnp.sum(qrot * crot, axis=1, keepdims=True)  # ⟨q, c⟩
    else:
        qt = qrot - crot
    cross, delta = _estimate_block(qt, codes_wb, rnorm_row, cfac_t,
                                   dim_ext=dim_ext, bits=bits,
                                   query_bits=query_bits)
    lane = jax.lax.broadcasted_iota(jnp.int32, qt.shape, 1)
    qc2 = jnp.sum(jnp.where(lane < dim_ext, jnp.square(qt), 0.0),
                  axis=1, keepdims=True)
    qcn = jnp.sqrt(qc2)
    if ip_metric:
        est = -(base_ip + cross)
    else:
        rn2 = jnp.square(rnorm_row)
        est = jnp.maximum(qc2, 0.0) + rn2 - 2.0 * cross
    margin = estimator_margin(qcn, rnorm_row, errw_row, delta,
                              dim_ext, epsilon)
    return est, margin


def bq_record_geometry(words: int, bits: int):
    """Row geometry of the packed per-row BQ record plane used by the
    graph-traversal estimator (:mod:`raft_tpu.ops.beam_search`).

    A record is one dataset row's complete estimator input laid out
    contiguously so a beam gather touches ONE aligned slice per
    candidate instead of four strided planes: ``words`` int32 code
    words, then ``rnorm | cfac[bits] | errw`` as f32 bitcast to int32
    lanes. Records pad to a 4-lane multiple (``rec_pad``) and
    ``rpt = 128/gcd(rec_pad, 128)`` records tile one 128-lane-aligned
    plane row of ``pw`` lanes — every record starts on a lane boundary
    a DMA slice can address. Returns ``(rec, rec_pad, rpt, pw)``."""
    rec = words + bits + 2
    rec_pad = -(-rec // 4) * 4
    rpt = 128 // math.gcd(rec_pad, 128)
    return rec, rec_pad, rpt, rpt * rec_pad


def pack_bq_records(codes, rnorm, cfac, errw):
    """Pack per-row estimator inputs into the aligned record plane of
    :func:`bq_record_geometry` — ``(ceil(n/rpt), rpt·rec_pad)`` int32.
    Pad rows are all-zero; a zero record decodes to rnorm = 0 codes,
    which estimate-survives nothing once the candidate mask (ids ≥ 0)
    is applied, so padding never needs a side channel."""
    n, words = codes.shape
    bits = cfac.shape[1]
    _, rec_pad, rpt, _ = bq_record_geometry(words, bits)
    scal = jnp.concatenate(
        [rnorm[:, None], cfac, errw[:, None]], axis=1).astype(jnp.float32)
    row = jnp.concatenate(
        [codes.astype(jnp.int32),
         jax.lax.bitcast_convert_type(scal, jnp.int32)], axis=1)
    n_pad = -(-n // rpt) * rpt
    row = jnp.pad(row, ((0, n_pad - n), (0, rec_pad - row.shape[1])))
    return row.reshape(n_pad // rpt, rpt * rec_pad)


def unpack_bq_records(records, n: int, words: int, bits: int):
    """Exact inverse of :func:`pack_bq_records` — returns
    ``(codes (n, words) i32, rnorm (n,), cfac (n, bits), errw (n,))``.
    The XLA beam twin unpacks the SAME plane the kernel gathers from,
    so both engines estimate from identical bit patterns."""
    _, rec_pad, _, _ = bq_record_geometry(words, bits)
    rows = records.reshape(-1, rec_pad)[:n]
    codes = rows[:, :words]
    scal = jax.lax.bitcast_convert_type(
        rows[:, words:words + bits + 2], jnp.float32)
    return codes, scal[:, 0], scal[:, 1:1 + bits], scal[:, 1 + bits]


def bq_list_major_scan(qf, qrot, centers_rot, codes, rnorm, cfac, errw,
                       indices, data, data_norms, probes,
                       filter_words=None, init_d=None, init_i=None,
                       cold_planes=None, hot_slot_map=None,
                       cold_slot_map=None, *,
                       k: int, metric: DistanceType, epsilon: float,
                       engine: str = "xla", query_bits: int = _QUERY_BITS,
                       interpret: bool = False):
    """Run the fused estimate-then-rerank scan; returns the running
    top-k ``(best_d, best_i)`` with **exact** distances (full squared
    L2 with +inf pads, raw inner products with -inf pads for IP — the
    caller's metric epilog only handles the sqrt family).

    Both engines break distance ties by smallest dataset id (the
    ``_extract_topk`` order) and share one estimate/margin/prune code
    path, so their output ids are bit-identical. ``init_d``/``init_i``
    optionally provide the (q, k) running-state storage for the XLA
    engine (values are reset; the serving path donates them); the
    Pallas kernel keeps its state in VMEM scratch and ignores them.

    Probe slots carrying the sentinel value ``n_lists`` are masked
    probes (ragged rows, shard-unowned lists); both engines ignore
    them through the shared membership predicate.

    ``cold_planes`` (graftcast — the tiered BQ cold engine)
    optionally provides the cold halves of the five per-row record
    planes as ``(cold_codes, cold_rnorm, cold_cfac, cold_errw,
    cold_data)``; ``codes``/``rnorm``/``cfac``/``errw``/``data`` are
    then the HOT halves and each step selects every plane of its
    list from ONE tier via the shared
    ``(hot_slot_map, cold_slot_map)`` pair (:func:`raft_tpu.ops
    .tier_scan.tier_slot_pair` — one slot decision per step, so the
    estimate and its rerank rows can never split across tiers). XLA
    engine only: the dual-source fused kernel is the on-chip
    follow-on (``resolve_tier_bq_engine`` degrades)."""
    expect(engine in ("pallas", "xla"),
           f"bq_list_major_scan engine must be pallas|xla, got "
           f"{engine!r}")
    expect(data is not None and data_norms is not None,
           "fused BQ scan needs the raw-vector rerank plane "
           "(build with store_vectors=True)")
    if engine == "pallas":
        expect(cold_planes is None,
               "the fused BQ Pallas kernel has no dual-tier source "
               "yet — tiered BQ resolves to engine='xla' "
               "(resolve_tier_bq_engine)")
        return _bq_scan_pallas(
            qf, qrot, centers_rot, codes, rnorm, cfac, errw, indices,
            data, data_norms, probes, filter_words, k=k, metric=metric,
            epsilon=epsilon, query_bits=query_bits, interpret=interpret)
    return _bq_scan_xla(
        qf, qrot, centers_rot, codes, rnorm, cfac, errw, indices, data,
        data_norms, probes, filter_words, init_d, init_i,
        cold_planes=cold_planes, hot_slot_map=hot_slot_map,
        cold_slot_map=cold_slot_map, k=k,
        metric=metric, epsilon=epsilon, query_bits=query_bits)


# ---------------------------------------------------------------------------
# XLA engine — the portable parity reference
# ---------------------------------------------------------------------------


def _bq_scan_xla(qf, qrot, centers_rot, codes, rnorm, cfac, errw,
                 indices, data, data_norms, probes, filter_words,
                 init_d=None, init_i=None, cold_planes=None,
                 hot_slot_map=None, cold_slot_map=None, *, k: int,
                 metric: DistanceType, epsilon: float, query_bits: int):
    from raft_tpu.neighbors.filters import test_filter

    q, d = qf.shape
    # with a tiered record plane, codes.shape[0] is the HOT slot
    # count, not the list count — the resident id plane is the
    # authority (it is never tiered: ids gather per unique list)
    n_lists = indices.shape[0]
    tiered = cold_planes is not None
    if tiered:
        cold_codes, cold_rnorm, cold_cfac, cold_errw, cold_data = \
            cold_planes
    dim_ext = centers_rot.shape[1]
    bits = cfac.shape[2]
    ip_metric = metric == DistanceType.InnerProduct
    # OFF-TPU ONLY: pad the contraction dims to the SAME lane
    # multiples the Pallas kernel uses, so both engines run
    # identically-shaped f32 dots and reductions — the ulp-level
    # agreement the prune decisions (and therefore the
    # bit-parity-on-ids contract) rest on, at interpret-mode test
    # shapes. On TPU a misaligned dim means the kernel was excluded
    # by resolve_bq_engine anyway (there is nothing to bit-match),
    # and padding there would re-materialize the WHOLE rerank plane
    # per call — the exact cost the degrade rule exists to avoid.
    if jax.default_backend() != "tpu":
        d_pad = -(-d // 128) * 128
        de_pad = -(-dim_ext // 128) * 128
        if d_pad != d:
            qf = jnp.pad(qf, ((0, 0), (0, d_pad - d)))
            data = jnp.pad(data, ((0, 0), (0, 0), (0, d_pad - d)))
            if tiered:
                # the cold rerank plane must pad identically or the
                # hot/cold dots diverge from the all-HBM reference
                cold_data = jnp.pad(
                    cold_data, ((0, 0), (0, 0), (0, d_pad - d)))
        if de_pad != dim_ext:
            qrot = jnp.pad(qrot, ((0, 0), (0, de_pad - dim_ext)))
            centers_rot = jnp.pad(centers_rot,
                                  ((0, 0), (0, de_pad - dim_ext)))
    uniq = unique_lists(probes, n_lists)

    # gathered id planes, one per unique list; a shared (1-D) bitset
    # filter folds in here exactly like ivf_scan (filtered slot → id
    # -1 → padding); per-query (2-D) filters stay live and test inside
    # the step
    ids_g = jnp.take(indices, jnp.minimum(uniq, n_lists - 1), axis=0)
    filter_2d = (filter_words is not None
                 and getattr(filter_words, "ndim", 1) == 2)
    if filter_words is not None and not filter_2d:
        fbits = test_filter(filter_words, ids_g)
        ids_g = jnp.where(fbits & (ids_g >= 0), ids_g, -1)

    qn = jnp.sum(jnp.square(qf), axis=1, keepdims=True)

    def step(carry, xs):
        best_d, best_i = carry
        lid, ids_row = xs
        lidc = jnp.minimum(lid, n_lists - 1)      # sentinel-safe index
        if tiered:
            from raft_tpu.ops.tier_scan import (
                tier_block_select,
                tier_slot_pair,
            )

            # ONE slot decision per list — the estimate planes and
            # the rerank rows always come from the same tier
            hs, cs = tier_slot_pair(hot_slot_map, cold_slot_map,
                                    lidc)
            codes_b = tier_block_select(codes, cold_codes, hs, cs)
            rn = tier_block_select(rnorm, cold_rnorm, hs, cs)
            cf = tier_block_select(cfac, cold_cfac, hs, cs)
            ew = tier_block_select(errw, cold_errw, hs, cs)
        else:
            codes_b = jax.lax.dynamic_index_in_dim(codes, lidc, 0,
                                                   False)
            rn = jax.lax.dynamic_index_in_dim(rnorm, lidc, 0, False)
            cf = jax.lax.dynamic_index_in_dim(cfac, lidc, 0, False)
            ew = jax.lax.dynamic_index_in_dim(errw, lidc, 0, False)
        crot = jax.lax.dynamic_index_in_dim(centers_rot, lidc, 0, True)
        est, margin = _block_estimate(
            qrot, crot, rn[None, :], ew[None, :], jnp.transpose(cf),
            codes_b, dim_ext=dim_ext, bits=bits, query_bits=query_bits,
            epsilon=epsilon, ip_metric=ip_metric)
        ids_b = jnp.broadcast_to(ids_row[None, :], est.shape)
        probed = jnp.any(probes == lid, axis=1) & (lid < n_lists)
        ok = (ids_b >= 0) & probed[:, None]
        if filter_2d:
            ok = ok & test_filter(filter_words, ids_b)
        est = jnp.where(ok, est, jnp.inf)
        # the fused prune: only rows whose estimate (minus the error
        # bound) still beats the running k-th exact distance re-rank
        kth = best_d[:, k - 1 : k]
        cand = (est - margin) < kth
        if tiered:
            xb = tier_block_select(data, cold_data, hs, cs)
        else:
            xb = jax.lax.dynamic_index_in_dim(data, lidc, 0, False)
        xn = jax.lax.dynamic_index_in_dim(data_norms, lidc, 0, False)
        ipx = jax.lax.dot_general(
            qf, xb.astype(jnp.float32), (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )                                                      # (q, m)
        if ip_metric:
            exact = -ipx
        else:
            exact = jnp.maximum(qn + xn[None, :] - 2.0 * ipx, 0.0)
        exact = jnp.where(cand, exact, jnp.inf)
        return _merge_smallest_id(best_d, best_i, exact, ids_b, k), None

    init = (
        jnp.full((q, k), jnp.inf, jnp.float32) if init_d is None
        else jnp.full_like(init_d, jnp.inf),
        jnp.full((q, k), -1, jnp.int32) if init_i is None
        else jnp.full_like(init_i, -1),
    )
    (best_d, best_i), _ = jax.lax.scan(step, init, (uniq, ids_g))
    if ip_metric:
        best_d = -best_d          # inf (unfilled) -> -inf, ip exact
    return best_d, best_i


# ---------------------------------------------------------------------------
# Pallas engine — the fused kernel
# ---------------------------------------------------------------------------


def _bq_scan_kernel(u_ref, probes_ref, qrot_ref, qf_ref, crot_ref,
                    codes_ref, rn_ref, cf_ref, ew_ref, xn_ref, ids_ref,
                    data_ref, outd_ref, outi_ref, bestd, besti, vec,
                    sem, *, k: int, n_steps: int, n_lists: int,
                    ip_metric: bool, dim_ext: int, bits: int,
                    query_bits: int, epsilon: float):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        bestd[:] = jnp.full_like(bestd, jnp.inf)
        besti[:] = jnp.full_like(besti, -1)

    lid = u_ref[j]                        # scalar-prefetched list id
    lidc = jnp.minimum(lid, n_lists - 1)
    # estimate the whole tile against the packed sign words —
    # XOR+popcount on int32 lanes, 1/32nd the bytes of the vectors
    est, margin = _block_estimate(
        qrot_ref[:], crot_ref[0], rn_ref[0], ew_ref[0],
        jnp.transpose(cf_ref[0]), codes_ref[0], dim_ext=dim_ext,
        bits=bits, query_bits=query_bits, epsilon=epsilon,
        ip_metric=ip_metric)
    ids = ids_ref[0]                      # (1, m) — -1 marks pad/filtered
    probed = jnp.any(probes_ref[:] == lid, axis=1, keepdims=True)
    probed = jnp.logical_and(probed, lid < n_lists)
    est = jnp.where((ids >= 0) & probed, est, jnp.inf)

    # the fused prune: does ANY row of this block survive the bound?
    kth = bestd[:, k - 1 : k]
    cand = (est - margin) < kth
    any_cand = jnp.any(cand)

    @pl.when(any_cand)
    def _():
        # survivors exist — stream the block's raw vectors into VMEM
        # scratch (the ONLY vector read of the whole search; a fully
        # pruned block never touches them) and re-rank exactly
        cp = pltpu.make_async_copy(data_ref.at[pl.ds(lidc, 1)], vec,
                                   sem)
        cp.start()
        cp.wait()
        qt = qf_ref[:]
        ipx = jax.lax.dot_general(
            qt, vec[0], (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )                                 # (q_tile, m)
        if ip_metric:
            exact = -ipx
        else:
            qn = jnp.sum(jnp.square(qt), axis=1, keepdims=True)
            exact = jnp.maximum(qn + xn_ref[0] - 2.0 * ipx, 0.0)
        exact = jnp.where(cand, exact, jnp.inf)
        cat_d = jnp.concatenate([bestd[:], exact], axis=1)
        cat_i = jnp.concatenate(
            [besti[:], jnp.broadcast_to(ids, exact.shape)], axis=1)
        new_d, new_i = _extract_topk(cat_d, cat_i, k)
        bestd[:] = new_d
        besti[:] = new_i

    @pl.when(j == n_steps - 1)
    def _():
        outd_ref[:] = -bestd[:] if ip_metric else bestd[:]
        outi_ref[:] = besti[:]


def _bq_scan_pallas(qf, qrot, centers_rot, codes, rnorm, cfac, errw,
                    indices, data, data_norms, probes, filter_words, *,
                    k: int, metric: DistanceType, epsilon: float,
                    query_bits: int, interpret: bool, vmem_mb: int = 0):
    from raft_tpu.neighbors.filters import test_filter

    q, d = qf.shape
    n_lists, m, words = codes.shape
    dim_ext = centers_rot.shape[1]
    bits = cfac.shape[2]
    ip_metric = metric == DistanceType.InnerProduct
    if vmem_mb <= 0:
        vmem_mb = vmem_budget_mb()

    uniq = unique_lists(probes, n_lists)
    n_steps = uniq.shape[0]

    # gathered id planes + shared-filter fold, exactly like ivf_scan.
    # Per-query (2-D) filters CANNOT fold into the shared per-list
    # planes — resolve_bq_engine degrades them to xla, and a direct
    # caller bypassing it must hit this wall, not silent wrong masks
    expect(filter_words is None
           or getattr(filter_words, "ndim", 1) == 1,
           "the fused BQ Pallas engine supports shared (1-D) filters "
           "only — use engine='xla' for per-query filter words")
    # the small per-list planes (ids, norms, corrections, rotated
    # center) are gathered per unique list, like the id plane always
    # was; the heavy code/correction/vector planes stream by list id
    uc = jnp.minimum(uniq, n_lists - 1)
    ids_g = jnp.take(indices, uc, axis=0)
    if filter_words is not None:
        fbits = test_filter(filter_words, ids_g)
        ids_g = jnp.where(fbits & (ids_g >= 0), ids_g, -1)
    rn_g = jnp.take(rnorm, uc, axis=0)
    ew_g = jnp.take(errw, uc, axis=0)
    xn_g = jnp.take(data_norms, uc, axis=0)
    crot_g = jnp.take(centers_rot, uc, axis=0)

    # lane/sublane alignment; all no-ops on aligned serving layouts
    # (padded_extent rounds max_list_size to 8; resolve_bq_engine
    # degrades misaligned compiled runs — the pad path is interpret
    # mode's any-test-shape coverage)
    m_pad = -(-m // 8) * 8
    d_pad = -(-d // 128) * 128
    de_pad = -(-dim_ext // 128) * 128
    if m_pad != m:
        codes = jnp.pad(codes, ((0, 0), (0, m_pad - m), (0, 0)))
        cfac = jnp.pad(cfac, ((0, 0), (0, m_pad - m), (0, 0)))
        rn_g = jnp.pad(rn_g, ((0, 0), (0, m_pad - m)))
        ew_g = jnp.pad(ew_g, ((0, 0), (0, m_pad - m)))
        xn_g = jnp.pad(xn_g, ((0, 0), (0, m_pad - m)),
                       constant_values=jnp.inf)
        ids_g = jnp.pad(ids_g, ((0, 0), (0, m_pad - m)),
                        constant_values=-1)
    if m_pad != m or d_pad != d:
        data = jnp.pad(data, ((0, 0), (0, m_pad - m), (0, d_pad - d)))
    if de_pad != dim_ext:
        crot_g = jnp.pad(crot_g, ((0, 0), (0, de_pad - dim_ext)))
    # a unit middle axis: Mosaic wants a block's last two dims divisible
    # by (8, 128) or equal to the array's, and one list row is (1, m)
    rn_g, ew_g, xn_g, ids_g, crot_g = (
        a[:, None, :] for a in (rn_g, ew_g, xn_g, ids_g, crot_g))
    p = probes.shape[1]
    p_pad = -(-p // 128) * 128

    # query-tile sizing from the shared VMEM footprint model (the
    # same arithmetic resolve_bq_engine admitted this shape on)
    fixed, per_q = _vmem_plan(m_pad, d_pad, de_pad, p_pad, words,
                              bits, k)
    budget = (vmem_mb << 20) - fixed
    q_tile = min(max(8, (budget // per_q) // 8 * 8), -(-q // 8) * 8)
    q_pad = -(-q // q_tile) * q_tile

    qs = jnp.pad(qf.astype(jnp.float32), ((0, q_pad - q), (0, d_pad - d)))
    qr = jnp.pad(qrot.astype(jnp.float32),
                 ((0, q_pad - q), (0, de_pad - dim_ext)))
    # pad probe rows/cols with -1: a pad query probes nothing, so its
    # running state stays empty and its rows are sliced away
    probes_p = jnp.pad(probes.astype(jnp.int32),
                       ((0, q_pad - q), (0, p_pad - p)),
                       constant_values=-1)

    kernel = functools.partial(
        _bq_scan_kernel, k=k, n_steps=n_steps, n_lists=n_lists,
        ip_metric=ip_metric, dim_ext=dim_ext, bits=bits,
        query_bits=query_bits, epsilon=epsilon)
    clamp = n_lists - 1
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(q_pad // q_tile, n_steps),
        in_specs=[
            pl.BlockSpec((q_tile, p_pad), lambda i, j, u: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((q_tile, de_pad), lambda i, j, u: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((q_tile, d_pad), lambda i, j, u: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, de_pad), lambda i, j, u: (j, 0, 0),
                         memory_space=pltpu.VMEM),
            # the scalar-prefetched dynamic index maps: step j streams
            # list u[j]'s codes/corrections; the sentinel clamps to a
            # real list and is masked by the membership predicate
            pl.BlockSpec((1, m_pad, words),
                         lambda i, j, u: (jnp.minimum(u[j], clamp), 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, m_pad), lambda i, j, u: (j, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, m_pad, bits),
                         lambda i, j, u: (jnp.minimum(u[j], clamp), 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, m_pad), lambda i, j, u: (j, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, m_pad), lambda i, j, u: (j, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, m_pad), lambda i, j, u: (j, 0, 0),
                         memory_space=pltpu.VMEM),
            # the raw-vector plane stays in HBM: the kernel DMAs one
            # list block into VMEM scratch only when the prune left
            # survivors — the conditional read the one-stream
            # accounting is about
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=(
            pl.BlockSpec((q_tile, k), lambda i, j, u: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((q_tile, k), lambda i, j, u: (i, 0),
                         memory_space=pltpu.VMEM),
        ),
        scratch_shapes=[
            pltpu.VMEM((q_tile, k), jnp.float32),
            pltpu.VMEM((q_tile, k), jnp.int32),
            pltpu.VMEM((1, m_pad, d_pad), jnp.float32),
            pltpu.SemaphoreType.DMA,
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
    )(uniq, probes_p, qr, qs, crot_g, codes, rn_g, cfac, ew_g,
      xn_g, ids_g, data)
    return outd[:q], outi[:q]
