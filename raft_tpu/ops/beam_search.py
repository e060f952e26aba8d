"""Single-dispatch CAGRA beam search — the TPU re-design of the
reference's persistent single-CTA search kernel
(``detail/cagra/search_single_cta_kernel-inl.cuh``; plan notes
``search_plan.cuh:40-49``).

The XLA path (``neighbors/cagra._search_batch``) walks the graph with a
``lax.while_loop`` whose every iteration gathers ``w·deg`` dataset rows
from HBM — row gathers and per-iteration loop sync are exactly what TPUs
do worst. This kernel instead runs the WHOLE walk in one ``pallas_call``:

- the (quantizable) **dataset lives in VMEM** for the kernel's lifetime
  when it fits (v5e has 128 MB; 200k×128 bf16 = 51 MB) — candidate rows
  become dynamic VMEM loads, ~cycles each, no HBM latency, no XLA
  gather op. Bigger datasets (SIFT-1M and up) stay **HBM-resident**
  (``ds_mode="hbm"``): candidate rows are DMA'd in per-query batches,
  double-buffered so query ``b+1``'s row fetches fly while query ``b``
  scores — the true analog of the reference's any-size persistent
  kernel, which streams dataset rows from global memory the same way;
- the **graph stays in HBM**; only the ``w`` chosen parents' adjacency
  rows are DMA'd per iteration (w·deg·4 B per query — hundreds of bytes,
  latency hidden behind scoring);
- parent selection, id-dedup, and the top-L merge are the same
  extract-min VPU network as ``ops/fused_topk`` — no sorts anywhere;
- queries run in blocks of ``block_q`` per grid step, so scoring is a
  few small MXU contractions per iteration rather than scalar work;
- **per-row iteration budgets** arrive as a scalar-prefetched vector
  (``row_iters``): a row past its budget contributes inert no-op
  iterations, so one compiled executable serves every per-request
  ``max_iterations`` in a ragged batch bit-identically to a solo run;
- **BQ-coded traversal** (``bq_records``): gathered neighbors are first
  scored by the RaBitQ XOR+popcount estimate against a packed per-row
  record plane (:func:`raft_tpu.ops.bq_scan.bq_record_geometry`), and
  the raw dataset rows of a query's candidate batch are fetched ONLY
  when some candidate's estimate-minus-margin beats the running L-th
  exact distance (``pl.when`` conditional DMA — the bq_scan discipline
  on the neighbor-gather path). HBM traffic for the non-survivor
  majority drops from full-precision rows to code records.

Scope (the wrapper in ``neighbors/cagra`` falls back to the XLA path
otherwise): L2Expanded/L2SqrtExpanded/InnerProduct, f32/bf16/int8
dataset, ``dim % 128 == 0``, no sample filter. Any dataset size: the
VMEM budget only decides residency, not validity.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_tpu.core.chips import vmem_budget_mb
from raft_tpu.core.validation import expect
from raft_tpu.distance.types import DistanceType
from raft_tpu.ops.bq_scan import _block_estimate, bq_record_geometry
from raft_tpu.neighbors._exact import dedup_candidate_mask
from raft_tpu.ops.fused_topk import _extract_topk

_SUPPORTED = (DistanceType.L2Expanded, DistanceType.L2SqrtExpanded,
              DistanceType.InnerProduct)


def beam_search_fits(n: int, dim: int, itemsize: int,
                     vmem_mb: int = 0, extra_bytes: int = 0) -> bool:
    """Whether (n, dim) fits the VMEM-resident dataset budget (with
    ~8 MB headroom for the kernel's scratch and queries). Since the
    HBM-resident mode landed this decides *placement* (``ds_mode``
    auto), not whether the kernel applies at all. ``extra_bytes``
    charges co-resident planes (the BQ record plane) to the same
    budget."""
    if vmem_mb <= 0:
        vmem_mb = vmem_budget_mb()
    return n * dim * itemsize + extra_bytes <= (vmem_mb - 8) * 1024 * 1024


def pad_graph(graph) -> jax.Array:
    """Pad adjacency rows to the next 128 multiple (lane-aligned DMA
    unit) with -1 fill.  Call once per index when searching in query
    tiles; ``beam_search`` pads unpadded graphs itself otherwise."""
    deg = graph.shape[1]
    Gp = -(-deg // 128) * 128
    if Gp == deg:
        return graph
    return jnp.pad(graph, ((0, 0), (0, Gp - deg)), constant_values=-1)


def _beam_kernel(riters_ref, q_ref, seeds_ref, ds_ref, graph_ref, *rest,
                 L: int, w: int, k: int, C: int, deg: int, Gp: int,
                 max_iters: int, ip_metric: bool, ds_vmem: bool,
                 bq_bits: int, bq_query_bits: int, bq_epsilon: float):
    use_bq = bq_bits > 0
    pos = 0
    if use_bq:
        qrot_ref, crot_ref, rec_ref = rest[pos:pos + 3]
        pos += 3
    outd_ref, outi_ref = rest[pos:pos + 2]
    pos += 2
    cand_ref, cand_sm, dist_ref, rows_ref, gsm, sem = rest[pos:pos + 6]
    pos += 6
    if use_bq:
        bqtiles_ref, surv_ref = rest[pos:pos + 2]
        pos += 2
    dsem = rest[pos:]

    B, d = q_ref.shape
    qf = q_ref[:].astype(jnp.float32)                       # (B, d)
    qn = jnp.sum(jnp.square(qf), axis=1, keepdims=True)     # (B, 1)
    # bf16- and int8-origin rows multiply exactly in the f32
    # accumulator at DEFAULT (|int8| <= 127 is bf16-exact); f32 rows
    # need HIGHEST — the same exact-kNN choice as
    # fused_topk._knn_kernel and _exact.gathered_distances
    prec = (jax.lax.Precision.HIGHEST if ds_ref.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)

    # per-row iteration budget: B scalar SMEM reads select into a
    # (B, 1) lane vector the loop body compares its index against
    base = pl.program_id(0) * B
    rowi = jax.lax.broadcasted_iota(jnp.int32, (B, 1), 0)
    it_vec = jnp.zeros((B, 1), jnp.int32)
    for b in range(B):
        it_vec = jnp.where(rowi == b, riters_ref[base + b], it_vec)

    if use_bq:
        words = bq_bits * d // 32
        _, rec_pad, rpt, _ = bq_record_geometry(words, bq_bits)

    def score_rows(b, rows):
        """(C, d) gathered rows -> min-form distances into dist_ref[b]
        via two small MXU contractions."""
        ip = jax.lax.dot_general(
            qf[b:b + 1], rows, (((1,), (1,)), ((), ())),
            precision=prec,
            preferred_element_type=jnp.float32)             # (1, C)
        if ip_metric:
            dist_ref[pl.ds(b, 1), :] = -ip
        else:
            rn = jax.lax.dot_general(
                jnp.ones((1, d), jnp.float32), rows * rows,
                (((1,), (1,)), ((), ())),
                precision=prec,
                preferred_element_type=jnp.float32)         # (1, C)
            dist_ref[pl.ds(b, 1), :] = jnp.maximum(
                rn - 2.0 * ip + qn[b], 0.0)

    def estimate_cand(cand, dvals):
        """BQ phase: per query, gather each candidate's packed record
        tile (dynamic VMEM loads — the plane is VMEM-resident), select
        the record's lane window, and run the shared
        :func:`raft_tpu.ops.bq_scan._block_estimate` math. A candidate
        survives iff its estimate minus the RaBitQ margin could still
        beat the query's running L-th exact distance."""
        for b in range(B):
            def gtile(c, _):
                tid = cand_sm[b, c] // rpt
                bqtiles_ref[pl.ds(c, 1), :] = rec_ref[pl.ds(tid, 1), :]
                return 0
            jax.lax.fori_loop(0, C, gtile, 0, unroll=1)
            tiles = bqtiles_ref[:]                          # (C, PW)
            offc = jnp.transpose(jnp.maximum(cand[b:b + 1], 0) % rpt)
            recs = tiles[:, 0:rec_pad]
            for o in range(1, rpt):
                recs = jnp.where(
                    offc == o, tiles[:, o * rec_pad:(o + 1) * rec_pad],
                    recs)                                   # (C, rec_pad)
            codes_wb = recs[:, :words]
            scal = jax.lax.bitcast_convert_type(
                recs[:, words:words + bq_bits + 2], jnp.float32)
            rnorm_row = jnp.transpose(scal[:, 0:1])         # (1, C)
            cfac_t = jnp.transpose(scal[:, 1:1 + bq_bits])  # (bits, C)
            errw_row = jnp.transpose(scal[:, 1 + bq_bits:2 + bq_bits])
            est, margin = _block_estimate(
                qrot_ref[b:b + 1].astype(jnp.float32), crot_ref[:],
                rnorm_row, errw_row, cfac_t, codes_wb,
                dim_ext=d, bits=bq_bits, query_bits=bq_query_bits,
                epsilon=bq_epsilon, ip_metric=ip_metric)
            kth = dvals[b:b + 1, L - 1:L]
            surv = ((est - margin) < kth) & (cand[b:b + 1] >= 0)
            surv_ref[pl.ds(b, 1), :] = surv.astype(jnp.int32)

    def score_cand(cand, dvals):
        """(B, C) candidate ids -> (B, C) min-form distances.

        VMEM-resident dataset: dynamic VMEM row loads (cycles each).
        HBM-resident dataset: per-query DMA batches, double-buffered —
        query b+1's C row fetches are in flight on the other
        buffer/semaphore while query b's rows score on the MXU.

        With BQ traversal the estimate phase runs first and a query's
        raw-row batch is gathered/DMA'd ONLY when it still holds an
        estimate-survivor — non-survivor batches cost codes, not rows."""
        # ids must be scalars for dynamic addressing: VMEM -> SMEM.
        # Invalid ids (-1) are clamped for the gather only — compiled
        # Mosaic has no OOB clamp; masking happens on the way out.
        cand_ref[:] = jnp.maximum(cand, 0)
        cp = pltpu.make_async_copy(cand_ref, cand_sm, sem)
        cp.start()
        cp.wait()
        if use_bq:
            estimate_cand(cand, dvals)

            def anyb(b):
                return jnp.any(surv_ref[pl.ds(b, 1), :] == 1)
        if ds_vmem:
            for b in range(B):
                def scoreb(b=b):
                    def gather(c, _):
                        rid = cand_sm[b, c]
                        rows_ref[pl.ds(c, 1), :] = ds_ref[pl.ds(rid, 1), :]
                        return 0
                    # Mosaic lowers fori_loop only at unroll=1 or a full
                    # unroll; partial unrolls are rejected at compile
                    # time.
                    jax.lax.fori_loop(0, C, gather, 0, unroll=1)
                    score_rows(b, rows_ref[:].astype(jnp.float32))
                if use_bq:
                    pl.when(anyb(b))(scoreb)
                else:
                    scoreb()
        else:
            dsem_ref = dsem[0]

            def fetch(b, slot):
                """Start query b's C row DMAs into buffer ``slot``."""
                def start(c, _):
                    rid = cand_sm[b, c]
                    pltpu.make_async_copy(
                        ds_ref.at[pl.ds(rid, 1), :],
                        rows_ref.at[slot, pl.ds(c, 1), :],
                        dsem_ref.at[slot]).start()
                    return 0
                jax.lax.fori_loop(0, C, start, 0, unroll=1)

            def drain(slot):
                """Retire the C row copies targeting ``slot`` with ONE
                semaphore wait: DMA waits decrement by the descriptor's
                byte count, and a (C, d) descriptor's bytes equal the
                sum of the C (1, d) transfers that signalled the sem —
                C serial scalar-core waits would sit on the hot path.
                The descriptor is built from the (C, d) landing buffer
                (src shape only feeds the byte count), not a dataset
                slice — ds_ref[0:C] would be an invalid slice whenever
                n < C (tiny dataset forced to hbm mode)."""
                pltpu.make_async_copy(
                    rows_ref.at[slot],
                    rows_ref.at[slot],
                    dsem_ref.at[slot]).wait()

            def maybe(b, fn):
                # the fetch/drain/score trio for query b shares ONE
                # predicate (surv_ref is stable inside score_cand), so
                # a skipped fetch can never strand a drain
                if use_bq:
                    pl.when(anyb(b))(fn)
                else:
                    fn()

            maybe(0, lambda: fetch(0, 0))
            for b in range(B):
                slot = b % 2
                if b + 1 < B:
                    maybe(b + 1,
                          lambda b=b: fetch(b + 1, (b + 1) % 2))

                def retire(b=b, slot=slot):
                    drain(slot)
                    score_rows(b, rows_ref[slot].astype(jnp.float32))
                maybe(b, retire)
        if use_bq:
            # skipped rows hold stale dist lanes — the survivor mask
            # (which already folds cand >= 0) is the source of truth
            return jnp.where(surv_ref[:] == 1, dist_ref[:], jnp.inf)
        return jnp.where(cand < 0, jnp.inf, dist_ref[:])

    def merge(ids, dvals, expl, cand, cd):
        """Dedup-aware top-L merge (the XLA path's _buffer_merge with
        lax.top_k replaced by the extract-min network; same shared
        dedup mask as that engine)."""
        buf_ids = jnp.where(ids >= 0, ids, -2)
        dup = dedup_candidate_mask(cand, buf_ids)
        cd = jnp.where(dup | (cand < 0), jnp.inf, cd)

        all_d = jnp.concatenate([dvals, cd], axis=1)        # (B, L+C)
        all_i = jnp.concatenate([ids, cand], axis=1)
        new_d, new_i = _extract_topk(all_d, all_i, L)
        # explored flags follow ids (buffer ids are unique post-dedup;
        # fresh candidates enter unexplored)
        keep = jnp.any(
            (new_i[:, :, None] == buf_ids[:, None, :]) & (expl == 1)[:, None, :],
            axis=2)
        return new_i, new_d, keep.astype(jnp.int32)

    # ---- seed rounds: the buffer starts as the best L of ALL seeds.
    # Seeds arrive as a multiple of the candidate width C and merge in
    # C-wide chunks, so any XLA-engine seed count (L > C, extra
    # num_random_samplings draws) rides the same scoring path.
    seeds = seeds_ref[:]                                    # (B, S)
    ids = jnp.full((B, L), -1, jnp.int32)
    dvals = jnp.full((B, L), jnp.inf)
    expl = jnp.zeros((B, L), jnp.int32)
    for chunk in range(seeds.shape[1] // C):
        cand = seeds[:, chunk * C:(chunk + 1) * C]
        ids, dvals, expl = merge(ids, dvals, expl, cand,
                                 score_cand(cand, dvals))

    def body(it, state):
        ids, dvals, expl = state
        # ---- pick w best unexplored as parents (extract-min rounds).
        # A row past its iteration budget contributes no parents: its
        # candidates are all -1, its explored flags untouched — the
        # whole iteration is a bit-exact no-op for that row.
        masked = jnp.where((expl == 1) | (ids < 0), jnp.inf, dvals)
        _, parents = _extract_topk(masked, ids, w)          # (B, w)
        pvalid = (parents >= 0) & (it < it_vec)
        # mark parents explored (ids are unique in the buffer)
        expl = jnp.where(
            jnp.any(ids[:, :, None] == jnp.where(
                pvalid, parents, -3)[:, None, :], axis=2),
            1, expl)

        # ---- fetch the parents' adjacency rows from HBM.  Mosaic only
        # allows lane-dim DMA slices at 128-aligned offsets/widths, so
        # the graph arrives padded to Gp (= deg rounded up to 128),
        # whole padded rows land at j*Gp offsets, and the compact
        # (B, C) candidate block is re-assembled with aligned-start
        # static value slices (both patterns verified on the compiler).
        cand_ref[:] = jnp.concatenate(
            [jnp.where(pvalid, parents, 0),
             jnp.zeros((B, C - w), jnp.int32)], axis=1)
        cp = pltpu.make_async_copy(cand_ref, cand_sm, sem)
        cp.start()
        cp.wait()
        dmas = []
        for b in range(B):
            for j in range(w):
                dmas.append(pltpu.make_async_copy(
                    graph_ref.at[pl.ds(cand_sm[b, j], 1), :],
                    gsm.at[pl.ds(b * w + j, 1), :],
                    sem))
                dmas[-1].start()
        for dma in dmas:
            dma.wait()
        gv = gsm[:].reshape(B, w * Gp)
        cand = jnp.concatenate(
            [gv[:, j * Gp:j * Gp + deg] for j in range(w)], axis=1)
        # lanes of an invalid parent are masked out
        lane = jax.lax.broadcasted_iota(jnp.int32, (B, C), 1) // deg
        ok = jnp.zeros((B, C), jnp.bool_)
        for j in range(w):
            ok = ok | ((lane == j) & pvalid[:, j:j + 1])
        cand = jnp.where(ok, cand, -1)

        cd = score_cand(cand, dvals)
        return merge(ids, dvals, expl, cand, cd)

    ids, dvals, _ = jax.lax.fori_loop(0, max_iters, body,
                                      (ids, dvals, expl))
    outd_ref[:] = dvals[:, :k]
    outi_ref[:] = jnp.where(jnp.isfinite(dvals[:, :k]), ids[:, :k], -1)


@functools.partial(
    jax.jit,
    static_argnames=("k", "L", "w", "max_iters", "metric", "block_q",
                     "interpret", "vmem_mb", "deg", "ds_mode",
                     "bq_bits", "bq_query_bits", "bq_epsilon"))
def beam_search(queries, dataset, graph, seeds, k: int, L: int, w: int,
                max_iters: int, metric: DistanceType, *,
                row_iters=None,
                bq_records=None, bq_qrot=None, bq_crot=None,
                bq_bits: int = 0, bq_query_bits: int = 4,
                bq_epsilon: float = 3.0,
                block_q: int = 8, interpret: bool = False,
                vmem_mb: int = 0,
                deg: int = 0,
                ds_mode: str = "auto") -> Tuple[jax.Array, jax.Array]:
    """One-dispatch graph beam search (see module docstring).

    ``seeds`` must be (q, m·w·deg) int32 for integer m ≥ 1 — the seed
    rounds reuse the candidate scoring path in w·deg-wide chunks.
    Returns min-form (q, k) distances + ids; the caller applies sqrt /
    IP negation.

    ``row_iters``: optional (q,) int32 per-row iteration budgets for
    ragged serving — row r runs ``min(row_iters[r], max_iters)`` live
    iterations and inert no-ops after, bit-identical to a solo run at
    ``max_iterations=row_iters[r]``. None means every row runs
    ``max_iters``.

    ``bq_records``/``bq_qrot``/``bq_crot`` (+ the ``bq_*`` statics)
    enable BQ-coded traversal: records is the
    :func:`raft_tpu.ops.bq_scan.pack_bq_records` plane over the WHOLE
    dataset, qrot the rotated queries (q, d), crot the rotated center
    row (1, d). The plane must be VMEM-co-resident with the kernel's
    scratch.

    ``deg``: the graph's logical degree, when ``graph`` arrives with
    its rows already padded to a 128 multiple (see ``pad_graph``) —
    callers that search in query tiles pad once instead of per tile.
    0 means the graph is unpadded and its width is the degree.

    ``ds_mode``: ``"vmem"`` pins the dataset VMEM-resident (must fit
    the budget), ``"hbm"`` streams candidate rows by double-buffered
    DMA from HBM (any size), ``"auto"`` picks by ``beam_search_fits``."""
    q, d = queries.shape
    n, gw = graph.shape
    deg = deg or gw
    expect(deg <= gw, "beam_search: deg exceeds graph width")
    C = w * deg
    expect(metric in _SUPPORTED, f"beam_search: unsupported {metric}")
    expect(d % 128 == 0, "beam_search: dim must be lane-aligned (128)")
    expect(seeds.ndim == 2 and seeds.shape[0] == q
           and seeds.shape[1] >= C and seeds.shape[1] % C == 0,
           "beam_search: seeds must be (q, m*w*deg)")
    expect(k <= L, "beam_search: k must be <= itopk L")
    if vmem_mb <= 0:
        vmem_mb = vmem_budget_mb()

    use_bq = bq_records is not None
    plane_bytes = 0
    if use_bq:
        expect(1 <= bq_bits <= 8,
               "beam_search: bq_records needs bq_bits in 1..8")
        # dim is lane-aligned, so dim_ext == d and the rotated query
        # carries exactly d lanes
        words = bq_bits * d // 32
        _, rec_pad, rpt, pw = bq_record_geometry(words, bq_bits)
        expect(tuple(bq_records.shape) == (-(-n // rpt), pw),
               "beam_search: bq_records does not match "
               f"bq_record_geometry(words={words}, bits={bq_bits}) "
               f"for n={n}")
        expect(bq_qrot is not None and tuple(bq_qrot.shape) == (q, d),
               "beam_search: bq_qrot must be (q, dim) rotated queries")
        expect(bq_crot is not None and tuple(bq_crot.shape) == (1, d),
               "beam_search: bq_crot must be the (1, dim) rotated "
               "center")
        # the plane is VMEM-resident in BOTH dataset modes (it is the
        # prune side of the conditional DMA) — it must leave the ~8 MB
        # scratch headroom; dataset placement charges it as
        # extra_bytes below
        plane_bytes = 4 * bq_records.shape[0] * pw
        expect(plane_bytes <= (vmem_mb - 8) * 1024 * 1024,
               "beam_search: BQ record plane exceeds the VMEM budget")

    B = block_q
    if row_iters is None:
        row_iters = jnp.full((q,), max_iters, jnp.int32)
    expect(row_iters.shape == (q,),
           "beam_search: row_iters must be (q,)")
    pad_q = (-q) % B
    if pad_q:
        queries = jnp.pad(queries, ((0, pad_q), (0, 0)))
        seeds = jnp.pad(seeds, ((0, pad_q), (0, 0)))
        row_iters = jnp.pad(row_iters, (0, pad_q))
        if use_bq:
            bq_qrot = jnp.pad(bq_qrot, ((0, pad_q), (0, 0)))
    qp = q + pad_q
    # bf16 halves and int8 quarters the VMEM residency (int8 is the
    # CAGRA-Q role: quantized scan + exact refine outside)
    ds = (dataset if dataset.dtype in (jnp.bfloat16, jnp.int8)
          else dataset.astype(jnp.float32))
    qs = queries.astype(jnp.float32)
    # Lane-dim DMA slices must be 128-aligned: ship the graph with its
    # rows padded to Gp and fetch whole padded rows (costs HBM
    # bandwidth ~Gp/deg per fetch; candidate scoring stays at C wide).
    Gp = -(-deg // 128) * 128
    expect(gw in (deg, Gp),
           "beam_search: graph width must be deg or deg padded to 128")
    if gw != Gp:
        graph = pad_graph(graph)

    expect(ds_mode in ("auto", "vmem", "hbm"),
           f"beam_search: ds_mode must be auto/vmem/hbm, got {ds_mode!r}")
    itemsize = jnp.dtype(ds.dtype).itemsize
    if ds_mode == "auto":
        ds_mode = ("vmem" if beam_search_fits(n, ds.shape[1], itemsize,
                                              vmem_mb, plane_bytes)
                   else "hbm")
    elif ds_mode == "vmem":
        expect(beam_search_fits(n, ds.shape[1], itemsize, vmem_mb,
                                plane_bytes),
               f"beam_search: dataset ({n}x{ds.shape[1]} {ds.dtype}) "
               "exceeds the VMEM budget; use ds_mode='hbm' or 'auto'")
    ds_vmem = ds_mode == "vmem"

    kernel = functools.partial(
        _beam_kernel, L=L, w=w, k=k, C=C, deg=deg, Gp=Gp,
        max_iters=max_iters,
        ip_metric=metric == DistanceType.InnerProduct,
        ds_vmem=ds_vmem,
        bq_bits=bq_bits if use_bq else 0,
        bq_query_bits=bq_query_bits, bq_epsilon=bq_epsilon)
    # HBM mode: candidate rows land in a (2, C, d) double buffer with a
    # per-buffer DMA semaphore; VMEM mode gathers into one (C, d) block
    if ds_vmem:
        ds_spec = pl.BlockSpec((n, ds.shape[1]), lambda i, rr: (0, 0))
        rows_scratch = pltpu.VMEM((C, d), ds.dtype)
        extra_scratch = []
    else:
        ds_spec = pl.BlockSpec(memory_space=pl.ANY)
        rows_scratch = pltpu.VMEM((2, C, d), ds.dtype)
        extra_scratch = [pltpu.SemaphoreType.DMA((2,))]
    operands = [jnp.asarray(row_iters, jnp.int32), qs, seeds, ds, graph]
    in_specs = [
        pl.BlockSpec((B, d), lambda i, rr: (i, 0)),                # queries
        pl.BlockSpec((B, seeds.shape[1]), lambda i, rr: (i, 0)),   # seeds
        ds_spec,                                                   # dataset
        pl.BlockSpec(memory_space=pl.ANY),                  # graph (HBM)
    ]
    bq_scratch = []
    if use_bq:
        operands += [bq_qrot.astype(jnp.float32),
                     bq_crot.astype(jnp.float32),
                     bq_records]
        in_specs += [
            pl.BlockSpec((B, d), lambda i, rr: (i, 0)),            # qrot
            pl.BlockSpec((1, d), lambda i, rr: (0, 0)),            # crot
            pl.BlockSpec(bq_records.shape,
                         lambda i, rr: (0, 0)),       # record plane (VMEM)
        ]
        bq_scratch = [
            pltpu.VMEM((C, pw), jnp.int32),     # gathered record tiles
            pltpu.VMEM((B, C), jnp.int32),      # estimate survivors
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(qp // B,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((B, k), lambda i, rr: (i, 0)),
            pl.BlockSpec((B, k), lambda i, rr: (i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((B, C), jnp.int32),      # cand staging
            pltpu.SMEM((B, C), jnp.int32),      # cand scalars
            pltpu.VMEM((B, C), jnp.float32),    # distances
            rows_scratch,                       # gathered rows
            pltpu.VMEM((B * w, Gp), jnp.int32),  # graph rows landing
            pltpu.SemaphoreType.DMA,
        ] + bq_scratch + extra_scratch,
    )
    outd, outi = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((qp, k), jnp.float32),
            jax.ShapeDtypeStruct((qp, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_mb * 1024 * 1024),
        interpret=interpret,
    )(*operands)
    return outd[:q], outi[:q]
