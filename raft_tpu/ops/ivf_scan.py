"""List-major IVF probe scan — the TPU port of the reference's flagship
``ivf_flat_interleaved_scan`` (``detail/ivf_flat_interleaved_scan-inl.cuh``),
re-designed per the two papers the survey flags for this kernel:

- TPU-KNN (arxiv 2206.14286): peak FLOP/s on TPU means expressing kNN as
  dense contractions with an in-register merge — never as gathers
  feeding batched matvecs.
- Ragged Paged Attention (arxiv 2604.15464): the TPU-native way to fetch
  data-dependent pages is a **scalar-prefetched block index map** — the
  page table (here: the step schedule) rides ahead of the grid in SMEM
  and steers each step's HBM->VMEM block DMA.

The rank-major scan (``ivf_flat._search_impl_fn`` with
``scan_engine="rank"``) gathers one probed list *per query* per probe
rank: a `(q, m, d)` HBM materialization and a gather-bound batched
matvec, repeated ``n_probes`` times. This module turns the scan
**list-major**: each probed list's ``(max_list_size, d)`` block streams
from the packed ``data`` tensor once, so per-probe HBM traffic drops
from ``q * n_probes`` gathered lists to at most ``min(n_lists,
q * n_probes)`` streamed lists.

Two engines share the formulation and are bit-identical to each other
in ids and distances:

- ``pallas``: the grouped kernel. The ``q * p`` (query row, probe slot)
  pairs sort by list id (sentinel pairs last, dropped); each list's run
  of pairs is cut into groups of at most ``G`` rows, one grid step
  each, and a step contracts only its group's ``(G, d)`` query rows
  against the list, so a list is scored only against the queries that
  probed it. ``G`` is the mean number of pairs a probed list can have,
  ``q*p / min(n_lists, q*p)``, rounded up to a multiple of 8 and held
  to [8, 256] and to what VMEM holds (:func:`group_shape`); the grid
  has the static cap ``S = min(n_lists, q*p) + (q*p) // G`` steps,
  which ``sum_l ceil(c_l / G)`` never exceeds. The step's list id is
  the scalar-prefetched operand of the ``data`` BlockSpec: a list with
  more than ``G`` probers takes consecutive steps of one block index,
  so its block moves once, and the steps past the real ones keep the
  last block and compute nothing. Each step extracts its own ``(G,
  k)`` top-k with the ``ops.fused_topk._extract_topk`` network and
  carries no state across steps; an XLA epilogue hands every pair its
  block and takes each row's top-k of its ``p * k`` candidates in the
  same (distance, smallest id) order. VMEM holds one list block
  (double-buffered, widened) and a ``(G, d)`` query block with its
  ``(G, m)`` distance strip. Shared (1-D) bitset filters fold into the
  gathered id planes before the kernel (a filtered slot becomes id -1
  — padding).
- ``xla``: a ``lax.scan`` over the union of probed lists, contracting
  each against the whole query tile, with a per-query "did this query
  probe this list" mask and one lexicographic two-key ``lax.sort``
  merge (the same smallest-id tie-break, any k without unrolling) —
  the portable fallback (CPU/GPU, 2-D per-query filters, large k,
  misaligned layouts on TPU) and the parity reference.

Against the rank-major scan the indices are bit-identical and the
distances agree to XLA's dot-reassociation tolerance — the same caveat
as ``beam_search``'s two lowerings.

**Storage dtypes.** The kernel streams float32, bfloat16, uint8 and
int8 lists. A list block moves HBM->VMEM in its stored dtype and is
widened in VMEM, never in HBM. Float lists widen to float32 and
contract at ``HIGHEST`` precision. Byte lists (``uint8``/``int8``, the
BIGANN-style corpora) widen to bfloat16, which holds every byte value
exactly, and contract in two single-pass bfloat16 products with
float32 accumulation: one with the queries rounded to bfloat16 and one
with the remainder (:func:`_split_bf16`). For byte-valued queries the
remainder is zero, every product is exact and every partial sum is an
integer below 128 * 255**2 < 2**24, so the distances are the exact
integers. The layout wants lists
padded to the dtype's sublane multiple (32 rows for bytes,
:func:`raft_tpu.neighbors._packing.sublane_multiple`).

**Ragged query-tile front** (the continuous-batching serving path):
several requests with *different* per-request ``n_probes`` pack
adjacently into one fixed query tile, and each row's probe slots past
its own budget mask to the sentinel id ``n_lists``
(:func:`ragged_row_probes` / :func:`ragged_probes`). Sentinel-valued
probe slots are exactly how the list-sharded indexes already mark
not-owned probes, and how the serving executor marks the probes of
bucket-pad rows, so BOTH engines serve the packed tile unchanged: the
grouped kernel never gives a sentinel slot a pair, and the XLA scan's
membership predicate rejects it. One executable therefore serves every
load shape; the per-request results are bit-identical to solo calls.
The same front covers the whole index zoo (graftragged): the PQ LUT
scan and the fused BQ engines consume the identical sentinel-masked
probes, and on the mesh :func:`ragged_owned` folds each row's budget
into the sharded probe-ownership mask — so a replicated packed tile
serves the list-sharded families through their unchanged shard
bodies.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_tpu.core.chips import vmem_budget_mb
from raft_tpu.core.logger import logger
from raft_tpu.core.validation import expect
from raft_tpu.distance.types import DistanceType
from raft_tpu.neighbors._packing import sublane_multiple
from raft_tpu.ops.fused_topk import _extract_topk

SCAN_ENGINES = ("auto", "pallas", "xla", "rank")

# the merge network unrolls k rounds; past this the XLA merge wins
_PALLAS_MAX_K = 128

# list storage the Pallas kernel streams (bytes widen in VMEM)
_KERNEL_STORAGE = (jnp.float32, jnp.bfloat16, jnp.uint8, jnp.int8)


@functools.lru_cache(maxsize=None)
def _warn_degrade(family: str, reason: str) -> None:
    logger.warning("%s: the pallas engine cannot serve this search (%s);"
                   " serving it with the xla engine", family, reason)


def degrade(family: str, reason: str) -> str:
    """Resolve a Pallas request to ``"xla"`` — out loud: the first
    degrade per (family, reason) logs a warning, so a run can tell
    which engine served it (the executor's cost table also records
    the resolved engine of every executable)."""
    _warn_degrade(family, reason)
    return "xla"


def resolve_scan_engine(engine: str, *, data=None, filter_words=None,
                        k=None, vmem_mb: int = 0) -> str:
    """Resolve a ``scan_engine`` search param to a concrete engine. The
    Pallas kernel serves float32, bfloat16, uint8 and int8 lists (byte
    lists widen to bfloat16 in VMEM; see the module docstring).

    ``auto`` is the Pallas kernel on TPU and the list-major XLA scan
    elsewhere. ``pallas`` degrades to ``xla`` when the kernel's
    preconditions fail: per-query (2-D) filter words (the id-fold
    trick needs one shared id plane), storage other than
    float32/bfloat16/uint8/int8, ``k`` past the unrolled-merge budget,
    a list layout that is not whole tiles on TPU, or a single list
    block that cannot fit the VMEM budget double-buffered.
    ``rank`` is the legacy rank-major gather scan, kept for parity
    testing and as the small-``n_lists`` escape hatch."""
    expect(engine in SCAN_ENGINES,
           f"scan_engine must be one of {SCAN_ENGINES}, got {engine!r}")
    if engine == "auto":
        engine = "pallas" if jax.default_backend() == "tpu" else "xla"
    if engine != "pallas":
        return engine
    if filter_words is not None and getattr(filter_words, "ndim", 1) == 2:
        return degrade("ivf_scan", "per-query filter words")
    if k is not None and k > _PALLAS_MAX_K:
        return degrade("ivf_scan", f"k > {_PALLAS_MAX_K}")
    if data is not None:
        if data.dtype not in _KERNEL_STORAGE:
            return degrade("ivf_scan", f"{data.dtype} storage")
        sub = sublane_multiple(data.dtype)
        m_pad = -(-data.shape[1] // sub) * sub
        d_pad = -(-data.shape[2] // 128) * 128
        # on real hardware a misaligned layout would force _scan_pallas
        # to jnp.pad the WHOLE packed tensor per call — a full HBM
        # read+write dwarfing the probe scan — so compiled runs demand
        # build-time alignment (padded_extent rounds m to the dtype's
        # sublane multiple; lane-aligned dims like 128/256 give d).
        # Interpret mode (off-TPU) keeps the pad path: it exists so CPU
        # CI can cover the kernel at any test shape.
        if jax.default_backend() == "tpu" and (
                m_pad != data.shape[1] or d_pad != data.shape[2]):
            return degrade("ivf_scan", "list layout not tile-aligned")
        if vmem_mb <= 0:
            vmem_mb = vmem_budget_mb()
        # mirror _scan_pallas's budget: the list block + margin fixed
        # cost must leave room for at least one minimal (8-row) query
        # group — otherwise the kernel's group floor would overshoot
        # vmem_limit_bytes and fail Mosaic compilation instead of
        # degrading here
        fixed = _vmem_fixed(m_pad, d_pad, data.dtype)
        per_row = _vmem_per_row(m_pad, d_pad, k or _PALLAS_MAX_K,
                                data.dtype)
        if fixed + 8 * per_row > vmem_mb << 20:
            return degrade("ivf_scan", "list block exceeds the VMEM budget")
    return engine


def _is_bytes(dtype) -> bool:
    return jnp.dtype(dtype) in (jnp.uint8, jnp.int8)


def _vmem_fixed(m_pad: int, d_pad: int, dtype) -> int:
    """VMEM the kernel holds whatever the query group: the list block
    double-buffered, its widened copy, the norm and id rows, and a
    2 MB margin. Float lists widen to float32 (one strip of the
    block's size); byte lists pass through int32 and float32 on their
    way to bfloat16 (10 bytes an element at most)."""
    itemsize = jnp.dtype(dtype).itemsize
    if _is_bytes(dtype):
        return m_pad * (d_pad * (2 * itemsize + 10) + 24) + (2 << 20)
    return 3 * m_pad * (d_pad * itemsize + 8) + (2 << 20)


def _vmem_per_row(m_pad: int, d_pad: int, k: int, dtype) -> int:
    """VMEM per row of a query group: the float32 query row
    double-buffered (its two bfloat16 halves beside it for byte
    lists), the ``(m)`` distance and top-k extraction intermediates
    (one more product strip for byte lists) and the double-buffered
    ``(k)`` output rows."""
    per_m = 28 if _is_bytes(dtype) else 24
    return 12 * d_pad + per_m * m_pad + 16 * k


def probe_histogram(probes: jax.Array, counts: jax.Array,
                    n_valid=None, owned=None) -> jax.Array:
    """Scatter-add a ``bincount`` of the selected probe ids into the
    running ``counts`` plane — the device half of graftgauge's
    probe-frequency accounting, shared by every IVF family's search
    body (single-chip and the shard-local half of the sharded ones).

    ``probes`` is the (q, n_probes) int32 probe selection; ``counts``
    is the donated (n_lists,) int32 cumulative plane (the serving
    executor threads it like the top-k state, so steady state stays
    zero-recompile). ``n_valid`` (traced scalar) masks the executor's
    inert bucket-pad rows — a pad query's phantom probes must not
    pollute the traffic histogram; ``owned`` is the sharded families'
    per-slot ownership mask (count a probe exactly once mesh-wide, on
    the shard that owns the list). Masked slots redirect to the
    out-of-range index ``n_lists`` and ``mode="drop"`` discards them —
    including sentinel-valued masked probes, which already carry
    ``n_lists``. Pure accumulation: the search results never read the
    plane, so bit-identity is untouched by construction."""
    n_lists = counts.shape[0]
    ids = probes.astype(jnp.int32)
    if owned is not None:
        ids = jnp.where(owned, ids, n_lists)
    if n_valid is not None:
        valid = jnp.arange(ids.shape[0], dtype=jnp.int32) < n_valid
        ids = jnp.where(valid[:, None], ids, n_lists)
    return counts.at[ids.reshape(-1)].add(1, mode="drop")


def ragged_row_probes(sizes, n_probes_list, tile: int):
    """Host-side half of the ragged query-tile front (Ragged Paged
    Attention's packing descriptor, arxiv 2604.15464): expand one
    packed tile's per-request row ranges into the per-ROW probe-budget
    plane the device front consumes.

    ``sizes[j]`` rows of request ``j`` occupy the next ``sizes[j]``
    packed rows (requests pack adjacently, in order), and every row of
    request ``j`` carries that request's probe budget
    ``n_probes_list[j]``. Rows past ``sum(sizes)`` are tile padding and
    get budget 0 — a pad row probes nothing, so it contributes nothing
    to any result, the probed-list union, or the probe-frequency
    histogram. Returns a ``(tile,)`` int32 numpy array (the serving
    path packs host-side; the executor ships it with the queries)."""
    out = np.zeros((tile,), np.int32)
    row = 0
    for m, p in zip(sizes, n_probes_list):
        out[row:row + m] = p
        row += m
    expect(row <= tile, f"packed rows {row} overflow the tile {tile}")
    return out


def ragged_probes(probes: jax.Array, row_probes: jax.Array,
                  n_lists: int) -> jax.Array:
    """Device half of the ragged front: mask each row's probe slots
    past its own budget to the sentinel id ``n_lists``.

    ``probes`` is the coarse selection at the packed tile's CLASS cap
    (``(tile, n_probes_class)``, exact top-k — so slots ``[0, b)`` of a
    row with budget ``b <= n_probes_class`` are exactly what a solo
    search with ``n_probes=b`` would have selected); ``row_probes`` is
    :func:`ragged_row_probes`'s per-row budget plane. Sentinel-masked
    slots ride the exact machinery the list-sharded indexes already
    use for not-owned probes: the grouped Pallas scan gives them no
    (query, list) pair, the XLA scan's membership predicate rejects
    them (``lid < n_lists``), and :func:`probe_histogram` drops them — so
    one packed executable serves every per-request ``n_probes`` in the
    class, bit-identical per request to the solo call."""
    slot = jnp.arange(probes.shape[1], dtype=jnp.int32)
    return jnp.where(slot[None, :] < row_probes[:, None], probes,
                     n_lists)


def ragged_owned(mine: jax.Array, row_probes: jax.Array,
                 shards: int = 1) -> jax.Array:
    """Fold a packed ragged tile's per-row probe budgets into a
    sharded probe-ownership mask — the mesh half of the ragged front.

    ``mine`` is :func:`raft_tpu.distributed.ivf.select_probes_sharded`'s
    per-(row, probe-rank) ownership mask, whose columns are
    rank-ordered by the exact coarse top-k (a total order, so the
    first ``b`` columns ARE the solo ``n_probes=b`` selection — the
    same prefix property the single-chip front rides). A row keeps
    only the slots below its own budget; everything downstream
    (sentinel masking for the scan, ``owned=`` for
    :func:`probe_histogram`) already consumes the mask, so the sharded
    bodies serve packed tiles with one ``jnp.logical_and``.

    ``shards`` converts the global per-row budget to the per-shard one
    for ``probe_mode="local"`` (each shard probes its own
    ``ceil(b / R)`` lists, exactly as
    :func:`~raft_tpu.distributed.ivf.resolve_probe_budget` resolves
    the scalar budget). Pad rows carry budget 0 and own nothing."""
    slot = jnp.arange(mine.shape[1], dtype=jnp.int32)
    budget = row_probes
    if shards > 1:
        budget = -(-row_probes // shards)       # ceil(b / R), 0 -> 0
    return jnp.logical_and(mine, slot[None, :] < budget[:, None])


def unique_lists(probes: jax.Array, n_lists: int) -> jax.Array:
    """Sorted union of probed list ids, padded to the static cap
    ``min(n_lists, q * n_probes)`` with the sentinel id ``n_lists``.

    The engines' membership predicates reject sentinel steps outright
    (``lid < n_lists``), so the ragged union rides a fixed shape — the
    same tail-masking discipline as ``fused_topk``'s partial final
    block. Probe slots may themselves carry the sentinel value
    ``n_lists`` ("masked probe" — e.g. a probe owned by another shard
    of a list-sharded index): they collapse into the sentinel steps and
    contribute nothing to any query's results."""
    q, p = probes.shape
    cap = min(n_lists, q * p)
    flat = jnp.sort(probes.reshape(-1).astype(jnp.int32))
    first = jnp.concatenate(
        [jnp.ones((1,), bool), flat[1:] != flat[:-1]])
    rank = jnp.cumsum(first) - 1          # unique slot of each element
    slot = jnp.where(first, rank, cap)    # non-first -> out of range
    uniq = jnp.full((cap,), n_lists, jnp.int32)
    return uniq.at[slot].set(flat, mode="drop")


def list_major_scan(qf, data, data_norms, indices, probes,
                    filter_words=None, init_d=None, init_i=None, *,
                    k: int, metric: DistanceType, engine: str = "xla",
                    interpret: bool = False):
    """Run the probe scan list-major; returns the pre-epilog running
    top-k ``(best_d, best_i)`` in the rank-major scan's convention
    (min-space ``norms - 2 x·y`` for L2 with +inf pads; raw inner
    products for IP with -inf pads), so the caller's metric epilog is
    shared across engines. Byte (uint8/int8) lists stream as bytes, and
    their distances to byte-valued queries are exact integers in both
    engines.

    Both engines break distance ties by smallest dataset id (the
    ``_extract_topk`` order), so their outputs are bit-identical to
    each other even on exact duplicates. ``init_d``/``init_i``
    optionally provide the (q, k) running-state storage for the XLA
    engine (values are reset; the serving path donates them); the
    grouped Pallas engine carries no running state and ignores them.

    Probe slots carrying the sentinel value ``n_lists`` are masked
    probes (the list-sharded indexes mark not-owned probes this way);
    they are ignored by both engines."""
    expect(engine in ("pallas", "xla"),
           f"list_major_scan engine must be pallas|xla, got {engine!r}")
    if engine == "pallas":
        return _scan_pallas(qf, data, data_norms, indices, probes,
                            filter_words, k=k, metric=metric,
                            interpret=interpret)
    return _scan_xla(qf, data, data_norms, indices, probes, filter_words,
                     init_d, init_i, k=k, metric=metric)


# ---------------------------------------------------------------------------
# XLA list-major engine
# ---------------------------------------------------------------------------


def _smallest_k(dist, ids, k: int):
    """The k smallest of each row's min-space ``(dist, ids)`` under the
    smallest-id tie-break (the ``_extract_topk`` order) as one
    lexicographic two-key sort; unfilled slots read id -1."""
    sd, si = jax.lax.sort((dist, ids), dimension=1, num_keys=2)
    sd, si = sd[:, :k], si[:, :k]
    return sd, jnp.where(jnp.isfinite(sd), si, -1)


def _merge_smallest_id(best_d, best_i, dist, ids, k: int):
    """Min-space running top-k merge with the smallest-id tie-break, so
    the XLA engine matches the Pallas kernel bit-for-bit on exact
    ties (``merge_topk``'s positional tie-break would not), and any k
    works without unrolling k rounds."""
    return _smallest_k(jnp.concatenate([best_d, dist], axis=1),
                       jnp.concatenate([best_i, ids], axis=1), k)


def _scan_xla(qf, data, data_norms, indices, probes, filter_words,
              init_d=None, init_i=None, *, k: int, metric: DistanceType):
    from raft_tpu.neighbors.filters import test_filter

    q = qf.shape[0]
    n_lists = data.shape[0]
    ip_metric = metric == DistanceType.InnerProduct
    uniq = unique_lists(probes, n_lists)

    # min-space scan like the Pallas kernel (IP negates back at the
    # end — exact for floats), so the tie-break order is identical
    def step(carry, lid):
        best_d, best_i = carry
        lidc = jnp.minimum(lid, n_lists - 1)      # sentinel-safe index
        rows = jax.lax.dynamic_index_in_dim(
            data, lidc, 0, False).astype(jnp.float32)         # (m, d)
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
        # membership: which queries probed this list. A sentinel step
        # (lid == n_lists) matches nothing — including masked probe
        # slots, which carry the sentinel value themselves.
        probed = jnp.any(probes == lid, axis=1) & (lid < n_lists)  # (q,)
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
# Pallas grouped engine
# ---------------------------------------------------------------------------


def _group_rows(q: int, p: int, n_lists: int) -> int:
    """Rows of one query group: the mean number of (query, probe)
    pairs a probed list can have, ``q*p / min(n_lists, q*p)``, rounded
    up to a multiple of 8 and held to [8, 256]."""
    pairs = q * p
    mean = -(-pairs // min(n_lists, pairs))
    return max(8, min(256, -(-mean // 8) * 8))


def group_shape(q: int, p: int, data_shape, dtype, k: int,
                vmem_mb: int = 0):
    """``(G, S)`` of the grouped Pallas scan of a ``(q, p)`` probe
    block over lists of ``data_shape`` ``(n_lists, m, d)``: ``G`` rows
    a group (:func:`_group_rows`, lowered to what the VMEM budget
    holds beside one list block), and the static step cap
    ``S = min(n_lists, q*p) + (q*p) // G``. A list with ``c`` probers
    takes ``ceil(c / G)`` steps, and ``sum ceil(c_l / G) <= sum
    floor(c_l / G) + (lists probed) <= S``."""
    n_lists, m, d = data_shape
    sub = sublane_multiple(dtype)
    m_pad = -(-m // sub) * sub
    d_pad = -(-d // 128) * 128
    if vmem_mb <= 0:
        vmem_mb = vmem_budget_mb()
    room = (vmem_mb << 20) - _vmem_fixed(m_pad, d_pad, dtype)
    fit = room // _vmem_per_row(m_pad, d_pad, k, dtype) // 8 * 8
    g = max(8, min(_group_rows(q, p, n_lists), fit))
    pairs = q * p
    return g, min(n_lists, pairs) + pairs // g


def _schedule(probes, n_lists: int, g: int, s: int):
    """The grouped scan's steps, on the device. The ``q*p`` (query row,
    probe slot) pairs sort by list id (sentinel and out-of-range ids
    last, dropped); each list's run of pairs is cut into groups of at
    most ``g`` rows, one step each, numbered in list order. Returns

    - ``uniq`` ``(min(n_lists, q*p),)``: the probed lists in order,
      sentinel-padded (the id and norm planes are gathered by it);
    - ``steps`` ``(2*s + 1,)``: each step's list id, then each step's
      rank in ``uniq``, then the number of real steps. Steps past the
      real ones repeat the last real step's list, so their blocks are
      not fetched again;
    - ``rows`` ``(s * g,)``: the query row of each group slot, ``q``
      (a zero row) where a group is not full;
    - ``src`` ``(q*p,)``: for each pair, in ``probes``' row-major
      order, the group slot holding its results, -1 for a sentinel
      pair or a repeat of a list the row already probed."""
    q, p = probes.shape
    n = q * p
    pos = jnp.arange(n, dtype=jnp.int32)
    lid = probes.reshape(-1).astype(jnp.int32)
    lid = jnp.where((lid >= 0) & (lid < n_lists), lid, n_lists)
    lid, pair = jax.lax.sort((lid, pos), num_keys=2)
    row = pair // p
    valid = lid < n_lists
    first = jnp.concatenate([jnp.ones((1,), bool), lid[1:] != lid[:-1]])
    off = pos - jax.lax.cummax(jnp.where(first, pos, 0))
    opens = valid & (off % g == 0)
    step = jnp.cumsum(opens.astype(jnp.int32)) - 1
    n_real = jnp.sum(opens.astype(jnp.int32))
    rank = jnp.cumsum(first.astype(jnp.int32)) - 1

    cap = min(n_lists, n)
    uniq = jnp.full((cap,), n_lists, jnp.int32).at[
        jnp.where(first & valid, rank, cap)].set(lid, mode="drop")
    at = jnp.where(opens, step, s)
    lists = jnp.zeros((s,), jnp.int32).at[at].set(lid, mode="drop")
    ranks = jnp.zeros((s,), jnp.int32).at[at].set(rank, mode="drop")
    last = jnp.maximum(n_real - 1, 0)
    tail = jnp.arange(s) >= n_real
    lists = jnp.where(tail, lists[last], lists)
    ranks = jnp.where(tail, ranks[last], ranks)
    steps = jnp.concatenate([lists, ranks, n_real[None]])

    slot = jnp.where(valid, step * g + off % g, s * g)
    rows = jnp.full((s * g,), q, jnp.int32).at[slot].set(row, mode="drop")
    repeat = ~first & (row == jnp.roll(row, 1))
    src = jnp.where(valid & ~repeat, slot, -1)
    src = jnp.zeros((n,), jnp.int32).at[pair].set(src)
    return uniq, steps, rows, src


def _group_topk(dist, ids_ref, outd_ref, outi_ref, *, k: int):
    """The step's own ``(G, k)`` top-k of its min-space ``(G, m)``
    distances, smallest id first on ties; pad and filtered slots (id
    -1) never surface."""
    ids = ids_ref[0]                      # (1, m)
    dist = jnp.where(ids >= 0, dist, jnp.inf)
    d, i = _extract_topk(dist, jnp.broadcast_to(ids, dist.shape), k)
    outd_ref[0] = d
    outi_ref[0] = i


def _ivf_scan_kernel(steps_ref, q_ref, x_ref, xn_ref, ids_ref, outd_ref,
                     outi_ref, *, k: int, n_steps: int, ip_metric: bool):
    # steps past the real ones keep their blocks and compute nothing
    @pl.when(pl.program_id(0) < steps_ref[2 * n_steps])
    def _():
        # the group's (G, d) query rows against the whole (m, d) list
        # in one MXU contraction; float32 at HIGHEST, as the XLA
        # engine, so a row's products never depend on the other rows
        xt = x_ref[0].astype(jnp.float32)     # (m, d)
        ip = jax.lax.dot_general(
            q_ref[0], xt, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )                                     # (G, m)
        dist = -ip if ip_metric else xn_ref[0] - 2.0 * ip
        _group_topk(dist, ids_ref, outd_ref, outi_ref, k=k)


def _split_bf16(q):
    """``(hi, lo)`` bfloat16 halves of float32 queries: ``hi`` is ``q``
    rounded to bfloat16, ``lo`` the remainder rounded again.
    ``q - hi - lo`` is at most 2**-16 |q| elementwise, and ``lo`` is 0
    wherever ``q`` holds an integer of at most 8 significant bits (any
    byte value)."""
    hi = q.astype(jnp.bfloat16)
    lo = (q - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def _ivf_scan_bytes_kernel(steps_ref, q_ref, x_ref, xn_ref, ids_ref,
                           outd_ref, outi_ref, *, k: int, n_steps: int,
                           ip_metric: bool):
    """The grouped scan over uint8/int8 lists. The block arrives as
    bytes and widens to bfloat16 here, in VMEM; bfloat16's 8
    significant bits hold every byte value exactly. The contraction is
    two single-pass (DEFAULT precision) bfloat16 products accumulated
    in float32, ``hi . x + lo . x`` (:func:`_split_bf16`). Byte-valued
    queries have ``lo = 0``, each product of two bytes is exact in
    float32, and every partial sum is an integer of magnitude at most
    128 * 255**2 = 8,323,200 < 2**24, so ``x . q``, the stored norm and
    ``norm - 2 x . q`` are all exact integers. Float queries keep
    ``|error| <= 2**-16 * sum_i |q_i| |x_i|`` on the inner product, plus
    float32 accumulation rounding. On a v5e, at 8,192 lists x 6,976
    slots x 128 and 1,000 queries, the ungrouped form of this body took
    263 ms a call, the float body on bytes widened to float32 at
    ``HIGHEST`` 788 ms."""
    @pl.when(pl.program_id(0) < steps_ref[2 * n_steps])
    def _():
        xt = x_ref[0].astype(jnp.int32).astype(jnp.float32).astype(
            jnp.bfloat16)                     # (m, d), exact
        hi, lo = _split_bf16(q_ref[0])
        dims = (((1,), (1,)), ((), ()))
        ip = jax.lax.dot_general(hi, xt, dims,
                                 preferred_element_type=jnp.float32)
        ip = ip + jax.lax.dot_general(lo, xt, dims,
                                      preferred_element_type=jnp.float32)
        dist = -ip if ip_metric else xn_ref[0] - 2.0 * ip
        _group_topk(dist, ids_ref, outd_ref, outi_ref, k=k)


def _scan_pallas(qf, data, data_norms, indices, probes, filter_words, *,
                 k: int, metric: DistanceType, interpret: bool,
                 vmem_mb: int = 0):
    from raft_tpu.neighbors.filters import test_filter

    q, d = qf.shape
    n_lists, m, _ = data.shape
    p = probes.shape[1]
    ip_metric = metric == DistanceType.InnerProduct
    if vmem_mb <= 0:
        vmem_mb = vmem_budget_mb()
    sub = sublane_multiple(data.dtype)
    g, s = group_shape(q, p, data.shape, data.dtype, k, vmem_mb)
    uniq, steps, rows, src = _schedule(probes, n_lists, g, s)

    # id and norm planes of the probed lists, one row each in list
    # order (4 B/slot each, 1/32 of the d=128 float stream); a shared
    # bitset filter folds in here: a filtered slot becomes id -1, i.e.
    # padding, so the kernel needs no per-element word gathers
    # (Mosaic lowers those to the scalar core)
    uc = jnp.minimum(uniq, n_lists - 1)
    ids_g = jnp.take(indices, uc, axis=0)
    xn_g = jnp.take(data_norms, uc, axis=0)
    if filter_words is not None:
        bits = test_filter(filter_words, ids_g)
        ids_g = jnp.where(bits & (ids_g >= 0), ids_g, -1)

    # lane/sublane alignment; all no-ops on aligned serving layouts
    # (padded_extent rounds max_list_size to the dtype's sublane
    # multiple, d=128-multiples common)
    m_pad = -(-m // sub) * sub
    d_pad = -(-d // 128) * 128
    if m_pad != m or d_pad != d:
        data = jnp.pad(data, ((0, 0), (0, m_pad - m), (0, d_pad - d)))
        xn_g = jnp.pad(xn_g, ((0, 0), (0, m_pad - m)))
        ids_g = jnp.pad(ids_g, ((0, 0), (0, m_pad - m)),
                        constant_values=-1)
    # a unit middle axis: Mosaic wants a block's last two dims divisible
    # by (8, 128) or equal to the array's, and one list row is (1, m)
    xn_g = xn_g[:, None, :]
    ids_g = ids_g[:, None, :]

    # each group's query rows, gathered in step order; row q is zero
    qs = jnp.pad(qf.astype(jnp.float32), ((0, 1), (0, d_pad - d)))
    qg = jnp.take(qs, rows, axis=0).reshape(s, g, d_pad)

    def real(i, st):                      # a tail step keeps the last block
        return jnp.minimum(i, jnp.maximum(st[2 * s] - 1, 0))

    kernel = functools.partial(
        _ivf_scan_bytes_kernel if _is_bytes(data.dtype)
        else _ivf_scan_kernel, k=k, n_steps=s, ip_metric=ip_metric)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(s,),
        in_specs=[
            pl.BlockSpec((1, g, d_pad), lambda i, st: (real(i, st), 0, 0),
                         memory_space=pltpu.VMEM),
            # the scalar-prefetched dynamic index map: step i streams
            # list st[i]'s block; consecutive steps of one list keep
            # the block, so it moves HBM->VMEM once
            pl.BlockSpec((1, m_pad, d_pad),
                         lambda i, st: (st[i], 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, m_pad), lambda i, st: (st[s + i], 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, m_pad), lambda i, st: (st[s + i], 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((1, g, k), lambda i, st: (real(i, st), 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, g, k), lambda i, st: (real(i, st), 0, 0),
                         memory_space=pltpu.VMEM),
        ),
    )
    outd, outi = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((s, g, k), jnp.float32),
            jax.ShapeDtypeStruct((s, g, k), jnp.int32),
        ),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_mb << 20),
        interpret=interpret,
    )(steps, qg, data, xn_g, ids_g)

    # each pair's (k) block back to its row, then the row's top-k of
    # its p*k candidates under the kernel's (distance, id) order
    ok = (src >= 0)[:, None]
    at = jnp.maximum(src, 0)
    cand_d = jnp.where(ok, outd.reshape(s * g, k)[at], jnp.inf)
    cand_i = jnp.where(ok, outi.reshape(s * g, k)[at], -1)
    best_d, best_i = _smallest_k(cand_d.reshape(q, p * k),
                                 cand_i.reshape(q, p * k), k)
    if ip_metric:
        best_d = -best_d          # inf (unfilled) -> -inf, ip exact
    return best_d, best_i
