"""``comms_t``-shaped collectives over XLA — analog of
``core/comms.hpp:125-215`` (``comms_iface``) / ``:242`` (``comms_t``).

Free functions mirror the reference's collective set (allreduce, bcast,
reduce, allgather, gather, reducescatter, alltoall, p2p send/recv) as
``jax.lax`` calls valid inside a ``shard_map``-decorated program over a
named mesh axis — the TPU's NCCL ring is the ICI torus and XLA schedules
the transfers. ``Comms`` packages a mesh + axis with rank/size accessors
and a ``run`` helper so algorithms can be written against the same
"get the comms, call collectives" shape as the reference
(``resource::get_comms(handle).allreduce(...)``).

Unlike NCCL, these collectives are *compiled into* the program: there is
no stream to synchronize and no comm to abort — XLA's SPMD partitioner
proves shape agreement at trace time, which is why the reference's
error-propagating ``sync_stream`` barrier (``core/comms.hpp:282-291``)
reduces to :func:`barrier` here.
"""

from __future__ import annotations

import dataclasses
import enum
import time
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from raft_tpu.core import tracing


def _count_collective(family: str, tree) -> None:
    """Trace-time calls/bytes accounting for one collective veneer call
    (PR 7 graftscope v2): bumps ``comms.<family>.calls`` and
    ``comms.<family>.modeled_bytes`` (summed over the payload pytree's
    static shapes — available on tracers) under one lock. This runs as
    plain Python while the program is being *traced*, so the traced
    body gains no ops and no host syncs; AOT executables trace once,
    so the steady-state dispatch cost is exactly zero. The counters
    therefore inventory the collective families (and modeled per-shard
    payload bytes) compiled into the process's programs — the wire-cost
    ledger a scrape reads next to the ``serving.collective.*`` payload
    gauges."""
    nbytes = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        n = 1
        for d in shape:
            n *= int(d)
        nbytes += n * jnp.dtype(dtype).itemsize
    tracing.inc_counters({
        f"comms.{family}.calls": 1.0,
        f"comms.{family}.modeled_bytes": float(nbytes),
    })


def timed_dispatch(family: str, thunk: Callable, axis: str = "data", *,
                   modeled_bytes: float = 0.0,
                   trace_ids: Tuple[int, ...] = (),
                   attrs: Optional[dict] = None):
    """Host-side timed dispatch of one collective-bearing program —
    the PR 6 discipline applied to the mesh: timing wraps the *call
    site* of the compiled program (``thunk``), never the traced body,
    so no host syncs ride into ``shard_map``. Records a
    ``comms.dispatch.<family>`` span into the flight recorder and
    bumps ``comms.dispatch.<family>.{calls,seconds,modeled_bytes}``
    under one lock. ``modeled_bytes`` is the caller's per-dispatch
    wire model (``collective_payload_model``); ``axis`` names the mesh
    axis whose collectives the dispatch carries (span attr only).

    Returns ``thunk()``'s result unchanged. Note the timing covers
    dispatch (and whatever the thunk itself blocks on) — callers that
    want readiness-inclusive timing block inside the thunk, as the
    traced direct-search entries do."""
    t0 = time.perf_counter()
    out = thunk()
    t1 = time.perf_counter()
    a = {"axis": axis, "modeled_bytes": float(modeled_bytes)}
    a.update(attrs or {})
    tracing.record_span(f"comms.dispatch.{family}", t0, t1,
                        trace_ids=trace_ids, attrs=a)
    tracing.inc_counters({
        f"comms.dispatch.{family}.calls": 1.0,
        f"comms.dispatch.{family}.seconds": t1 - t0,
        f"comms.dispatch.{family}.modeled_bytes": float(modeled_bytes),
    })
    return out


class Op(enum.Enum):
    """Reduction ops (``core/comms.hpp`` ``op_t``: SUM/PROD/MIN/MAX)."""

    SUM = "sum"
    PROD = "prod"
    MIN = "min"
    MAX = "max"


def shard_map(f, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map`` — THE spelling every mesh program in this repo
    goes through, so the collective accounting and the R3 lint rule
    have one place to look."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def axis_size(axis: str) -> int:
    """Static mesh-axis size inside a mapped program."""
    return jax.lax.axis_size(axis)


# ---------------------------------------------------------------------------
# collectives — call inside shard_map over the named axis
# ---------------------------------------------------------------------------


def _allreduce_impl(x, op: Op, axis: str):
    """Uncounted all-reduce body — delegating veneers (:func:`reduce`,
    :func:`reducescatter`'s non-SUM branch) call this so one logical
    collective bumps the ledger exactly once, under its own family."""
    if op == Op.SUM:
        return jax.lax.psum(x, axis)
    if op == Op.MAX:
        return jax.lax.pmax(x, axis)
    if op == Op.MIN:
        return jax.lax.pmin(x, axis)
    # PROD: no native pprod — gather then reduce (correct for any sign)
    return jnp.prod(jax.lax.all_gather(x, axis), axis=0)


def allreduce(x, op: Op = Op.SUM, axis: str = "data"):
    """``comms_t::allreduce`` → psum/pmax/pmin (XLA all-reduce on ICI)."""
    _count_collective("allreduce", x)
    return _allreduce_impl(x, op, axis)


def bcast(x, root: int = 0, axis: str = "data"):
    """``comms_t::bcast``: every rank ends with root's value."""
    _count_collective("bcast", x)
    rank = jax.lax.axis_index(axis)
    contrib = jnp.where(rank == root, x, jnp.zeros_like(x))
    return jax.lax.psum(contrib, axis)


def reduce(x, root: int = 0, op: Op = Op.SUM, axis: str = "data"):
    """``comms_t::reduce``: the reduced value (the reference only
    guarantees it on root; here every rank gets it, a superset).

    Cost note (VERDICT r2 weak #6): XLA exposes no root-only
    collective, but on the ICI torus this superset is NOT an R× tax —
    ring all-reduce and optimal reduce-to-root both move ~(R-1)/R of
    the payload per link; only the final broadcast leg (~1× payload)
    is extra. The same argument covers :func:`gather` vs a true
    root-only gather (ring allgather's per-link traffic equals the
    hop-by-hop forwarding a rooted gather needs). DCN-spanning meshes
    are where a rooted variant would pay; revisit if a DCN profile
    shows these hot."""
    _count_collective("reduce", x)
    return _allreduce_impl(x, op, axis)


def allgather(x, axis: str = "data", tiled: bool = False):
    """``comms_t::allgather``: stack (or concat when ``tiled``) every
    rank's block along a new leading axis."""
    _count_collective("allgather", x)
    return jax.lax.all_gather(x, axis, tiled=tiled)


# low-precision wire formats for result-carrying collectives — the
# EQuARX move (PAPERS.md): the ICI payload shrinks, the math around the
# collective stays full precision. "f32" is the identity.
WIRE_DTYPES = ("f32", "bf16")


def resolve_wire_dtype(wire_dtype: str):
    """Map a ``wire_dtype`` param to its jnp dtype (validating)."""
    if wire_dtype not in WIRE_DTYPES:
        raise ValueError(
            f"wire_dtype must be one of {WIRE_DTYPES}, got {wire_dtype!r}")
    return jnp.float32 if wire_dtype == "f32" else jnp.bfloat16


def allgather_wire(x, axis: str = "data", wire_dtype: str = "f32"):
    """:func:`allgather` with an optional low-precision wire format:
    the payload is cast to ``wire_dtype`` *before* the collective (so
    the gather moves half the bytes for bf16) and upcast back after.
    Callers that merge gathered candidates should re-rank the ties the
    compression creates deterministically (the distributed searches
    tie-break by exact id)."""
    wd = resolve_wire_dtype(wire_dtype)
    if x.dtype == wd:
        _count_collective("allgather_wire", x)
        return jax.lax.all_gather(x, axis)
    xw = x.astype(wd)
    _count_collective("allgather_wire", xw)
    return jax.lax.all_gather(xw, axis).astype(x.dtype)


# wire formats for result-*reducing* collectives (allreduce /
# reducescatter): SUM tolerates int8 too — EQuARX's recipe quantizes
# per feature block, moves codes + scale planes on the wire, and sums
# in ONE dequantized f32 epilog, so the narrow wire never compounds
# per-hop rounding
REDUCE_WIRE_DTYPES = ("f32", "bf16", "int8")

# feature-block width of the EQuARX block-wise scales: one f32 scale
# per 128 payload elements — the lane width, and small enough that one
# outlier only poisons its own block's resolution
QUANT_BLOCK = 128


def resolve_reduce_wire_dtype(wire_dtype: str) -> str:
    """Validate a reducing-collective ``wire_dtype`` (identity mapping —
    ``int8`` has no jnp carrier; the quantized collectives pack it with
    explicit block-wise scale planes)."""
    if wire_dtype not in REDUCE_WIRE_DTYPES:
        raise ValueError(
            f"reduce wire_dtype must be one of {REDUCE_WIRE_DTYPES}, "
            f"got {wire_dtype!r}")
    return wire_dtype


def _quantize_blocks(x, block: int):
    """Symmetric EQuARX block quantization along the last axis: pad to
    a multiple of ``block``, one f32 scale (``max|block| / 127``) per
    feature block. Returns ``(codes int8 (..., nb, block),
    scales f32 (..., nb, 1))`` — the uncounted prolog shared by the
    quantized reducing collectives."""
    n = x.shape[-1]
    nb = -(-n // block)
    pad = nb * block - n
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    xb = x.reshape(x.shape[:-1] + (nb, block))
    scale = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    scale = jnp.maximum(scale, jnp.finfo(jnp.float32).tiny)
    q8 = jnp.clip(jnp.round(xb * (127.0 / scale)), -127, 127)
    return q8.astype(jnp.int8), scale


def _dequantize_blocks(xb, n: int):
    """Flatten a dequantized (..., nb, block) f32 block view back to
    (..., n) — the epilog twin of :func:`_quantize_blocks`."""
    flat = xb.reshape(xb.shape[:-2] + (xb.shape[-2] * xb.shape[-1],))
    return flat[..., :n]


def allreduce_quantized(x, op: Op = Op.SUM, axis: str = "data",
                        wire_dtype: str = "f32",
                        block: int = QUANT_BLOCK):
    """:func:`allreduce` with an opt-in quantized wire (the EQuARX
    move applied to the *reducing* collectives — the distributed
    k-means centroid-sum path):

    - ``"f32"``: delegates to the exact all-reduce (counted under this
      veneer's own ledger family).
    - integer payloads (counts): ALWAYS the exact int32 wire,
      whatever ``wire_dtype`` says — quantizing a count is never
      acceptable, and int32 already matches f32's wire bytes.
    - ``"bf16"``: the payload travels as bf16 and every rank's
      contribution is summed in ONE f32 epilog (gather + sum), so the
      narrow wire never compounds per-hop rounding.
    - ``"int8"``: block-wise scales (:data:`QUANT_BLOCK` features per
      f32 scale) ride beside the int8 codes; one dequantized f32
      epilog sums the per-rank contributions.

    Narrow wires are SUM-only (MAX/MIN/PROD of quantized codes would
    reduce *rounded* values with no epilog to repair them)."""
    resolve_reduce_wire_dtype(wire_dtype)
    if jnp.issubdtype(x.dtype, jnp.integer):
        xi = x.astype(jnp.int32)
        _count_collective("allreduce_quantized", xi)
        return _allreduce_impl(xi, op, axis).astype(x.dtype)
    if wire_dtype == "f32":
        _count_collective("allreduce_quantized", x)
        return _allreduce_impl(x, op, axis)
    if op != Op.SUM:
        raise ValueError(
            f"quantized allreduce wires are SUM-only, got {op}")
    if wire_dtype == "bf16":
        xw = x.astype(jnp.bfloat16)
        _count_collective("allreduce_quantized", xw)
        return jnp.sum(jax.lax.all_gather(xw, axis).astype(jnp.float32),
                       axis=0)
    q8, scale = _quantize_blocks(x, block)
    _count_collective("allreduce_quantized", (q8, scale))
    all_q = jax.lax.all_gather(q8, axis)
    all_s = jax.lax.all_gather(scale, axis)
    acc = jnp.sum(all_q.astype(jnp.float32) * (all_s * (1.0 / 127.0)),
                  axis=0)
    return _dequantize_blocks(acc, x.shape[-1])


def reducescatter_quantized(x, op: Op = Op.SUM, axis: str = "data",
                            wire_dtype: str = "f32",
                            block: int = QUANT_BLOCK, fold=None):
    """:func:`reducescatter` with an opt-in quantized wire: quantize →
    exchange row blocks in the narrow dtype (+ scale planes) → ONE
    dequantized fold epilog. Rank r returns the ``op``-reduction of
    every rank's r-th row block (leading dim must divide the axis).

    ``fold`` replaces the ``op``-reduction with the caller's own
    associative merge over the dequantized ``(R, rows/R, ...)`` f32
    rank stack — the hook the 2-D mesh query×list top-k merge folds
    through (its reduction is a sort-merge, not an :class:`Op`; the
    received blocks stack in rank order, matching the allgather-merge
    candidate order exactly).

    Integer payloads always take the exact int32 wire; the pure
    ``f32``/``SUM``/no-``fold`` case lowers to the native
    psum_scatter."""
    resolve_reduce_wire_dtype(wire_dtype)
    if (wire_dtype == "f32" and op == Op.SUM and fold is None
            and not jnp.issubdtype(x.dtype, jnp.integer)):
        _count_collective("reducescatter_quantized", x)
        return jax.lax.psum_scatter(x, axis, tiled=True)
    if jnp.issubdtype(x.dtype, jnp.integer):
        xi = x.astype(jnp.int32)
        _count_collective("reducescatter_quantized", xi)
        stack = _alltoall_impl(xi, axis).astype(x.dtype)
    elif wire_dtype == "f32":
        _count_collective("reducescatter_quantized", x)
        stack = _alltoall_impl(x, axis)
    elif wire_dtype == "bf16":
        xw = x.astype(jnp.bfloat16)
        _count_collective("reducescatter_quantized", xw)
        stack = _alltoall_impl(xw, axis).astype(jnp.float32)
    else:
        if op != Op.SUM and fold is None:
            raise ValueError(
                f"quantized reducescatter wires are SUM-only, got {op}")
        q8, scale = _quantize_blocks(x, block)
        _count_collective("reducescatter_quantized", (q8, scale))
        all_q = _alltoall_impl(q8, axis)
        all_s = _alltoall_impl(scale, axis)
        stack = _dequantize_blocks(
            all_q.astype(jnp.float32) * (all_s * (1.0 / 127.0)),
            x.shape[-1])
    if fold is not None:
        return fold(stack)
    if op == Op.SUM:
        return jnp.sum(stack, axis=0)
    if op == Op.MAX:
        return jnp.max(stack, axis=0)
    if op == Op.MIN:
        return jnp.min(stack, axis=0)
    return jnp.prod(stack, axis=0)


# wire formats for the coarse/probe-candidate exchange: the payload is
# *candidate scores* (compared, never accumulated), so it tolerates a
# harder squeeze than the result merge — int8 with a per-row affine
# scale pair (the EQuARX block-scaling recipe) quarters the bytes of f32
PROBE_WIRE_DTYPES = ("f32", "bf16", "int8")


def resolve_probe_wire_dtype(wire_dtype: str) -> str:
    """Validate a probe-exchange ``wire_dtype`` (identity mapping —
    ``int8`` has no jnp carrier; :func:`allgather_quantized` packs it
    with an explicit per-row scale plane)."""
    if wire_dtype not in PROBE_WIRE_DTYPES:
        raise ValueError(
            f"probe wire_dtype must be one of {PROBE_WIRE_DTYPES}, "
            f"got {wire_dtype!r}")
    return wire_dtype


def allgather_quantized(x, axis: str = "data", wire_dtype: str = "f32",
                        scale_ref=None):
    """:func:`allgather` of a (rows, n) score block with an opt-in
    quantized wire format, dequantized after the collective:

    - ``"f32"`` / ``"bf16"``: :func:`allgather_wire` (cast-only).
    - ``"int8"``: affine per-row quantization — each row travels as
      int8 codes plus TWO f32 planes (the row's minimum and range), so
      the payload is ~1/4 of f32 for n >> 1. Rounding is
      round-half-to-even (jnp.round), deterministic across shards.

    ``scale_ref`` (int8 only) supplies the block the per-row affine
    scales derive from — pass the FULL pre-selection score block when
    ``x`` is a selected subset, and the codes become independent of
    *which* candidates were selected (and of how many): the
    block-independence the ragged serving family's cap-vs-solo
    bit-identity contract needs (PR 17 retired the int8 ragged pin on
    exactly this property). Quantization is monotone per row, so
    ranking survives up to the ties it creates — the caller must break
    those deterministically (the probe selects sort by
    (distance, id))."""
    if wire_dtype != "int8":
        return allgather_wire(x, axis, wire_dtype)
    ref = x if scale_ref is None else scale_ref
    lo = jnp.min(ref, axis=-1, keepdims=True)
    span = jnp.max(ref, axis=-1, keepdims=True) - lo
    span = jnp.maximum(span, jnp.finfo(jnp.float32).tiny)
    q8 = jnp.clip(jnp.round((x - lo) * (254.0 / span)) - 127.0,
                  -127, 127).astype(jnp.int8)
    _count_collective("allgather_quantized", (q8, lo, span))
    all_q = jax.lax.all_gather(q8, axis)
    all_lo = jax.lax.all_gather(lo, axis)
    all_sp = jax.lax.all_gather(span, axis)
    return ((all_q.astype(jnp.float32) + 127.0) * (all_sp * (1.0 / 254.0))
            + all_lo)


def gather(x, root: int = 0, axis: str = "data", tiled: bool = False):
    """``comms_t::gather`` (valid on every rank, superset of reference;
    per-link cost on ICI matches a rooted gather — see
    :func:`reduce`)."""
    _count_collective("gather", x)
    return jax.lax.all_gather(x, axis, tiled=tiled)


def allgatherv(x, valid_size, axis: str = "data"):
    """``comms_t::allgatherv``: ragged gather emulated with the padded
    block + per-rank sizes (TPU collectives need static shapes).

    Returns (stacked (n_ranks, max_block, ...), sizes (n_ranks,))."""
    sizes = jnp.asarray(valid_size, jnp.int32)
    _count_collective("allgatherv", (x, sizes))   # both wire payloads
    return (
        jax.lax.all_gather(x, axis),
        jax.lax.all_gather(sizes, axis),
    )


def reducescatter(x, op: Op = Op.SUM, axis: str = "data"):
    """``comms_t::reducescatter`` → psum_scatter over the leading dim."""
    _count_collective("reducescatter", x)
    if op != Op.SUM:
        gathered = _allreduce_impl(x, op, axis)
        n = axis_size(axis)
        rank = jax.lax.axis_index(axis)
        block = x.shape[0] // n
        return jax.lax.dynamic_slice_in_dim(gathered, rank * block, block)
    return jax.lax.psum_scatter(x, axis, tiled=True)


def _alltoall_impl(x, axis: str):
    """Uncounted all-to-all body — :func:`reducescatter_quantized`'s
    row-block exchange routes through this so one logical quantized
    collective bumps the ledger exactly once, under its own family."""
    n = axis_size(axis)
    blocks = x.reshape((n, x.shape[0] // n) + x.shape[1:])
    return jax.lax.all_to_all(blocks, axis, split_axis=0, concat_axis=0)


def alltoall(x, axis: str = "data"):
    """``comms_t`` device_multicast/alltoall: exchange row blocks so rank
    r receives block r from every rank (``lax.all_to_all``)."""
    _count_collective("alltoall", x)
    return _alltoall_impl(x, axis)


def _ring_permute(x, offset: int, axis: str):
    """Uncounted ring-shift body shared by send/recv (each veneer
    bumps its own ledger family exactly once)."""
    n = axis_size(axis)
    perm = [(i, (i + offset) % n) for i in range(n)]
    return jax.lax.ppermute(x, axis, perm)


def device_send(x, dest_offset: int = 1, axis: str = "data"):
    """Ring send: rank r's value moves to rank (r + dest_offset) % n —
    the p2p pattern expressible on the ICI torus (``comms_t::device_send``;
    arbitrary pairs route through :func:`device_sendrecv` perms)."""
    _count_collective("device_send", x)
    return _ring_permute(x, dest_offset, axis)


def device_recv(x, src_offset: int = 1, axis: str = "data"):
    """Ring recv: receive the value from rank (r - src_offset) % n."""
    _count_collective("device_recv", x)
    return _ring_permute(x, src_offset, axis)


def device_sendrecv(x, perm: Sequence[tuple], axis: str = "data"):
    """``comms_t::device_sendrecv``: explicit (src, dst) pair list."""
    _count_collective("device_sendrecv", x)
    return jax.lax.ppermute(x, axis, list(perm))


def mark_varying(x, axis: str = "data"):
    """Mark a value device-varying for shard_map's validity check."""
    return jax.lax.pcast(x, axis, to="varying")


def barrier(axis: str = "data"):
    """``comms_t::barrier`` / ``sync_stream``: a psum fence all ranks
    must reach; returns the rank count."""
    return jax.lax.psum(jnp.ones((), jnp.int32), axis)


def rank(axis: str = "data"):
    """``comms_t::get_rank``."""
    return jax.lax.axis_index(axis)


def size(axis: str = "data"):
    """``comms_t::get_size``."""
    return axis_size(axis)


# ---------------------------------------------------------------------------
# Comms handle
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Comms:
    """Mesh + axis handle injected into :class:`~raft_tpu.core.Resources`
    (role of ``std_comms`` built by ``build_comms_nccl_only``,
    ``comms/std_comms.hpp:69``, and of raft-dask's ``Comms``,
    ``raft_dask/common/comms.py:39``).

    ``axis`` is the mesh axis this communicator spans; ``split`` carves
    sub-communicators out of a multi-axis mesh the way ``comm_split`` +
    ``set_subcomm`` build 2D process grids (``core/resource/sub_comms.hpp``).
    """

    mesh: Mesh
    axis: str = "data"

    @property
    def size(self) -> int:
        return self.mesh.shape[self.axis]

    @property
    def nranks(self) -> int:
        return self.size

    @property
    def process_rank(self) -> int:
        """This *process*'s rank (multi-host); device-level rank is
        :func:`rank` inside the mapped program."""
        return jax.process_index()

    def sharding(self, *spec) -> NamedSharding:
        """NamedSharding over this comms' mesh."""
        return NamedSharding(self.mesh, P(*spec))

    def row_sharded(self) -> NamedSharding:
        return self.sharding(self.axis)

    def replicated(self) -> NamedSharding:
        return self.sharding()

    def run(
        self,
        fn: Callable,
        *args,
        in_specs,
        out_specs,
        check_vma: bool = True,
    ):
        """shard_map ``fn`` over this mesh: the body may call the module's
        collectives with ``axis=self.axis``."""
        return shard_map(
            fn, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=check_vma,
        )(*args)

    def split(self, axis: str) -> "Comms":
        """Sub-communicator over another axis of the same mesh
        (``comms_t::comm_split`` for static 2D grids)."""
        if axis not in self.mesh.axis_names:
            raise ValueError(f"axis {axis!r} not in mesh {self.mesh.axis_names}")
        return Comms(self.mesh, axis)

    # -- self-tests (role of comms/comms_test.hpp:34-118) --------------------

    def test_allreduce(self) -> bool:
        n = self.size
        x = jnp.arange(n, dtype=jnp.float32)
        out = self.run(
            lambda v: allreduce(v, Op.SUM, self.axis),
            jax.device_put(x, self.row_sharded()),
            in_specs=P(self.axis), out_specs=P(self.axis),
        )
        return bool(jnp.all(out == jnp.sum(x)))

    def test_bcast(self, root: int = 0) -> bool:
        n = self.size
        x = jnp.arange(n, dtype=jnp.float32) + 3
        out = self.run(
            lambda v: bcast(v, root, self.axis),
            jax.device_put(x, self.row_sharded()),
            in_specs=P(self.axis), out_specs=P(self.axis),
        )
        return bool(jnp.all(out == x[root]))

    def test_pointToPoint_simple_send_recv(self) -> bool:
        n = self.size
        x = jnp.arange(n, dtype=jnp.float32)
        out = self.run(
            lambda v: device_send(v, 1, self.axis),
            jax.device_put(x, self.row_sharded()),
            in_specs=P(self.axis), out_specs=P(self.axis),
        )
        return bool(jnp.all(out == jnp.roll(x, 1)))
