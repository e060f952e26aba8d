"""Per-primitive micro-benchmarks — the ``cpp/bench/prims`` analog.

Each bench reports wall-clock ms plus achieved GB/s (against the bytes
the primitive must move through HBM) and MFU (against the configured
matmul peak), so per-primitive regressions and anomalies (e.g. a bf16
path running slower than f32) are visible in isolation rather than
buried in an end-to-end number. Reference: the gbench suite under
``cpp/bench/prims/`` (e.g. ``matrix/select_k.cu``).

Run::

    python -m raft_tpu.bench.prims [--filter substr] [--size tiny|small|full]
        [--out results.jsonl] [--seconds 10]

Output: one JSON line per bench on stdout (and optionally appended to
``--out``). The peaks come from the chip table
(:mod:`raft_tpu.core.chips`, keyed by ``device_kind``); off the TPU
``bw_frac`` and ``mfu`` are null — a CPU run has no device roofline.

Timing is fetch-anchored and pipelined exactly like ``bench.py``: each
measurement dispatches a run of iterations and fetches one element at
the end, so per-dispatch overhead amortizes out.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Callable, Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from raft_tpu.core.chips import chip_spec


def _peaks() -> Optional[tuple]:
    """(bf16 FLOP/s, HBM bytes/s) of the attached chip; None off the
    TPU. An unknown TPU kind raises (see ``chip_spec``)."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return None
    spec = chip_spec(dev)
    return spec.bf16_flops_per_s, spec.hbm_bytes_per_s


def _fetch(out) -> None:
    """Anchor completion on a host fetch of one element."""
    leaf = jax.tree_util.tree_leaves(out)[0]
    np.asarray(leaf.ravel()[:1])


def timeit_stats(fn: Callable[[], object], budget_s: float = 10.0) -> Dict:
    """Pipelined, fetch-anchored timing: dispatch a run of iterations
    and fetch once, so per-call dispatch overhead amortizes out. This is
    THE timing methodology for the repo — ``bench.py`` and the prims
    suite both call it, so a fix to the anchor or pipe sizing lands in
    both. Returns best/median seconds-per-iteration plus the schedule
    used."""
    _fetch(fn())  # compile + warm
    t0 = time.perf_counter()
    _fetch(fn())
    est = max(time.perf_counter() - t0, 1e-5)
    pipe = max(3, min(50, int(budget_s / 2 / est)))
    rates = []
    t_meas = time.perf_counter()
    while len(rates) < 6 and (
        not rates or time.perf_counter() - t_meas < budget_s
    ):
        t0 = time.perf_counter()
        out = None
        for _ in range(pipe):
            out = fn()
        _fetch(out)
        rates.append((time.perf_counter() - t0) / pipe)
    return {
        "best_s": min(rates),
        "median_s": sorted(rates)[len(rates) // 2],
        "single_iter_est_s": est,
        "pipe": pipe,
        "batches": len(rates),
    }


def timeit(fn: Callable[[], object], budget_s: float = 10.0) -> float:
    """Best steady-state seconds/iteration (see :func:`timeit_stats`)."""
    return timeit_stats(fn, budget_s)["best_s"]


def loop_queries(fn: Callable, queries, m: int) -> Callable[[], object]:
    """Wrap a ``(d, i) = fn(q)`` search in an m-iteration in-program
    loop whose carried query tile gets a data-dependent perturbation
    each step — XLA can neither hoist nor CSE the body, so one dispatch
    executes m real searches back-to-back."""
    import jax.numpy as jnp

    @jax.jit
    def run(q0):
        def body(_, carry):
            acc, q = carry
            d, _ = fn(q)
            pert = jnp.tanh(jnp.nanmin(d)).astype(jnp.float32) * 1e-6
            return (acc + pert, (q0 + pert).astype(q0.dtype))

        acc, _ = jax.lax.fori_loop(0, m, body, (jnp.float32(0.0), q0))
        return acc

    return lambda: run(queries)


# Slope pass spreads per dataset dtype, shared by bench.py and the
# profile scripts so a jitter recalibration can't drift between them.
# Calibration (r3): dispatch jitter of up to ~4 ms swallowed a 2-vs-8
# spread at f32 (~0.9 ms/pass), and bf16 passes are ~2x faster, so
# bf16 gets twice the passes.
SLOPE_PASSES = {"float32": (2, 16), "bfloat16": (2, 32)}


def slope_passes(dtype) -> tuple:
    """(low, high) in-program pass counts for slope timing of a
    dataset-streaming kernel at ``dtype`` (jnp/np dtype, scalar type,
    or name)."""
    name = np.dtype(dtype).name
    return SLOPE_PASSES.get(name, SLOPE_PASSES["float32"])


def timeit_slope(make_fn: Callable[[int], Callable[[], object]],
                 m1: int, m2: int, reps: int = 4) -> Dict:
    """Per-iteration seconds from the slope between an m1- and an
    m2-iteration in-program loop: slope = (T(m2) - T(m1)) / (m2 - m1).
    Cancels per-dispatch overhead entirely, which otherwise floors
    every single-dispatch number regardless of kernel cost. Uses
    best-of-``reps`` walls for each loop length."""
    f1, f2 = make_fn(m1), make_fn(m2)

    def best_wall(f):
        _fetch(f())  # compile + warm
        walls = []
        for _ in range(reps):
            t0 = time.perf_counter()
            _fetch(f())
            walls.append(time.perf_counter() - t0)
        return min(walls)

    t1, t2 = best_wall(f1), best_wall(f2)
    return {
        "slope_s": (t2 - t1) / (m2 - m1),
        "t1_s": t1,
        "t2_s": t2,
        "m1": m1,
        "m2": m2,
    }


@dataclasses.dataclass
class Prim:
    """One registered micro-bench: ``make(size)`` returns
    ``(run_fn, bytes_moved, flops, shape_desc)``."""

    name: str
    make: Callable[[str], tuple]


_REGISTRY: List[Prim] = []


def _register(name: str):
    def deco(fn):
        _REGISTRY.append(Prim(name, fn))
        return fn
    return deco


def _dims(size: str, tiny, small, full):
    return {"tiny": tiny, "small": small, "full": full}[size]


# ---------------------------------------------------------------------------
# the primitives
# ---------------------------------------------------------------------------


def _interp() -> bool:
    """Pallas kernels need interpret mode off-TPU; timings there are
    only smoke-level, but the suite stays runnable in CPU CI."""
    return jax.default_backend() != "tpu"


@_register("stream_read_f32")
def _stream_read(size: str):
    """Pure HBM stream ceiling: Pallas row-sum over a large array.
    This is the number every bandwidth-bound bench below is judged
    against (the 'prove the ceiling' probe)."""
    from raft_tpu.ops.fused_topk import stream_read_sum

    n, d = _dims(size, (1 << 14, 128), (1 << 18, 128), (1 << 22, 128))
    x = jax.random.normal(jax.random.key(0), (n, d), jnp.float32)
    jax.block_until_ready(x)
    return (lambda: stream_read_sum(x, interpret=_interp()),
            n * d * 4, n * d, f"{n}x{d} f32")


@_register("stream_read_f32_xl")
def _stream_read_xl(size: str):
    """The anomaly-resolver probe (VERDICT r2 weak #3): a working set
    ≥ 4 GB at --size full, so no cache level can flatter the slope —
    an above-roofline reading here would mean the methodology itself
    is broken, not reuse. tiny/small stay CI-sized."""
    from raft_tpu.ops.fused_topk import stream_read_sum

    n, d = _dims(size, (1 << 14, 128), (1 << 18, 128), (1 << 23, 128))
    x = jax.random.normal(jax.random.key(3), (n, d), jnp.float32)
    jax.block_until_ready(x)
    return (lambda: stream_read_sum(x, interpret=_interp()),
            n * d * 4, n * d, f"{n}x{d} f32 ({n * d * 4 / 1e9:.1f} GB)")


@_register("stream_read_bf16")
def _stream_read_bf16(size: str):
    from raft_tpu.ops.fused_topk import stream_read_sum

    n, d = _dims(size, (1 << 14, 128), (1 << 18, 128), (1 << 22, 128))
    x = jax.random.normal(jax.random.key(0), (n, d), jnp.bfloat16)
    jax.block_until_ready(x)
    return (lambda: stream_read_sum(x, interpret=_interp()),
            n * d * 2, n * d, f"{n}x{d} bf16")


@_register("pairwise_l2")
def _pairwise_l2(size: str):
    from raft_tpu.distance import pairwise_distance
    from raft_tpu.distance.types import DistanceType

    m, n, d = _dims(size, (256, 256, 64), (2048, 2048, 128),
                    (8192, 8192, 128))
    kx, ky = jax.random.split(jax.random.key(1))
    x = jax.random.normal(kx, (m, d), jnp.float32)
    y = jax.random.normal(ky, (n, d), jnp.float32)
    jax.block_until_ready((x, y))
    # NB every run fn below receives its arrays as jit ARGUMENTS (not
    # zero-arg closures): captured arrays become compile-time constants
    # and XLA constant-folds the whole benchmark away
    run = jax.jit(lambda a, b: pairwise_distance(
        None, a, b, DistanceType.L2Expanded))
    return (lambda: run(x, y), (m * d + n * d + m * n) * 4, 2 * m * n * d,
            f"{m}x{n}x{d} f32")


@_register("select_k_xla")
def _select_k_xla(size: str):
    from raft_tpu.matrix.select_k import select_k

    b, n, k = _dims(size, (16, 1 << 12, 32), (64, 1 << 16, 64),
                    (64, 1 << 20, 64))
    v = jax.random.normal(jax.random.key(2), (b, n), jnp.float32)
    jax.block_until_ready(v)
    return (lambda: select_k(None, v, k), b * n * 4, 0, f"{b}x{n} k={k}")


@_register("select_k_pallas")
def _select_k_pallas(size: str):
    from raft_tpu.ops.fused_topk import select_k_tiles

    b, n, k = _dims(size, (16, 1 << 12, 32), (64, 1 << 16, 64),
                    (64, 1 << 20, 64))
    v = jax.random.normal(jax.random.key(2), (b, n), jnp.float32)
    jax.block_until_ready(v)
    return (lambda: select_k_tiles(v, k, interpret=_interp()),
            b * n * 4, 0, f"{b}x{n} k={k}")


@_register("fused_knn_f32")
def _fused_knn_f32(size: str):
    return _fused_knn_case(size, jnp.float32)


@_register("fused_knn_bf16")
def _fused_knn_bf16(size: str):
    return _fused_knn_case(size, jnp.bfloat16)


def _fused_knn_case(size: str, dtype):
    from raft_tpu.distance.types import DistanceType
    from raft_tpu.ops.fused_topk import fused_knn

    n, d, q, k = _dims(size, (1 << 13, 128, 10, 10),
                       (1 << 17, 128, 10, 10), (1 << 20, 128, 10, 10))
    kd, kq = jax.random.split(jax.random.key(3))
    ds = jax.random.normal(kd, (n, d), jnp.float32)
    norms = jnp.sum(jnp.square(ds), axis=1)
    ds = ds.astype(dtype)
    qs = jax.random.normal(kq, (q, d), jnp.float32)
    jax.block_until_ready((ds, qs, norms))
    itemsize = 2 if dtype == jnp.bfloat16 else 4
    return (lambda: fused_knn(qs, ds, k, DistanceType.L2Expanded,
                              dataset_norms=norms, interpret=_interp()),
            n * d * itemsize, 2 * q * n * d,
            f"{n}x{d} {np.dtype(dtype).name} q={q} k={k}")


@_register("pq_score_onehot")
def _pq_score_onehot(size: str):
    return _pq_score_case(size, "onehot")


@_register("pq_score_gather")
def _pq_score_gather(size: str):
    return _pq_score_case(size, "gather")


@_register("pq_score_select4")
def _pq_score_select4(size: str):
    """The masked-sum path at its design point: 4-bit codes (J=16)."""
    return _pq_score_case(size, "select", J=16)


def _pq_score_case(size: str, mode: str, J: int = 256):
    from raft_tpu.neighbors.ivf_pq import score_fn

    q, m, s, _ = _dims(size, (4, 1 << 10, 16, 256), (10, 1 << 15, 64, 256),
                       (10, 1 << 17, 64, 256))
    kl, kr = jax.random.split(jax.random.key(4))
    lut = jax.random.normal(kl, (q, s, J), jnp.float32)
    rows = jax.random.randint(kr, (q, m, s), 0, J, jnp.int32).astype(jnp.uint8)
    jax.block_until_ready((lut, rows))
    jscore = jax.jit(score_fn(mode, J))
    run = lambda: jscore(lut, rows)  # noqa: E731
    # effective flops: the useful work is q·m·s adds; the one-hot and
    # select paths physically perform ~2·q·m·s·J ops — report the
    # physical number so MFU reflects what the units execute
    flops = 2 * q * m * s * J if mode in ("onehot", "select") else q * m * s
    nbytes = q * m * s + q * s * J * 4 + q * m * 4  # codes + LUT + out
    return (run, nbytes, flops, f"q={q} m={m} s={s} J={J}")


@_register("bq_score")
def _bq_score(size: str):
    """IVF-BQ sign-code scoring core (int32 word unpack + fused level
    GEMMs) — the lookup-free alternative to the pq_score family (the
    rank-major estimate path; the fused engines score the packed
    words directly by XOR+popcount)."""
    from raft_tpu.neighbors.ivf_bq import _unpack_pm1

    q, m, d, bits = _dims(size, (4, 1 << 10, 64, 2), (10, 1 << 15, 128, 2),
                          (10, 1 << 17, 128, 2))
    kq_, kb = jax.random.split(jax.random.key(12))
    qrot = jax.random.normal(kq_, (q, d), jnp.float32)
    words = jax.random.randint(kb, (q, m, bits * d // 32),
                               jnp.iinfo(jnp.int32).min,
                               jnp.iinfo(jnp.int32).max, jnp.int32)
    a = jnp.abs(jax.random.normal(kb, (q, m, bits), jnp.float32))
    jax.block_until_ready((qrot, words, a))

    @jax.jit
    def score(qr, wo, aa):
        pm1 = _unpack_pm1(wo).reshape(q, m, bits, d)
        crosses = jnp.einsum("qd,qmld->qml", qr.astype(jnp.bfloat16), pm1,
                             preferred_element_type=jnp.float32)
        return jnp.sum(aa * crosses, axis=-1)

    nbytes = q * m * bits * d // 8 + q * d * 4 + q * m * 4
    return (lambda: score(qrot, words, a), nbytes, 2 * q * m * bits * d,
            f"q={q} m={m} d={d} bits={bits}")


@_register("fused_l2_nn")
def _fused_l2_nn(size: str):
    from raft_tpu.distance.fused_l2_nn import fused_l2_nn_argmin

    n, c, d = _dims(size, (1 << 12, 256, 64), (1 << 17, 1024, 128),
                    (1 << 18, 1024, 128))
    kx, kc = jax.random.split(jax.random.key(5))
    x = jax.random.normal(kx, (n, d), jnp.float32)
    cent = jax.random.normal(kc, (c, d), jnp.float32)
    jax.block_until_ready((x, cent))
    return (lambda: fused_l2_nn_argmin(None, x, cent),
            n * d * 4, 2 * n * c * d, f"{n}x{c}x{d} f32")


@_register("norm_rows")
def _norm_rows(size: str):
    """Row L2 norms (``cpp/bench/prims/linalg`` norm family)."""
    from raft_tpu.linalg import L2Norm, norm

    n, d = _dims(size, (1 << 13, 128), (1 << 18, 128), (1 << 20, 128))
    x = jax.random.normal(jax.random.key(6), (n, d), jnp.float32)
    jax.block_until_ready(x)
    jn = jax.jit(lambda v: norm(None, v, L2Norm))
    return (lambda: jn(x), n * d * 4, 2 * n * d, f"{n}x{d} f32")


@_register("matrix_gather")
def _matrix_gather(size: str):
    """Row gather (``cpp/bench/prims/matrix/gather.cu``) — the op whose
    TPU scalar-core lowering motivated the gather-free redesigns."""
    n, m, d = _dims(size, (1 << 13, 1 << 10, 128), (1 << 18, 1 << 15, 128),
                    (1 << 20, 1 << 17, 128))
    from raft_tpu.matrix import gather

    kx, ki = jax.random.split(jax.random.key(7))
    x = jax.random.normal(kx, (n, d), jnp.float32)
    idx = jax.random.randint(ki, (m,), 0, n, jnp.int32)
    jax.block_until_ready((x, idx))
    jg = jax.jit(gather)
    return (lambda: jg(x, idx), m * d * 4, 0, f"{m} of {n}x{d}")


@_register("rng_normal")
def _rng_normal(size: str):
    """RNG throughput (``cpp/bench/prims/random``)."""
    from raft_tpu.random import RngState, normal

    n, d = _dims(size, (1 << 13, 128), (1 << 18, 128), (1 << 20, 128))
    jr = jax.jit(lambda: normal(RngState(0), (n, d)))
    return (lambda: jr(), n * d * 4, 0, f"{n}x{d} f32")


@_register("permute")
def _permute(size: str):
    from raft_tpu.random import RngState, permute

    n, _ = _dims(size, (1 << 16, 0), (1 << 20, 0), (1 << 22, 0))
    jp = jax.jit(lambda: permute(RngState(1), n))
    return (lambda: jp(), n * 4, 0, f"perm of {n}")


@_register("bitset_test")
def _bitset_test(size: str):
    """core bitset test throughput (``cpp/bench/prims/core/bitset``)."""
    from raft_tpu.core.bitset import Bitset, test_words

    n, m = _dims(size, (1 << 16, 1 << 13), (1 << 22, 1 << 18),
                 (1 << 24, 1 << 20))
    bs = Bitset.from_mask(jnp.ones((n,), bool))
    idx = jax.random.randint(jax.random.key(9), (m,), 0, n, jnp.int32)
    jax.block_until_ready((bs.words, idx))
    jt = jax.jit(test_words)
    # bytes: a 4-byte index read + a 4-byte gathered word per test
    return (lambda: jt(bs.words, idx), m * 8, 0, f"{m} tests of {n} bits")


@_register("sparse_spmm")
def _sparse_spmm(size: str):
    """CSR x dense (``cpp/bench/prims/sparse``)."""
    import scipy.sparse as sps

    from raft_tpu.sparse import CSR
    from raft_tpu.sparse.linalg import spmm

    n, d, nnz_per = _dims(size, (1 << 10, 64, 16), (1 << 14, 128, 32),
                          (1 << 16, 128, 32))
    rng = np.random.default_rng(10)
    rows = np.repeat(np.arange(n), nnz_per)
    cols = rng.integers(0, n, n * nnz_per)
    vals = rng.standard_normal(n * nnz_per).astype(np.float32)
    csr = CSR.from_scipy(sps.csr_matrix((vals, (rows, cols)), shape=(n, n)))
    dense = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    jax.block_until_ready(dense)
    js = jax.jit(lambda mat: spmm(csr, mat))
    return (lambda: js(dense), n * nnz_per * 8 + n * d * 4,
            2 * n * nnz_per * d, f"{n}x{n} nnz/row={nnz_per} x {n}x{d}")


@_register("ivf_flat_search")
def _ivf_flat_search(size: str):
    """End-to-end IVF-Flat probe scan (``cpp/bench/prims/neighbors``)."""
    from raft_tpu.neighbors import ivf_flat

    n, d, q, p = _dims(size, (1 << 13, 64, 32, 8), (1 << 17, 128, 100, 32),
                       (1 << 20, 128, 100, 32))
    rng = np.random.default_rng(11)
    x = rng.standard_normal((n, d)).astype(np.float32)
    idx = ivf_flat.build(None, ivf_flat.IvfFlatIndexParams(
        n_lists=max(32, n // 256)), x)
    qs = jnp.asarray(rng.standard_normal((q, d)), jnp.float32)
    jax.block_until_ready((idx.data, qs))
    sp = ivf_flat.IvfFlatSearchParams(n_probes=p)
    avg_m = idx.max_list_size
    return (lambda: ivf_flat.search(None, sp, idx, qs, 10),
            q * p * avg_m * d * 4, 2 * q * p * avg_m * d,
            f"{n}x{d} p={p} q={q}")


@_register("kmeans_iter")
def _kmeans_iter(size: str):
    """One balanced-EM iteration: predict labels + recompute centers —
    the hot loop of every IVF build (``balancing_em_iters``)."""
    from raft_tpu.cluster.kmeans_balanced import (
        _calc_centers_and_sizes, _predict_impl)
    from raft_tpu.distance.types import DistanceType

    n, c, d = _dims(size, (1 << 12, 256, 64), (1 << 17, 1024, 128),
                    (1 << 18, 1024, 128))
    kx, kc = jax.random.split(jax.random.key(6))
    x = jax.random.normal(kx, (n, d), jnp.float32)
    cent = jax.random.normal(kc, (c, d), jnp.float32)
    jax.block_until_ready((x, cent))

    @jax.jit
    def step(xa, ca):
        labels = _predict_impl(xa, ca, DistanceType.L2Expanded)
        return _calc_centers_and_sizes(xa, labels, c)

    # predict reads x once + centers; update reads x again (scatter-add)
    return (lambda: step(x, cent), 2 * n * d * 4, 2 * n * c * d,
            f"{n}x{c}x{d} f32")


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def run_prims(
    size: str = "small",
    name_filter: str = "",
    budget_s: float = 10.0,
    out_path: Optional[str] = None,
) -> List[Dict]:
    results = []
    peaks = _peaks()
    for prim in _REGISTRY:
        if name_filter and name_filter not in prim.name:
            continue
        try:
            fn, nbytes, flops, shape = prim.make(size)
            dt = timeit(fn, budget_s)
        except Exception as e:  # keep the suite going past one bad prim
            rec = {"prim": prim.name, "error": f"{type(e).__name__}: {e}"}
            print(json.dumps(rec), flush=True)
            results.append(rec)
            continue
        rec = {
            "prim": prim.name,
            "shape": shape,
            "ms": round(dt * 1e3, 3),
            "gbps": round(nbytes / dt / 1e9, 2),
            "bw_frac": (round(nbytes / dt / peaks[1], 4)
                        if peaks else None),
            "mfu": (round(flops / dt / peaks[0], 4)
                    if peaks and flops else None),
            "backend": jax.default_backend(),
        }
        print(json.dumps(rec), flush=True)
        results.append(rec)
    if out_path:
        with open(out_path, "a") as fh:
            for rec in results:
                fh.write(json.dumps(rec) + "\n")
    return results


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--filter", default="", help="substring filter on names")
    p.add_argument("--size", default="small",
                   choices=("tiny", "small", "full"))
    p.add_argument("--seconds", type=float, default=10.0,
                   help="per-prim measurement budget")
    p.add_argument("--out", default=None, help="append JSONL here")
    args = p.parse_args(argv)
    run_prims(args.size, args.filter, args.seconds, args.out)


if __name__ == "__main__":
    main()
