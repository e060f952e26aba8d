#!/usr/bin/env python
"""Chip smoke: the served vector-search path, end to end, on one TPU.

One process drives every index family through the entry points a user
calls — build, ``SearchExecutor.warmup``, then ``DynamicBatcher.submit``
of batch-10 requests — on a SIFT-1M-shaped deployment, and checks what
comes back against a plain host reference. It is the quickest proof
that the system still starts on the chip; it measures no speed.

Deployment (``raft_tpu/bench/conf/sift-128-euclidean.json``'s build
parameters at SIFT-1M's shape): 1,000,000 x 128 float32, L2, k=10,
clustered data generated from ``--seed``. Brute force with f32 and
bf16 storage, IVF-Flat, IVF-PQ (``pq_dim=64``, ``pq_bits=8``) and
IVF-BQ at ``n_lists=1024`` over the whole corpus; CAGRA over its first
:data:`CAGRA_N` rows: its graph build (a batched IVF-PQ self-search)
took 875 s at 250,000 rows on a v5e, so 50,000 is the size that keeps
the whole run inside its 1200 s budget.

Checks, per family: recall@10 against numpy float64 exact kNN
(tie-aware, ``utils.eval_recall``) at or above :data:`FLOORS`; zero
executor compiles after warmup; and for the kernel families the
resolved engine is ``pallas``, the warmed executable holds a
``tpu_custom_call``, and its ids equal the ``xla`` engine's. Any
failed check raises; the run then exits non-zero and prints no
result line.

``--chips 4`` runs only the list-sharded mesh phase: ``dist_ivf``
over four devices against a single-chip IVF-Flat index on the same
data — ids and distances bit-identical (``probe_mode="global"``),
lists spread evenly over the devices.

The last line of standard output is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

N, DIM, K = 1_000_000, 128, 10
N_LISTS, PQ_DIM, PQ_BITS = 1024, 64, 8
N_PROBES = 64            # IVF-Flat / IVF-PQ / IVF-BQ probe setting
CAGRA_N = 50_000         # CAGRA's corpus: the first CAGRA_N rows
CAGRA_DEGREE, CAGRA_IDEGREE = 64, 128
CAGRA_ITOPK = 64
BATCH, REQUESTS = 10, 30
N_CLUSTERS = 1024
SEED = 0

# recall@10 floors against the float64 host reference, at the settings
# above (brute force is exact up to its storage dtype)
FLOORS = {
    "bf_f32": 0.999,
    "bf_bf16": 0.99,
    "ivf_flat": 0.90,
    "ivf_pq": 0.50,
    "ivf_bq": 0.90,
    "cagra": 0.90,
}
# families a Pallas kernel serves on the chip
KERNEL_FAMILIES = ("bf_f32", "bf_bf16", "ivf_flat", "ivf_bq", "cagra")


class SmokeError(RuntimeError):
    """A phase's check failed."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeError(msg)


def emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


# ---------------------------------------------------------------------------
# data and the host reference
# ---------------------------------------------------------------------------


def make_data(n: int, dim: int, n_queries: int, seed: int,
              n_clusters: int = N_CLUSTERS):
    """Gaussian clusters (unit spread around unit-normal centers, so
    neighborhoods overlap and IVF recall depends on the probe count);
    queries come from the same mixture."""
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((n_clusters, dim), dtype=np.float32)
    x = rng.standard_normal((n, dim), dtype=np.float32)
    x += centers[rng.integers(0, n_clusters, n)]
    q = rng.standard_normal((n_queries, dim), dtype=np.float32)
    q += centers[rng.integers(0, n_clusters, n_queries)]
    return x, q


def exact_knn(x: np.ndarray, q: np.ndarray, k: int, block: int = 65536,
              shortlist: int = 128):
    """Exact squared-L2 kNN on the host in float64 — the reference,
    independent of the code under test. A float32 BLAS pass keeps each
    query's ``shortlist`` nearest rows (float32's error is ~1e-6 of a
    distance, far inside the gap between the k-th and the
    ``shortlist``-th neighbor); those are then ranked in float64."""
    qn = np.sum(q * q, axis=1, keepdims=True)
    cand_d = np.full((len(q), 0), np.inf, np.float32)
    cand_i = np.zeros((len(q), 0), np.int64)
    for s in range(0, len(x), block):
        xb = x[s:s + block]
        d = qn + np.sum(xb * xb, axis=1)[None, :] - 2.0 * (q @ xb.T)
        cd = np.concatenate([cand_d, d], axis=1)
        ci = np.concatenate(
            [cand_i, np.broadcast_to(np.arange(s, s + len(xb)), d.shape)],
            axis=1)
        top = np.argpartition(cd, min(shortlist, cd.shape[1]) - 1,
                              axis=1)[:, :shortlist]
        cand_d = np.take_along_axis(cd, top, axis=1)
        cand_i = np.take_along_axis(ci, top, axis=1)
    diff = x[cand_i].astype(np.float64) - q[:, None, :].astype(np.float64)
    d64 = np.sum(diff * diff, axis=2)
    order = np.argsort(d64, axis=1, kind="stable")[:, :k]
    return (np.take_along_axis(d64, order, axis=1),
            np.take_along_axis(cand_i, order, axis=1))


def recall(ref, got_d, got_i) -> float:
    from raft_tpu.utils import eval_recall

    ref_d, ref_i = ref
    r, _, _ = eval_recall(ref_i, got_i, ref_d, got_d)
    return float(r)


def id_agreement(i_a, d_a, i_b, d_b, rtol: float = 1e-5):
    """(positions whose ids differ, of those the ones NOT at a distance
    tie) between two engines' results."""
    diff = i_a != i_b
    tie = np.isclose(d_a, d_b, rtol=rtol, atol=0.0)
    return int(diff.sum()), int((diff & ~tie).sum())


def block_on(index) -> None:
    """Wait for every device array an index holds."""
    import jax

    jax.block_until_ready([
        getattr(index, f.name) for f in dataclasses.fields(index)
        if isinstance(getattr(index, f.name), jax.Array)])


def bytes_in_use(devices=None) -> list:
    import jax

    out = []
    for dev in devices or jax.local_devices():
        stats = dev.memory_stats() or {}
        out.append(int(stats.get("bytes_in_use", -1)))
    return out


# ---------------------------------------------------------------------------
# the served path
# ---------------------------------------------------------------------------


def serve(index, queries, k: int, *, params=None, batch: int = BATCH,
          **kw):
    """Warm one executor for ``index``, then answer batch-``batch``
    requests through the ``DynamicBatcher``, one in flight at a time
    (each micro-batch is one request, so it lands in the warmed
    bucket). Returns ``(d, i, info)``; raises if anything compiled
    after warmup."""
    from raft_tpu import SearchExecutor
    from raft_tpu.serving import BatcherConfig, DynamicBatcher

    ex = SearchExecutor()
    t0 = time.perf_counter()
    ex.warmup(index, buckets=(ex.bucket_for(batch),), k=k, params=params,
              **kw)
    warmup_s = time.perf_counter() - t0
    compiles = ex.stats.compile_count
    outs = []
    t0 = time.perf_counter()
    with DynamicBatcher(ex, BatcherConfig(max_wait_s=0.0)) as b:
        for s in range(0, len(queries), batch):
            h = b.submit(index, queries[s:s + batch], k, params=params,
                         **kw)
            outs.append(h.result(timeout=600))
    serve_s = time.perf_counter() - t0
    late = ex.stats.compile_count - compiles
    check(late == 0, f"{late} executor compiles after warmup")
    (info,) = ex.executable_costs().items()
    digest, cost = info
    d = np.concatenate([np.asarray(o[0]) for o in outs])
    i = np.concatenate([np.asarray(o[1]) for o in outs])
    return d, i, {
        "warmup_s": warmup_s, "serve_s": serve_s,
        "requests": len(outs), "compiles_after_warmup": late,
        "engine": cost["engine"],
        "tpu_custom_call": "tpu_custom_call" in ex.executable_text(digest),
    }


def run_family(name, build, params, queries, ref, xla_search, *,
               require_kernels: bool, floors=None, **kw) -> dict:
    """Build, serve, check one family; ``xla_search(index)`` runs the
    same search on the xla engine (kernel families only)."""
    floors = floors or FLOORS
    t0 = time.perf_counter()
    index = build()
    block_on(index)
    build_s = time.perf_counter() - t0
    d, i, info = serve(index, queries, K, params=params, **kw)
    rec = {"phase": name, "build_s": build_s, **info,
           "recall": recall(ref, d, i), "floor": floors[name],
           "bytes_in_use": bytes_in_use()[0]}
    if xla_search is not None:
        dx, ix = xla_search(index)
        n_diff, n_real = id_agreement(i, d, np.asarray(ix),
                                      np.asarray(dx))
        rec.update(xla_id_mismatches=n_diff, xla_tie_swaps=n_diff - n_real)
    emit(rec)
    check(rec["recall"] >= floors[name],
          f"{name}: recall {rec['recall']} under floor {floors[name]}")
    if xla_search is not None:
        check(n_real == 0,
              f"{name}: {n_real} ids differ from the xla engine")
    if require_kernels and name in KERNEL_FAMILIES:
        check(info["engine"] == "pallas",
              f"{name}: served by {info['engine']!r}, not pallas")
        check(info["tpu_custom_call"],
              f"{name}: no tpu_custom_call in the warmed executable")
    return rec


def run_single_chip(x, q, *, seed: int = SEED, n_lists: int = N_LISTS,
                    pq_dim: int = PQ_DIM, n_probes: int = N_PROBES,
                    cagra_n: int = CAGRA_N, cagra_degree: int = CAGRA_DEGREE,
                    cagra_idegree: int = CAGRA_IDEGREE,
                    require_kernels: bool = True, floors=None) -> list:
    """Every single-chip family on ``x``/``q``; returns the records."""
    from raft_tpu import Resources
    from raft_tpu.neighbors import brute_force, cagra, ivf_bq, ivf_flat
    from raft_tpu.neighbors import ivf_pq

    t0 = time.perf_counter()
    ref = exact_knn(x, q, K)
    emit({"phase": "reference", "seconds": time.perf_counter() - t0,
          "queries": len(q)})
    res = Resources(seed=seed)
    recs = []

    def bf_xla(index):
        os.environ["RAFT_TPU_DISABLE_FUSED"] = "1"
        try:
            return brute_force.search(res, index, q, K)
        finally:
            del os.environ["RAFT_TPU_DISABLE_FUSED"]

    for name, storage in (("bf_f32", None), ("bf_bf16", "bfloat16")):
        recs.append(run_family(
            name, lambda s=storage: brute_force.build(
                res, x, storage_dtype=s),
            None, q, ref, bf_xla, require_kernels=require_kernels,
            floors=floors))

    fp = ivf_flat.IvfFlatSearchParams(n_probes=n_probes,
                                      scan_engine="pallas")
    recs.append(run_family(
        "ivf_flat",
        lambda: ivf_flat.build(Resources(seed=seed),
                               ivf_flat.IvfFlatIndexParams(n_lists=n_lists),
                               x),
        fp, q, ref,
        lambda idx: ivf_flat.search(
            res, dataclasses.replace(fp, scan_engine="xla"), idx, q, K),
        require_kernels=require_kernels, floors=floors))

    recs.append(run_family(
        "ivf_pq",
        lambda: ivf_pq.build(Resources(seed=seed), ivf_pq.IvfPqIndexParams(
            n_lists=n_lists, pq_dim=pq_dim, pq_bits=PQ_BITS), x),
        ivf_pq.IvfPqSearchParams(n_probes=n_probes), q, ref, None,
        require_kernels=require_kernels, floors=floors))

    bp = ivf_bq.IvfBqSearchParams(n_probes=n_probes, scan_engine="pallas")
    recs.append(run_family(
        "ivf_bq",
        lambda: ivf_bq.build(Resources(seed=seed),
                             ivf_bq.IvfBqIndexParams(n_lists=n_lists), x),
        bp, q, ref,
        lambda idx: ivf_bq.search(
            res, dataclasses.replace(bp, scan_engine="xla"), idx, q, K),
        require_kernels=require_kernels, floors=floors))

    xc = x[:cagra_n]
    t0 = time.perf_counter()
    cref = exact_knn(xc, q, K)
    emit({"phase": "cagra_reference", "seconds": time.perf_counter() - t0,
          "rows": cagra_n})
    cp = cagra.CagraSearchParams(itopk_size=CAGRA_ITOPK, algo="pallas")
    recs.append(run_family(
        "cagra",
        lambda: cagra.build(Resources(seed=seed), cagra.CagraIndexParams(
            graph_degree=cagra_degree,
            intermediate_graph_degree=cagra_idegree), xc),
        cp, q, cref,
        lambda idx: cagra.search(
            res, dataclasses.replace(cp, algo="xla"), idx, q, K),
        require_kernels=require_kernels, floors=floors))
    return recs


def run_mesh(x, q, devices, *, seed: int = SEED, n_lists: int = N_LISTS,
             n_probes: int = N_PROBES) -> dict:
    """The list-sharded IVF-Flat index over ``devices`` against the
    single-chip index it was dealt from: bit-identical results, lists
    spread evenly."""
    from jax.sharding import Mesh

    from raft_tpu import Resources
    from raft_tpu.comms import Comms
    from raft_tpu.distributed import ivf as dist_ivf
    from raft_tpu.neighbors import ivf_flat

    ref = exact_knn(x, q, K)
    comms = Comms(Mesh(np.asarray(devices), ("data",)), "data")
    sp = ivf_flat.IvfFlatSearchParams(n_probes=n_probes,
                                      scan_engine="pallas")
    t0 = time.perf_counter()
    single = ivf_flat.build(Resources(seed=seed),
                            ivf_flat.IvfFlatIndexParams(n_lists=n_lists), x)
    block_on(single)
    build_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    dist = dist_ivf.shard_index(comms, single)
    block_on(dist)
    shard_s = time.perf_counter() - t0
    lists = {str(s.device.id): int(s.data.shape[0])
             for s in dist.data.addressable_shards}
    index_bytes = {str(dev.id): 0 for dev in devices}
    for arr in (dist.centers, dist.data, dist.data_norms, dist.indices,
                dist.list_sizes):
        for s in arr.addressable_shards:
            index_bytes[str(s.device.id)] += int(s.data.nbytes)
    d0, i0, info0 = serve(single, q, K, params=sp)
    # the single-chip index leaves device 0 before the mesh's bytes
    # are read: nothing of the mesh index may pile up there
    del single
    gc.collect()
    in_use = bytes_in_use(devices)
    d1, i1, info = serve(dist, q, K, params=sp)
    rec = {"phase": "mesh_ivf_flat", "devices": len(devices),
           "build_s": build_s, "shard_s": shard_s, **info,
           "single_chip_warmup_s": info0["warmup_s"],
           "lists_per_device": lists, "index_bytes_per_device": index_bytes,
           "bytes_in_use_per_device": in_use,
           "ids_equal": bool(np.array_equal(i0, i1)),
           "distances_equal": bool(np.array_equal(d0, d1)),
           "recall": recall(ref, d1, i1), "floor": FLOORS["ivf_flat"]}
    emit(rec)
    check(rec["ids_equal"] and rec["distances_equal"],
          "mesh results differ from the single-chip index")
    share = n_lists // len(devices)
    check(all(v == share for v in lists.values()),
          f"lists not spread evenly: {lists}")
    check(rec["recall"] >= FLOORS["ivf_flat"],
          f"mesh recall {rec['recall']} under floor")
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the list-sharded mesh phase")
    ap.add_argument("--seed", type=int, default=SEED)
    args = ap.parse_args(argv)

    import jax

    from raft_tpu.core.resources import init_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(jax.devices())} device(s)", file=sys.stderr)
        return 2
    emit({"phase": "start", "device_kind": dev.device_kind,
          "devices": len(jax.devices()),
          "compile_cache": init_compile_cache(),
          "bytes_in_use": bytes_in_use()})
    t0 = time.perf_counter()
    x, q = make_data(N, DIM, BATCH * REQUESTS, args.seed)
    emit({"phase": "data", "seconds": time.perf_counter() - t0,
          "rows": N, "dim": DIM, "queries": len(q)})
    try:
        if args.chips == 4:
            run_mesh(x, q, jax.devices()[:4], seed=args.seed)
        else:
            run_single_chip(x, q, seed=args.seed)
    except SmokeError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    emit({"ok": True, "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
