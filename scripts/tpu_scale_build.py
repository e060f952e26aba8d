#!/usr/bin/env python
"""Single-chip scale proof: streaming IVF-PQ build at 100M+ rows —
VERDICT r2 item #4. Exercises the billion-row plumbing (2-D slot
indexing, native IO prefetch) at a dataset size many times HBM
(100M × 96 f32 = 38.4 GB vs 16 GB HBM on v5e); the role of the
reference's managed-memory spill (``ivf_pq_build.cuh:1542-1554``).

Stages (each timed, JSON lines on stdout):
  1. generate the fbin on disk in chunks (skipped if present)
  2. ivf_pq.build_streaming over the file
  3. search QPS at n_probes in {32, 64}
  4. recall@10 against a streamed exact ground truth (chunked
     brute-force scan + knn_merge_parts)

Usage: python scripts/tpu_scale_build.py [--rows 100000000] [--dim 96]
       [--path /tmp/scale.fbin] [--queries 100] [--rehearsal]
(--rehearsal = 2M rows; the CPU-sized dry run of the same code path.)
"""

import argparse
import json
import os
import time

import numpy as np


def emit(piece, **kw):
    print(json.dumps({"piece": piece, **kw}), flush=True)


def gen_fbin(path: str, rows: int, dim: int, chunk: int = 1 << 20,
             n_clusters: int = 4096, seed: int = 7):
    """Clustered synthetic data (IVF's target regime), written chunkwise
    so host memory stays at one chunk."""
    want_bytes = 8 + rows * dim * 4
    if os.path.exists(path):
        with open(path, "rb") as f:
            hdr = np.fromfile(f, np.int32, 2)
        # header AND size must match — a crashed prior run leaves a
        # truncated file with a valid header
        if (len(hdr) == 2 and hdr[0] == rows and hdr[1] == dim
                and os.path.getsize(path) == want_bytes):
            emit("gen", skipped=True)
            return
    rng = np.random.default_rng(seed)
    centers = (rng.standard_normal((n_clusters, dim)) * 4).astype(np.float32)
    t0 = time.perf_counter()
    with open(path, "wb") as f:
        np.asarray([rows, dim], np.int32).tofile(f)
        for start in range(0, rows, chunk):
            n = min(chunk, rows - start)
            labels = rng.integers(0, n_clusters, n)
            block = centers[labels] + rng.standard_normal(
                (n, dim)).astype(np.float32)
            block.astype(np.float32).tofile(f)
    emit("gen", s=round(time.perf_counter() - t0, 1),
         gb=round(rows * dim * 4 / 1e9, 1))


def exact_gt(ds, q, k: int, chunk: int = 1 << 20):
    """Streamed exact ground truth: chunked fused/brute scan + merge."""
    import jax.numpy as jnp
    from raft_tpu.neighbors import brute_force
    from raft_tpu.neighbors.brute_force import knn_merge_parts

    parts_d, parts_i = [], []
    for start in range(0, ds.n_rows, chunk):
        n = min(chunk, ds.n_rows - start)
        block = ds.read(start, n)
        d, i = brute_force.knn(None, block, q, k)
        parts_d.append(jnp.asarray(d))
        parts_i.append(jnp.asarray(i) + start)
    all_d = jnp.stack(parts_d)                  # (P, q, k)
    all_i = jnp.stack(parts_i)
    return knn_merge_parts(all_d, all_i, True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=100_000_000)
    ap.add_argument("--dim", type=int, default=96)
    ap.add_argument("--path", default="/tmp/scale.fbin")
    ap.add_argument("--queries", type=int, default=100)
    ap.add_argument("--n-lists", type=int, default=0,
                    help="0 = auto (~sqrt(n) rounded to 1k)")
    ap.add_argument("--pq-bits", type=int, default=8,
                    help="codebook bits (8 = the reference's high-"
                         "recall regime; 4 halves the code bytes)")
    ap.add_argument("--pq-dim", type=int, default=0,
                    help="0 = dim/2 (codes dim/2 bytes/vector at 8 "
                         "bits)")
    ap.add_argument("--rehearsal", action="store_true",
                    help="2M rows — the CPU dry run of the same path")
    args = ap.parse_args()
    if args.rehearsal:
        args.rows = min(args.rows, 2_000_000)

    import jax

    from raft_tpu.core.resources import init_compile_cache

    init_compile_cache()
    pq_dim = args.pq_dim or args.dim // 2
    emit("config", backend=jax.default_backend(), rows=args.rows,
         dim=args.dim, pq_dim=pq_dim, pq_bits=args.pq_bits)

    from raft_tpu.io import BinDataset
    from raft_tpu.neighbors import ivf_pq
    from raft_tpu.utils import eval_recall

    gen_fbin(args.path, args.rows, args.dim)
    ds = BinDataset(args.path)
    rng = np.random.default_rng(1)
    qpos = rng.integers(0, ds.n_rows, args.queries)
    q = np.stack([ds.read(int(p), 1)[0] for p in qpos])
    q = q + rng.standard_normal(q.shape).astype(np.float32)

    n_lists = args.n_lists or max(1024,
                                  int(round((args.rows ** 0.5) / 1024)) * 1024)
    params = ivf_pq.IvfPqIndexParams(
        n_lists=n_lists, pq_dim=pq_dim, pq_bits=args.pq_bits,
        kmeans_n_iters=10)
    t0 = time.perf_counter()
    index = ivf_pq.build_streaming(None, params, ds)
    np.asarray(index.list_sizes[:1])
    build_s = time.perf_counter() - t0
    # stored bytes/vector, not logical: codes are one uint8 per
    # sub-dim except the packed 4-bit/even-pq_dim layout (ivf_pq.py)
    packed = args.pq_bits == 4 and pq_dim % 2 == 0
    emit("build_streaming", s=round(build_s, 1),
         vectors_per_s=round(args.rows / build_s),
         n_lists=n_lists,
         pq_stored_bytes=pq_dim // 2 if packed else pq_dim)

    gt_t0 = time.perf_counter()
    _, gt_i = exact_gt(ds, q, 10)
    gt = np.asarray(gt_i)
    emit("exact_gt", s=round(time.perf_counter() - gt_t0, 1))

    def disk_refine(cand, k):
        """Exact re-rank of over-fetched candidates with rows read
        straight off the fbin (the dataset exceeds HBM by design, so
        refinement gathers from disk — the role of the reference's
        host-memory refinement pass)."""
        cand = np.asarray(cand)
        out = np.empty((cand.shape[0], k), np.int64)
        for qi in range(cand.shape[0]):
            ids = cand[qi][cand[qi] >= 0]
            rows = np.stack([ds.read(int(r), 1)[0] for r in ids])
            dd = np.sum((rows - q[qi]) ** 2, axis=1)
            out[qi] = ids[np.argsort(dd, kind="stable")[:k]]
        return out

    for p in (32, 64):
        sp = ivf_pq.IvfPqSearchParams(n_probes=p)
        d, i = ivf_pq.search(None, sp, index, q, 10)   # compile
        np.asarray(i[:1])
        t0 = time.perf_counter()
        iters = 5
        for _ in range(iters):
            d, i = ivf_pq.search(None, sp, index, q, 10)
        np.asarray(i[:1])
        dt = (time.perf_counter() - t0) / iters
        r, _, _ = eval_recall(gt, np.asarray(i))
        emit(f"search_p{p}", ms=round(dt * 1e3, 2),
             qps=round(args.queries / dt, 1), recall=round(float(r), 4))

        # over-fetch 4x + exact disk refine (recall as the reference
        # reports it: refine_ratio 4, raft_ann_benchmarks.md)
        _, cand = ivf_pq.search(None, sp, index, q, 40)
        ref_ids = disk_refine(cand, 10)
        r4, _, _ = eval_recall(gt, ref_ids)
        emit(f"search_p{p}_refined4x", recall=round(float(r4), 4))


if __name__ == "__main__":
    main()
