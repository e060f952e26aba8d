#!/usr/bin/env python
"""Headline benchmark — one JSON line on stdout for the driver.

Flagship config: exact brute-force kNN on SIFT-shaped synthetic data
(1M × 128 float32, k=10, query batch 10 — the reference's "batch size
10" headline regime, ``docs/source/raft_ann_benchmarks.md``). Exact
search ⇒ recall@10 is 1.0 by construction; the figure of merit is QPS.

``vs_baseline`` normalizes QPS by the single-chip HBM roofline for this
config: each batch must stream the whole dataset (512 MB) from HBM, so
roofline QPS = batch · BW / bytes (BW from the chip table,
``raft_tpu.core.chips``: 10 · 819e9 / 512e6 ≈ 16k QPS on v5e). A value of 1.0 means memory-bound optimal. (The reference
repo publishes no numeric tables to compare against — see BASELINE.md.)

One process, on the chip: with no TPU it exits non-zero (a CPU
number is never reported under a device metric's name). The measured
call goes through the serving path (``SearchExecutor``: bucketed
batch, AOT-compiled executable), and the JSON line carries
``compile_count`` / ``cache_hits`` / ``warmup_seconds`` so the
trajectory catches recompile regressions.

Progress goes to stderr so a slow run is diagnosable; stdout carries
exactly one JSON line. Env knobs: BENCH_N / BENCH_DIM / BENCH_BATCH /
BENCH_K / BENCH_SECONDS (measurement budget, default 45) /
BENCH_DTYPE (float32|bfloat16 dataset storage; default bfloat16 —
validated in-run against exact-f32 ids) /
RAFT_TPU_DISABLE_FUSED=1 (force the XLA tile-scan path). Opt-in
riders: BENCH_IVF_SWEEP=1 (probe-scan engine A/B with roofline
annotations), BENCH_MULTICHIP=1 (mesh-native serving: per-chip QPS,
compile counts and modeled lean collective bytes for the list-sharded
index across every visible chip), BENCH_SERVING=1 (request frontend:
bursty open-loop load through the DynamicBatcher — p50/p95/p99
latency, shed rate and batch occupancy next to the one-request-per-
call baseline QPS), BENCH_BQ=1 (RaBitQ IVF-BQ: fused
estimate-then-rerank vs estimate+refine recall at equal over-fetch,
modeled bytes/vector and one-stream bytes vs the two-pass model,
achieved GB/s vs the stream_read_sum roofline), BENCH_CAGRA=1
(graftbeam CAGRA A/B: random-pool vs coarse-plane seeding vs
coarse + BQ-coded traversal — recall, QPS, modeled gather bytes vs
the stream roofline, survivor-fraction estimator replay, pad waste
and compiles-during-measure), BENCH_TIERED=1
(grafttier: hot/cold tiered storage — bit-identity vs the all-HBM
index, hot GB/s vs the HBM roofline and cold GB/s vs a host-link
roofline, two live placement epochs with zero backend compiles and
deterministic swap bytes), BENCH_FLEET=1 (graftroute: the fleet
router through the device-free N-replica harness — steer and
f32-wire fan-out bit-identity vs the solo oracle, bf16-wire recall,
modeled merge-payload bytes per wire dtype).
"""

import json
import os
import sys
import time

T0 = time.perf_counter()

N = int(os.environ.get("BENCH_N", 1_000_000))
D = int(os.environ.get("BENCH_DIM", 128))
BATCH = int(os.environ.get("BENCH_BATCH", 10))
K = int(os.environ.get("BENCH_K", 10))
BUDGET_S = float(os.environ.get("BENCH_SECONDS", 45))


def log(msg):
    print(f"[bench +{time.perf_counter() - T0:.1f}s] {msg}",
          file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the measurement
# ---------------------------------------------------------------------------


def main():
    log(f"importing jax (config {N}x{D}, batch {BATCH}, k {K})")
    import jax
    import jax.numpy as jnp

    from raft_tpu.core.chips import chip_spec
    from raft_tpu.core.resources import init_compile_cache
    from raft_tpu.neighbors import brute_force

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        log(f"no TPU (JAX found {dev.platform!r}); nothing to measure")
        sys.exit(2)
    # the peak comes from the chip table; an unknown kind raises
    hbm_bytes_per_s = chip_spec(dev).hbm_bytes_per_s
    roofline_qps = BATCH * hbm_bytes_per_s / (N * D * 4)
    init_compile_cache()
    log(f"device: {dev.device_kind} x{len(jax.devices())}")
    key = jax.random.key(0)
    kd, kq = jax.random.split(key)
    dataset = jax.random.normal(kd, (N, D), jnp.float32)
    queries = jax.random.normal(kq, (BATCH, D), jnp.float32)
    jax.block_until_ready((dataset, queries))
    log("data generated")

    # Storage dtype: bf16 (the MXU-native layout — halves the HBM
    # stream, the config's bottleneck) unless BENCH_DTYPE forces f32. bf16 "exactness" is validated below against true-f32
    # ids and the run falls back to f32 if recall@K slips under 0.99.
    want = os.environ.get("BENCH_DTYPE")
    if want not in (None, "float32", "bfloat16"):
        log(f"unrecognized BENCH_DTYPE={want!r}; using the default")
        want = None
    if want is None:
        want = "bfloat16"
    storage = jnp.float32 if want == "float32" else jnp.bfloat16

    recall = None
    bf16_fell_back = False
    if storage == jnp.bfloat16:
        from raft_tpu.utils import eval_recall

        index32 = brute_force.build(None, dataset)
        d32, ids32 = brute_force.search(None, index32, queries, K,
                                        db_tile=262144)
        index = brute_force.build(None, dataset, storage_dtype=storage)
        d16, ids16 = brute_force.search(None, index, queries, K,
                                        db_tile=262144)
        import numpy as np
        # tie-aware: a different id at an equal distance is not a miss.
        # eps=1e-2 relative, not the 1e-3 default: the actual distances
        # carry bf16 rounding (~0.4% relative), so a true tie shows up
        # at sub-percent, not sub-tenth-percent, agreement
        recall, _, _ = eval_recall(np.asarray(ids32), np.asarray(ids16),
                                   np.asarray(d32), np.asarray(d16),
                                   eps=1e-2)
        recall = float(recall)
        log(f"bf16 recall@{K} vs exact f32 ids: {recall:.4f}")
        if recall < 0.99:
            log("bf16 recall under 0.99 — falling back to f32 storage")
            index, recall = index32, None
            bf16_fell_back = True
        del index32
    else:
        index = brute_force.build(None, dataset, storage_dtype=storage)
    jax.block_until_ready(index.norms)
    log(f"index built (storage {index.dataset.dtype}, norms cached)")

    # Serving path: AOT-warm the batch's bucket, then measure through
    # the compiled executable — the steady state a frontend would see.
    # The executor's counters ride along in the JSON line so the bench
    # trajectory catches recompile regressions (a healthy run compiles
    # during warmup only; cache_hits ≈ the iteration count).
    from raft_tpu import SearchExecutor

    executor = SearchExecutor()
    t_warm = time.perf_counter()
    executor.warmup(index, buckets=(executor.bucket_for(BATCH),), k=K,
                    db_tile=262144)
    warmup_seconds = time.perf_counter() - t_warm
    log(f"executor warmup: {warmup_seconds:.2f}s "
        f"({executor.stats.compile_count} compiles)")

    def run():
        return executor.search(index, queries, K, db_tile=262144)

    # Two-stage measurement:
    #   1. pipelined dispatch timing, printed immediately. Its
    #      per-iteration number includes the per-dispatch gap, so it
    #      understates on-chip throughput.
    #   2. slope timing — the fused kernel's `passes` mode repeats the
    #      dataset stream M times inside ONE dispatch (grid wrap, same
    #      compiled shape family as a normal call); per-pass time from
    #      the slope between two pass counts cancels the overhead.
    from raft_tpu.bench.prims import timeit_slope, timeit_stats

    tag = os.environ.get("BENCH_TAG", "")
    tag = f"_{tag}" if tag else ""
    suffix = os.environ.get("BENCH_SUFFIX", "")
    # when BENCH_DTYPE=bfloat16 was explicitly requested but validation
    # forced f32 storage, say so in the metric name — otherwise an
    # external tag like BENCH_TAG=bf16 would label an f32 measurement
    # as bf16 with no machine-readable hint (ADVICE r3)
    if index.dataset.dtype == jnp.bfloat16:
        sdt = "_bf16"
    elif bf16_fell_back and os.environ.get("BENCH_DTYPE") == "bfloat16":
        sdt = "_f32fallback"
    else:
        sdt = ""
    metric = (f"brute_force_knn_qps_sift1m_shape_b{BATCH}_k{K}{sdt}"
              f"{tag}{suffix}")

    last_rec = {}

    def emit(dt):
        # vs_baseline stays normalized by the f32-config roofline: the
        # problem solved (same vectors, queries, k, recall~1) is the
        # reference config; bf16 storage is this framework's internal
        # layout choice, and its measured recall is reported alongside
        qps = BATCH / dt
        rec = {
            "metric": metric,
            "value": round(qps, 2),
            "unit": "QPS",
            "vs_baseline": round(qps / roofline_qps, 4),
            "storage_dtype": str(index.dataset.dtype),
            "compile_count": executor.stats.compile_count,
            "cache_hits": executor.stats.cache_hits,
            "warmup_seconds": round(warmup_seconds, 3),
        }
        if recall is not None:
            rec["recall_at_k_vs_f32_exact"] = round(recall, 4)
        last_rec.clear()
        last_rec.update(rec)
        print(json.dumps(rec), flush=True)

    stats = timeit_stats(run, BUDGET_S)
    dt = stats["best_s"]
    log(f"single-iter estimate {stats['single_iter_est_s'] * 1e3:.1f} ms; "
        f"{stats['batches']} batches of {stats['pipe']}, "
        f"best {dt * 1e3:.2f} ms/iter, "
        f"median {stats['median_s'] * 1e3:.2f} ms/iter")
    emit(dt)

    from raft_tpu.neighbors.brute_force import _use_fused_kernel
    from raft_tpu.ops.fused_topk import fused_knn

    if _use_fused_kernel(index.metric, K, BATCH):
        def make_passes(m):
            return lambda: fused_knn(queries, index.dataset, K,
                                     index.metric,
                                     dataset_norms=index.norms, passes=m)

        try:
            from raft_tpu.bench.prims import slope_passes

            lo, hi = slope_passes(index.dataset.dtype)
            sl = timeit_slope(make_passes, lo, hi)
            log(f"slope timing: T({sl['m1']})={sl['t1_s'] * 1e3:.1f} ms, "
                f"T({sl['m2']})={sl['t2_s'] * 1e3:.1f} ms -> "
                f"{sl['slope_s'] * 1e3:.2f} ms/iter")
            # sanity gates: no slower than the dispatch-bound number it
            # refines, and no faster than 1.1x the device HBM roofline
            # in REAL bytes — a noise-dominated slope must not
            # overwrite the honest pipelined result. (The old 2 TB/s
            # ceiling let a physically impossible bf16 slope through in
            # round 3; any stream "faster" than the roofline is jitter,
            # not throughput.)
            itemsize = index.dataset.dtype.itemsize
            floor_s = (N * D * itemsize) / (1.1 * hbm_bytes_per_s)
            if floor_s <= sl["slope_s"] <= dt * 1.2:
                emit(min(sl["slope_s"], dt))
            else:
                log(f"slope {sl['slope_s'] * 1e3:.3f} ms outside "
                    f"[{floor_s * 1e3:.3f}, {dt * 1.2 * 1e3:.3f}] ms; "
                    "keeping pipelined result")
        except Exception as e:  # noqa: BLE001 — keep pipelined result
            log(f"slope timing failed ({e}); keeping pipelined result")
    else:
        log("fused kernel not in play for this config; keeping "
            "pipelined result")

    # opt-in rider: IVF-Flat probe-scan engine sweep with
    # distance-to-roofline annotations; the enriched record re-emits
    # with the headline fields intact (the parent keeps the LAST line)
    # Each rider FOLDS its block into last_rec before printing, so the
    # final JSON line — the one ci/bench_compare.py reads — carries
    # EVERY rider that ran. (Before PR 12 each rider copied only the
    # headline record: with BENCH_SERVING and BENCH_BQ both pinned,
    # the last line held just "bq" and every serving.* tolerance band
    # was silently ungated — compare() skips baseline-missing columns.)
    if os.environ.get("BENCH_IVF_SWEEP") == "1" and last_rec:
        try:
            last_rec["ivf_sweep"] = _ivf_engine_sweep()
            print(json.dumps(last_rec), flush=True)
        except Exception as e:  # noqa: BLE001 — keep headline record
            log(f"ivf engine sweep failed ({e}); keeping headline record")

    # opt-in rider: mesh-native serving — list-sharded IVF through the
    # mesh-aware executor across every visible chip
    if os.environ.get("BENCH_MULTICHIP") == "1" and last_rec:
        try:
            last_rec["multichip"] = _multichip_rider()
            print(json.dumps(last_rec), flush=True)
        except Exception as e:  # noqa: BLE001 — keep headline record
            log(f"multichip rider failed ({e}); keeping headline record")

    # opt-in rider: the request frontend — bursty open-loop load
    # through the DynamicBatcher vs one-request-per-call dispatch
    if os.environ.get("BENCH_SERVING") == "1" and last_rec:
        try:
            last_rec["serving"] = _serving_rider()
            print(json.dumps(last_rec), flush=True)
        except Exception as e:  # noqa: BLE001 — keep headline record
            log(f"serving rider failed ({e}); keeping headline record")

    # opt-in rider: RaBitQ IVF-BQ — fused estimate-then-rerank vs the
    # legacy estimate+refine path, with one-stream byte accounting
    if os.environ.get("BENCH_BQ") == "1" and last_rec:
        try:
            last_rec["bq"] = _bq_rider()
            print(json.dumps(last_rec), flush=True)
        except Exception as e:  # noqa: BLE001 — keep headline record
            log(f"bq rider failed ({e}); keeping headline record")

    # opt-in rider: graftbeam — the rebuilt CAGRA serving path, three
    # seed/traversal arms on one index with modeled gather bytes
    if os.environ.get("BENCH_CAGRA") == "1" and last_rec:
        try:
            last_rec["cagra"] = _cagra_rider()
            print(json.dumps(last_rec), flush=True)
        except Exception as e:  # noqa: BLE001 — keep headline record
            log(f"cagra rider failed ({e}); keeping headline record")

    # opt-in rider: grafttier — hot/cold tiered storage under the
    # dual-roofline accounting, with placement epochs live
    if os.environ.get("BENCH_TIERED") == "1" and last_rec:
        try:
            last_rec["tiered"] = _tiered_rider()
            print(json.dumps(last_rec), flush=True)
        except Exception as e:  # noqa: BLE001 — keep headline record
            log(f"tiered rider failed ({e}); keeping headline record")

    # opt-in rider: graftroute — the fleet router through the
    # device-free N-replica harness: steer/fan-out bit-identity,
    # bf16-wire recall, and the modeled merge-payload bytes
    if os.environ.get("BENCH_FLEET") == "1" and last_rec:
        try:
            last_rec["fleet"] = _fleet_rider()
            print(json.dumps(last_rec), flush=True)
        except Exception as e:  # noqa: BLE001 — keep headline record
            log(f"fleet rider failed ({e}); keeping headline record")


def _ivf_engine_sweep():
    """BENCH_IVF_SWEEP=1 rider: A/B the IVF-Flat probe-scan engines
    (pallas list-major / xla list-major / legacy rank-major) through
    the serving path. Each case carries the modeled probe-scan HBM
    bytes (gathered lists for rank-major, the probed-list union
    streamed once for list-major) converted to achieved GB/s, next to
    a ``stream_read_sum`` roofline probe of the same packed tensor —
    so the BENCH json shows distance-to-roofline, not just wall time.
    Env knobs: BENCH_IVF_N / BENCH_IVF_LISTS / BENCH_IVF_PROBES /
    BENCH_IVF_SECONDS (per-case budget)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from raft_tpu import SearchExecutor
    from raft_tpu.bench.prims import timeit_stats
    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.ops.fused_topk import stream_read_sum
    from raft_tpu.ops.ivf_scan import resolve_scan_engine, unique_lists

    n = int(os.environ.get("BENCH_IVF_N", 200_000))
    n_lists = int(os.environ.get("BENCH_IVF_LISTS", 256))
    n_probes = int(os.environ.get("BENCH_IVF_PROBES", 20))
    budget = float(os.environ.get("BENCH_IVF_SECONDS", 8))
    kd, kq = jax.random.split(jax.random.key(1))
    x = jax.random.normal(kd, (n, D), jnp.float32)
    queries = jax.random.normal(kq, (BATCH, D), jnp.float32)
    log(f"ivf sweep: building index ({n}x{D}, {n_lists} lists)")
    index = ivf_flat.build(None, ivf_flat.IvfFlatIndexParams(
        n_lists=n_lists, kmeans_n_iters=10), x)
    m = index.max_list_size
    itemsize = index.data.dtype.itemsize
    jax.block_until_ready(index.data)

    # roofline: a pure streamed read of the packed list tensor — the
    # ceiling every scan engine is judged against
    flat = index.data.reshape(n_lists * m, D)
    interp = jax.default_backend() != "tpu"
    st = timeit_stats(lambda: stream_read_sum(flat, interpret=interp),
                      min(budget, 6.0))
    roof_gbps = flat.size * itemsize / st["best_s"] / 1e9
    log(f"ivf sweep roofline (stream_read_sum): {roof_gbps:.1f} GB/s")

    # probed-union size for the list-major bytes model
    qf = queries.astype(jnp.float32)
    ip = qf @ index.centers.T
    score = -(index.center_norms[None, :] - 2.0 * ip)
    probes = jax.lax.top_k(score, n_probes)[1].astype(jnp.int32)
    n_union = int((np.asarray(unique_lists(probes, n_lists))
                   < n_lists).sum())

    slot_bytes = D * itemsize + 8          # data row + norm + id
    cases = []
    for engine in ("pallas", "xla", "rank"):
        resolved = resolve_scan_engine(engine, data=index.data, k=K)
        p = ivf_flat.IvfFlatSearchParams(n_probes=n_probes,
                                         scan_engine=engine)
        ex = SearchExecutor()
        ex.warmup(index, buckets=(ex.bucket_for(BATCH),), k=K, params=p)
        stats = timeit_stats(
            lambda: ex.search(index, queries, K, params=p), budget)
        dt = stats["best_s"]
        bytes_model = (BATCH * n_probes * m * slot_bytes
                       if resolved == "rank"
                       else n_union * m * slot_bytes)
        gbps = bytes_model / dt / 1e9
        cases.append({
            "engine": engine, "resolved": resolved,
            "best_s": round(dt, 6), "qps": round(BATCH / dt, 2),
            "model_bytes": bytes_model,
            "achieved_gbps": round(gbps, 2),
            "vs_roofline": round(gbps / roof_gbps, 4),
        })
        log(f"ivf sweep {engine}->{resolved}: {dt * 1e3:.2f} ms/iter, "
            f"{gbps:.1f} GB/s ({gbps / roof_gbps:.3f} of roofline)")
    return {"n": n, "dim": D, "n_lists": n_lists, "n_probes": n_probes,
            "batch": BATCH, "max_list_size": m, "union_lists": n_union,
            "roofline_gbps": round(roof_gbps, 2), "cases": cases}


def _multichip_rider():
    """BENCH_MULTICHIP=1 rider: the mesh-native serving path — a
    list-sharded IVF-Flat index over EVERY visible chip, searched
    through the mesh-aware ``SearchExecutor``. Emits per-chip and
    aggregate QPS per scan engine, compile counts (executor bookkeeping
    + jax's backend-compile ground truth, so a recompiling steady state
    is machine-visible), and the modeled lean collective payloads
    (O(q · n_probes) probe candidates, O(q · k) merge, per wire_dtype)
    next to the dense coarse-block baseline they replaced.

    graftwire adds two sub-blocks: ``kmeans_wire`` (quantized-vs-f32
    distributed k-means build A/B — per-iteration wall clock, modeled
    wire bytes, inertia delta per reduce wire) and ``grid2d`` (the 2-D
    query×list grid under mixed-size load, with the
    compiles-during-load column that pins the zero-recompile steady
    state). Env knobs: BENCH_MC_N / BENCH_MC_LISTS / BENCH_MC_PROBES /
    BENCH_MC_SECONDS (per-case budget) / BENCH_MC_KMEANS_ITERS /
    BENCH_MC_KMEANS_ROWS."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from raft_tpu import SearchExecutor
    from raft_tpu.bench.prims import timeit_stats
    from raft_tpu.comms import local_comms
    from raft_tpu.core import tracing
    from raft_tpu.distributed import ivf as dist_ivf
    from raft_tpu.neighbors import ivf_flat

    n = int(os.environ.get("BENCH_MC_N", 200_000))
    n_lists = int(os.environ.get("BENCH_MC_LISTS", 512))
    n_probes = int(os.environ.get("BENCH_MC_PROBES", 20))
    budget = float(os.environ.get("BENCH_MC_SECONDS", 8))
    n_dev = len(jax.devices())
    comms = local_comms()
    tracing.install_xla_compile_listener()

    kd, kq = jax.random.split(jax.random.key(2))
    x = jax.random.normal(kd, (n, D), jnp.float32)
    queries = jax.random.normal(kq, (BATCH, D), jnp.float32)
    log(f"multichip: building sharded index ({n}x{D}, {n_lists} lists, "
        f"{n_dev} chips)")
    tracing.reset_counters("distributed.build.")
    index = dist_ivf.build(None, comms, ivf_flat.IvfFlatIndexParams(
        n_lists=n_lists, kmeans_n_iters=10), x)
    build_peak = tracing.get_counter(
        "distributed.build.peak_deal_block_bytes")

    cases = []
    for engine, wire in (("auto", "f32"), ("auto", "bf16"),
                         ("rank", "f32")):
        from raft_tpu.ops.ivf_scan import resolve_scan_engine

        resolved = resolve_scan_engine(engine, data=index.data, k=K)
        p = ivf_flat.IvfFlatSearchParams(n_probes=n_probes,
                                         scan_engine=engine)
        ex = SearchExecutor()
        ex.warmup(index, buckets=(ex.bucket_for(BATCH),), k=K, params=p,
                  wire_dtype=wire)
        # one primer call so the per-batch-size pad/place micro-programs
        # compile outside the measured (and counted) window
        ex.search(index, queries, K, params=p, wire_dtype=wire)
        backend0 = tracing.get_counter(tracing.XLA_COMPILE_COUNT)
        stats = timeit_stats(
            lambda: ex.search(index, queries, K, params=p,
                              wire_dtype=wire), budget)
        dt = stats["best_s"]
        model = dist_ivf.collective_payload_model(
            BATCH, K, n_probes, index.n_lists, comms.size, wire)
        cases.append({
            "engine": engine, "resolved": resolved, "wire_dtype": wire,
            "best_s": round(dt, 6),
            "qps": round(BATCH / dt, 2),
            "qps_per_chip": round(BATCH / dt / n_dev, 2),
            "compile_count": ex.stats.compile_count,
            "backend_compiles_during_measure": (
                tracing.get_counter(tracing.XLA_COMPILE_COUNT) - backend0),
            "modeled_collective_bytes": model,
        })
        log(f"multichip {engine}/{wire}->{resolved}: "
            f"{dt * 1e3:.2f} ms/iter, {BATCH / dt / n_dev:.1f} QPS/chip, "
            f"coarse {model['coarse_bytes']}B vs dense "
            f"{model['dense_coarse_bytes']}B, merge "
            f"{model['merge_bytes']}B")
    # graftwire rider: quantized-vs-f32 distributed k-means build A/B —
    # per-iteration wall clock, the payload model's per-iteration wire
    # bytes, and the inertia delta the narrow wire costs
    from raft_tpu.distributed import kmeans as dist_kmeans

    km_iters = int(os.environ.get("BENCH_MC_KMEANS_ITERS", 10))
    km_clusters = min(n_lists, 256)
    km_rows = int(os.environ.get("BENCH_MC_KMEANS_ROWS", 32_768))
    km_rows = -(-km_rows // comms.size) * comms.size
    kx = jax.random.normal(jax.random.key(5), (km_rows, D),
                           jnp.float32)
    kmeans_cases = {}
    inertia_f32 = None
    for wire in ("f32", "bf16", "int8"):
        def _fit(wire=wire):
            c, i = dist_kmeans.fit(comms, kx, km_clusters,
                                   n_iters=km_iters, wire_dtype=wire)
            jax.block_until_ready(c)
            return i
        inertia = float(_fit())  # warm the compile, capture inertia
        stats = timeit_stats(_fit, budget / 2)
        per_iter = stats["best_s"] / km_iters
        if wire == "f32":
            inertia_f32 = inertia
        model = dist_kmeans.collective_payload_model(km_clusters, D,
                                                     wire)
        # dict keyed by wire (not a list) so the CI gate's dotted
        # tolerance paths reach the columns
        kmeans_cases[wire] = {
            "per_iter_s": round(per_iter, 6),
            "modeled_iter_wire_bytes": model["iter_bytes"],
            "inertia": round(inertia, 2),
            "inertia_vs_f32": round(inertia / inertia_f32, 6),
        }
        log(f"multichip kmeans {wire}: {per_iter * 1e3:.2f} ms/iter, "
            f"{model['iter_bytes']}B/iter wire, inertia x"
            f"{inertia / inertia_f32:.4f}")

    # graftwire rider: the 2-D query×list grid serves bucketed with
    # ZERO steady-state compiles — the compiles-during-load column is
    # the acceptance gate (it used to recompile per batch size)
    grid2d = None
    if n_dev >= 4 and n_dev % 2 == 0:
        from jax.sharding import Mesh

        from raft_tpu.comms.comms import Comms

        devs = np.array(jax.devices()).reshape(n_dev // 2, 2)
        comms2 = Comms(Mesh(devs, ("lists", "queries")), "lists")
        index2 = dist_ivf.build(None, comms2, ivf_flat.IvfFlatIndexParams(
            n_lists=n_lists, kmeans_n_iters=4), x)
        p2 = ivf_flat.IvfFlatSearchParams(n_probes=n_probes,
                                          scan_engine="auto")
        ex2 = SearchExecutor()
        ex2.warmup(index2, buckets=(ex2.bucket_for(BATCH),), k=K,
                   params=p2, query_axis="queries")
        qs = np.asarray(queries)
        # primer sweep compiles the per-size pad micro-programs
        sizes = tuple(sorted({BATCH, max(1, BATCH - 3),
                              BATCH // 2 + 1}))
        for m in sizes:
            ex2.search(index2, qs[:m], K, params=p2,
                       query_axis="queries")
        backend0 = tracing.get_counter(tracing.XLA_COMPILE_COUNT)
        t0 = time.perf_counter()
        rounds = 0
        while time.perf_counter() - t0 < budget / 2:
            for m in sizes:
                jax.block_until_ready(ex2.search(
                    index2, qs[:m], K, params=p2,
                    query_axis="queries")[0])
            rounds += 1
        dt = (time.perf_counter() - t0) / max(rounds * len(sizes), 1)
        grid2d = {
            "mesh_shape": [n_dev // 2, 2],
            "best_s": round(dt, 6),
            "qps": round(BATCH / dt, 2),
            "compiles_during_load": (
                tracing.get_counter(tracing.XLA_COMPILE_COUNT)
                - backend0),
        }
        log(f"multichip 2-D grid {n_dev // 2}x2: {dt * 1e3:.2f} ms/iter"
            f", {grid2d['compiles_during_load']:.0f} compiles under "
            "mixed-size load")

    return {"n": n, "dim": D, "n_lists": n_lists, "n_probes": n_probes,
            "batch": BATCH, "n_chips": n_dev,
            "build_peak_deal_block_bytes": int(build_peak),
            "cases": cases,
            "kmeans_wire": {"n_rows": int(kx.shape[0]),
                            "n_clusters": km_clusters,
                            "n_iters": km_iters,
                            "cases": kmeans_cases},
            "grid2d": grid2d}


def _bq_rider():
    """BENCH_BQ=1 rider: the RaBitQ IVF-BQ A/B — the fused
    estimate-then-rerank scan (exact distances, one list-major
    stream) against the legacy estimate+refine two-pass path at equal
    over-fetch, with the byte accounting the acceptance criterion is
    about:

    - ``bytes_per_vector_codes`` vs ``bytes_per_vector_raw``: the scan
      stream's compression (packed sign words + correction scalars vs
      f32 rows);
    - ``fused_model_bytes``: ONE stream of codes + corrections + the
      raw vectors of *survivor blocks only* (the prune decisions are
      replayed host-side with the engines' own margin rule), next to
      ``two_pass_model_bytes`` (estimate stream + an unconditional
      exact pass over every probed block). ``survivor_row_fraction``
      is the prune rule's deterministic CI signal — block-level
      skips (`one_stream_fraction` < 1) only bite at scale, where a
      block's every probing query has a tight running k-th;
    - achieved GB/s of the fused search against a ``stream_read_sum``
      roofline of the raw-vector tensor.

    Env knobs: BENCH_BQ_N / BENCH_BQ_LISTS / BENCH_BQ_PROBES /
    BENCH_BQ_BITS / BENCH_BQ_SECONDS."""
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from raft_tpu import SearchExecutor
    from raft_tpu.bench.prims import timeit_stats
    from raft_tpu.neighbors import brute_force, ivf_bq
    from raft_tpu.neighbors.ivf_bq import (
        _unpack_pm1,
        estimator_margin,
        overfetch_budget,
    )
    from raft_tpu.neighbors.refine import refine
    from raft_tpu.ops.bq_scan import resolve_bq_engine
    from raft_tpu.ops.fused_topk import stream_read_sum
    from raft_tpu.ops.ivf_scan import unique_lists

    n = int(os.environ.get("BENCH_BQ_N", 100_000))
    n_lists = int(os.environ.get("BENCH_BQ_LISTS", 128))
    n_probes = int(os.environ.get("BENCH_BQ_PROBES", 16))
    bits = int(os.environ.get("BENCH_BQ_BITS", 1))
    budget = float(os.environ.get("BENCH_BQ_SECONDS", 8))
    kd, kq = jax.random.split(jax.random.key(7))
    x = jax.random.normal(kd, (n, D), jnp.float32)
    queries = jax.random.normal(kq, (BATCH, D), jnp.float32)
    log(f"bq rider: building RaBitQ index ({n}x{D}, {n_lists} lists, "
        f"{bits} bit/dim + rerank plane)")
    index = ivf_bq.build(None, ivf_bq.IvfBqIndexParams(
        n_lists=n_lists, bits=bits, kmeans_n_iters=10), x)
    m = index.max_list_size
    de = index.dim_ext
    words = index.codes.shape[2]
    jax.block_until_ready(index.data)
    _, gt = brute_force.knn(None, x, queries, K)
    gt = np.asarray(gt)

    def recall(ids):
        ids = np.asarray(ids)
        return float(np.mean([len(set(ids[r]) & set(gt[r])) / K
                              for r in range(ids.shape[0])]))

    # roofline: a pure streamed read of the raw-vector plane — the
    # ceiling the rerank stream is judged against
    flat = index.data.reshape(n_lists * m, D)
    interp = jax.default_backend() != "tpu"
    st = timeit_stats(lambda: stream_read_sum(flat, interpret=interp),
                      min(budget, 6.0))
    roof_gbps = flat.size * 4 / st["best_s"] / 1e9
    log(f"bq roofline (stream_read_sum raw vectors): "
        f"{roof_gbps:.1f} GB/s")

    # per-vector scan-stream bytes: packed sign words + the three
    # correction scalars (+ per-level scales) + the id slot
    code_slot = words * 4 + (bits + 2) * 4 + 4
    raw_slot = D * 4 + 8                    # f32 row + norm + id

    # probed-union + host-side replay of the fused prune (the
    # engines' margin rule) -> survivor blocks for the byte model
    qf = np.asarray(queries, np.float32)
    centers = np.asarray(index.centers)
    qc2_all = (np.sum(qf * qf, 1)[:, None]
               + np.sum(centers * centers, 1)[None, :]
               - 2.0 * qf @ centers.T)
    probes = jnp.asarray(np.argsort(qc2_all, axis=1)[:, :n_probes],
                         jnp.int32)
    uniq = np.asarray(unique_lists(probes, n_lists))
    uniq = uniq[uniq < n_lists]
    rot = np.asarray(index.rotation)
    qrot = qf @ rot.T
    crot = centers @ rot.T
    rnorm = np.asarray(index.rnorm)
    cfac = np.asarray(index.cfac)
    errw = np.asarray(index.errw)
    ids_plane = np.asarray(index.indices)
    pm1 = np.asarray(_unpack_pm1(index.codes, jnp.float32)).reshape(
        n_lists, m, bits, de)
    recon = ((rnorm[..., None] * cfac)[..., None] * pm1).sum(axis=2)
    xnorms = np.asarray(index.data_norms)
    xplane = np.asarray(index.data)
    probed = np.zeros((BATCH, n_lists), bool)
    np.put_along_axis(probed, np.asarray(probes), True, axis=1)
    kth = np.full((BATCH,), np.inf, np.float32)
    topk = [[] for _ in range(BATCH)]
    survivor_blocks = 0
    survivor_rows = 0
    probed_rows = 0
    for lid in uniq:
        qt = qrot - crot[lid]
        qc2 = np.sum(qt * qt, 1, keepdims=True)
        delta = ((qt.max(1, keepdims=True) - qt.min(1, keepdims=True))
                 / 15.0)
        est = qc2 + np.square(rnorm[lid])[None, :] \
            - 2.0 * qt @ recon[lid].T
        margin = np.asarray(estimator_margin(
            jnp.asarray(np.sqrt(qc2)), jnp.asarray(rnorm[lid])[None],
            jnp.asarray(errw[lid])[None], jnp.asarray(delta), de, 3.0))
        ok = (ids_plane[lid][None, :] >= 0) & probed[:, lid : lid + 1]
        cand = ((est - margin) < kth[:, None]) & ok
        survivor_rows += int(cand.sum())
        probed_rows += int(ok.sum())
        if not cand.any():
            continue
        survivor_blocks += 1
        exact = (np.sum(qf * qf, 1, keepdims=True) + xnorms[lid][None]
                 - 2.0 * qf @ xplane[lid].T)
        for r in range(BATCH):
            if cand[r].any():
                topk[r].extend(exact[r][cand[r]].tolist())
                topk[r] = sorted(topk[r])[:K]
                if len(topk[r]) == K:
                    kth[r] = topk[r][-1]
    # the byte models: fused = ONE list-major stream (codes +
    # corrections for every probed block, raw vectors only for blocks
    # the prune left survivors in — per-block DMA granularity, the
    # kernel's actual unit); two-pass = reading each probed block
    # TWICE (an estimate pass then a full exact pass — the roofline
    # antipattern the fusion removes). Both are replays of the
    # engines' own margin rule, deterministic under the pinned seeds:
    # the gate pins survivor_row_fraction (margin/prune-math
    # regressions move it), while block-level pruning only bites at
    # scale — many blocks, tight kth — and on the real chip.
    est_stream = len(uniq) * m * code_slot
    fused_model_bytes = est_stream + survivor_blocks * m * raw_slot
    two_pass_model_bytes = est_stream + len(uniq) * m * raw_slot
    row_frac = survivor_rows / max(probed_rows, 1)
    log(f"bq prune replay: {survivor_blocks}/{len(uniq)} blocks, "
        f"{row_frac:.3f} of probed rows kept for exact re-rank")

    engine = resolve_bq_engine("auto", data=index.data, k=K,
                               dim_ext=de, bits=bits)
    p = ivf_bq.IvfBqSearchParams(n_probes=n_probes)
    ex = SearchExecutor()
    ex.warmup(index, buckets=(ex.bucket_for(BATCH),), k=K, params=p)
    stats = timeit_stats(
        lambda: ex.search(index, queries, K, params=p), budget)
    dt = stats["best_s"]
    d_f, i_f = ex.search(index, queries, K, params=p)
    fused_recall = recall(i_f)
    gbps = fused_model_bytes / dt / 1e9
    log(f"bq fused ({engine}): {dt * 1e3:.2f} ms/iter, recall@{K} "
        f"{fused_recall:.4f}, {gbps:.1f} GB/s modeled "
        f"({gbps / roof_gbps:.3f} of roofline)")

    # legacy estimate+refine at the bound-derived over-fetch
    est_index = _dc.replace(index, data=None, data_norms=None)
    fetch = overfetch_budget(est_index, K)
    pe = ivf_bq.IvfBqSearchParams(n_probes=n_probes,
                                  scan_engine="rank")

    def est_refine():
        _, cand = ivf_bq.search(None, pe, est_index, queries, fetch)
        return refine(None, x, queries, cand, K)

    est_stats = timeit_stats(lambda: jax.block_until_ready(
        est_refine()[0]), budget)
    _, i_e = est_refine()
    est_recall = recall(i_e)
    _, i_ek = ivf_bq.search(None, pe, est_index, queries, K)
    log(f"bq estimate+refine (fetch {fetch}): "
        f"{est_stats['best_s'] * 1e3:.2f} ms/iter, recall@{K} "
        f"{est_recall:.4f}; raw estimate@{K} {recall(i_ek):.4f}")

    return {
        "n": n, "dim": D, "dim_ext": de, "n_lists": n_lists,
        "n_probes": n_probes, "bits": bits, "batch": BATCH, "k": K,
        "engine": engine, "max_list_size": m,
        "union_lists": int(len(uniq)),
        "survivor_blocks": int(survivor_blocks),
        "survivor_row_fraction": round(row_frac, 4),
        "bytes_per_vector_codes": code_slot,
        "bytes_per_vector_raw": raw_slot,
        "fused_model_bytes": int(fused_model_bytes),
        "two_pass_model_bytes": int(two_pass_model_bytes),
        "one_stream_fraction": round(
            fused_model_bytes / max(two_pass_model_bytes, 1), 4),
        "roofline_gbps": round(roof_gbps, 2),
        "fused_best_s": round(dt, 6),
        "fused_qps": round(BATCH / dt, 2),
        "fused_recall": round(fused_recall, 4),
        "achieved_gbps": round(gbps, 2),
        "vs_roofline": round(gbps / roof_gbps, 4),
        "estimate_fetch": int(fetch),
        "estimate_refine_best_s": round(est_stats["best_s"], 6),
        "estimate_refine_recall": round(est_recall, 4),
        "estimate_at_k_recall": round(recall(i_ek), 4),
    }


def _cagra_rider():
    """BENCH_CAGRA=1 rider: the graftbeam A/B — three arms of the
    rebuilt CAGRA serving path on ONE index (seed plane + BQ record
    plane built once):

    - ``pool``: the legacy query-aware strided seed pool at a big
      ``seed_pool`` budget;
    - ``coarse``: IVF-coarse seeding from the build-time k-means seed
      plane at an 8x smaller ``seed_pool`` — the frontier-shift claim
      is ``pool_shrink_factor`` next to the two recall columns;
    - ``coarse_bq``: coarse seeding + BQ-coded traversal — graph
      neighbors scored by the packed-record XOR+popcount estimate,
      exact distances DMA'd only for estimate-survivors.

    Each arm reports recall@K, QPS, and a deterministic modeled
    gather-byte account (seed-stage rows + per-iteration candidate
    gathers; the BQ arm charges the record plane ONCE — its tile
    loads are VMEM-resident — plus the survivor fraction of raw-row
    DMAs, where the survivor fraction is a host-side replay of the
    shared estimator margin rule against each query's TRUE k-th
    distance) against a ``stream_read_sum`` roofline. ``compiles_during_measure`` must stay 0 — every arm
    serves AOT through the executor — and ``raggable`` records that
    the default-params CAGRA plan joins the ragged family (the PR 15
    fallback pin retired).

    Env knobs: BENCH_CAGRA_N / BENCH_CAGRA_DEG / BENCH_CAGRA_BITS /
    BENCH_CAGRA_POOL / BENCH_CAGRA_COARSE_POOL / BENCH_CAGRA_SECONDS.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from raft_tpu import SearchExecutor
    from raft_tpu.bench.prims import timeit_stats
    from raft_tpu.core import tracing
    from raft_tpu.neighbors import brute_force, cagra
    from raft_tpu.ops.bq_scan import (
        _block_estimate,
        auto_query_bits,
        unpack_bq_records,
    )
    from raft_tpu.ops.fused_topk import stream_read_sum

    n = int(os.environ.get("BENCH_CAGRA_N", 100_000))
    deg = int(os.environ.get("BENCH_CAGRA_DEG", 32))
    bits = int(os.environ.get("BENCH_CAGRA_BITS", 2))
    pool_big = int(os.environ.get("BENCH_CAGRA_POOL", 8192))
    pool_small = int(os.environ.get("BENCH_CAGRA_COARSE_POOL", 1024))
    budget = float(os.environ.get("BENCH_CAGRA_SECONDS", 8))
    kd, kq = jax.random.split(jax.random.key(11))
    x = jax.random.normal(kd, (n, D), jnp.float32)
    queries = jax.random.normal(kq, (BATCH, D), jnp.float32)
    log(f"cagra rider: building graph index ({n}x{D}, degree {deg}, "
        f"seed plane + {bits}-bit BQ record plane)")
    index = cagra.build(None, cagra.CagraIndexParams(
        graph_degree=deg, bq_bits=bits), x)
    jax.block_until_ready(index.graph)
    _, gt = brute_force.knn(None, x, queries, K)
    gt = np.asarray(gt)

    def recall(ids):
        ids = np.asarray(ids)
        return float(np.mean([len(set(ids[r]) & set(gt[r])) / K
                              for r in range(ids.shape[0])]))

    itemsize = jnp.dtype(index.dataset.dtype).itemsize
    interp = jax.default_backend() != "tpu"
    st = timeit_stats(
        lambda: stream_read_sum(index.dataset, interpret=interp),
        min(budget, 6.0))
    roof_gbps = index.dataset.size * itemsize / st["best_s"] / 1e9
    log(f"cagra roofline (stream_read_sum dataset): "
        f"{roof_gbps:.1f} GB/s")

    # survivor fraction for the BQ arm: replay the SHARED estimator
    # (the exact _block_estimate math both engines run) on a strided
    # row sample against each query's TRUE k-th exact distance — a
    # deterministic margin/prune-math signal, like the bq rider's
    words = bits * ((D + 31) // 32)
    de = ((D + 31) // 32) * 32
    codes, rnorm, cfac, errw = unpack_bq_records(
        index.bq_records, n, words, bits)
    samp = jnp.arange(0, n, max(1, n // 4096))[:4096]
    qrot = cagra._rotate_queries(queries, index.bq_rotation)
    est, margin = _block_estimate(
        qrot, index.bq_center_rot,
        rnorm[samp][None, :], errw[samp][None, :],
        jnp.transpose(cfac[samp]), codes[samp],
        dim_ext=de, bits=bits, query_bits=auto_query_bits(bits),
        epsilon=cagra.CagraSearchParams().bq_epsilon, ip_metric=False)
    qf = np.asarray(queries, np.float32)
    xf = np.asarray(index.dataset, np.float32)
    d_all = (np.sum(qf * qf, 1)[:, None] + np.sum(xf * xf, 1)[None, :]
             - 2.0 * qf @ xf.T)
    kth = np.partition(d_all, K - 1, axis=1)[:, K - 1:K]
    surv_frac = float(np.mean(
        (np.asarray(est) - np.asarray(margin)) < kth))
    log(f"cagra bq estimator replay: survivor fraction "
        f"{surv_frac:.4f} over {int(samp.shape[0])} sampled rows")

    cap = int(index.seed_members.shape[1])
    n_lists = int(index.seed_centers.shape[0])
    arms = {
        "pool": cagra.CagraSearchParams(
            seed_mode="pool", seed_pool=pool_big),
        "coarse": cagra.CagraSearchParams(
            seed_mode="coarse", seed_pool=pool_small),
        "coarse_bq": cagra.CagraSearchParams(
            seed_mode="coarse", seed_pool=pool_small,
            bq_traversal="on"),
    }
    tracing.install_xla_compile_listener()
    out = {"n": n, "dim": D, "degree": deg, "bits": bits, "k": K,
           "batch": BATCH, "roofline_gbps": round(roof_gbps, 2),
           "survivor_row_fraction": round(surv_frac, 4),
           "pool_shrink_factor": round(pool_big / pool_small, 2)}
    compiles_total = 0
    for name, p in arms.items():
        ex = SearchExecutor()
        bucket = ex.bucket_for(BATCH)
        ex.warmup(index, buckets=(bucket,), k=K, params=p)
        b0 = tracing.get_counter(tracing.XLA_COMPILE_COUNT)
        stats = timeit_stats(
            lambda: ex.search(index, queries, K, params=p), budget)
        compiles = int(tracing.get_counter(tracing.XLA_COMPILE_COUNT)
                       - b0)
        compiles_total += compiles
        d_a, i_a = ex.search(index, queries, K, params=p)
        cfg = cagra.derive_search_config(p, index, K)
        c_width = cfg["w"] * deg
        # seed stage: pool arm scores `seed_pool` strided raw rows per
        # query; coarse scores the center plane (f32) once per query
        # plus the probed lists' member rows
        if name == "pool":
            seed_bytes = BATCH * min(pool_big, n) * D * itemsize
        else:
            probes = max(1, min(-(-pool_small // cap), n_lists))
            seed_bytes = BATCH * (n_lists * D * 4
                                  + probes * cap * D * itemsize)
        # traversal: C candidate gathers per iteration per query. The
        # BQ arm's record-tile loads are VMEM-resident (the plane
        # streams into VMEM ONCE — charged here), so its HBM side is
        # only the survivor fraction of exact-row DMAs
        hops = BATCH * cfg["max_iters"] * c_width
        if name == "coarse_bq":
            trav_bytes = (index.bq_records.size * 4
                          + surv_frac * hops * D * itemsize)
        else:
            trav_bytes = hops * D * itemsize
        model_bytes = int(seed_bytes + trav_bytes)
        dt = stats["best_s"]
        gbps = model_bytes / dt / 1e9
        raggable = ex.ragged_key(index, K, params=p) is not None
        log(f"cagra {name}: {dt * 1e3:.2f} ms/iter, recall@{K} "
            f"{recall(i_a):.4f}, {gbps:.1f} GB/s modeled "
            f"({gbps / roof_gbps:.3f} of roofline), "
            f"{compiles} compiles during measure")
        out[name] = {
            "seed_pool": int(p.seed_pool),
            "recall": round(recall(i_a), 4),
            "best_s": round(dt, 6),
            "qps": round(BATCH / dt, 2),
            "model_bytes": model_bytes,
            "model_gbps": round(gbps, 2),
            "vs_roofline": round(gbps / roof_gbps, 4),
            "compiles_during_measure": compiles,
            "raggable": bool(raggable),
        }
    out["compiles_during_measure"] = compiles_total
    out["raggable"] = int(all(out[a]["raggable"] for a in arms))
    out["bq_byte_reduction"] = round(
        out["coarse"]["model_bytes"]
        / max(out["coarse_bq"]["model_bytes"], 1), 4)
    # pad waste of the bucketed front at this batch size (the ragged
    # family's pad behavior is gated by the serving rider's legs)
    bucket = SearchExecutor().bucket_for(BATCH)
    out["bucket"] = int(bucket)
    out["pad_fraction"] = round(1.0 - BATCH / bucket, 4)
    return out


def _tiered_rider():
    """BENCH_TIERED=1 rider: grafttier's billion-scale tiered storage
    under the TPU-KNN DUAL-roofline accounting. Half the lists go
    cold (host-resident where the backend supports memory kinds; the
    honest device fallback elsewhere — ``host_resident`` says which),
    and the record carries:

    - the hot stream's achieved GB/s next to an HBM roofline
      (``stream_read_sum`` over the hot plane) and the cold stream's
      achieved GB/s next to a HOST-link roofline (a timed
      host→device transfer of one cold-tier-sized buffer — the
      ceiling the manual-DMA pipeline is judged against);
    - ``bit_identical`` (tiered executor results vs the all-HBM
      index — the correctness gate column);
    - two LIVE placement epochs under a manual clock:
      ``compiles_during_epochs`` (must stay 0 — re-placement only
      permutes the fixed hot slots) and the per-epoch swap bytes
      (deterministic at the pinned config: targeted traffic promotes
      the same lists every run).

    Env knobs: BENCH_TIER_N / BENCH_TIER_LISTS / BENCH_TIER_PROBES /
    BENCH_TIER_SECONDS."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from raft_tpu import SearchExecutor
    from raft_tpu.bench.prims import timeit_stats
    from raft_tpu.core import tracing
    from raft_tpu.neighbors import ivf_flat, tiered
    from raft_tpu.ops.fused_topk import stream_read_sum
    from raft_tpu.ops.ivf_scan import unique_lists
    from raft_tpu.serving.harness import ManualClock
    from raft_tpu.serving.placement import PlacementConfig, TierManager

    n = int(os.environ.get("BENCH_TIER_N", 200_000))
    n_lists = int(os.environ.get("BENCH_TIER_LISTS", 256))
    n_probes = int(os.environ.get("BENCH_TIER_PROBES", 20))
    budget = float(os.environ.get("BENCH_TIER_SECONDS", 8))

    kd, kq = jax.random.split(jax.random.key(7))
    x = jax.random.normal(kd, (n, D), jnp.float32)
    queries = jax.random.normal(kq, (BATCH, D), jnp.float32)
    log(f"tiered rider: building index ({n}x{D}, {n_lists} lists)")
    index = ivf_flat.build(None, ivf_flat.IvfFlatIndexParams(
        n_lists=n_lists, kmeans_n_iters=10), x)
    t = tiered.build_tiered(index, hot_fraction=0.5)
    m = t.max_list_size
    itemsize = 4
    interp = jax.default_backend() != "tpu"

    # --- dual rooflines. HBM: a pure streamed read of the hot plane.
    # Host link: a timed host→device transfer of one cold-tier-sized
    # buffer — the ceiling the cold manual-DMA stream is judged
    # against (on CPU both pools are the same memory; the on-chip
    # numbers are what the evidence debt item collects).
    hot_flat = t.hot_data.reshape(t.n_hot * m, D)
    st = timeit_stats(lambda: stream_read_sum(hot_flat,
                                              interpret=interp),
                      min(budget, 6.0))
    hbm_roof_gbps = hot_flat.size * itemsize / st["best_s"] / 1e9
    cold_host = np.zeros((t.n_cold * m, D), np.float32)
    st = timeit_stats(
        lambda: jax.block_until_ready(jax.device_put(cold_host)),
        min(budget, 6.0))
    host_roof_gbps = cold_host.nbytes / st["best_s"] / 1e9
    log(f"tiered rooflines: HBM {hbm_roof_gbps:.1f} GB/s, host link "
        f"{host_roof_gbps:.1f} GB/s")

    # --- probed-union split for the per-tier byte models (host-side
    # replay of the engines' own coarse selection — deterministic
    # under the pinned seeds)
    qf = queries.astype(jnp.float32)
    ip = qf @ t.centers.T
    score = -(t.center_norms[None, :] - 2.0 * ip)
    probes = jax.lax.top_k(score, n_probes)[1].astype(jnp.int32)
    uniq = np.asarray(unique_lists(probes, n_lists))
    uniq = uniq[uniq < n_lists]
    cold_map = np.asarray(t.cold_slot_map)
    union_cold = int((cold_map[uniq] >= 0).sum())
    union_hot = int(len(uniq) - union_cold)
    # hot stream reads data+norms+ids from HBM; a cold list's data
    # crosses the host link while its norm/id planes stay HBM
    hot_model_bytes = (union_hot * m * (D * itemsize + 8)
                       + union_cold * m * 8)
    cold_model_bytes = union_cold * m * D * itemsize

    # --- serving: tiered executor vs the all-HBM index
    p = tiered.TieredSearchParams(n_probes=n_probes)
    ex = SearchExecutor(probe_accounting=True)
    ex.warmup(t, buckets=(ex.bucket_for(BATCH),), k=K, params=p)
    stats = timeit_stats(
        lambda: ex.search(t, queries, K, params=p), budget)
    dt = stats["best_s"]
    d_t, i_t = ex.search(t, queries, K, params=p)
    pf = ivf_flat.IvfFlatSearchParams(n_probes=n_probes)
    d_f, i_f = ivf_flat.search(None, pf, index, queries, K)
    bit_identical = bool(
        (np.asarray(d_t) == np.asarray(d_f)).all()
        and (np.asarray(i_t) == np.asarray(i_f)).all())
    hot_gbps = hot_model_bytes / dt / 1e9
    cold_gbps = cold_model_bytes / dt / 1e9
    log(f"tiered serving: {dt * 1e3:.2f} ms/iter, bit_identical="
        f"{bit_identical}, hot {hot_gbps:.1f} GB/s "
        f"({hot_gbps / hbm_roof_gbps:.3f} of HBM roofline), cold "
        f"{cold_gbps:.1f} GB/s "
        f"({cold_gbps / host_roof_gbps:.3f} of host roofline)")

    # --- live placement epochs: targeted traffic at two cold lists,
    # one warm epoch (the fixed-width swap programs specialize once),
    # then two gated epochs — zero backend compiles, deterministic
    # swap bytes
    clock = ManualClock()
    mgr = TierManager(t, ex, clock=clock, config=PlacementConfig(
        epoch_every_s=1.0, max_swaps_per_epoch=4))
    centers_np = np.asarray(t.centers)

    def targeted(lid, seed):
        rng = np.random.default_rng(seed)
        return (np.tile(centers_np[lid], (BATCH, 1))
                + 0.01 * rng.standard_normal((BATCH, D))
                ).astype(np.float32)

    ex.search(t, targeted(int(t.cold_lists[0]), 0), K, params=p)
    mgr.epoch()                      # warm the swap programs
    tracing.install_xla_compile_listener()
    c0 = tracing.counters().get(tracing.XLA_COMPILE_COUNT, 0)
    swap_bytes = []
    for step in (1, 2):
        for _ in range(2):
            ex.search(t, targeted(int(t.cold_lists[0]), step), K,
                      params=p)
        b0 = tracing.get_counter("tier.swap_bytes")
        mgr.epoch()
        swap_bytes.append(
            int(tracing.get_counter("tier.swap_bytes") - b0))
        ex.search(t, queries, K, params=p)
    compiles = int(tracing.counters().get(tracing.XLA_COMPILE_COUNT, 0)
                   - c0)
    d_t2, i_t2 = ex.search(t, queries, K, params=p)
    post_identical = bool(
        (np.asarray(d_t2) == np.asarray(d_f)).all()
        and (np.asarray(i_t2) == np.asarray(i_f)).all())
    log(f"tiered epochs: swap bytes {swap_bytes}, compiles during "
        f"epochs {compiles}, post-epoch bit_identical={post_identical}")

    # --- prefetch A/B (PR 18 graftcast): the SAME seeded drifting
    # hot set served twice — reactive epochs vs the forecast-driven
    # prefetcher. A forecast hit moved its block at stage time, so
    # the epoch path's cold-stream bytes (tier.promote_cold_bytes)
    # must STRICTLY drop with the prefetcher on; and after one warm
    # drift cycle (the stage/mix programs specialize once, like the
    # warm epoch above) the measured window must add ZERO backend
    # compiles. Both legs replay identical traffic (pinned rng), so
    # their epochs run identical plans — the bytes column isolates
    # the prefetcher.
    from raft_tpu.serving.prefetch import HITS, ISSUED, MISSES
    from raft_tpu.serving.prefetch import PrefetchConfig

    def _prefetch_leg(with_prefetch):
        t2 = tiered.build_tiered(index, hot_fraction=0.5)
        ex2 = SearchExecutor(probe_accounting=True)
        clk = ManualClock()
        mgr2 = TierManager(t2, ex2, clock=clk, config=PlacementConfig(
            epoch_every_s=60.0, max_swaps_per_epoch=4,
            prefetch_lead_s=10.0))
        if with_prefetch:
            mgr2.enable_prefetch(config=PrefetchConfig(alpha=0.5))
        hot0 = [int(lid) for lid in t2.hot_lists[:8]]
        cold0 = [int(lid) for lid in t2.cold_lists[:8]]
        ex2.warmup(t2, buckets=(ex2.bucket_for(BATCH),), k=K, params=p)
        lat = []

        def drive(lists, ticks, measure=False):
            rng = np.random.default_rng(11)
            lists = np.asarray(lists)
            for _ in range(ticks):
                lids = lists[rng.integers(0, len(lists), BATCH)]
                q2 = (centers_np[lids]
                      + 0.01 * rng.standard_normal((BATCH, D))
                      ).astype(np.float32)
                t0 = time.perf_counter()
                jax.block_until_ready(
                    ex2.search(t2, q2, K, params=p)[0])
                if measure:
                    lat.append(time.perf_counter() - t0)
                clk.advance(11.0)
                mgr2.tick()

        drive(hot0, 12)              # settle on hot0
        drive(cold0, 14)             # warm drift cycle (specialize)
        c0 = dict(tracing.counters())
        drive(hot0, 14, measure=True)   # measured drift-back
        c1 = dict(tracing.counters())

        def delta(name):
            return float(c1.get(name, 0) - c0.get(name, 0))

        lat.sort()
        return {
            "promotions": delta("tier.promotions"),
            "promote_cold_bytes": delta("tier.promote_cold_bytes"),
            "prefetch_issued": delta(ISSUED),
            "prefetch_hits": delta(HITS),
            "prefetch_misses": delta(MISSES),
            "compiles_during_load": delta(tracing.XLA_COMPILE_COUNT),
            "p99_ms": round(
                lat[min(len(lat) - 1, int(0.99 * len(lat)))] * 1e3, 3),
        }

    log("tiered prefetch A/B: reactive leg")
    reactive = _prefetch_leg(False)
    log("tiered prefetch A/B: prefetch-on leg")
    on = _prefetch_leg(True)
    pf_total = on["prefetch_hits"] + on["prefetch_misses"]
    prefetch_ab = {
        "reactive": reactive,
        "on": on,
        "hit_rate": round(on["prefetch_hits"] / pf_total, 4)
        if pf_total else 0.0,
        "cold_bytes_saved": reactive["promote_cold_bytes"]
        - on["promote_cold_bytes"],
        "reduces_cold_bytes": int(
            on["promote_cold_bytes"] < reactive["promote_cold_bytes"]),
    }
    log(f"tiered prefetch A/B: hits {on['prefetch_hits']:.0f}/"
        f"{on['prefetch_issued']:.0f} issued, cold bytes "
        f"{reactive['promote_cold_bytes']:.0f} -> "
        f"{on['promote_cold_bytes']:.0f}, compiles during load "
        f"{on['compiles_during_load']:.0f}")

    return {
        "n": n, "dim": D, "n_lists": n_lists, "n_probes": n_probes,
        "batch": BATCH, "k": K, "max_list_size": m,
        "hot_lists": t.n_hot, "cold_lists": t.n_cold,
        "host_resident": int(t.host_resident),
        "union_lists": int(len(uniq)),
        "union_hot": union_hot, "union_cold": union_cold,
        "hot_model_bytes": int(hot_model_bytes),
        "cold_model_bytes": int(cold_model_bytes),
        "best_s": round(dt, 6), "qps": round(BATCH / dt, 2),
        "bit_identical": int(bit_identical and post_identical),
        "hot_gbps": round(hot_gbps, 2),
        "cold_gbps": round(cold_gbps, 2),
        "hbm_roofline_gbps": round(hbm_roof_gbps, 2),
        "host_roofline_gbps": round(host_roof_gbps, 2),
        "vs_hbm_roofline": round(hot_gbps / hbm_roof_gbps, 4),
        "vs_host_roofline": round(cold_gbps / host_roof_gbps, 4),
        "epochs": 2,
        "swap_bytes_per_epoch": swap_bytes,
        "swap_bytes_total": int(sum(swap_bytes)),
        "compiles_during_epochs": compiles,
        "prefetch": prefetch_ab,
    }


def _fleet_rider():
    """BENCH_FLEET=1 rider: graftroute's fleet router through the
    device-free N-replica harness (deterministic hash engine — the
    numbers gate ROUTING structure, not scan kernels). The planner
    places a skewed traffic plane (hot head replicated fleet-wide,
    long tail owned once), then three routed legs run against the
    solo-replica oracle:

    - ``steer``: head-covered batches steered whole to one hot
      replica — must be bit-identical to solo;
    - ``fanout_f32``: tail batches partitioned owner-wise, merged on
      the f32 wire — must also be bit-identical (the exact-merge
      contract);
    - ``fanout_bf16``: the same legs on the opt-in bf16 distance
      wire (ids stay exact int32) — half the merge payload, recall
      pinned >= 0.99 and deterministic at the seeded config.

    The merge-bytes columns come from ``route_payload_model`` (the
    ``collective_payload_model`` convention), so the bf16 < f32
    payload ordering is encoded exactly; coverage/fan-out fractions
    come off the router's own gauge view. Env knobs:
    BENCH_FLEET_REPLICAS / BENCH_FLEET_LISTS / BENCH_FLEET_SECONDS.
    """
    import numpy as np

    from raft_tpu.bench.prims import timeit_stats
    from raft_tpu.fleet import (
        FleetPlanConfig,
        QueryRouter,
        RouterConfig,
        make_fleet,
        plan_fleet,
        route_payload_model,
    )

    n_replicas = int(os.environ.get("BENCH_FLEET_REPLICAS", 4))
    n_lists = int(os.environ.get("BENCH_FLEET_LISTS", 64))
    budget = float(os.environ.get("BENCH_FLEET_SECONDS", 2))

    h = make_fleet(n_replicas, n_lists=n_lists)
    # skewed plane: the head half is hot enough to replicate onto
    # every replica (hot_share_ratio 0.5 → copies saturate at fleet
    # size), the tail is owned exactly once
    counts = np.ones(n_lists, np.int64)
    counts[: n_lists // 2] = 10_000
    table = plan_fleet(
        counts, {n: None for n in h.replicas}, label="ivf:0",
        version=1, config=FleetPlanConfig(hot_share_ratio=0.5))
    log(f"fleet rider: {n_replicas} replicas, {n_lists} lists, "
        f"{table.replicated_lists()} replicated")

    def _router(wire):
        r = QueryRouter(h.replicas, resolve_probes=h.resolve_probes,
                        clock=h.clock,
                        config=RouterConfig(merge_wire_dtype=wire))
        assert r.apply_table(table)
        return r

    r32 = _router("f32")
    # head batch: every probed list inside the replicated head →
    # steered whole; tail batch: probes cross the singleton tail →
    # owner-wise fan-out
    q_head = h.make_queries(BATCH, 0)
    q_tail = h.make_queries(BATCH, n_lists // 2)

    legs = []
    for name, q in (("steer", q_head), ("fanout_f32", q_tail)):
        ref_d, ref_i = h.solo(q, K)
        d, i, dec = r32.route(q, K)
        bit = bool(np.array_equal(np.asarray(d), ref_d)
                   and np.array_equal(np.asarray(i), ref_i))
        st = timeit_stats(lambda: r32.route(q, K),
                          min(budget, 3.0))
        legs.append((name, {
            "mode": dec.mode, "legs": dec.legs,
            "bit_identical": int(bit),
            "best_s": round(st["best_s"], 6),
            "qps": round(BATCH / st["best_s"], 2),
        }))
        log(f"fleet {name}: mode={dec.mode} legs={dec.legs} "
            f"bit_identical={bit} {st['best_s'] * 1e3:.3f} ms/iter")

    # bf16 wire: same fan-out legs, half-width distance payload;
    # recall vs the solo oracle (ids exact int32 on any wire)
    rb = _router("bf16")
    ref_d, ref_i = h.solo(q_tail, K)
    d, i, dec = rb.route(q_tail, K)
    ib = np.asarray(i)
    hits = sum(
        len(set(ib[row].tolist()) & set(ref_i[row].tolist()))
        for row in range(ref_i.shape[0]))
    recall = hits / float(ref_i.size)
    st = timeit_stats(lambda: rb.route(q_tail, K), min(budget, 3.0))
    pay32 = route_payload_model(BATCH, K, dec.legs, "f32")
    pay16 = route_payload_model(BATCH, K, dec.legs, "bf16")
    log(f"fleet fanout_bf16: recall={recall:.4f} merge bytes "
        f"{pay32['merge_bytes']} -> {pay16['merge_bytes']}")
    legs.append(("fanout_bf16", {
        "mode": dec.mode, "legs": dec.legs,
        "recall": round(recall, 4),
        "best_s": round(st["best_s"], 6),
        "qps": round(BATCH / st["best_s"], 2),
    }))

    # coverage split on a FRESH router under a fixed 12-head /
    # 4-tail batch schedule — the timed routers above saw a host-
    # speed-dependent number of iterations, this column must be
    # exact at the pinned geometry
    rc = _router("f32")
    for b in range(16):
        start = 0 if b % 4 else n_lists // 2
        rc.route(h.make_queries(BATCH, start), K)
    snap = rc.snapshot()["router"]
    req = snap["requests"]
    rec = {
        "replicas": n_replicas, "n_lists": n_lists,
        "batch": BATCH, "k": K,
        "table_version": table.version,
        "replicated_lists": table.replicated_lists(),
        "cold_owned": len(table.cold_owned),
        "requests": req,
        "coverage_rate": round(snap["steered"] / req, 4),
        "fanout_fraction": round(snap["fanout"] / req, 4),
        "merge_bytes_f32": pay32["merge_bytes"],
        "merge_bytes_bf16": pay16["merge_bytes"],
        "wire_bytes_saved_frac": round(
            1.0 - pay16["merge_bytes"] / pay32["merge_bytes"], 4),
    }
    rec.update(legs)
    return rec


def _serving_rider():
    """BENCH_SERVING=1 rider: the request frontend under bursty
    open-loop load. A DynamicBatcher in front of a warmed
    ``SearchExecutor`` takes bursts of small (1-4 row) requests on a
    fixed schedule (open loop — submission does not wait for
    completions) and the rider emits p50/p95/p99 end-to-end latency,
    the shed/reject rates, and the measured batch occupancy
    (requests per executor call — the coalescing win) next to the
    one-request-per-call baseline's QPS over the same request stream.

    PR 6 (graftscope): the record also carries the cost-analysis-
    derived achieved-vs-roofline columns — modeled bytes/flops from
    each executable's compile-time ``cost_analysis()`` divided by the
    measured execute-latency histogram, next to a ``stream_read_sum``
    roofline probe of the packed list tensor. These are the SAME
    counters the live ``serving.execute.*`` metrics and the exporter's
    ``derived`` block read, so BENCH JSONs and a running scrape agree
    by construction.

    PR 9 (ragged continuous batching): the record carries a
    ``ragged`` A/B block — the SAME request stream driven through the
    packed-batch plan family (``BatcherConfig(ragged=True)``, one
    executable at ``BENCH_SV_RAGGED_TILE`` rows) next to the bucketed
    leg, with the columns the acceptance criteria gate on: pad-waste
    fraction (bucketed pow2 rounding wastes up to ~50%; the packed
    tile only pads timer-fired partials), executables compiled (one
    vs the ladder), backend compiles during load, and p99 at the same
    offered load.

    PR 12 (graftfleet): a ``continuous`` A/B block — the SAME
    bucketed stream with a ``ContinuousCapture`` armed (REAL
    ``jax.profiler`` windows ticked from the open-loop pump hook), so
    the gated ``p99_ratio`` column prices steady-state attribution
    against the capture-free leg, next to the capture/window/duty
    accounting.

    Env knobs: BENCH_SV_N / BENCH_SV_LISTS / BENCH_SV_BURSTS /
    BENCH_SV_BURST (requests per burst) / BENCH_SV_MAX_ROWS (request
    sizes draw 1..max — the size variance the pad-waste A/B regime is
    defined over) / BENCH_SV_PERIOD_MS / BENCH_SV_WAIT_MS (batcher
    max-wait) / BENCH_SV_TIMEOUT_MS (per-request deadline) /
    BENCH_SV_RAGGED_TILE (packed tile rows) / BENCH_SV_RAGGED_SMALL
    (dual small tile, 0 = off) / BENCH_SV_FAMILIES (=1: PQ + BQ +
    mesh ragged legs) / BENCH_SV_MESH_SHARDS (mesh-leg device floor)
    / BENCH_SV_CONT (=1, continuous A/B on) / BENCH_SV_CONT_PERIOD_MS
    / BENCH_SV_CONT_CAPTURE_MS (scheduler cadence for the A/B).

    PR 15 (graftragged): ``ragged_families`` legs drive the SAME
    stream through the PQ, BQ, and mesh ragged fronts — the unified
    ragged plan family across the index zoo — each gated on the
    structural acceptance columns (≤ 2 executables via the dual
    tile, tight compiles-during-load, pad waste ≤ 0.05 band)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from raft_tpu import SearchExecutor
    from raft_tpu.core import tracing
    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.serving import BatcherConfig, DynamicBatcher
    from raft_tpu.serving import metrics as sv_metrics
    from raft_tpu.serving.harness import burst_schedule, drive_open_loop

    n = int(os.environ.get("BENCH_SV_N", 200_000))
    n_lists = int(os.environ.get("BENCH_SV_LISTS", 256))
    n_bursts = int(os.environ.get("BENCH_SV_BURSTS", 50))
    burst = int(os.environ.get("BENCH_SV_BURST", 16))
    max_rows = int(os.environ.get("BENCH_SV_MAX_ROWS", 4))
    period_s = float(os.environ.get("BENCH_SV_PERIOD_MS", 10)) / 1e3
    max_wait_s = float(os.environ.get("BENCH_SV_WAIT_MS", 2)) / 1e3
    timeout_s = float(os.environ.get("BENCH_SV_TIMEOUT_MS", 250)) / 1e3

    kd, kq = jax.random.split(jax.random.key(5))
    x = np.asarray(jax.random.normal(kd, (n, D), jnp.float32))
    rng = np.random.default_rng(9)
    log(f"serving rider: building index ({n}x{D}, {n_lists} lists)")
    index = ivf_flat.build(None, ivf_flat.IvfFlatIndexParams(
        n_lists=n_lists, kmeans_n_iters=10), x)
    p = ivf_flat.IvfFlatSearchParams(n_probes=20)
    ex = SearchExecutor()
    ex.warmup(index, k=K, params=p)
    tracing.install_xla_compile_listener()

    # pre-draw the request stream: bursts of mixed-size blocks
    # (1..BENCH_SV_MAX_ROWS rows). Size variance is what makes the
    # pad-waste A/B honest: whole-request assembly stops mid-bucket
    # when the next request does not fit, while the ragged path splits
    # at tile boundaries and keeps every tile full.
    blocks = [rng.standard_normal(
        (int(rng.integers(1, max_rows + 1)), D)).astype(np.float32)
        for _ in range(n_bursts * burst)]

    # baseline: the same stream, one executor call per request — also
    # the honest measurement of the raw bucket ladder's pad waste
    # (every request pow2-rounds alone; coalescing hides most of it,
    # splitting kills it)
    sv_metrics.reset()
    t0 = time.perf_counter()
    for b in blocks:
        jax.block_until_ready(ex.search(index, b, K, params=p))
    base_dt = time.perf_counter() - t0
    base_qps = len(blocks) / base_dt
    base_pad_waste = sv_metrics.derived()["pad_waste_fraction"]
    log(f"serving rider baseline: {base_qps:.1f} req/s "
        f"(one call per request, pad waste {base_pad_waste:.3f})")

    sv_metrics.reset()
    b = DynamicBatcher(ex, BatcherConfig(max_wait_s=max_wait_s,
                                         full_batch_rows=256))
    clock = b._clock
    backend0 = tracing.get_counter(tracing.XLA_COMPILE_COUNT)

    def submit(ordinal, _t):
        return b.submit(index, blocks[ordinal], K, params=p,
                        timeout_s=timeout_s)

    t0 = time.perf_counter()
    handles = drive_open_loop(
        submit, burst_schedule(n_bursts, burst, period_s,
                               start_s=clock.now()), clock)
    done = sum(1 for h in handles if h.exception(timeout=30.0) is None)
    dt = time.perf_counter() - t0
    b.close()

    snap = sv_metrics.snapshot()
    occ = snap["occupancy"]
    e2e = snap["histograms"].get(sv_metrics.E2E, {})
    shed = snap["counters"].get("serving.batcher.shed_deadline", 0)
    rej = snap["counters"].get("serving.admission.rejected", 0)
    slo_ok = snap["counters"].get(sv_metrics.SLO_ATTAINED, 0)
    slo_miss = snap["counters"].get(sv_metrics.SLO_MISSED, 0)
    der = snap["derived"]

    # roofline: a pure streamed read of the packed list tensor — the
    # same ceiling the IVF sweep judges engines against, here next to
    # the achieved number derived from cost_analysis + execute latency
    roof_gbps = 0.0
    try:
        from raft_tpu.bench.prims import timeit_stats
        from raft_tpu.ops.fused_topk import stream_read_sum

        flat = jnp.asarray(index.data).reshape(-1, D)
        interp = jax.default_backend() != "tpu"
        st = timeit_stats(lambda: stream_read_sum(flat, interpret=interp),
                          2.0)
        roof_gbps = (flat.size * index.data.dtype.itemsize
                     / st["best_s"] / 1e9)
    except Exception as e:  # noqa: BLE001 — roofline probe is best-effort
        log(f"serving rider roofline probe failed ({e})")
    # ---- ragged A/B leg: the SAME stream through the packed-batch
    # plan family — continuous admission with tile-boundary splits,
    # one executable per tile (BENCH_SV_RAGGED_TILE rows, plus the
    # optional BENCH_SV_RAGGED_SMALL dual tile — ≤ 2 total)
    ragged_tile = int(os.environ.get("BENCH_SV_RAGGED_TILE", 64))
    ragged_small = int(os.environ.get("BENCH_SV_RAGGED_SMALL", 0))

    def _ragged_executor():
        return SearchExecutor(
            ragged_tile=ragged_tile,
            ragged_tile_small=ragged_small or None)

    def _drive_ragged(idx, params, legs_bursts, **sub_kw):
        """One ragged A/B leg: warm the packed executable(s), drive
        the SAME mixed-size stream through BatcherConfig(ragged=True),
        and report the acceptance columns (pad waste, executables,
        compiles during load, p99 at the offered load)."""
        ex_f = _ragged_executor()
        ex_f.warmup_ragged(idx, k=K, params=params, **sub_kw)
        sv_metrics.reset()
        bf = DynamicBatcher(ex_f, BatcherConfig(max_wait_s=max_wait_s,
                                                full_batch_rows=256,
                                                ragged=True))
        backend0_f = tracing.get_counter(tracing.XLA_COMPILE_COUNT)

        def submit_f(ordinal, _t):
            return bf.submit(idx, blocks[ordinal], K, params=params,
                             timeout_s=timeout_s, **sub_kw)

        t0 = time.perf_counter()
        handles_f = drive_open_loop(
            submit_f, burst_schedule(legs_bursts, burst, period_s,
                                     start_s=bf._clock.now()),
            bf._clock)
        done_f = sum(1 for h in handles_f
                     if h.exception(timeout=30.0) is None)
        dt_f = time.perf_counter() - t0
        bf.close()
        snap_f = sv_metrics.snapshot()
        e2e_f = snap_f["histograms"].get(sv_metrics.E2E, {})
        occ_f = snap_f["occupancy"]
        return {
            "tile_rows": ragged_tile,
            "tile_rows_small": ragged_small,
            "requests": len(handles_f), "completed": done_f,
            "qps": round(done_f / dt_f, 2),
            "p50_ms": round(e2e_f.get("p50", 0) * 1e3, 3),
            "p95_ms": round(e2e_f.get("p95", 0) * 1e3, 3),
            "p99_ms": round(e2e_f.get("p99", 0) * 1e3, 3),
            "requests_per_batch": round(occ_f["requests_per_batch"], 2),
            "rows_per_batch": round(occ_f["rows_per_batch"], 2),
            "pad_waste_fraction": round(
                snap_f["derived"]["pad_waste_fraction"], 4),
            "pad_waste_by_class":
                snap_f["derived"]["pad_waste_by_class"],
            "backend_compiles_during_load": (
                tracing.get_counter(tracing.XLA_COMPILE_COUNT)
                - backend0_f),
            "executables": ex_f.ragged_executables(),
        }

    ragged_out = _drive_ragged(index, p, n_bursts)

    # ---- ragged family legs (graftragged): the SAME mixed-size
    # stream through the PQ, BQ, and mesh ragged fronts — the whole
    # index zoo serving from the one ragged plan family. Each leg
    # gates the structural acceptance columns (≤ 2 executables, tight
    # compiles-during-load, pad waste ≤ baseline + 0.05); the mesh
    # leg needs >= BENCH_SV_MESH_SHARDS local devices (the pinned CI
    # config forces virtual CPU devices via XLA_FLAGS) and is
    # reported absent otherwise.
    fam_out = {}
    if os.environ.get("BENCH_SV_FAMILIES", "1") == "1":
        from raft_tpu.neighbors import ivf_bq, ivf_pq

        fam_bursts = max(2, n_bursts // 2)
        log("serving rider: building PQ/BQ family-leg indexes")
        pq_index = ivf_pq.build(None, ivf_pq.IvfPqIndexParams(
            n_lists=n_lists, pq_dim=max(4, D // 8),
            kmeans_n_iters=10), x)
        # the list-major union engine is the raggable one (auto
        # resolves to rank-major on CPU, which has no membership mask)
        fam_out["pq"] = _drive_ragged(
            pq_index, ivf_pq.IvfPqSearchParams(
                n_probes=20, scan_engine="xla"), fam_bursts)
        bq_index = ivf_bq.build(None, ivf_bq.IvfBqIndexParams(
            n_lists=n_lists, bits=2, kmeans_n_iters=10), x)
        fam_out["bq"] = _drive_ragged(
            bq_index, ivf_bq.IvfBqSearchParams(
                n_probes=20, scan_engine="xla"), fam_bursts)
        mesh_shards = int(os.environ.get("BENCH_SV_MESH_SHARDS", 4))
        if jax.device_count() >= mesh_shards:
            from raft_tpu.comms import local_comms
            from raft_tpu.distributed import ivf as dist_ivf

            comms = local_comms(
                shape=(jax.device_count(),))
            log(f"serving rider: building {comms.size}-shard mesh "
                "family-leg index")
            mesh_index = dist_ivf.build(None, comms, ivf_flat.
                                        IvfFlatIndexParams(
                                            n_lists=n_lists,
                                            kmeans_n_iters=10), x)
            fam_out["mesh"] = dict(_drive_ragged(
                mesh_index, ivf_flat.IvfFlatSearchParams(
                    n_probes=20, scan_engine="xla"), fam_bursts),
                shards=comms.size)
        else:
            log(f"serving rider: mesh family leg skipped — "
                f"{jax.device_count()} device(s) < {mesh_shards}")

    # ---- continuous-capture overhead A/B (PR 12 graftfleet): the
    # SAME bucketed stream with a ContinuousCapture armed (REAL
    # jax.profiler windows, driven from the open-loop pump hook) —
    # the p99 delta vs the capture-free leg above is the price of
    # steady-state attribution, gated tight in ci/bench_compare.py.
    # The first tick always captures (the budget admits it), so every
    # run pays at least one real profiler window; the default 1%
    # budget then gates the rest — the honest deployment cadence.
    cont_out = {}
    if os.environ.get("BENCH_SV_CONT", "1") == "1":
        import tempfile

        from raft_tpu.serving import ContinuousCapture, ContinuousConfig
        from raft_tpu.serving import continuous as cont_mod

        cont_period = float(
            os.environ.get("BENCH_SV_CONT_PERIOD_MS", 50)) / 1e3
        cont_cap = float(
            os.environ.get("BENCH_SV_CONT_CAPTURE_MS", 20)) / 1e3
        p99_off_ms = round(e2e.get("p99", 0) * 1e3, 3)
        sv_metrics.reset()
        bc = DynamicBatcher(ex, BatcherConfig(max_wait_s=max_wait_s,
                                              full_batch_rows=256))
        cc = ContinuousCapture(
            executor=ex, clock=bc._clock,
            config=ContinuousConfig(period_s=cont_period,
                                    capture_seconds=cont_cap),
            profile_dir=tempfile.mkdtemp(prefix="bench_cont_prof_"))
        counters0 = {name: tracing.get_counter(name) for name in (
            cont_mod.CAPTURES, cont_mod.EMPTY, cont_mod.ERRORS)}

        def submit_c(ordinal, _t):
            return bc.submit(index, blocks[ordinal], K, params=p,
                             timeout_s=timeout_s)

        t0 = time.perf_counter()
        handles_c = drive_open_loop(
            submit_c, burst_schedule(n_bursts, burst, period_s,
                                     start_s=bc._clock.now()),
            bc._clock, pump=cc.tick)
        done_c = sum(1 for h in handles_c
                     if h.exception(timeout=30.0) is None)
        dt_c = time.perf_counter() - t0
        cc.tick()             # one more chance past the load window
        bc.close()
        e2e_c = sv_metrics.snapshot()["histograms"].get(
            sv_metrics.E2E, {})
        p99_on_ms = round(e2e_c.get("p99", 0) * 1e3, 3)
        deltas = {name: tracing.get_counter(name) - v0
                  for name, v0 in counters0.items()}
        cont_out = {
            "period_ms": cont_period * 1e3,
            "capture_ms": cont_cap * 1e3,
            "requests": len(handles_c), "completed": done_c,
            "qps": round(done_c / dt_c, 2),
            "p99_ms": p99_on_ms,
            "p99_off_ms": p99_off_ms,
            # the gated overhead signal: on/off tail ratio over the
            # identical stream (CI hosts are noisy on absolutes)
            "p99_ratio": round(p99_on_ms / max(p99_off_ms, 1e-9), 4),
            # attempts = captured + empty + failed windows: whether a
            # 20 ms window caught a dispatch is thread-timing luck,
            # paying for real profiler windows is not
            "captures": int(deltas[cont_mod.CAPTURES]),
            "capture_attempts": int(sum(deltas.values())),
            "rolling_windows": int(tracing.get_gauge(
                "serving.attribution.rolling.windows")),
            "duty_cycle": round(cc.duty_cycle(), 5),
        }
        log(f"serving rider continuous A/B: p99 {p99_on_ms} ms with "
            f"duty cycle on vs {p99_off_ms} ms off (ratio "
            f"{cont_out['p99_ratio']}), "
            f"{cont_out['capture_attempts']} capture window(s), "
            f"{cont_out['rolling_windows']} attributed")

    out = {
        "n": n, "dim": D, "n_lists": n_lists, "k": K,
        "bursts": n_bursts, "burst_size": burst,
        "period_ms": period_s * 1e3, "max_wait_ms": max_wait_s * 1e3,
        "requests": len(handles), "completed": done,
        "qps": round(done / dt, 2),
        "baseline_one_per_call_qps": round(base_qps, 2),
        "baseline_pad_waste_fraction": round(base_pad_waste, 4),
        "p50_ms": round(e2e.get("p50", 0) * 1e3, 3),
        "p95_ms": round(e2e.get("p95", 0) * 1e3, 3),
        "p99_ms": round(e2e.get("p99", 0) * 1e3, 3),
        "shed_rate": round(shed / max(len(handles), 1), 4),
        "reject_rate": round(rej / max(len(handles), 1), 4),
        # graftscope v2: deadline-SLO attainment over the same stream
        "slo_attained": int(slo_ok),
        "slo_missed": int(slo_miss),
        "slo_burn_rate": round(
            tracing.get_gauge(sv_metrics.SLO_BURN_RATE), 4),
        "requests_per_batch": round(occ["requests_per_batch"], 2),
        "rows_per_batch": round(occ["rows_per_batch"], 2),
        "backend_compiles_during_load": (
            tracing.get_counter(tracing.XLA_COMPILE_COUNT) - backend0),
        # graftscope: live-metric accounting reproduced in the JSON
        "modeled_exec_bytes": int(der["modeled_bytes_total"]),
        "modeled_exec_flops": int(der["modeled_flops_total"]),
        "execute_seconds_total": round(der["execute_seconds_total"], 6),
        "achieved_gbps": round(der["achieved_gbps"], 3),
        "achieved_gflops": round(der["achieved_gflops"], 3),
        "roofline_gbps": round(roof_gbps, 3),
        "vs_roofline": (round(der["achieved_gbps"] / roof_gbps, 4)
                        if roof_gbps else 0.0),
        "cache_hit_rate": round(der["cache_hit_rate"], 4),
        "executables": len(ex.executable_costs()),
        "pad_waste_fraction": round(der["pad_waste_fraction"], 4),
        "ragged": ragged_out,
        "ragged_families": fam_out,
        "continuous": cont_out,
    }
    log(f"serving rider: {out['qps']} req/s through the batcher "
        f"(occupancy {out['requests_per_batch']} req/call, "
        f"p99 {out['p99_ms']} ms, shed {out['shed_rate']}, "
        f"scan {out['achieved_gbps']} GB/s = {out['vs_roofline']} of "
        f"roofline)")
    log(f"serving rider ragged A/B: {ragged_out['qps']} req/s, p99 "
        f"{ragged_out['p99_ms']} ms, pad waste "
        f"{ragged_out['pad_waste_fraction']} (bucketed "
        f"{out['pad_waste_fraction']}), "
        f"{ragged_out['executables']} executable(s) vs "
        f"{out['executables']}, compiles during load "
        f"{ragged_out['backend_compiles_during_load']}")
    for fam, rec in fam_out.items():
        log(f"serving rider ragged {fam}: {rec['qps']} req/s, p99 "
            f"{rec['p99_ms']} ms, pad waste "
            f"{rec['pad_waste_fraction']}, {rec['executables']} "
            f"executable(s), compiles during load "
            f"{rec['backend_compiles_during_load']}")
    return out


if __name__ == "__main__":
    main()
