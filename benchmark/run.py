"""Run one benchmark cell once, on the chip, through the path users call.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads`` entry) names a
configuration and a traffic mix, each a file of its own (see
:mod:`benchmark.spec`). One run:

1. set-up (``setup_s``, from process start to the first timed
   request): the corpus made from the configuration's ``data_seed`` in
   its ``dataset.dtype`` (float32, uint8 or int8), sharded by rows over
   the cell's ``chips`` and drawn block by block on the chip that holds
   each block (:mod:`benchmark.data`), and the query pool from
   ``--seed``; the index built by the family's adapter, which may take
   the corpus and the cell's chips (:mod:`benchmark.spec`); the
   executor warmed for exactly the buckets and host-side shapes this
   mix produces, one pass through the batcher;
2. the window: the mix's clients drive ``DynamicBatcher.submit`` (the
   default ``BatcherConfig``) for ``--seconds``; with ``--trace 1`` the
   profiler records it;
3. after the window: the chips' peak memory is read, the program's
   state freed, and the plain reference the configuration names judges
   every answer the clients received (:mod:`benchmark.check`), each
   chip searching the rows it holds.

The last line of standard output is the result. With ``--trace 0`` its
metrics are the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics. Standard error gives each phase's seconds with the
peak memory of each of the cell's chips (``memory_peak_bytes`` is the
fullest). Without a TPU, or with fewer chips than the cell asks for, it
prints no result and exits 2.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import spec  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
TPU_LOG_DIR = os.path.join(ROOT, ".tpu_logs")


class NoChip(RuntimeError):
    """The machine lacks what the cell asks for."""


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_chips(chips: int):
    """The devices to run on; raises :class:`NoChip` off a TPU or with
    too few chips, and ``KeyError`` for a kind the peak table lacks."""
    import jax

    from benchmark import peaks

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"cell asks for {chips} chips, JAX found {len(devs)}")
    peaks.lookup(devs[0].device_kind)
    return devs


class Window:
    """What the program counted over the window, for the readers in
    ``metrics/``: counter and histogram differences, the executor's
    compiles, the trace's reduction and the kernel's work."""

    def __init__(self, cell, before, after, trace, work, n_requests,
                 peak):
        self.cell, self.trace, self.work = cell, trace, work
        self.before, self.after = before, after
        self.n_requests, self.peak = n_requests, peak

    def counter(self, name: str) -> float:
        return (self.after["counters"].get(name, 0.0)
                - self.before["counters"].get(name, 0.0))

    def hist(self, name: str):
        """``(count, sum)`` observed in the window."""
        a = self.after["hists"].get(name, {"count": 0, "sum": 0.0})
        b = self.before["hists"].get(name, {"count": 0, "sum": 0.0})
        return a["count"] - b["count"], a["sum"] - b["sum"]

    def hist_mean_ms(self, name: str):
        n, s = self.hist(name)
        return 1e3 * s / n if n else None

    def compiles(self) -> int:
        return self.after["compiles"] - self.before["compiles"]

    def kernel_roofline(self, kernel: str):
        """Percent of the roofline the kernel reached over the traced
        run: the ideal time of every dispatch's work over the kernel's
        summed device time. Nothing where the cell runs another kernel,
        where dispatches held more than one request (the work of a
        coalesced dispatch is not the sum of its requests'), or where
        the trace's kernel events do not pair one to one with the
        dispatches."""
        from benchmark import peaks

        if (self.trace is None or self.work is None
                or self.cell.conf.get("kernel") != kernel):
            return None
        batches = self.counter("serving.batcher.batches")
        if batches != self.n_requests or self.counter(
                "serving.batcher.requests") != self.n_requests:
            return None
        events, seconds = self.trace["kernels"].get(kernel, (0, 0.0))
        if events != self.n_requests or seconds <= 0:
            return None
        ideal = peaks.ideal_seconds(self.work[0], self.work[1], self.peak)
        return 100.0 * ideal / seconds


def peak_bytes(devs) -> list:
    """Each device's peak memory in use so far (0 where the backend
    keeps no count)."""
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devs]


class Phases:
    """Seconds each step of set-up and judging took, on standard error,
    with each device's peak memory so far."""

    def __init__(self, devs=()):
        self.devs = list(devs)
        self.t = time.perf_counter()

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        print(f"phase {name} {now - self.t:.3f} s peak_bytes "
              f"{peak_bytes(self.devs)}", file=sys.stderr)
        self.t = now


def build(cell, corpus, devs):
    """The family's index over the corpus: ``build_on`` with the corpus
    and the cell's devices where the adapter has it, else ``build`` with
    the corpus's array."""
    if hasattr(cell.family, "build_on"):
        return cell.family.build_on(cell.conf, corpus, devs)
    return cell.family.build(cell.conf, corpus.array)


def snapshot(ex) -> dict:
    from raft_tpu.core import tracing

    return {"counters": tracing.counters(),
            "hists": {k: {"count": v["count"], "sum": v["sum"]}
                      for k, v in tracing.histograms().items()},
            "compiles": ex.stats.compile_count,
            "xla_compiles": tracing.get_counter(tracing.XLA_COMPILE_COUNT)}


def warm(cell, ex, index, params, pool, k: int) -> None:
    """Compile and touch exactly what this mix will run: the buckets of
    every micro-batch size it can coalesce, and the host-side pad and
    split shapes of each, through the executor's batch entry."""
    import numpy as np
    from raft_tpu.serving import BatcherConfig

    from benchmark import traffic

    m = int(cell.traffic["queries_per_request"])
    most = traffic.coalesced_counts(cell.traffic,
                                    BatcherConfig().full_batch_rows)
    sizes = range(1, most + 1)
    ex.warmup(index, buckets=sorted({ex.bucket_for(r * m) for r in sizes}),
              k=k, params=params)
    for r in sizes:
        blocks = [pool[(j * m + np.arange(m)) % len(pool)] for j in range(r)]
        for d, i in ex.search_blocks(index, blocks, k, params=params):
            np.asarray(d), np.asarray(i)


def measure(cell, seed: int, seconds: float, trace: bool, devs) -> dict:
    """One run of ``cell``; returns the result object."""
    import jax
    import numpy as np
    from raft_tpu import SearchExecutor
    from raft_tpu.core import tracing
    from raft_tpu.core.resources import init_compile_cache
    from raft_tpu.serving import BatcherConfig, DynamicBatcher

    from benchmark import check, data, peaks
    from benchmark import trace as trace_mod
    from benchmark import traffic as traffic_mod

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    init_compile_cache()
    tracing.install_xla_compile_listener()
    devs = list(devs[:cell.chips])
    dev = devs[0]
    peak = peaks.lookup(dev.device_kind) if dev.platform == "tpu" else None
    conf, tr = cell.conf, cell.traffic
    ds = conf["dataset"]
    k = int(ds["k"])
    traffic_mod.check_mix(tr)

    phases = Phases(devs)
    corpus, pool_dev = data.for_dataset(ds, seed, int(tr["pool"]), devs)
    pool = np.asarray(pool_dev)
    phases.mark("data")
    index = build(cell, corpus, devs)
    jax.block_until_ready(index)
    phases.mark("build")
    if hasattr(cell.family, "describe"):
        print("index", cell.family.describe(index), file=sys.stderr)
    params = cell.family.search_params(conf)
    ex = SearchExecutor()
    warm(cell, ex, index, params, pool, k)
    phases.mark("warm")
    batcher = DynamicBatcher(ex, BatcherConfig())

    def submit(q):
        return batcher.submit(index, q, k, params=params)

    for j in range(2 * int(tr["clients"])):       # the batcher's own path
        rows = (j * int(tr["queries_per_request"])
                + np.arange(int(tr["queries_per_request"]))) % len(pool)
        d, i = submit(pool[rows]).result(timeout=600)
        np.asarray(d), np.asarray(i)
    loop = traffic_mod.ClosedLoop(tr, pool, submit)
    phases.mark("batcher_warm")
    before = snapshot(ex)
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    setup_s = time.perf_counter() - T_START
    t0, t_end = loop.run(seconds)
    if trace:
        jax.profiler.stop_trace()
    after = snapshot(ex)
    mem = peak_bytes(devs)
    memory_peak = max(mem)
    print(f"memory peak_bytes by chip {mem}", file=sys.stderr)

    recs = loop.records
    ok = [r for r in recs if r.error is None]
    failed = len(recs) - len(ok) + loop.stuck
    in_win = [r for r in ok if r.t_done <= t_end]
    work = None
    if trace and cell.work is not None:
        inputs = cell.family.work_inputs(conf, index, pool_dev)
        work = cell.work.totals(inputs, [r.rows for r in recs])
    batcher.close()
    del batcher, ex, index, loop
    gc.collect()

    phases = Phases(devs)
    x = corpus.array
    ref = cell.reference.knn(x, pool_dev, k)
    phases.mark("reference")
    if ok:
        qids = np.concatenate([r.rows for r in ok])
        dist = np.concatenate([r.dist for r in ok])
        ids = np.concatenate([r.ids for r in ok])
        win = np.concatenate([np.full(len(r.rows), r.t_done <= t_end)
                              for r in ok])
    else:
        qids = np.zeros(0, np.int64)
        dist, ids = np.zeros((0, k)), np.zeros((0, k), np.int64)
        win = np.zeros(0, bool)
    verdict = check.judge(x, pool, ref, (qids, dist, ids, win, failed),
                          conf.get("limits", {}),
                          cell.reference.true_distances)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    phases.mark("judge")
    result = {"correct": verdict["correct"], "attempted": len(recs),
              "failed": failed}
    xla_in_window = after["xla_compiles"] - before["xla_compiles"]
    if not trace:
        lat = np.array([r.t_done - r.t_sub for r in in_win])
        values = {
            "qps": sum(len(r.rows) for r in in_win) / (t_end - t0),
            "latency_p95_ms": (float(np.percentile(lat, 95)) * 1e3
                               if len(lat) else float("inf")),
            "recall_at_10": verdict["recall"],
            "setup_s": setup_s,
        }
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end() if m["name"] in values}
    else:
        red = trace_mod.reduce_dir(TRACE_DIR, kernels=_kernel_names(cell))
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = red["breakdown"]
        phases.mark("trace_reduce")
        win_ctx = Window(cell, before, after, red, work, len(recs), peak)
        metrics = {}
        for entry, reader in cell.per_layer():
            v = reader.read(win_ctx)
            if v is not None:
                metrics[entry["name"]] = {"value": v, "unit": entry["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = verdict["checks"]
    _finite(result)
    tenths = np.zeros(10)
    for r in in_win:
        tenths[min(9, int(10 * (r.t_done - t0) / (t_end - t0)))] += len(
            r.rows)
    print("queries/s by tenth of the window "
          + " ".join(f"{v * 10 / (t_end - t0):.0f}" for v in tenths),
          file=sys.stderr)
    print(f"requests {len(recs)} in window {len(in_win)} failed {failed} "
          f"xla compiles in window {xla_in_window}", file=sys.stderr)
    for name, c in verdict["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    return result


def _finite(obj):
    """Replace non-finite numbers in place by the largest float, so the
    result line is strict JSON (an invalid answer reads infinity)."""
    for key, v in (obj.items() if isinstance(obj, dict) else enumerate(obj)):
        if isinstance(v, (dict, list)):
            _finite(v)
        elif isinstance(v, float) and not v == v:
            obj[key] = sys.float_info.max
        elif isinstance(v, float) and abs(v) == float("inf"):
            obj[key] = sys.float_info.max if v > 0 else -sys.float_info.max


def _kernel_names(cell) -> dict:
    if cell.work is None:
        return {}
    return {cell.conf["kernel"]: tuple(cell.work.TRACE_PATTERNS)}


def main(argv=None, *, bench_path: str = spec.BENCHMARK_JSON,
         dirs=(spec.BENCH_DIR,)) -> int:
    args = parse_args(argv)
    try:
        cell = spec.Cell(args.workload, bench_path, dirs)
    except (OSError, spec.SpecError) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ.setdefault("TPU_LOG_DIR", TPU_LOG_DIR)   # not /tmp
    try:
        devs = require_chips(cell.chips)
    except (NoChip, KeyError) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    result = measure(cell, args.seed, args.seconds, bool(args.trace), devs)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
