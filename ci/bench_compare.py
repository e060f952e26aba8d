#!/usr/bin/env python
"""CI perf-regression gate (graftscope v2) — diff a fresh
``BENCH_SERVING`` run against the committed baseline with tolerance
bands, and sanity-check the test session's ``ci/metrics_snapshot.json``
modeled-throughput columns.

Why: PRs 1–6 built the serving hot path and the instrumentation that
prices it, but nothing *gated* on the numbers — a PR could halve
steady-state QPS or silently stop pricing dispatches and CI would stay
green. This script closes that loop:

1. **Bench diff** — replay the baseline's pinned small-config bench
   and compare the recorded columns against ``ci/bench_baseline.json``.
   ``bench.py`` measures only on a TPU, so ``--run`` replays only
   there; the committed baseline is a CPU record from before that
   rule and no longer replays (its successor is the chip benchmark,
   ROADMAP S1). Bands are wide where CI
   machines are noisy (wall-clock QPS/p99) and tight where the quantity
   is structural (batch occupancy, backend compiles during load —
   a recompiling steady state is a bug regardless of wall clock).
2. **Snapshot floors** — the metrics snapshot the test session drops
   must still carry live modeled-throughput accounting
   (``serving.execute.modeled_{bytes,flops}`` > 0): if a refactor
   disconnects cost introspection from the dispatch path, every
   achieved-GB/s surface goes dark while looking "green"; this catches
   it structurally.

Exit codes: 0 pass, 1 regression (messages on stderr), 2 usage/missing
inputs. Re-baseline deliberately with ``--update`` (writes the fresh
record + current default tolerances back to the baseline file) — the
diff then shows reviewers exactly what moved.

**Multi-baseline** (PR 8): ``--baseline`` repeats, and with none given
every committed ``ci/bench_baseline*.json`` gates — so a TPU-recorded
baseline (``ci/bench_baseline_tpu.json``) rides next to the pinned CPU
one. A
baseline carrying ``"requires_backend"`` is skipped with a note when
the current jax backend differs (the TPU baseline is inert on CPU CI
and live on the TPU runner); each baseline replays its OWN pinned env,
and identical envs share one bench run.

Usage::

    python ci/bench_compare.py --run --snapshot ci/metrics_snapshot.json
    python ci/bench_compare.py --run --update        # re-baseline
    python ci/bench_compare.py --fresh some_run.json  # offline diff
    python ci/bench_compare.py --run \
        --baseline ci/bench_baseline_tpu.json         # TPU gate only
"""

from __future__ import annotations

import argparse
import glob as _glob
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE_PATH = os.path.join(REPO, "ci", "bench_baseline.json")

# The pinned replay config: small enough for seconds-scale CI on CPU,
# big enough that the serving rider coalesces real micro-batches. It is
# recorded into the baseline and replayed from there on compare runs,
# so baseline and fresh always measure the same problem.
PINNED_ENV = {
    "BENCH_CHILD": "1",
    "JAX_PLATFORMS": "cpu",
    "BENCH_N": "20000",
    "BENCH_DIM": "64",
    "BENCH_BATCH": "10",
    "BENCH_K": "10",
    "BENCH_SECONDS": "3",
    "BENCH_DTYPE": "float32",
    "BENCH_SERVING": "1",
    "BENCH_SV_N": "20000",
    "BENCH_SV_LISTS": "32",
    "BENCH_SV_BURSTS": "6",
    # high occupancy with MIXED request sizes — the regime the
    # pad-waste acceptance column is defined over: whole-request
    # assembly stops mid-bucket when the next (large) request does
    # not fit, so the bucketed leg pays the pow2 rounding, while the
    # ragged leg splits at tile boundaries and keeps tiles full
    # (light load pads partial tiles on both paths, but light load
    # has idle compute to burn)
    "BENCH_SV_BURST": "8",
    "BENCH_SV_MAX_ROWS": "96",
    "BENCH_SV_RAGGED_TILE": "128",
    # graftragged (PR 15): the dual small tile and the PQ/BQ/mesh
    # family legs; the forced virtual CPU devices give the mesh leg
    # its 4-shard mesh (every rider in the child sees 4 devices —
    # single-device riders place on device 0 as before)
    "BENCH_SV_RAGGED_SMALL": "32",
    "BENCH_SV_FAMILIES": "1",
    "BENCH_SV_MESH_SHARDS": "4",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
    "BENCH_SV_PERIOD_MS": "10",
    "BENCH_SV_WAIT_MS": "2",
    # generous deadline: on a loaded CI host the CPU executes batches
    # near the second scale, and a deadline-shed would make the
    # completion column timing-flaky — attainment is still measured
    # (slo_* columns), it just isn't gated
    "BENCH_SV_TIMEOUT_MS": "10000",
    # graftfleet continuous-capture overhead A/B (PR 12): a fast
    # cadence so the seconds-scale run still pays >= 1 real profiler
    # window; the 1% duty budget then gates the rest as deployed
    "BENCH_SV_CONT": "1",
    "BENCH_SV_CONT_PERIOD_MS": "50",
    "BENCH_SV_CONT_CAPTURE_MS": "20",
    # RaBitQ IVF-BQ rider (this PR): small enough for seconds-scale
    # CPU CI, clustered enough that the recall floor band is stable
    "BENCH_BQ": "1",
    "BENCH_BQ_N": "20000",
    "BENCH_BQ_LISTS": "32",
    "BENCH_BQ_PROBES": "8",
    "BENCH_BQ_SECONDS": "2",
    # graftbeam (PR 16): the CAGRA A/B rider — pool vs coarse-plane
    # seeding vs coarse + BQ traversal on one small graph index; the
    # coarse pool is pinned 8x under the legacy pool (the frontier
    # claim the recall bands then hold at)
    "BENCH_CAGRA": "1",
    "BENCH_CAGRA_N": "8000",
    "BENCH_CAGRA_DEG": "16",
    "BENCH_CAGRA_BITS": "2",
    "BENCH_CAGRA_POOL": "4096",
    "BENCH_CAGRA_COARSE_POOL": "512",
    "BENCH_CAGRA_SECONDS": "2",
    # graftwire (this PR): the multichip rider on the 4 forced virtual
    # CPU devices — the quantized-vs-f32 k-means build A/B and the 2-D
    # query×list grid's compiles-during-load column; small enough for
    # seconds-scale CI, sharded enough that the wires actually cross
    # shard boundaries
    "BENCH_MULTICHIP": "1",
    "BENCH_MC_N": "4096",
    "BENCH_MC_LISTS": "32",
    "BENCH_MC_PROBES": "5",
    "BENCH_MC_SECONDS": "1",
    "BENCH_MC_KMEANS_ITERS": "3",
    "BENCH_MC_KMEANS_ROWS": "2048",
    # grafttier (PR 14): tiered storage rider — half the lists cold,
    # dual rooflines, two live placement epochs
    "BENCH_TIERED": "1",
    "BENCH_TIER_N": "20000",
    "BENCH_TIER_LISTS": "32",
    "BENCH_TIER_PROBES": "8",
    "BENCH_TIER_SECONDS": "2",
    # graftroute (PR 20): the fleet-router rider — device-free
    # N-replica harness, so every structural column (bit-identity,
    # recall, merge bytes, coverage split) is deterministic at the
    # pinned geometry
    "BENCH_FLEET": "1",
    "BENCH_FLEET_REPLICAS": "4",
    "BENCH_FLEET_LISTS": "64",
    "BENCH_FLEET_SECONDS": "1",
}

# Tolerance bands, keyed by dotted path into the bench record.
#   min_ratio:    fresh >= baseline * r   (higher is better)
#   max_ratio:    fresh <= baseline * r   (lower is better; a zero
#                 baseline only requires fresh to stay finite-small
#                 via max_increase when given)
#   max_increase: fresh <= baseline + n   (absolute slack)
# Wall-clock columns get wide bands (shared CI hosts are noisy);
# structural columns get tight ones.
DEFAULT_TOLERANCES = {
    "value": {"min_ratio": 0.30},                  # headline QPS
    "serving.qps": {"min_ratio": 0.30},
    "serving.baseline_one_per_call_qps": {"min_ratio": 0.30},
    "serving.p99_ms": {"max_ratio": 4.0, "max_increase": 50.0},
    "serving.requests_per_batch": {"min_ratio": 0.6},
    "serving.completed": {"min_ratio": 0.9},
    # steady state must not start recompiling: small absolute slack
    # covers the per-batch-size pad/concat micro-programs whose count
    # varies with thread-timing-dependent batch composition
    "serving.backend_compiles_during_load": {"max_increase": 25},
    "serving.modeled_exec_bytes": {"min_ratio": 0.5},
    "serving.modeled_exec_flops": {"min_ratio": 0.5},
    # ragged A/B leg (PR 9): same stream through the packed-batch
    # plan family. Structural columns are TIGHT — the whole point is
    # one executable, no recompiles, near-zero pad — while wall-clock
    # columns keep the wide CI-host bands.
    "serving.ragged.qps": {"min_ratio": 0.30},
    "serving.ragged.completed": {"min_ratio": 0.9},
    "serving.ragged.p99_ms": {"max_ratio": 4.0, "max_increase": 50.0},
    "serving.ragged.pad_waste_fraction": {"max_increase": 0.05},
    # the packed path has NO per-shape micro-programs (host-side
    # packing in, one batched fetch out), so its during-load compile
    # band is far tighter than the bucketed leg's
    "serving.ragged.backend_compiles_during_load": {"max_increase": 5},
    "serving.ragged.executables": {"max_increase": 0},
    "serving.pad_waste_fraction": {"max_increase": 0.15},
    # graftragged family legs (PR 15): PQ, BQ, and the 4-shard mesh
    # serve the SAME mixed-size stream through the unified ragged plan
    # family. Structural columns TIGHT per leg — at most the dual-tile
    # executable pair, a near-zero during-load compile band (the
    # packed path has no per-shape micro-programs; the small slack
    # covers one-time lazily-created planes), pad waste inside the
    # acceptance band — while wall-clock columns keep the wide
    # CI-host bands.
    "serving.ragged_families.pq.completed": {"min_ratio": 0.9},
    "serving.ragged_families.pq.qps": {"min_ratio": 0.30},
    "serving.ragged_families.pq.p99_ms": {"max_ratio": 4.0,
                                          "max_increase": 50.0},
    "serving.ragged_families.pq.pad_waste_fraction":
        {"max_increase": 0.05},
    "serving.ragged_families.pq.backend_compiles_during_load":
        {"max_increase": 5},
    "serving.ragged_families.pq.executables": {"max_increase": 0},
    "serving.ragged_families.bq.completed": {"min_ratio": 0.9},
    "serving.ragged_families.bq.qps": {"min_ratio": 0.30},
    "serving.ragged_families.bq.p99_ms": {"max_ratio": 4.0,
                                          "max_increase": 50.0},
    "serving.ragged_families.bq.pad_waste_fraction":
        {"max_increase": 0.05},
    "serving.ragged_families.bq.backend_compiles_during_load":
        {"max_increase": 5},
    "serving.ragged_families.bq.executables": {"max_increase": 0},
    "serving.ragged_families.mesh.completed": {"min_ratio": 0.9},
    "serving.ragged_families.mesh.qps": {"min_ratio": 0.30},
    "serving.ragged_families.mesh.p99_ms": {"max_ratio": 4.0,
                                            "max_increase": 50.0},
    "serving.ragged_families.mesh.pad_waste_fraction":
        {"max_increase": 0.05},
    "serving.ragged_families.mesh.backend_compiles_during_load":
        {"max_increase": 5},
    "serving.ragged_families.mesh.executables": {"max_increase": 0},
    "serving.ragged_families.mesh.shards": {"min_ratio": 1.0,
                                            "max_increase": 0},
    # RaBitQ IVF-BQ rider: the recall floor band (the fused exact
    # rerank must keep hitting the probe-set ceiling; the
    # deterministic pinned config makes these tight), the structural
    # codes-slot width, and the prune rule's deterministic signal —
    # survivor_row_fraction is a host-side replay of the engines' own
    # margin rule on the pinned seeds, so a margin/prune-math change
    # that starts re-ranking materially more rows moves it exactly
    # (block-level one_stream_fraction only separates at production
    # scale and is reported, not gated)
    "bq.fused_recall": {"min_ratio": 0.95},
    "bq.estimate_refine_recall": {"min_ratio": 0.90},
    "bq.bytes_per_vector_codes": {"max_increase": 0},
    "bq.survivor_row_fraction": {"max_increase": 0.05},
    "bq.fused_qps": {"min_ratio": 0.30},
    # graftbeam CAGRA rider (PR 16). Recall bands per arm (the pinned
    # seeds make recall deterministic on CPU; the ratio band absorbs
    # platform-precision wiggle); pool_shrink_factor is structural —
    # the coarse arm must keep serving from a pool >= 8x smaller;
    # compiles_during_measure pins the AOT steady state; raggable
    # pins the retired per-block dispatch exemption (the default
    # CAGRA plan must stay inside the ragged family). QPS keeps the
    # wide wall-clock band; modeled byte columns are reported, and
    # the BQ arm's byte reduction is banded loosely (the survivor
    # fraction moves it only through margin/prune-math changes).
    "cagra.pool.recall": {"min_ratio": 0.95},
    "cagra.coarse.recall": {"min_ratio": 0.95},
    "cagra.coarse_bq.recall": {"min_ratio": 0.95},
    "cagra.coarse.qps": {"min_ratio": 0.30},
    "cagra.coarse_bq.qps": {"min_ratio": 0.30},
    "cagra.pool_shrink_factor": {"min_ratio": 1.0, "max_increase": 0},
    "cagra.bq_byte_reduction": {"min_ratio": 0.9},
    "cagra.compiles_during_measure": {"max_increase": 0},
    "cagra.raggable": {"min_ratio": 1.0},
    "cagra.survivor_row_fraction": {"max_increase": 0.05},
    # graftfleet continuous-capture overhead A/B (PR 12): the same
    # bucketed stream with real profiler windows armed. The RATIO
    # band is the tight one — p99 with the duty cycle on may not
    # drift past baseline + 1.0x of the capture-free leg (absolute
    # p99 keeps the wide wall-clock band); capture_attempts proves
    # every gated run actually paid for profiler windows
    "serving.continuous.p99_ms": {"max_ratio": 4.0,
                                  "max_increase": 50.0},
    "serving.continuous.p99_ratio": {"max_increase": 1.0},
    # how many ticks fire inside the short load window is wall-clock
    # timing; the structural claim is "every gated run paid for AT
    # LEAST one real profiler window" (0.15 x the 6-attempt baseline
    # floors the integer count at 1)
    "serving.continuous.capture_attempts": {"min_ratio": 0.15},
    "serving.continuous.completed": {"min_ratio": 0.9},
    # grafttier tiered storage (PR 14). Structural columns TIGHT:
    # bit_identical is the correctness gate (tiered results must
    # equal the all-HBM index, pre and post placement epochs);
    # compiles_during_epochs pins the zero-recompile-across-
    # re-placement contract; cold_lists and the per-epoch swap bytes
    # are exact at the pinned config (pinned seeds → deterministic
    # coarse selection → deterministic plans). GB/s columns keep the
    # wide wall-clock bands.
    "tiered.bit_identical": {"min_ratio": 1.0},
    "tiered.compiles_during_epochs": {"max_increase": 0},
    "tiered.cold_lists": {"min_ratio": 1.0, "max_increase": 0},
    "tiered.swap_bytes_total": {"min_ratio": 1.0, "max_increase": 0},
    "tiered.qps": {"min_ratio": 0.30},
    "tiered.hot_gbps": {"min_ratio": 0.2},
    "tiered.cold_gbps": {"min_ratio": 0.2},
    # graftcast prefetch A/B (PR 18). Structural columns TIGHT:
    # reduces_cold_bytes is the acceptance criterion itself —
    # prefetch-on must STRICTLY beat the reactive leg's cold-stream
    # bytes on the identical seeded drift (both legs replay the same
    # traffic, so the promotions match and only staged hits separate
    # them); compiles_during_load pins "the prefetcher adds zero" (the
    # measured window runs after one warm drift cycle, like the epoch
    # warm above); hit_rate keeps a generous floor band (the forecast
    # is deterministic at the pinned seeds, the band absorbs plan-
    # policy tuning). p99 keeps the wide wall-clock band.
    "tiered.prefetch.reduces_cold_bytes": {"min_ratio": 1.0},
    "tiered.prefetch.on.compiles_during_load": {"max_increase": 0},
    "tiered.prefetch.hit_rate": {"min_ratio": 0.5},
    "tiered.prefetch.on.p99_ms": {"max_ratio": 4.0,
                                  "max_increase": 50.0},
    # graftwire multichip rider (this PR). Structural columns TIGHT:
    # the 2-D query×list grid must keep serving mixed batch sizes with
    # ZERO backend compiles after warmup+primer (the recompile hole
    # this PR closed — any regression reopens it); the modeled
    # per-EM-iteration wire bytes are exact at the pinned config, so
    # the int8 < bf16 < f32 ordering is encoded in the recorded
    # values with zero slack; the narrow-wire inertia ratios may not
    # drift past 2% of the f32 EM (the same tolerance the tier-1
    # convergence test pins). Wall-clock columns keep the wide bands.
    "multichip.grid2d.compiles_during_load": {"max_increase": 0},
    "multichip.grid2d.qps": {"min_ratio": 0.30},
    "multichip.kmeans_wire.cases.bf16.modeled_iter_wire_bytes":
        {"max_increase": 0},
    "multichip.kmeans_wire.cases.int8.modeled_iter_wire_bytes":
        {"max_increase": 0},
    "multichip.kmeans_wire.cases.bf16.inertia_vs_f32":
        {"max_increase": 0.02},
    "multichip.kmeans_wire.cases.int8.inertia_vs_f32":
        {"max_increase": 0.02},
    # graftroute fleet router (PR 20). Everything except wall clock
    # is deterministic in the device-free harness, so the structural
    # columns are EXACT: steered and f32-wire fan-out answers must
    # stay bit-identical to the solo oracle, the bf16-wire recall is
    # a fixed value >= the 0.99 floor at the pinned seed, the
    # modeled merge payloads follow route_payload_model with zero
    # slack (bf16 strictly under f32), and the planner's
    # replication/coverage split cannot drift at the pinned plane.
    # QPS columns are host-side routing overhead — wide bands.
    "fleet.steer.bit_identical": {"min_ratio": 1.0},
    "fleet.fanout_f32.bit_identical": {"min_ratio": 1.0},
    "fleet.fanout_bf16.recall": {"min_ratio": 0.99},
    "fleet.merge_bytes_f32": {"min_ratio": 1.0, "max_increase": 0},
    "fleet.merge_bytes_bf16": {"min_ratio": 1.0, "max_increase": 0},
    "fleet.wire_bytes_saved_frac": {"min_ratio": 1.0,
                                    "max_increase": 0},
    "fleet.replicated_lists": {"min_ratio": 1.0, "max_increase": 0},
    "fleet.coverage_rate": {"min_ratio": 1.0, "max_increase": 0},
    "fleet.fanout_fraction": {"min_ratio": 1.0, "max_increase": 0},
    "fleet.steer.qps": {"min_ratio": 0.30},
    "fleet.fanout_f32.qps": {"min_ratio": 0.30},
}

# counters the test session's metrics snapshot must carry ABOVE these
# values — the modeled-throughput accounting staying alive, and (PR 8)
# the graftgauge probe-frequency accounting: ``accounted`` mirrors the
# lifetime total fetched off the DEVICE counter planes, so a refactor
# that silently disconnects the scatter-add (or the scrape-side fetch)
# zeroes it and fails here structurally
SNAPSHOT_FLOORS = {
    "serving.execute.calls": 0.0,
    "serving.execute.modeled_bytes": 0.0,
    "serving.execute.modeled_flops": 0.0,
    "index.probe.dispatches": 0.0,
    "index.probe_freq.accounted": 0.0,
    # graftflight (PR 11): trace ingestion and incident capture must
    # stay alive — a refactor that silently disconnects the parser
    # pipeline or the flight-recorder triggers zeroes these
    "profiling.captures": 0.0,
    "incident.bundles": 0.0,
    # graftfleet (PR 12): the continuous-capture -> rolling-EWMA
    # pipeline and the multi-replica federation scrape loop must stay
    # alive the same way
    "profiling.rolling.folds": 0.0,
    "fleet.scrapes": 0.0,
    # graftledger (PR 13): the dispatch-time watermark sample must
    # stay wired into the executor — a refactor that disconnects
    # MemoryLedger.sample_dispatch() from the dispatch path zeroes
    # this and fails structurally
    "memory.samples": 0.0,
    # grafttier (PR 14): placement swaps must actually move blocks —
    # the tier-1 epoch suite promotes/demotes through apply_plan, so
    # a refactor that disconnects the swap executor (or its byte
    # accounting) zeroes the lifetime ledger and fails here
    "tier.swaps": 0.0,
    "tier.swap_bytes": 0.0,
    # graftroute (PR 20): the router must actually route and the
    # planner must actually plan in the tier-1 session — a refactor
    # that silently disconnects either (or their metric emission)
    # zeroes the lifetime ledger and fails structurally
    "fleet.route.requests": 0.0,
    "fleet.plan.builds": 0.0,
}


def get_path(record: dict, dotted: str):
    """Resolve ``"serving.qps"``-style paths; None when absent."""
    cur = record
    for part in dotted.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur


def compare(baseline: dict, fresh: dict, tolerances=None) -> list:
    """Regression messages from diffing two bench records (empty list
    = within bands). Columns missing from the BASELINE are skipped (an
    old baseline predating a new column must not fail the gate);
    columns missing from the FRESH record are regressions — the
    measurement itself disappeared."""
    msgs = []
    for path, tol in (tolerances or DEFAULT_TOLERANCES).items():
        base = get_path(baseline, path)
        if base is None:
            continue
        got = get_path(fresh, path)
        if got is None:
            msgs.append(f"{path}: present in baseline ({base}) but "
                        "missing from the fresh record")
            continue
        base, got = float(base), float(got)
        if "min_ratio" in tol and got < base * tol["min_ratio"]:
            msgs.append(
                f"{path}: {got:g} < {tol['min_ratio']:g}x baseline "
                f"({base:g}) — throughput regression")
        ceiling = None
        if "max_ratio" in tol and base > 0:
            ceiling = base * tol["max_ratio"]
        if "max_increase" in tol:
            inc = base + tol["max_increase"]
            ceiling = inc if ceiling is None else max(ceiling, inc)
        if ceiling is not None and got > ceiling:
            msgs.append(
                f"{path}: {got:g} > allowed {ceiling:g} "
                f"(baseline {base:g}) — latency/compile regression")
    return msgs


def check_snapshot(snapshot: dict, floors=None) -> list:
    """Floor checks on the test session's metrics snapshot: the
    modeled-throughput counters must exist and exceed their floors.
    Reads the session-lifetime ledger (``counters_lifetime`` — totals
    that survive per-test ``reset_counters()`` isolation) when the
    snapshot carries one; the live ``counters`` view only holds what
    ran after the LAST reset, which depends on test ordering."""
    msgs = []
    counters = snapshot.get("counters_lifetime") or \
        snapshot.get("counters", {})
    for name, floor in (floors or SNAPSHOT_FLOORS).items():
        v = counters.get(name)
        if v is None:
            msgs.append(f"metrics snapshot: counter {name!r} missing — "
                        "modeled-throughput accounting went dark")
        elif float(v) <= floor:
            msgs.append(f"metrics snapshot: {name} = {v} (must be > "
                        f"{floor}) — modeled-throughput accounting "
                        "went dark")
    return msgs


def run_bench(env_overrides: dict) -> dict:
    """Run the bench CHILD directly (no backend probes — the pinned
    config is CPU) and return its last JSON stdout line."""
    env = dict(os.environ)
    env.pop("BENCH_TAG", None)              # the metric naming knobs
    env.pop("BENCH_SUFFIX", None)
    env.update(env_overrides)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, env=env, timeout=1800)
    rec = None
    for line in proc.stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
    if rec is None:
        sys.stderr.write(proc.stderr[-4000:] + "\n")
        raise RuntimeError(
            f"bench child produced no JSON (exit {proc.returncode})")
    return rec


def backend_available(required: str) -> bool:
    """Whether the jax backend matches a baseline's
    ``requires_backend`` declaration. Asked in a child process that
    exits before any bench child starts: a parent that touched jax
    would hold the chip the bench child needs."""
    proc = subprocess.run(
        [sys.executable, "-c",
         "import jax; print(jax.default_backend())"],
        capture_output=True, text=True, timeout=300)
    return proc.returncode == 0 and proc.stdout.strip() == required


def default_baselines() -> list:
    """Every committed ``ci/bench_baseline*.json``, sorted — the
    multi-baseline default, so a TPU-recorded baseline gates
    automatically once committed. Falls back to the canonical path
    (for the --update bootstrap) when none exist yet."""
    found = sorted(_glob.glob(
        os.path.join(REPO, "ci", "bench_baseline*.json")))
    return found or [BASELINE_PATH]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--baseline", action="append",
                    help="baseline JSON to gate against (repeatable; "
                    "default: every ci/bench_baseline*.json)")
    ap.add_argument("--fresh", help="existing bench-record JSON to "
                    "diff instead of running the bench")
    ap.add_argument("--run", action="store_true",
                    help="run each baseline's pinned bench config to "
                    "get the fresh record")
    ap.add_argument("--snapshot", help="metrics_snapshot.json to "
                    "floor-check (skipped silently if the file is "
                    "absent — local runs without the pytest artifact)")
    ap.add_argument("--update", action="store_true",
                    help="write the fresh record back as the baseline "
                    "(deliberate re-baseline) instead of comparing")
    args = ap.parse_args(argv)

    paths = args.baseline or default_baselines()
    if args.update and len(paths) != 1:
        sys.stderr.write(
            "bench_compare: --update needs exactly ONE --baseline "
            f"target, got {len(paths)}\n")
        return 2
    if not (args.fresh or args.run or args.update):
        sys.stderr.write("bench_compare: need --run or --fresh\n")
        return 2

    fresh_fixed = None
    if args.fresh:
        with open(args.fresh) as f:
            fresh_fixed = json.load(f)

    msgs = []
    gated = 0
    failing_paths = []
    run_cache: dict = {}       # env (sorted tuple) -> bench record
    for path in paths:
        baseline = None
        if os.path.exists(path):
            with open(path) as f:
                baseline = json.load(f)
        if baseline is None and not args.update:
            sys.stderr.write(
                f"bench_compare: no baseline at {path} — run with "
                "--update to create one\n")
            return 2
        required = (baseline or {}).get("requires_backend")
        if required and not backend_available(required):
            print(f"bench_compare: SKIP {os.path.basename(path)} — "
                  f"requires backend {required!r}, not present")
            continue

        # gating replays the baseline's pinned env (baseline and fresh
        # always measure the same problem); a deliberate --update
        # re-baselines onto the CURRENT pinned config, so pinned-env
        # changes land together with the record they produced
        env = (dict(PINNED_ENV) if args.update
               else dict((baseline or {}).get("env") or PINNED_ENV))
        if fresh_fixed is not None:
            fresh = fresh_fixed
        else:
            key = tuple(sorted(env.items()))
            if key not in run_cache:
                print(f"bench_compare: running pinned bench config "
                      f"({env.get('BENCH_N')}x{env.get('BENCH_DIM')}, "
                      f"serving rider on)", flush=True)
                run_cache[key] = run_bench(env)
            fresh = run_cache[key]

        if args.update:
            out = {
                "env": env,
                "tolerances": DEFAULT_TOLERANCES,
                "snapshot_floors": SNAPSHOT_FLOORS,
                "record": fresh,
            }
            if required:
                out["requires_backend"] = required
            with open(path, "w") as f:
                json.dump(out, f, indent=2, sort_keys=True)
                f.write("\n")
            print(f"bench_compare: baseline updated at {path}")
            return 0

        gated += 1
        path_msgs = compare(
            baseline.get("record", {}), fresh,
            baseline.get("tolerances") or DEFAULT_TOLERANCES)
        if args.snapshot and os.path.exists(args.snapshot):
            with open(args.snapshot) as f:
                path_msgs += check_snapshot(
                    json.load(f),
                    baseline.get("snapshot_floors") or SNAPSHOT_FLOORS)
        if path_msgs:
            failing_paths.append(path)
        msgs += [f"[{os.path.basename(path)}] {m}" for m in path_msgs]

    if msgs:
        for m in msgs:
            sys.stderr.write(f"bench_compare: REGRESSION: {m}\n")
        # --update takes exactly one target, so the hint names each
        # failing baseline explicitly
        for p in failing_paths:
            rel = os.path.relpath(p, REPO) if p.startswith(REPO) else p
            sys.stderr.write(
                "bench_compare: if the change is intentional, "
                "re-baseline with: python ci/bench_compare.py --run "
                f"--update --baseline {rel}\n")
        return 1
    print(f"bench_compare: OK — fresh run within tolerance of "
          f"{gated} baseline(s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
