"""Serving-frontend metrics, exported through the existing
:mod:`raft_tpu.core.tracing` registry.

Per-stage latency **histograms** (log2 buckets, p50/p95/p99 estimates):

- ``serving.batcher.queue_wait_seconds``   — admission → batch assembly
- ``serving.batcher.assembly_seconds``     — group pop + block concat
- ``serving.batcher.execute_seconds``      — device execute (blocked)
- ``serving.batcher.split_seconds``        — result re-split + handle set
- ``serving.batcher.e2e_seconds``          — admission → handle complete
- ``serving.batcher.hold_seconds``         — timer holding a queued group
- ``serving.batcher.device_wait_seconds``  — worker blocked on the device
- ``serving.executor.{prepare,enqueue,slice}_seconds`` — the executor's
  host stages inside execute (named in ``core/executor.py``)

**Counters** (throughput / shed / occupancy):

- ``serving.admission.accepted`` / ``.rejected``  — admission outcomes
- ``serving.batcher.requests`` / ``.rows``        — dispatched work
- ``serving.batcher.batches``                     — executor calls made
- ``serving.batcher.shed_deadline``               — expired → shed
- ``serving.batcher.cancelled``                   — cancelled in queue
- ``serving.batcher.shutdown_shed``               — shed at close()
- ``serving.execute.calls`` / ``.rows`` /
  ``.modeled_flops`` / ``.modeled_bytes``         — executor dispatches
  priced by each executable's compile-time ``cost_analysis()``
- ``serving.execute.eager_programs``              — eager device
  programs launched beside the compiled executable
- ``serving.execute.padded_rows``                 — dispatched row
  capacity incl. bucket/tile pad; with ``.rows`` it derives the
  pad-waste fraction the ragged-vs-bucketed A/B gates on
- ``serving.execute.{rows,padded_rows}.p<NP>.t<T>`` — the ragged
  dispatch core's per-(params class, tile) split of the two counters
  above (graftragged): ``derived()["pad_waste_by_class"]`` and the
  exporter's ``serving_execute_*{params_class=,tile=}`` labeled
  families attribute pad waste to the small-vs-large tile choice
- ``serving.batcher.group_starvation_s``          — (gauge) longest any
  dispatch-ready group waited while another was served — the
  cross-index fairness budget's observable

**Gauges** (PR 6 graftscope):

- ``serving.admission.queue_depth`` / ``.shed_level`` /
  ``.arrival_rate_hz``                            — admission state
- ``serving.executable.<digest>.flops`` /
  ``.bytes_accessed`` / ``.peak_hbm_bytes``       — per-executable cost
- ``serving.executor.cached_executables``         — AOT cache size
- ``serving.collective.<family>.<wire>.<probe_wire>.*_bytes``
                                                  — modeled mesh wire

**SLO surface** (PR 7 graftscope v2, batcher clock domain):

- ``serving.slo.attained`` / ``.missed``          — deadline-attainment
  counters: every deadline-carrying request that reaches ``submit()``
  lands as exactly one of the two (on-time result → attained; completed
  past its deadline, shed for expiry before dispatch, rejected at
  admission, or failed with its batch → missed — overload and executor
  failure must drive the burn rate UP, not starve the window into a
  healthy-looking 0.0; exempt are the deliberate shutdown drain and
  caller cancellation that wins before dispatch — a request the client
  abandoned is not a service outcome)
- ``serving.slo.burn_rate``                       — sliding-window gauge:
  the window's miss fraction over the SLO's error budget
  (``1 − target``); 1.0 = burning budget exactly as provisioned, >1 =
  on track to exhaust it. All timestamps come from the batcher clock,
  so the manual-clock tests pin the window arithmetic exactly.
- ``serving.slo.window_total`` / ``.window_missed`` — current window
  contents (the burn rate's numerator/denominator, for debugging)
- ``serving.mesh.shard_skew`` / ``.slowest_shard`` /
  ``.shard_time_{max,mean}_s``                    — straggler detector
  output (see :func:`raft_tpu.core.tracing.record_mesh_spans`)
- ``serving.slo.burn_rate.<label>`` / ``serving.slo.alert`` — the
  multiwindow burn-rate policy (PR 8): labeled per-window gauges plus
  the combined alert that fires only when every window burns
  (:class:`MultiBurnConfig` / :class:`MultiBurnAlert`)

**graftgauge surface** (PR 8, published at scrape time by
:class:`~raft_tpu.serving.gauge.IndexGauge` and the executor):

- ``index.probe_freq.<label>.{total,probed_fraction,coverage_p01,
  coverage_p10}`` + ``.list.<lid>`` top-N samples — device-side
  probe-frequency accounting; ``index.probe_freq.accounted`` is the
  monotone counter mirror the CI snapshot floors check, and
  ``index.probe.{dispatches,rows}`` the per-dispatch host heartbeat
- ``index.health.<name>.*`` — list-occupancy skew, dead/overflow
  lists, fill fraction, Gini, per-shard imbalance
- ``index.recall.{estimate,ci_low,ci_high,window_pairs,window_trials}``
  + the ``index.recall.shadow_*`` lifecycle counters — windowed online
  recall estimation from shadow queries
- ``index.drift.score`` / ``index.drift.<name>.{score,alert}`` —
  streaming divergence of live traffic from the build-time baseline

**graftflight surface** (PR 11):

- ``serving.batcher.execute_seconds.p<NP>`` — per-params-class
  execute-latency histograms (:func:`params_class` /
  :func:`observe_execute_class`; rendered as
  ``{params_class=...}``-labeled Prometheus families) — the latency
  axis pairing the ``index.recall.sweep.p<NP>`` recall gauges
- ``serving.attribution.{device_seconds,modeled_bytes,modeled_flops}``
  + ``serving.executable.<digest>.measured_*`` — device-truth
  attribution from profiler captures
  (:mod:`raft_tpu.core.profiling`); :func:`derived` publishes
  ``device_achieved_gbps``/``gflops`` and ``measured_executables``
  next to the wall-clock-derived numbers
- ``profiling.captures`` / ``incident.*`` — trace-ingestion and
  flight-recorder (:mod:`raft_tpu.serving.flight`) lifetime counters

**graftfleet surface** (PR 12):

- ``serving.attribution.rolling.*`` — the EWMA-folded steady-state
  attribution (:class:`raft_tpu.core.profiling.RollingAttribution`)
  the continuous low-duty-cycle scheduler
  (:mod:`raft_tpu.serving.continuous`) feeds; :func:`derived` carries
  the ``rolling_*`` columns next to the wall-clock and incident-
  snapshot numbers
- ``serving.mesh.shard_skew_p50``/``_p99`` — per-dispatch straggler
  skew distribution from a capture's invocation windows
- ``continuous.{ticks,captures,deferred,skipped,empty,errors}`` +
  ``profiling.rolling.folds`` — scheduler/fold lifetime accounting
- ``fleet.*`` — multi-replica federation
  (:mod:`raft_tpu.serving.federation`): scrape/health counters, fleet
  probe coverage, pooled recall, pooled drift

**graftledger surface** (PR 13, published at scrape time by
:class:`raft_tpu.core.memwatch.MemoryLedger`):

- ``memory.index.<label>.{resident_bytes,shard_bytes}`` — the
  resident-bytes model per watched index (labeled Prometheus
  families); ``memory.resident.total_bytes`` the sum
- ``memory.device.<ordinal>.{in_use,peak,limit}_bytes`` — live
  ``device.memory_stats()`` truth (absent on backends without it;
  ``memory.live.supported`` says which)
- ``memory.forecast.peak_bytes`` / ``memory.reserved.*`` — the
  reservation forecast (resident + donated state + probe planes +
  max compile-time temp); ``memory.hbm.headroom_bytes`` the live
  headroom (−1 when unknowable); ``memory.divergence_bytes`` the
  modeled-vs-live gap (fragmentation / untracked allocations)
- ``memory.watermark.{in_use,forecast}_peak_bytes`` — dispatch-time
  high-water marks; ``memory.samples`` the heartbeat counter the CI
  snapshot floor checks; ``memory.gate.{admitted,refused}`` the
  capacity-gate ledger
- ``fleet.memory.{resident_bytes,headroom_min_bytes}`` +
  ``fleet.replica.<name>.headroom_bytes`` — the federated memory
  view (headroom min / resident sum); ``fleet.slo.burn_rate.*`` /
  ``fleet.slo.alert`` the fleet-level multiburn alert over the
  merged windows

Batch **occupancy** — the coalescing win the ISSUE's acceptance
criterion gates on — is derived, not stored: ``requests / batches``
(and ``rows / batches``) from one counters snapshot. Likewise the
**achieved-bandwidth** numbers (:func:`derived`): modeled bytes/flops
over the measured execute-latency sum — the TPU-KNN roofline
accounting as a running metric, from the same inputs the BENCH rider
reports — plus the executor cache hit-rate.
"""

from __future__ import annotations

import collections
import dataclasses
import re
import threading
from typing import Optional

from raft_tpu.core import profiling, tracing
from raft_tpu.core.executor import (  # noqa: F401 — re-exported
    EAGER_PROGRAMS,
    ENQUEUE,
    ENQUEUE_SPAN,
    PREPARE,
    PREPARE_SPAN,
    SLICE,
    SLICE_SPAN,
    STAGE_PREFIX,
)

PREFIX = "serving.batcher."

QUEUE_WAIT = PREFIX + "queue_wait_seconds"
ASSEMBLY = PREFIX + "assembly_seconds"
EXECUTE = PREFIX + "execute_seconds"
SPLIT = PREFIX + "split_seconds"
E2E = PREFIX + "e2e_seconds"

# host stages of the served path, each a span (the profiler annotation
# and, where a histogram is named, the stage's latency). The batcher's
# own: the timer half of the dual trigger holding a queued group for
# company, the untimed wait on an empty queue (profiler only), and the
# worker blocking on the device (the last of the four stages inside
# EXECUTE). The executor's three stages and its eager-program counter
# are named where they are recorded (core/executor.py).
HOLD_SPAN = PREFIX + "hold"
HOLD = PREFIX + "hold_seconds"
IDLE_SPAN = PREFIX + "idle"
DEVICE_WAIT_SPAN = PREFIX + "device_wait"
DEVICE_WAIT = PREFIX + "device_wait_seconds"

SLO_ATTAINED = "serving.slo.attained"
SLO_MISSED = "serving.slo.missed"
SLO_BURN_RATE = "serving.slo.burn_rate"
SLO_ALERT = "serving.slo.alert"


@dataclasses.dataclass(frozen=True)
class SloConfig:
    """Deadline-SLO definition for the burn-rate window.

    ``target`` is the attainment objective (0.999 = "99.9% of
    deadline-carrying requests complete on time"); its complement is
    the error budget the burn rate is normalized by. ``window_s`` is
    the sliding window (batcher clock domain) the rate is computed
    over — short windows catch fast burns, long windows catch slow
    leaks; run one exporter-side recording per deployment and let the
    alerting layer combine windows."""

    window_s: float = 60.0
    target: float = 0.999


class SloWindow:
    """Deadline-attainment accounting in the batcher clock's domain.

    :meth:`record` counts one deadline-carrying request's outcome into
    the monotone ``serving.slo.{attained,missed}`` counters AND a
    sliding window of (timestamp, attained) events; the **burn rate**
    — window miss fraction ÷ error budget, the standard SRE
    multiwindow-alerting quantity — publishes as the
    ``serving.slo.burn_rate`` gauge. Everything is keyed to caller
    timestamps (``clock.now()`` / the batcher's stage times), so the
    window never reads a wall clock and the manual-clock tests pin it
    exactly. Thread-safe: one lock, O(events-in-window) memory; the
    miss count is maintained incrementally on append/prune, so every
    operation is O(events-pruned), not O(window) — record() sits on
    the per-request completion path.

    ``label`` suffixes the published gauge names
    (``serving.slo.burn_rate.<label>``) so several windows over the
    same outcome stream — the multiburn alert's 5 m + 1 h pair —
    publish side by side; unlabeled keeps the original flat names.
    ``prefix`` relocates the whole gauge family (default
    ``serving.slo.`` — the fleet aggregator's federated windows
    publish under ``fleet.slo.`` so a replica-local and a fleet-wide
    burn rate can coexist in one registry)."""

    def __init__(self, config: Optional[SloConfig] = None, *,
                 label: Optional[str] = None,
                 prefix: str = "serving.slo."):
        self.config = config or SloConfig()
        self.label = label
        self.prefix = prefix
        self._suffix = f".{label}" if label else ""
        self._lock = threading.Lock()
        # events are (timestamp, attained, n): n > 1 carries a BATCH
        # of same-outcome outcomes in one entry — the federation path
        # folds per-merge deltas of fleet counter sums, and appending
        # thousands of unit events per merge would make the window
        # O(fleet traffic) instead of O(merges)
        self._events: "collections.deque" = collections.deque()  # guarded-by: _lock
        self._total = 0   # guarded-by: _lock
        self._missed = 0  # guarded-by: _lock

    def _prune_locked(self, now: float) -> None:
        horizon = now - self.config.window_s
        while self._events and self._events[0][0] <= horizon:
            _, ok, n = self._events.popleft()
            self._total -= n
            if not ok:
                self._missed -= n

    def _counts(self, now: float):
        with self._lock:
            self._prune_locked(now)
            return self._total, self._missed

    def _append(self, now: float, attained: bool, n: int = 1) -> None:
        """Window bookkeeping only — no counter bump, no publish. The
        multiburn alert fans one outcome into several windows and must
        bump the process-wide attained/missed counters exactly once."""
        if n <= 0:
            return
        with self._lock:
            self._events.append((now, attained, n))
            self._total += n
            if not attained:
                self._missed += n

    def record_batch(self, now: float, attained_n: int,
                     missed_n: int) -> None:
        """Fold a BATCH of outcomes into the window WITHOUT bumping
        the process-wide attained/missed counters — the federation
        path: the outcomes already counted in their replica processes,
        and the aggregator only needs them windowed. Publishes."""
        self._append(now, True, int(attained_n))
        self._append(now, False, int(missed_n))
        self.publish(now)

    def record(self, now: float, attained: bool) -> None:
        """Count one outcome at clock time ``now`` and re-publish."""
        tracing.inc_counter(SLO_ATTAINED if attained else SLO_MISSED)
        self._append(now, attained)
        self.publish(now)

    def burn_rate(self, now: float) -> float:
        """Window miss fraction over the error budget at ``now`` (0.0
        for an empty window — no traffic burns no budget)."""
        total, missed = self._counts(now)
        if total == 0:
            return 0.0
        budget = max(1.0 - self.config.target, 1e-9)
        return (missed / total) / budget

    def publish(self, now: float) -> None:
        """Re-publish the window gauges as of ``now`` — called on every
        record and by the exporter's scrape-time refresh, so a quiet
        service's burn rate decays as its misses age out of the
        window."""
        total, missed = self._counts(now)
        budget = max(1.0 - self.config.target, 1e-9)
        tracing.set_gauges({
            self.prefix + "burn_rate" + self._suffix:
                (missed / total) / budget if total else 0.0,
            self.prefix + "window_total" + self._suffix: float(total),
            self.prefix + "window_missed" + self._suffix: float(missed),
        })


@dataclasses.dataclass(frozen=True)
class MultiBurnConfig:
    """Multiwindow burn-rate alert policy (the SRE multiburn pattern):
    a short window catches fast burns, a long window confirms they are
    sustained, and the alert fires only when BOTH burn past
    ``alert_burn`` — a short spike that the long window absorbs, or a
    slow leak the short window has already recovered from, pages
    nobody. Defaults pair 5 m + 1 h at burn 1.0 (consuming error
    budget exactly as provisioned)."""

    short: SloConfig = SloConfig(window_s=300.0)
    long: SloConfig = SloConfig(window_s=3600.0)
    short_label: str = "5m"
    long_label: str = "1h"
    alert_burn: float = 1.0


class MultiBurnAlert:
    """Paired :class:`SloWindow` recorder + the ``serving.slo.alert``
    gauge. Batcher-facing duck type of a single ``SloWindow``
    (``record(now, attained)`` / ``publish(now)``), so
    ``BatcherConfig.multiburn`` swaps it in without touching any
    completion path; each outcome bumps the process-wide
    attained/missed counters exactly once and lands in both windows.
    All timestamps are caller-clock-domain — the ManualClock tests pin
    window arithmetic and the alert transition exactly."""

    def __init__(self, config: Optional[MultiBurnConfig] = None, *,
                 prefix: str = "serving.slo."):
        self.config = config or MultiBurnConfig()
        self.prefix = prefix
        self.windows = (
            SloWindow(self.config.short, label=self.config.short_label,
                      prefix=prefix),
            SloWindow(self.config.long, label=self.config.long_label,
                      prefix=prefix),
        )

    def record(self, now: float, attained: bool) -> None:
        """One outcome → both windows; counters bumped once."""
        tracing.inc_counter(SLO_ATTAINED if attained else SLO_MISSED)
        for w in self.windows:
            w._append(now, attained)
        self.publish(now)

    def record_batch(self, now: float, attained_n: int,
                     missed_n: int) -> None:
        """Batched outcomes → both windows, NO process-counter bumps
        — the federation path (see :meth:`SloWindow.record_batch`):
        the fleet aggregator folds per-merge deltas of the summed
        replica attained/missed counters, whose unit outcomes were
        already counted where they happened."""
        for w in self.windows:
            w._append(now, True, int(attained_n))
            w._append(now, False, int(missed_n))
        self.publish(now)

    def burn_rates(self, now: float) -> tuple:
        return tuple(w.burn_rate(now) for w in self.windows)

    def alert(self, now: float) -> bool:
        """True iff EVERY window burns at/above the policy threshold."""
        return all(r >= self.config.alert_burn
                   for r in self.burn_rates(now))

    def publish(self, now: float) -> None:
        """Re-publish each window's labeled gauges plus the combined
        ``serving.slo.alert`` (1.0 firing / 0.0 quiet) — scrape-time
        refresh decays both windows and may clear the alert."""
        for w in self.windows:
            w.publish(now)
        tracing.set_gauge(self.prefix + "alert",
                          1.0 if self.alert(now) else 0.0)


def observe_stage(name: str, seconds: float) -> None:
    """Record one stage latency into its histogram."""
    tracing.observe(name, seconds)


def params_class(params) -> Optional[str]:
    """The latency label of a request's search params — ``p<NP>`` for
    params carrying ``n_probes`` (graftflight satellite, the
    graftgauge carried follow-on): the SAME spelling the params-sweep
    recall gauges use (``index.recall.sweep.p<NP>``), so the sweep's
    recall axis pairs with a measured latency axis and the live
    recall/latency frontier is complete. None for params with no
    ``n_probes`` knob (brute force, CAGRA) — those observe only the
    unlabeled family."""
    n_probes = getattr(params, "n_probes", None)
    if n_probes is None:
        return None
    return f"p{int(n_probes)}"


# label-cardinality bound for the per-params-class histograms:
# n_probes is client-supplied, and histograms are process-lifetime —
# without a cap, a client sweeping arbitrary values (an autotuner)
# would grow the registry and every /metrics payload without bound
# (the same leak PR 8's top-N probe gauges were engineered around).
# 32 distinct classes covers any realistic sweep; overflow is counted,
# not silent.
EXECUTE_CLASS_CAP = 32
_execute_classes: set = set()  # guarded-by: _execute_classes_lock
_execute_classes_lock = threading.Lock()


def observe_execute_class(label: str, seconds: float) -> None:
    """Record one dispatch's execute latency into the per-params-class
    histogram (``serving.batcher.execute_seconds.<label>`` — rendered
    by the exporter as the labeled
    ``serving_batcher_execute_seconds{params_class="<label>"}``
    Prometheus family next to the unlabeled aggregate). At most
    :data:`EXECUTE_CLASS_CAP` distinct labels materialize per process;
    past the cap a new label's observation lands only in the unlabeled
    aggregate and bumps ``serving.batcher.execute_class_dropped``."""
    with _execute_classes_lock:
        if label not in _execute_classes:
            if len(_execute_classes) >= EXECUTE_CLASS_CAP:
                tracing.inc_counter(PREFIX + "execute_class_dropped")
                return
            _execute_classes.add(label)
    tracing.observe(f"{EXECUTE}.{label}", seconds)


def batch_dispatched(n_requests: int, n_rows: int) -> None:
    """Count one dispatched micro-batch."""
    tracing.inc_counter(PREFIX + "batches")
    tracing.inc_counter(PREFIX + "requests", n_requests)
    tracing.inc_counter(PREFIX + "rows", n_rows)


def occupancy() -> dict:
    """Derived batch-occupancy stats: mean requests and rows per
    dispatched micro-batch (1.0 requests/batch == no coalescing)."""
    batches = tracing.get_counter(PREFIX + "batches")
    if batches == 0:
        return {"batches": 0, "requests_per_batch": 0.0,
                "rows_per_batch": 0.0}
    return {
        "batches": int(batches),
        "requests_per_batch":
            tracing.get_counter(PREFIX + "requests") / batches,
        "rows_per_batch": tracing.get_counter(PREFIX + "rows") / batches,
    }


def derived() -> dict:
    """Metrics computed from one counters read: executor cache
    hit-rate and live achieved GB/s / GFLOP/s (modeled bytes & flops
    from compile-time cost analysis, divided by the measured execute
    histogram's latency sum)."""
    hits = tracing.get_counter("serving.cache_hits")
    misses = tracing.get_counter("serving.cache_misses")
    exec_s = tracing.get_histogram(EXECUTE).snapshot()["sum"]
    rows = tracing.get_counter("serving.execute.rows")
    padded = tracing.get_counter("serving.execute.padded_rows")
    out = {
        "cache_hit_rate": hits / (hits + misses) if hits + misses else 0.0,
        "execute_seconds_total": exec_s,
        # the pad-waste fraction the ragged-vs-bucketed A/B gates on:
        # share of dispatched row capacity that was bucket/tile pad
        # (bucketed pow2 rounding wastes up to ~50%; the packed ragged
        # tile only pads the final partial tile)
        "pad_waste_fraction": 1.0 - rows / padded if padded else 0.0,
        "modeled_bytes_total":
            tracing.get_counter("serving.execute.modeled_bytes"),
        "modeled_flops_total":
            tracing.get_counter("serving.execute.modeled_flops"),
    }
    # per-(params class, tile) pad-waste attribution (graftragged):
    # the ragged dispatch core splits its rows/padded_rows counters as
    # serving.execute.{rows,padded_rows}.p<NP>.t<TILE>, so the waste
    # attributes to the small-vs-large tile choice per class — the
    # signal that says whether the dual tile earns its second
    # executable at the observed load mix
    by_class = {}
    split_pad = tracing.counters("serving.execute.padded_rows.")
    for name, pad in split_pad.items():
        label = name[len("serving.execute.padded_rows."):]
        r = tracing.get_counter("serving.execute.rows." + label)
        if pad:
            by_class[label] = 1.0 - r / pad
    out["pad_waste_by_class"] = by_class
    out["achieved_gbps"] = (
        out["modeled_bytes_total"] / exec_s / 1e9 if exec_s > 0 else 0.0)
    out["achieved_gflops"] = (
        out["modeled_flops_total"] / exec_s / 1e9 if exec_s > 0 else 0.0)
    # graftflight (PR 11): the DEVICE-measured counterparts, published
    # when a profiler capture was attributed — modeled bytes/flops over
    # MEASURED device seconds, next to the wall-clock-derived numbers
    # above so the two accountings can disagree visibly (wall clock
    # includes dispatch/readiness overhead the device never saw)
    att_s = tracing.get_counter(profiling.ATTRIBUTED_SECONDS)
    out["measured_device_seconds_total"] = att_s
    out["device_achieved_gbps"] = (
        tracing.get_counter(profiling.ATTRIBUTED_BYTES) / att_s / 1e9
        if att_s > 0 else 0.0)
    out["device_achieved_gflops"] = (
        tracing.get_counter(profiling.ATTRIBUTED_FLOPS) / att_s / 1e9
        if att_s > 0 else 0.0)
    # graftfleet (PR 12): the ROLLING measured view — EWMA over the
    # continuous scheduler's periodic capture windows, so this number
    # is continuously fresh rather than the last incident's snapshot
    rp = profiling.ROLLING_PREFIX
    out["rolling_windows"] = tracing.get_gauge(rp + "windows")
    out["rolling_device_seconds"] = tracing.get_gauge(
        rp + "device_seconds")
    out["rolling_gbps"] = tracing.get_gauge(rp + "gbps")
    out["rolling_gflops"] = tracing.get_gauge(rp + "gflops")
    # per-executable measured view, re-read from the attribution's
    # gauges (one scrape shows each resident program's measured
    # achieved GB/s / GFLOP/s — bytes-per-call x trace invocations
    # over its own measured device seconds)
    measured: dict = {}
    pat = re.compile(
        r"^serving\.executable\.([0-9a-f]+)\.measured_([a-z_]+)$")
    for name, v in tracing.gauges("serving.executable.").items():
        m = pat.match(name)
        if m:
            measured.setdefault(m.group(1), {})[m.group(2)] = v
    out["measured_executables"] = measured
    return out


def snapshot() -> dict:
    """One scrape of the whole serving surface: counters + gauges +
    per-stage histogram summaries + derived occupancy and achieved
    bandwidth (the bench rider's, the exporter's, and any monitoring
    agent's single entry point)."""
    return {
        "counters": tracing.counters("serving."),
        "gauges": tracing.gauges("serving."),
        "histograms": tracing.histograms(PREFIX),
        "occupancy": occupancy(),
        "derived": derived(),
    }


def reset() -> None:
    """Zero every serving + graftgauge counter, gauge, histogram, and
    the span flight recorder — test/bench isolation (counters fold
    into the lifetime ledger, so session artifacts survive)."""
    tracing.reset_counters("serving.")
    tracing.reset_gauges("serving.")
    tracing.reset_counters("index.")
    tracing.reset_gauges("index.")
    tracing.reset_counters("memory.")
    tracing.reset_gauges("memory.")
    tracing.reset_histograms(PREFIX)
    tracing.reset_histograms(STAGE_PREFIX)
    # the class-label cap tracks the histograms it guards
    with _execute_classes_lock:
        _execute_classes.clear()
    tracing.reset_spans()
