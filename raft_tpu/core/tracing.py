"""Profiler ranges and serving counters — analog of the reference's
NVTX RAII ranges plus a minimal metrics registry.

Reference: ``core/nvtx.hpp:20-70`` inserts named ranges at every public
entry point. The TPU-native equivalents are ``jax.named_scope`` (annotates
the jaxpr/HLO so ranges appear in XLA profiler traces) plus
``jax.profiler.TraceAnnotation`` for host-side spans. ``range`` composes
both so one decorator/context manager covers traced and untraced code.

The counter registry is the export surface for the serving path
(``core/executor.py``): compile counts, cache hits/evictions and warmup
time land here so a frontend (or the bench harness) can scrape one
place, and the serving frontend (``raft_tpu/serving/``) adds per-stage
latency histograms (:func:`observe` / :func:`histograms`) next to
them. ``install_xla_compile_listener`` additionally taps jax's
monitoring events so *every* backend compile in the process — not just
the executor's — is visible; that is what the tier-1 recompile
regression test asserts on.

PR 7 (graftscope v2) extends the layer into the mesh: per-shard
timings reduce through the **straggler detector**
(:func:`straggler_stats` / :func:`record_mesh_spans`) into
``serving.mesh.{shard_skew,slowest_shard}`` gauges, and the Chrome
trace export grew a ``trace_id`` filter so per-request fetches stop
dumping the whole ring.

PR 6 (graftscope) grows this module into the full observability core:

- **Gauges** (:func:`set_gauge`) — last-value metrics next to the
  monotone counters: per-executable cost-analysis numbers, queue
  depth, arrival rate, collective payload models.
- **Request spans** (:class:`Span` / :class:`SpanRecorder`) — a
  bounded, lock-protected ring buffer of host-side stage spans keyed
  by ``trace_id``, doubling as a flight recorder for post-mortems.
  :meth:`SpanRecorder.to_chrome_trace` exports Chrome trace-event JSON
  on the recording clock (the batcher's); the stage spans that line
  up with the ``jax.profiler`` device timeline are the profiler
  annotations :class:`host_span` opens beside them.
- :class:`Histogram` grew cumulative bucket counts (the Prometheus
  exposition format needs them) and its own lock — ``get_histogram``
  hands out live instances, so unlocked ``observe`` raced concurrent
  observers before PR 6.

None of it touches the device: recording a span or bumping a counter
is a dict/deque operation under a host lock, so instrumentation adds
no host syncs and cannot perturb the zero-recompile steady state.
"""

from __future__ import annotations

import builtins
import collections
import contextlib
import dataclasses
import functools
import itertools
import threading
import time
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np


@contextlib.contextmanager
def range(name: str, *fmt_args):
    """RAII-style profiling range (``common::nvtx::range``)."""
    label = name % fmt_args if fmt_args else name
    with jax.named_scope(label), jax.profiler.TraceAnnotation(label):
        yield


def annotated(name: str):
    """Decorator form, used on public API entry points."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with range(name):
                return fn(*args, **kwargs)

        return wrapper

    return deco


@contextlib.contextmanager
def capture(log_dir: str):
    """Capture an XLA profiler trace for the enclosed block — the role
    the gbench micro-benchmarks play as profiling entry points in the
    reference (SURVEY.md §5). View with TensorBoard or xprof:

        with tracing.capture("/tmp/trace"):
            index = ivf_flat.build(res, params, dataset)
    """
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def start_server(port: int = 9999):
    """Start the on-demand profiler server (``jax.profiler``) so a
    running service can be traced remotely."""
    return jax.profiler.start_server(port)


# ---------------------------------------------------------------------------
# counters — process-wide serving metrics registry
# ---------------------------------------------------------------------------

_counters: dict = {}  # guarded-by: _counters_lock
# process-lifetime totals: everything reset_counters() has folded away.
# Session-scoped artifacts (the CI metrics snapshot) read these so
# per-test isolation resets can't blank the session's accounting.
_counters_lifetime: dict = {}  # guarded-by: _counters_lock
_counters_lock = threading.Lock()


def inc_counter(name: str, amount: float = 1.0) -> None:
    """Add ``amount`` to a named process-wide counter (creates it at 0)."""
    with _counters_lock:
        _counters[name] = _counters.get(name, 0.0) + amount


def inc_counters(amounts: Dict[str, float]) -> None:
    """Add several counters under ONE lock acquisition — the per-call
    hot-path form (the executor bumps calls + modeled flops + modeled
    bytes per dispatch; three separate locks would triple the cost)."""
    with _counters_lock:
        for name, amount in amounts.items():
            _counters[name] = _counters.get(name, 0.0) + amount


def max_counter(name: str, value: float) -> None:
    """Raise a named counter to ``value`` if it is below it (creates it
    at ``value``) — high-water-mark counters like peak bytes."""
    with _counters_lock:
        _counters[name] = max(_counters.get(name, float("-inf")), value)


def get_counter(name: str) -> float:
    """Current value of a counter (0.0 if never incremented)."""
    with _counters_lock:
        return _counters.get(name, 0.0)


def counters(prefix: str = "") -> dict:
    """Snapshot of all counters whose name starts with ``prefix``."""
    with _counters_lock:
        return {k: v for k, v in _counters.items() if k.startswith(prefix)}


def reset_counters(prefix: str = "") -> None:
    """Zero (remove) counters matching ``prefix`` — test isolation.
    The removed counts fold into the process-lifetime ledger first
    (:func:`lifetime_counters`), so a session-end artifact still sees
    accounting that a mid-session reset wiped from the live view."""
    with _counters_lock:
        for k in [k for k in _counters if k.startswith(prefix)]:
            _counters_lifetime[k] = (
                _counters_lifetime.get(k, 0.0) + _counters.pop(k))


def lifetime_counters(prefix: str = "") -> dict:
    """Process-lifetime counter totals: the live counters plus every
    count a :func:`reset_counters` call has folded away. This is the
    ledger the CI metrics snapshot floors are checked against — "was
    the modeled-throughput accounting alive at any point this
    session" — NOT a metric surface (a scrape reads :func:`counters`;
    high-water ``max_counter`` values sum across resets here, which is
    fine for an is-it-alive floor but not for reporting)."""
    with _counters_lock:
        out = {k: v for k, v in _counters_lifetime.items()
               if k.startswith(prefix)}
        for k, v in _counters.items():
            if k.startswith(prefix):
                out[k] = out.get(k, 0.0) + v
        return out


# ---------------------------------------------------------------------------
# gauges — last-value metrics (cost-analysis numbers, queue depth, rates)
# ---------------------------------------------------------------------------

_gauges: dict = {}  # guarded-by: _counters_lock


def set_gauge(name: str, value: float) -> None:
    """Set a named process-wide gauge to ``value`` (last write wins) —
    the non-monotone sibling of :func:`inc_counter`, for quantities
    that go up AND down (queue depth, arrival rate) or describe a
    current object (an executable's cost-analysis flops)."""
    with _counters_lock:
        _gauges[name] = value


def set_gauges(values: Dict[str, float]) -> None:
    """Set several gauges under one lock acquisition."""
    with _counters_lock:
        _gauges.update(values)


def get_gauge(name: str, default: float = 0.0) -> float:
    """Current value of a gauge (``default`` if never set)."""
    with _counters_lock:
        return _gauges.get(name, default)


def gauges(prefix: str = "") -> dict:
    """Snapshot of all gauges whose name starts with ``prefix``."""
    with _counters_lock:
        return {k: v for k, v in _gauges.items() if k.startswith(prefix)}


def reset_gauges(prefix: str = "") -> None:
    """Drop gauges matching ``prefix`` — test isolation, and how the
    executor retires the per-executable gauges of an evicted entry."""
    with _counters_lock:
        for k in [k for k in _gauges if k.startswith(prefix)]:
            del _gauges[k]


# ---------------------------------------------------------------------------
# histograms — per-stage latency distributions for the serving frontend
# ---------------------------------------------------------------------------

# log2-spaced bucket upper bounds from 1 µs to ~67 s: wide enough for
# queue waits and device executes alike, cheap enough (27 ints) that
# observing on the per-request hot path is a dict lookup + increment
# (builtins.range — this module's own `range` is the profiling scope)
_HIST_BOUNDS = tuple(1e-6 * (2.0 ** i) for i in builtins.range(27))

_histograms: dict = {}


class Histogram:
    """Fixed-bound latency histogram (bounds in seconds, log2-spaced).

    ``observe`` is O(log n_buckets); ``quantile`` interpolates linearly
    inside the selected bucket, which is the usual Prometheus-style
    estimate — exact enough for p50/p95/p99 serving dashboards.
    Values past the last bound land in an overflow bucket whose
    quantile estimate is pinned at ``2 * bounds[-1]``.

    Every instance carries its own lock: :func:`get_histogram` hands
    out live objects, so ``observe``/``snapshot`` must be safe against
    concurrent callers without routing through the registry lock."""

    __slots__ = ("bounds", "counts", "count", "sum", "_lock")

    def __init__(self, bounds=_HIST_BOUNDS):
        self.bounds = tuple(bounds)
        self.counts = [0] * (len(self.bounds) + 1)  # +overflow bucket; guarded-by: _lock
        self.count = 0   # guarded-by: _lock
        self.sum = 0.0   # guarded-by: _lock
        self._lock = threading.Lock()

    def observe(self, value: float) -> None:
        lo, hi = 0, len(self.bounds)
        while lo < hi:
            mid = (lo + hi) // 2
            if value <= self.bounds[mid]:
                hi = mid
            else:
                lo = mid + 1
        with self._lock:
            self.counts[lo] += 1
            self.count += 1
            self.sum += value

    def _quantile_locked(self, q: float, counts, count) -> float:
        if count == 0:
            return 0.0
        target = q * count
        seen = 0
        for i, c in enumerate(counts):
            if seen + c >= target and c > 0:
                lo = self.bounds[i - 1] if i > 0 else 0.0
                hi = (self.bounds[i] if i < len(self.bounds)
                      else self.bounds[-1] * 2.0)
                frac = (target - seen) / c
                return lo + (hi - lo) * frac
            seen += c
        return self.bounds[-1] * 2.0

    def quantile(self, q: float) -> float:
        """Estimated q-quantile (0 <= q <= 1); 0.0 when empty."""
        with self._lock:
            counts, count = list(self.counts), self.count
        return self._quantile_locked(q, counts, count)

    def snapshot(self) -> dict:
        """One consistent read: count/sum/quantile estimates plus the
        bucket bounds and CUMULATIVE per-bucket counts (the last entry
        is the +Inf/overflow bucket and equals ``count``) — the shape
        the Prometheus exposition format wants."""
        with self._lock:
            counts, count, total = list(self.counts), self.count, self.sum
        cumulative = list(itertools.accumulate(counts))
        return {
            "count": count,
            "sum": total,
            "p50": self._quantile_locked(0.50, counts, count),
            "p95": self._quantile_locked(0.95, counts, count),
            "p99": self._quantile_locked(0.99, counts, count),
            "bucket_bounds": list(self.bounds),
            "bucket_counts": cumulative,
        }


def observe(name: str, value: float) -> None:
    """Record ``value`` (seconds) into the named process-wide histogram
    (created on first use)."""
    with _counters_lock:
        h = _histograms.get(name)
        if h is None:
            h = _histograms[name] = Histogram()
        h.observe(value)


def get_histogram(name: str) -> Histogram:
    """The named histogram (an empty one if never observed)."""
    with _counters_lock:
        h = _histograms.get(name)
        if h is None:
            h = _histograms[name] = Histogram()
        return h


def histograms(prefix: str = "") -> dict:
    """``{name: snapshot-dict}`` for histograms matching ``prefix``."""
    with _counters_lock:
        return {k: h.snapshot() for k, h in _histograms.items()
                if k.startswith(prefix)}


def reset_histograms(prefix: str = "") -> None:
    """Drop histograms matching ``prefix`` — test isolation."""
    with _counters_lock:
        for k in [k for k in _histograms if k.startswith(prefix)]:
            del _histograms[k]


# ---------------------------------------------------------------------------
# request spans — structured host-side stage timing with trace ids
# ---------------------------------------------------------------------------

_trace_ids = itertools.count(1)


def new_trace_id() -> int:
    """Mint a process-unique trace id (monotonically increasing int).
    One is stamped on every ``SearchRequest`` at construction and
    propagated through admission → assembly → execute → split, so a
    request's whole journey is one grep in the span ring."""
    return next(_trace_ids)


@dataclasses.dataclass(frozen=True)
class Span:
    """One completed host-side span.

    ``start``/``end`` are seconds in the *recording clock's* domain —
    the serving stack records with its injectable clock, so spans from
    a manual-clock test are exact virtual timestamps. That clock is not
    the profiler's: the copy of a stage span that lines up with the
    device ops is the ``jax.profiler.TraceAnnotation`` that
    :class:`host_span` opens beside it. Zero-duration spans are
    instant markers (shed/cancel/reject reasons). ``events`` is a
    tuple of ``(ts, name, attrs)`` marks inside the span."""

    name: str
    start: float
    end: float
    trace_ids: Tuple[int, ...] = ()
    attrs: Any = dataclasses.field(default_factory=dict)
    events: tuple = ()
    tid: int = 0

    @property
    def duration(self) -> float:
        return self.end - self.start


class SpanRecorder:
    """Bounded, lock-protected span ring buffer — the flight recorder.

    The ring holds the most recent ``capacity`` spans; overwrites are
    counted in :attr:`dropped` rather than silently vanishing, so a
    post-mortem knows whether it is looking at the full story. All
    mutation is a deque append under one lock: O(1), no allocation
    beyond the span itself, safe from any thread."""

    def __init__(self, capacity: int = 8192):
        self._lock = threading.Lock()
        self._buf: "collections.deque[Span]" = collections.deque(  # guarded-by: _lock
            maxlen=max(int(capacity), 1))
        self._dropped = 0  # guarded-by: _lock

    @property
    def capacity(self) -> int:
        return self._buf.maxlen  # graftlint: disable=R8(deque reference never rebinds; maxlen is immutable)

    @property
    def dropped(self) -> int:
        """Spans overwritten by the ring since the last :meth:`clear`."""
        with self._lock:
            return self._dropped

    def __len__(self) -> int:
        with self._lock:
            return len(self._buf)

    def record(self, name: str, start: float, end: float, *,
               trace_ids: Tuple[int, ...] = (), attrs: Optional[dict] = None,
               events: tuple = ()) -> Span:
        """Record one completed span (the serving stack's entry point —
        stages time themselves with their own clock and report here)."""
        span = Span(name=name, start=start, end=end,
                    trace_ids=tuple(trace_ids), attrs=dict(attrs or {}),
                    events=tuple(events),
                    tid=threading.get_ident())
        with self._lock:
            if len(self._buf) == self._buf.maxlen:
                self._dropped += 1
            self._buf.append(span)
        return span

    def event(self, name: str, ts: float, *,
              trace_ids: Tuple[int, ...] = (),
              attrs: Optional[dict] = None) -> Span:
        """Record an instant marker (zero-duration span) — shed,
        cancel, and reject reasons land here."""
        return self.record(name, ts, ts, trace_ids=trace_ids, attrs=attrs)

    def spans(self, trace_id: Optional[int] = None,
              name: Optional[str] = None) -> list:
        """Snapshot of recorded spans, oldest first, optionally
        filtered by ``trace_id`` membership and/or exact ``name``."""
        with self._lock:
            out = list(self._buf)
        if trace_id is not None:
            out = [s for s in out if trace_id in s.trace_ids]
        if name is not None:
            out = [s for s in out if s.name == name]
        return out

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()
            self._dropped = 0

    # -- Chrome trace-event JSON (Perfetto / chrome://tracing) --------------

    def to_chrome_trace(self, pid: int = 0,
                        trace_id: Optional[int] = None) -> dict:
        """Export the ring as a Chrome trace-event JSON object.

        Complete spans become ``"ph": "X"`` duration events (µs
        timestamps); span events and zero-duration spans additionally
        emit ``"ph": "i"`` instant marks so reasons are visible on the
        Perfetto timeline. The precise float seconds ride along in
        ``args`` (``t0_s``/``t1_s``) because µs conversion is lossy —
        :meth:`from_chrome_trace` reads those back, making the export
        a faithful round trip. The reserved arg keys (``trace_ids`` /
        ``t0_s`` / ``t1_s`` / ``events``) win over same-named span
        attrs: a colliding attr is shadowed in the export rather than
        corrupting the rebuilt span's timing.

        ``trace_id`` restricts the export to spans carrying that id —
        the per-request fetch (``/trace.json?trace_id=``); an unknown
        id yields an empty (but valid) trace rather than an error."""
        events = []
        for s in self.spans(trace_id=trace_id):
            args = dict(s.attrs)
            args.update({
                "trace_ids": list(s.trace_ids), "t0_s": s.start,
                "t1_s": s.end,
                "events": [[ts, name, dict(attrs)]
                           for ts, name, attrs in s.events]})
            events.append({
                "name": s.name, "ph": "X", "pid": pid, "tid": s.tid,
                "ts": s.start * 1e6,
                "dur": max(s.end - s.start, 0.0) * 1e6,
                "args": args,
            })
            for ts, name, attrs in s.events:
                events.append({
                    "name": f"{s.name}.{name}", "ph": "i", "s": "t",
                    "pid": pid, "tid": s.tid, "ts": ts * 1e6,
                    "args": dict(attrs),
                })
            if s.end == s.start:
                # shed/cancel/reject markers: a dur=0 "X" slice is
                # invisible in Perfetto, the "i" mark is clickable
                events.append({
                    "name": s.name, "ph": "i", "s": "t",
                    "pid": pid, "tid": s.tid, "ts": s.start * 1e6,
                    "args": dict(s.attrs),
                })
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    @staticmethod
    def from_chrome_trace(data: dict) -> list:
        """Rebuild the span list from :meth:`to_chrome_trace` output —
        the post-mortem path: load a dumped flight-recorder JSON back
        into :class:`Span` objects."""
        out = []
        for ev in data.get("traceEvents", []):
            if ev.get("ph") != "X":
                continue
            args = dict(ev.get("args", {}))
            trace_ids = tuple(args.pop("trace_ids", ()))
            start = args.pop("t0_s", ev.get("ts", 0.0) / 1e6)
            end = args.pop("t1_s",
                           (ev.get("ts", 0.0) + ev.get("dur", 0.0)) / 1e6)
            events = tuple((ts, name, dict(attrs))
                           for ts, name, attrs in args.pop("events", []))
            out.append(Span(name=ev.get("name", ""), start=start, end=end,
                            trace_ids=trace_ids, attrs=args, events=events,
                            tid=ev.get("tid", 0)))
        return out


_span_recorder = SpanRecorder()


def span_recorder() -> SpanRecorder:
    """The process-wide span ring (serving spans land here)."""
    return _span_recorder


def record_span(name: str, start: float, end: float, *,
                trace_ids: Tuple[int, ...] = (),
                attrs: Optional[dict] = None,
                events: tuple = ()) -> Span:
    """Record into the process-wide ring (see :class:`SpanRecorder`)."""
    return _span_recorder.record(name, start, end, trace_ids=trace_ids,
                                 attrs=attrs, events=events)


def span_event(name: str, ts: float, *, trace_ids: Tuple[int, ...] = (),
               attrs: Optional[dict] = None) -> Span:
    """Instant marker in the process-wide ring."""
    return _span_recorder.event(name, ts, trace_ids=trace_ids, attrs=attrs)


def reset_spans() -> None:
    """Drop every recorded span — test isolation."""
    _span_recorder.clear()


# ---------------------------------------------------------------------------
# mesh spans — per-shard attribution + the straggler detector (PR 7)
# ---------------------------------------------------------------------------

# the straggler gauges every mesh dispatch re-publishes
MESH_SHARD_SKEW = "serving.mesh.shard_skew"
MESH_SLOWEST_SHARD = "serving.mesh.slowest_shard"
MESH_SHARD_TIME_MAX = "serving.mesh.shard_time_max_s"
MESH_SHARD_TIME_MEAN = "serving.mesh.shard_time_mean_s"
# per-dispatch skew distribution (graftfleet, PR 12): when a capture's
# invocation windows yield one skew sample PER DISPATCH, the
# distribution publishes next to the last-dispatch gauge above
MESH_SHARD_SKEW_P50 = "serving.mesh.shard_skew_p50"
MESH_SHARD_SKEW_P99 = "serving.mesh.shard_skew_p99"


def sample_quantile(samples, q: float) -> float:
    """Linear-interpolated q-quantile of a small host-side sample list
    (numpy's default method, dependency-free) — 0.0 when empty. Pure
    function: the per-dispatch skew gauges are pinned exactly by the
    capture fixtures."""
    ts = sorted(float(s) for s in samples)
    if not ts:
        return 0.0
    if len(ts) == 1:
        return ts[0]
    pos = q * (len(ts) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(ts) - 1)
    return ts[lo] + (ts[hi] - ts[lo]) * (pos - lo)


def straggler_stats(timings) -> dict:
    """Reduce per-shard timings (seconds, index = shard ordinal) into
    straggler attribution: ``slowest_shard`` (argmax), ``shard_skew``
    (max − min — the wall-clock a perfectly balanced mesh would get
    back), plus max/mean. Pure function of its input, so the
    ShimExecutor-scripted tests pin the gauges exactly."""
    ts = [float(t) for t in timings]
    if not ts:
        return {"shards": 0, "shard_skew": 0.0, "slowest_shard": -1,
                "max_s": 0.0, "mean_s": 0.0}
    mx = max(ts)
    return {
        "shards": len(ts),
        "shard_skew": mx - min(ts),
        "slowest_shard": ts.index(mx),
        "max_s": mx,
        "mean_s": sum(ts) / len(ts),
    }


def poll_shard_timings(parts, t0: float, *,
                       poll_s: float = 50e-6) -> list:
    """Per-shard arrival offsets (seconds after ``t0``) from a
    NON-BLOCKING ``is_ready()`` poll over ``parts`` — a sequence of
    ``(distances, indices)`` array pairs, one per shard ordinal. The
    shared input half of the straggler detector (executor mesh_trace +
    ``ShardedIndex.search``).

    Why a poll and not a sequential block per shard: blocking in order
    makes readings cumulative — an early-ordinal straggler drags every
    later shard's reading up to its own and the skew gauge reports a
    balanced mesh in exactly the imbalance case it exists to detect.
    ``poll_s`` bounds the timing resolution; total wall time is
    unchanged (callers block on the same results right after).

    Host arrays (no ``is_ready``) are ready by definition; an
    ``is_ready`` that raises ``RuntimeError`` (a donated-state buffer
    consumed by a concurrent re-dispatch — the poll runs outside the
    executor lock) caps that shard's arrival at the consumption time
    rather than crashing the trace."""
    def _ready(a) -> bool:
        fn = getattr(a, "is_ready", None)
        if fn is None:
            return True
        try:
            return fn()
        except RuntimeError:
            return True

    timings = [0.0] * len(parts)
    # builtins.range — this module's own `range` is the profiling scope
    pending = set(builtins.range(len(parts)))
    while pending:
        for s in tuple(pending):
            d, i = parts[s]
            if _ready(d) and _ready(i):
                timings[s] = time.perf_counter() - t0
                pending.discard(s)
        if pending:
            time.sleep(poll_s)
    return timings


def record_mesh_spans(family: str, t0: float, t1: float, *,
                      trace_ids: Tuple[int, ...] = (),
                      phases: Optional[dict] = None,
                      shard_timings=None,
                      shard_attrs: Optional[dict] = None,
                      skew_samples=None,
                      count_dispatch: bool = True) -> dict:
    """Record one mesh dispatch into the flight recorder: a
    ``serving.mesh.<phase>`` span per entry of ``phases`` (attrs carry
    the modeled per-phase bytes — the phases share the dispatch window
    ``[t0, t1]`` because the compiled program is opaque host-side; the
    attribution is TPU-KNN-style modeled accounting, not a device
    profile), plus a ``serving.mesh.shard`` span per entry of
    ``shard_timings`` (seconds after ``t0`` at which that shard's
    output block became ready host-side). The straggler detector
    reduces the timings into the ``serving.mesh.*`` gauges and returns
    its stats. Everything here is host-side deque/dict work — no
    device interaction, same discipline as every other recorder.

    ``shard_attrs`` merges extra attrs onto every shard span —
    graftflight's measured re-emission marks them ``modeled: False``
    with ``source: "profiler"`` — and ``count_dispatch=False`` skips
    the ``serving.mesh.dispatches`` bump (re-attributing already
    counted dispatches from a capture is not a new dispatch).

    ``skew_samples`` (graftfleet, PR 12) carries one shard-skew sample
    PER DISPATCH — the per-invocation-window skews a capture's
    gap-clustering yields — and publishes their distribution as the
    ``serving.mesh.shard_skew_p50``/``_p99`` gauges: a capture holding
    several dispatches then attributes straggler skew per dispatch
    instead of smearing it over the whole window."""
    for phase, attrs in (phases or {}).items():
        a = dict(attrs or {})
        a["family"] = family
        record_span(f"serving.mesh.{phase}", t0, t1,
                    trace_ids=trace_ids, attrs=a)
    stats = straggler_stats(shard_timings or ())
    if shard_timings:
        for s, dt in enumerate(shard_timings):
            a = {"family": family, "shard": s}
            if shard_attrs:
                a.update(shard_attrs)
            record_span("serving.mesh.shard", t0, t0 + float(dt),
                        trace_ids=trace_ids, attrs=a)
        set_gauges({
            MESH_SHARD_SKEW: stats["shard_skew"],
            MESH_SLOWEST_SHARD: float(stats["slowest_shard"]),
            MESH_SHARD_TIME_MAX: stats["max_s"],
            MESH_SHARD_TIME_MEAN: stats["mean_s"],
        })
        if count_dispatch:
            inc_counter("serving.mesh.dispatches")
    if skew_samples:
        stats["shard_skew_p50"] = sample_quantile(skew_samples, 0.50)
        stats["shard_skew_p99"] = sample_quantile(skew_samples, 0.99)
        set_gauges({
            MESH_SHARD_SKEW_P50: stats["shard_skew_p50"],
            MESH_SHARD_SKEW_P99: stats["shard_skew_p99"],
        })
    return stats


# ---------------------------------------------------------------------------
# graftgauge — index-health, probe-frequency, and drift reducers (PR 8)
# ---------------------------------------------------------------------------
#
# Pure functions of host arrays: the serving layer fetches its inputs
# once per scrape (the executor's probe planes, an index's list_sizes)
# and reduces them here, so every gauge value is pinned exactly by a
# scripted test and nothing below ever touches the device.

# the flat (unlabeled) drift/recall gauge names graftgauge publishes
DRIFT_SCORE = "index.drift.score"
RECALL_ESTIMATE = "index.recall.estimate"


def index_health(list_sizes, max_list_size: Optional[int] = None,
                 shards: int = 0) -> dict:
    """Reduce one index's per-list populations into its health stats:
    occupancy skew (``max``/``mean``/``p99`` list size and the Gini
    coefficient of the size distribution), ``dead_lists`` (empty —
    wasted probes land there), ``overflow_lists`` (at the padded
    capacity ``max_list_size`` — the next extend() into them forces a
    full repack), and ``fill_fraction`` of the padded tensor. With
    ``shards`` > 0 the block-sharded layout's per-shard row totals
    reduce into ``shard_imbalance`` (max/mean — 1.0 is a perfectly
    balanced mesh) — the evidence the lifecycle/compaction direction
    needs to decide what to rebalance. Pure function of its inputs."""
    sizes = np.asarray(list_sizes, dtype=np.int64)
    n = int(sizes.size)
    total = int(sizes.sum())
    out = {
        "n_lists": n,
        "rows": total,
        "max_list_size": int(sizes.max()) if n else 0,
        "mean_list_size": total / n if n else 0.0,
        "p99_list_size": float(np.percentile(sizes, 99)) if n else 0.0,
        "dead_lists": int((sizes == 0).sum()),
        "overflow_lists": 0,
        "fill_fraction": 0.0,
        "gini": 0.0,
        "shard_imbalance": 1.0,
    }
    if max_list_size:
        out["overflow_lists"] = int((sizes >= max_list_size).sum())
        out["fill_fraction"] = (total / (n * max_list_size)
                                if n * max_list_size else 0.0)
    if total > 0 and n > 1:
        # Gini over list populations: 0 = perfectly even, ->1 = all
        # rows in one list (the standard inequality reduction)
        s = np.sort(sizes)
        cum = np.cumsum(s, dtype=np.float64)
        out["gini"] = float(
            (n + 1 - 2.0 * (cum.sum() / cum[-1])) / n)
    if shards > 1 and n % shards == 0:
        per_shard = sizes.reshape(shards, n // shards).sum(axis=1)
        mean = per_shard.mean()
        out["shard_imbalance"] = (float(per_shard.max() / mean)
                                  if mean > 0 else 1.0)
    return out


def probe_freq_stats(counts, top_n: int = 8) -> dict:
    """Reduce one cumulative probe-frequency plane into its traffic
    stats: lifetime ``total`` probes, ``probed_fraction`` (share of
    lists traffic ever touched — its complement is the cold set), the
    hot-set coverage fractions ``coverage_p01``/``coverage_p10``
    (share of all probes the hottest 1% / 10% of lists absorbed — the
    exact signal an HBM/host-RAM tier split keys on), and the
    ``top_n`` hottest lists as ``(list_id, count)`` pairs. Pure
    function of the fetched plane."""
    c = np.asarray(counts, dtype=np.int64)
    n = int(c.size)
    total = int(c.sum())
    if n == 0 or total == 0:
        return {"n_lists": n, "total": total, "probed_fraction": 0.0,
                "coverage_p01": 0.0, "coverage_p10": 0.0, "top": []}
    order = np.argsort(-c, kind="stable")
    sorted_c = c[order]
    cum = np.cumsum(sorted_c, dtype=np.float64)

    def coverage(frac: float) -> float:
        k = max(1, int(np.ceil(n * frac)))
        return float(cum[k - 1] / total)

    top = [(int(order[i]), int(sorted_c[i]))
           for i in builtins.range(min(top_n, n)) if sorted_c[i] > 0]
    return {
        "n_lists": n,
        "total": total,
        "probed_fraction": float((c > 0).sum() / n),
        "coverage_p01": coverage(0.01),
        "coverage_p10": coverage(0.10),
        "top": top,
    }


def js_divergence(p, q) -> float:
    """Jensen-Shannon divergence (base 2 — bounded [0, 1]) between two
    count histograms; the drift score's distance. Inputs need not be
    normalized; a zero histogram against a non-zero one scores 1.0
    (maximal drift), two zero histograms 0.0. Symmetric and finite
    even where one side has mass the other lacks — why it, and not
    KL, is the streaming drift metric."""
    pa = np.asarray(p, dtype=np.float64)
    qa = np.asarray(q, dtype=np.float64)
    ps, qs = pa.sum(), qa.sum()
    if ps == 0 and qs == 0:
        return 0.0
    if ps == 0 or qs == 0:
        return 1.0
    pa, qa = pa / ps, qa / qs
    m = 0.5 * (pa + qa)

    def kl(a, b):
        mask = a > 0
        return float(np.sum(a[mask] * np.log2(a[mask] / b[mask])))

    return 0.5 * kl(pa, m) + 0.5 * kl(qa, m)


class host_span:
    """One host stage, timed where the work happens::

        with tracing.host_span("serving.assembly", clock=clock.now,
                               hist=ASSEMBLY, ring=True, trace_ids=ids,
                               attrs={"rows": n}):
            ...

    On enter it opens a ``jax.profiler.TraceAnnotation(name)``: while a
    profiler trace runs, the stage lands on the calling thread's host
    line on the profiler's own clock, beside the device ops it
    dispatched (with no trace running, the annotation does nothing).
    On exit the duration, measured on ``clock`` (the
    serving batcher passes its injectable clock, so manual-clock runs
    stay exact; the default is ``time.perf_counter``), is observed into
    the histogram ``hist`` when one is named, and with ``ring=True``
    the span is recorded into the process-wide ring on that same clock.
    A stage that raises observes nothing and records its ring span with
    a ``failed`` event naming the error.

    :meth:`close` ends the stage early, at a boundary inside the
    ``with`` block (a later close or exit is then a no-op);
    ``start``/``end``/``duration`` read the clock's timestamps."""

    __slots__ = ("name", "hist", "ring", "trace_ids", "attrs", "_now",
                 "_annotation", "start", "end")

    def __init__(self, name: str, *, clock=time.perf_counter,
                 hist: Optional[str] = None, ring: bool = False,
                 trace_ids: Tuple[int, ...] = (),
                 attrs: Optional[dict] = None):
        self.name, self.hist, self.ring = name, hist, ring
        self.trace_ids, self.attrs = trace_ids, attrs
        self._now = clock
        self._annotation = None
        self.start = self.end = None

    def __enter__(self) -> "host_span":
        self._annotation = jax.profiler.TraceAnnotation(self.name)
        self._annotation.__enter__()
        self.start = self._now()
        return self

    def close(self, error: Optional[BaseException] = None) -> None:
        """End the stage now (idempotent)."""
        if self.end is not None:
            return
        self.end = self._now()
        self._annotation.__exit__(None, None, None)
        if error is None and self.hist is not None:
            observe(self.hist, self.end - self.start)
        if self.ring:
            events = () if error is None else (
                (self.end, "failed", {"error": type(error).__name__}),)
            record_span(self.name, self.start, self.end,
                        trace_ids=self.trace_ids, attrs=self.attrs,
                        events=events)

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.close(exc)
        return False

    @property
    def duration(self) -> float:
        return self.end - self.start


_compile_listener_installed = False

# every XLA backend compile in the process lands in these two counters
XLA_COMPILE_COUNT = "xla.backend_compile_count"
XLA_COMPILE_SECONDS = "xla.backend_compile_seconds"


def install_xla_compile_listener() -> None:
    """Count every XLA backend compile into :data:`XLA_COMPILE_COUNT` /
    :data:`XLA_COMPILE_SECONDS` via ``jax.monitoring``.

    Idempotent and process-wide. This is the ground truth the serving
    path's "steady state never compiles" guarantee is tested against:
    jax emits ``/jax/core/compile/backend_compile_duration`` exactly
    once per real (non-cached) executable build.
    """
    global _compile_listener_installed
    with _counters_lock:
        if _compile_listener_installed:
            return
        _compile_listener_installed = True

    def _on_event(name: str, secs: float, **kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            inc_counter(XLA_COMPILE_COUNT)
            inc_counter(XLA_COMPILE_SECONDS, secs)

    jax.monitoring.register_event_duration_secs_listener(_on_event)
