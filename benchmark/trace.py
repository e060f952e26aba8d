"""Reduction of a profiler trace to the benchmark's device numbers.

Built on ``jax.profiler.ProfileData`` alone, kept with the benchmark so
no program PR can move the yardstick. From one ``.xplane.pb``:

- the steady window: the host span ``bench.window`` the harness records
  around the measured seconds;
- ``busy_s``: the union of the device's op intervals (line ``XLA Ops``
  of each ``/device:TPU:<n>`` plane) inside the window, averaged over
  the devices that ran anything; ``window_s`` its length;
- kernel time: the summed device duration of the events whose trace
  name (the HLO instruction text) matches one of a kernel's patterns,
  over the whole trace;
- ``breakdown``: the device ops that took most time in the window, and
  the longest idle gaps, each named by the innermost host span that
  covers its middle (the harness's ``bench.*`` spans, or the runtime's
  own host events).
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
TOP = 10

Interval = Tuple[float, float]


def xplane_file(profile_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` trace dir."""
    found = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    return max(found, key=os.path.getmtime)


def _events(line) -> Iterable[Tuple[str, float, float]]:
    for e in line.events:
        yield e.name, e.start_ns, e.start_ns + e.duration_ns


def device_ops(pd) -> Dict[str, List[Tuple[str, float, float]]]:
    """``{plane name: [(op name, start_ns, end_ns)]}`` per TPU device."""
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                out[plane.name] = list(_events(line))
    return out


def host_spans(pd) -> List[Tuple[str, float, float]]:
    """Every host event with a duration, on every host thread."""
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                out.extend(e for e in _events(line) if e[2] > e[1])
    return out


def union(intervals: Iterable[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """Sorted disjoint union of ``intervals`` clipped to ``[lo, hi]``."""
    merged: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of ``[lo, hi]`` between the busy ones."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def short_name(text: str) -> str:
    """An op's HLO instruction name and opcode from its trace name
    (the whole instruction text), e.g. ``%fusion.2 fusion``; a Pallas
    kernel reads ``%<name> custom-call tpu_custom_call``."""
    name, _, rest = text.partition(" = ")
    if not rest:
        return text[:120]
    depth, i = 0, 0
    for i, ch in enumerate(rest):           # skip the result shape
        depth += ch == "(" or ch == "{"
        depth -= ch == ")" or ch == "}"
        if ch == " " and depth == 0:
            break
    op = rest[i + 1:].split("(", 1)[0]
    if 'custom_call_target="tpu_custom_call"' in rest:
        op += " tpu_custom_call"
    return f"{name} {op}"


def label(spans, t: float) -> str:
    """Name of the innermost host span covering instant ``t``."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and name != WINDOW_SPAN and (
                best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "no host span"


def reduce(pd, kernels: Dict[str, Sequence[str]]) -> dict:
    """The device numbers of one trace; ``kernels`` maps a kernel's
    name to regular expressions its events' trace names match. Raises
    ``ValueError`` when the trace holds no window span or no device
    op."""
    spans = host_spans(pd)
    window = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not window:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    lo, hi = window[0]
    ops = device_ops(pd)
    active = {p: evs for p, evs in ops.items() if evs}
    if not active:
        raise ValueError("trace holds no device op")
    busy_ns, all_gaps, per_op = 0.0, [], {}
    for plane, evs in active.items():
        busy = union(((s, e) for _, s, e in evs), lo, hi)
        busy_ns += sum(e - s for s, e in busy)
        all_gaps.extend(gaps(busy, lo, hi))
        for name, s, e in evs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                key = short_name(name)
                per_op[key] = per_op.get(key, 0.0) + d
    busy_s = busy_ns / len(active) / 1e9
    window_s = (hi - lo) / 1e9
    kern = {}
    for kernel, patterns in kernels.items():
        rx = [re.compile(p) for p in patterns]
        durs = [e - s for evs in active.values() for n, s, e in evs
                if any(r.match(n) for r in rx)]
        kern[kernel] = (len(durs), sum(durs) / 1e9)
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(all_gaps, key=lambda g: g[0] - g[1])[:TOP]
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "kernels": kern,
        "breakdown": {
            "device_ops": [[n, d / 1e9] for n, d in top_ops],
            "idle_gaps": [[label(spans, (s + e) / 2), (e - s) / 1e9]
                          for s, e in top_gaps],
        },
    }


def reduce_dir(profile_dir: str, kernels: Dict[str, Sequence[str]]) -> dict:
    """:func:`reduce` of the trace a ``jax.profiler`` run wrote."""
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(xplane_file(profile_dir)), kernels)
