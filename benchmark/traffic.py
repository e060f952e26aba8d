"""The one traffic generator: reads a mix's parameters from
``traffic/<name>.json`` and drives the served path with them.

Closed loop (``"loop": "closed"``): ``clients`` threads each send one
request of ``queries_per_request`` queries, wait for the result in
their hands (ids and distances copied to the host), and send the next;
queries are drawn from the ``pool`` in turn through one shared cursor.
Every request is timed from just before ``submit`` to the result on the
host. Host spans (``jax.profiler.TraceAnnotation``) mark each client's
submit and result wait, so a trace can say what the host was doing in
a device gap.
"""

from __future__ import annotations

import threading
import time

import numpy as np

RESULT_TIMEOUT_S = 120.0


def check_mix(traffic: dict) -> None:
    """Refuse a mix this generator cannot drive."""
    if traffic.get("loop") != "closed":
        raise ValueError(f"unsupported loop {traffic.get('loop')!r}: "
                         "this generator drives closed loops only")
    for key in ("clients", "queries_per_request", "pool"):
        if int(traffic[key]) < 1:
            raise ValueError(f"traffic {key} must be >= 1")


def coalesced_counts(traffic: dict, full_batch_rows: int):
    """How many requests one micro-batch can hold under this mix:
    1 to this many (the batcher never packs past ``full_batch_rows``
    rows, and a larger request dispatches alone)."""
    m, c = int(traffic["queries_per_request"]), int(traffic["clients"])
    if m >= full_batch_rows:
        return 1
    return max(1, min(c, full_batch_rows // m))


class Record:
    __slots__ = ("t_sub", "t_done", "rows", "dist", "ids", "error")

    def __init__(self, t_sub, t_done, rows, dist=None, ids=None,
                 error=None):
        self.t_sub, self.t_done, self.rows = t_sub, t_done, rows
        self.dist, self.ids, self.error = dist, ids, error


class ClosedLoop:
    """Drive ``submit(queries) -> handle`` with a mix for ``seconds``."""

    def __init__(self, traffic: dict, pool: np.ndarray, submit):
        check_mix(traffic)
        self.m = int(traffic["queries_per_request"])
        self.clients = int(traffic["clients"])
        self.pool = pool
        self.p = int(traffic["pool"])
        if self.p > len(pool):
            raise ValueError("traffic pool larger than the pool made")
        self.submit = submit
        self._cursor = 0
        self._lock = threading.Lock()
        self.records = []

    def next_rows(self) -> np.ndarray:
        with self._lock:
            start = self._cursor
            self._cursor = (start + self.m) % self.p
        return (start + np.arange(self.m)) % self.p

    def _client(self, go: threading.Event, out: list):
        import jax

        go.wait()
        while time.perf_counter() < self.t_end:
            rows = self.next_rows()
            t_sub = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation("bench.submit"):
                    h = self.submit(self.pool[rows])
                with jax.profiler.TraceAnnotation("bench.result"):
                    d, i = h.result(timeout=RESULT_TIMEOUT_S)
                    d, i = np.asarray(d), np.asarray(i)
                out.append(Record(t_sub, time.perf_counter(), rows, d, i))
            except Exception as e:  # noqa: BLE001 — counted as failed
                out.append(Record(t_sub, time.perf_counter(), rows,
                                  error=f"{type(e).__name__}: {e}"))

    def run(self, seconds: float):
        """Run the window; returns ``(t0, t_end)``. Clients finish the
        request in flight at the close, which counts only if it came
        back inside the window."""
        import jax

        go = threading.Event()
        outs = [[] for _ in range(self.clients)]
        threads = [threading.Thread(target=self._client, args=(go, o),
                                    name=f"bench-client-{c}", daemon=True)
                   for c, o in enumerate(outs)]
        for t in threads:
            t.start()
        self.t0 = time.perf_counter()
        self.t_end = self.t0 + seconds
        go.set()
        with jax.profiler.TraceAnnotation("bench.window"):
            time.sleep(max(0.0, self.t_end - time.perf_counter()))
        for t in threads:
            t.join(RESULT_TIMEOUT_S + 30.0)
        self.stuck = sum(t.is_alive() for t in threads)
        self.records = [r for o in outs for r in o]
        return self.t0, self.t_end
