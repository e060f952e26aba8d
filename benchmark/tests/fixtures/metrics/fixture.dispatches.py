"""Fixture metric: dispatches in the window (found by name only)."""


def read(w):
    return w.counter("serving.execute.calls")
