"""Modeled collective bytes per dispatch of a sharded index: the
window's ``serving.mesh.wire_bytes`` (each sharded dispatch adds its
executable's probe-exchange plus result-merge payload per chip) over
``serving.batcher.batches``. Nothing where the program keeps no such
counter or dispatched nothing."""

WIRE_BYTES = "serving.mesh.wire_bytes"


def read(w):
    batches = w.counter("serving.batcher.batches")
    if WIRE_BYTES not in w.after["counters"] or not batches:
        return None
    return w.counter(WIRE_BYTES) / batches
