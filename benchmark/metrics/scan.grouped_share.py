"""Share of the window's dispatches whose executable ran the grouped
Pallas list scan (``ops/ivf_scan``): counter
``serving.scan.grouped_dispatches`` over ``serving.batcher.batches``.
Every IVF-Flat dispatch adds 1 to the counter or, where its engine
degraded to the XLA scan, 0; so 1.0 means every batch ran the kernel.
Nothing where the program keeps no such counter or dispatched
nothing."""

GROUPED = "serving.scan.grouped_dispatches"


def read(w):
    batches = w.counter("serving.batcher.batches")
    if GROUPED not in w.after["counters"] or not batches:
        return None
    return w.counter(GROUPED) / batches
