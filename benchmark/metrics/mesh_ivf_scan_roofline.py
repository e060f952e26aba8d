"""Percent of the roofline the list-sharded byte scan reached: the
ideal time of the work ``work/mesh_ivf_scan.py`` counts, at one chip's
peaks, over the kernel's device time summed across the cell's chips
(the same as the ideal at all the chips' peaks over the mean chip
time). Nothing unless the cell runs that kernel and every dispatch
held one request.

A four-chip trace on a 2x2 v5e host keeps the device events of only
part of a 51 s window (~408 of 660 scan events, an 18.7 s stretch empty
on every chip), so the time is the captured events' mean scaled to every
dispatch: the ideal of the window's work over ``chips * requests``
times the mean event."""

KERNEL = "mesh_ivf_scan"


def read(w):
    from benchmark import peaks

    if (w.trace is None or w.work is None
            or w.cell.conf.get("kernel") != KERNEL):
        return None
    n = w.n_requests
    if (w.counter("serving.batcher.batches") != n
            or w.counter("serving.batcher.requests") != n):
        return None
    events, seconds = w.trace["kernels"].get(KERNEL, (0, 0.0))
    if not 0 < events <= w.cell.chips * n or seconds <= 0:
        return None
    device_s = w.cell.chips * n * seconds / events
    return 100.0 * peaks.ideal_seconds(w.work[0], w.work[1],
                                       w.peak) / device_s
