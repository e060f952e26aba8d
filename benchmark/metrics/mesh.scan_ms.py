"""Device time of the list-sharded byte scan per chip per dispatch:
the kernel's summed event seconds over its events (one per chip per
dispatch) in the traced run. Beside ``executor.device_wait_ms`` it
shows how much of a dispatch is not the scan: coarse select, the probe
exchange, the merge and the launch. Nothing where the trace holds no
such event."""

KERNEL = "mesh_ivf_scan"


def read(w):
    if w.trace is None:
        return None
    events, seconds = w.trace["kernels"].get(KERNEL, (0, 0.0))
    return 1e3 * seconds / events if events else None
