"""Mean time per dispatch the batcher's worker blocked on the device
(``block_until_ready`` on the results;
``serving.batcher.device_wait_seconds`` histogram sum over
``serving.batcher.batches``, whole window).
Nothing where the program records no device-wait span (it predates
the stage spans) or dispatched nothing."""

DEVICE_WAIT = "serving.batcher.device_wait_seconds"


def read(w):
    batches = w.counter("serving.batcher.batches")
    n, seconds = w.hist(DEVICE_WAIT)
    if not batches or not n:
        return None
    return 1e3 * seconds / batches
