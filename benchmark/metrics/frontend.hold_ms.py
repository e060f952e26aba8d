"""Mean time per dispatch the batcher's timer held a queued group for
company before dispatching it (``serving.batcher.hold_seconds``
histogram sum over ``serving.batcher.batches``, whole window).
Nothing where the program records no device-wait span (it predates
the stage spans) or dispatched nothing."""

STAGE = "serving.batcher.hold_seconds"
DEVICE_WAIT = "serving.batcher.device_wait_seconds"


def read(w):
    batches = w.counter("serving.batcher.batches")
    if not batches or not w.hist(DEVICE_WAIT)[0]:
        return None
    return 1e3 * w.hist(STAGE)[1] / batches
