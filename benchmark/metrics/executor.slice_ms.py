"""Mean time per dispatch spent cutting the bucket's output into each
request's result (``serving.executor.slice_seconds`` histogram sum
over ``serving.batcher.batches``, whole window).
Nothing where the program records no device-wait span (it predates
the stage spans) or dispatched nothing."""

STAGE = "serving.executor.slice_seconds"
DEVICE_WAIT = "serving.batcher.device_wait_seconds"


def read(w):
    batches = w.counter("serving.batcher.batches")
    if not batches or not w.hist(DEVICE_WAIT)[0]:
        return None
    return 1e3 * w.hist(STAGE)[1] / batches
