"""Eager device programs the executor launched per dispatch beside its
compiled executable (counter ``serving.execute.eager_programs`` over
``serving.batcher.batches``, whole window). Nothing where the program
records no device-wait span (it predates the counter) or dispatched
nothing."""

DEVICE_WAIT = "serving.batcher.device_wait_seconds"


def read(w):
    batches = w.counter("serving.batcher.batches")
    if not batches or not w.hist(DEVICE_WAIT)[0]:
        return None
    return w.counter("serving.execute.eager_programs") / batches
