"""Mean time a request waited in the batcher's queue before its
micro-batch was assembled (``QUEUE_WAIT`` histogram, sum over count,
whole window)."""


def read(w):
    from raft_tpu.serving import metrics

    return w.hist_mean_ms(metrics.QUEUE_WAIT)
