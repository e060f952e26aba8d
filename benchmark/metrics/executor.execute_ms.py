"""Mean time of one dispatch as the batcher sees it: executor call,
device work and ``block_until_ready`` (``EXECUTE`` histogram, sum over
count, whole window)."""


def read(w):
    from raft_tpu.serving import metrics

    return w.hist_mean_ms(metrics.EXECUTE)
