"""Query rows per executor dispatch over the window (counters
``serving.execute.rows`` over ``serving.execute.calls``)."""


def read(w):
    calls = w.counter("serving.execute.calls")
    return w.counter("serving.execute.rows") / calls if calls else None
