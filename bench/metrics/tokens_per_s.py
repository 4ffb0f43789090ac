"""Output tokens emitted inside the window, over the window's length."""


def read(run):
    n = sum(1 for r in run.requests for t in r.times[1:]
            if run.w0 <= t <= run.w1)
    return n / run.seconds
