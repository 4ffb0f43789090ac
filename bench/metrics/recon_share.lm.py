"""Share (%) of decode steps served by parity reconstruction, over all
decode steps of the requests that finished in the window (the program's
per-request ``reconstructed_steps``)."""


def read(run):
    done = [r for r in run.requests
            if len(r.times) > 1 and run.w0 <= r.times[-1] <= run.w1]
    steps = sum(len(r.tokens) - 1 for r in done)
    if not steps:
        return None
    return 100.0 * sum(r.reconstructed for r in done) / steps
