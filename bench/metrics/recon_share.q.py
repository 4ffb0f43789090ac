"""Share (%) of the predictions returned in the window that the frontend
reconstructed from a parity output (the program's own ``completed_by``
of each future)."""


def read(run):
    done = [r for r in run.requests if r.output is not None
            and run.w0 <= r.t_done <= run.w1]
    if not done:
        return None
    return 100.0 * sum(r.completed_by == "parity" for r in done) / len(done)
