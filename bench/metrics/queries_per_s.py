"""Predictions returned to their callers inside the window (by a member
instance or by reconstruction), over the window's length."""


def read(run):
    n = sum(1 for r in run.requests if r.output is not None
            and r.completed_by in ("model", "parity")
            and run.w0 <= r.t_done <= run.w1)
    return n / run.seconds
