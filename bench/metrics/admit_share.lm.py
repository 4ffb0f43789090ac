"""Share (%) of the window taken by admission passes (the scheduler's
``AdmitRecord``s: prefills of the requests admitted, parity columns
rebuilt, and the waits between), read from the program's recorder; a
pass that straddles an edge counts its part inside."""


def read(run):
    try:
        from repro.serving.tracing import RECORDER
    except ImportError:                 # a program without the recorder
        return None
    passes = RECORDER.window(run.w0, run.w1).admissions
    if not passes or run.seconds <= 0:
        return None
    inside = sum(min(a.t1, run.w1) - max(a.t0, run.w0) for a in passes)
    return 100.0 * inside / run.seconds
