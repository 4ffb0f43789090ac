"""Share (%) of the decode steps that ended in the window in which a member
missed the deadline and no parity could cover it, so the step waited for
the straggler (the program's ``StepRecord.stalled``)."""


def read(run):
    try:
        from repro.serving.tracing import RECORDER
    except ImportError:                 # a program without the recorder
        return None
    steps = [r for r in RECORDER.window(run.w0, run.w1).steps
             if run.w0 <= r.t1 <= run.w1]
    if not steps:
        return None
    return 100.0 * sum(r.stalled for r in steps) / len(steps)
