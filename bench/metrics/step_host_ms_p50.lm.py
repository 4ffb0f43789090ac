"""Median, over the decode steps that ended in the window, of the host time
of a step: the summed ``lm.step.*`` spans of the scheduler (inputs, embed,
encode, reconstruct, emit), read from the program's recorder.  Waits on
the executors are not spans, so they are left out."""
from collections import defaultdict

import numpy as np


def read(run):
    try:
        from repro.serving.tracing import RECORDER
    except ImportError:                 # a program without the recorder
        return None
    w = RECORDER.window(run.w0, run.w1)
    host = defaultdict(float)
    for s in w.spans:
        if s.name.startswith("lm.step.") and "step" in s.ids:
            host[s.ids["step"]] += s.seconds
    ms = [1e3 * host[r.id] for r in w.steps if run.w0 <= r.t1 <= run.w1]
    return float(np.percentile(ms, 50)) if ms else None
