"""90th percentile of the executors' dispatch of a decode step's jitted
program (``lm.member.dispatch`` and ``lm.parity.dispatch`` spans of decode
jobs: the call up to its return, the enqueue) that ended in the window,
read from the program's recorder."""
import numpy as np

NAMES = ("lm.member.dispatch", "lm.parity.dispatch")


def read(run):
    try:
        from repro.serving.tracing import RECORDER
    except ImportError:                 # a program without the recorder
        return None
    ms = [1e3 * s.seconds for s in RECORDER.window(run.w0, run.w1).spans
          if s.name in NAMES and "step" in s.ids
          and run.w0 <= s.t1 <= run.w1]
    return float(np.percentile(ms, 90)) if ms else None
