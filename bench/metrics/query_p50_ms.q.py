"""Median, over queries answered in the window, of submission to the
caller holding its prediction.  Recorded, not judged: in a closed loop the
latency is the clients' count over the rate, so it moves with
``queries_per_s``."""
import numpy as np


def read(run):
    ms = [1e3 * (r.t_done - r.t_submit) for r in run.requests
          if r.output is not None and run.w0 <= r.t_done <= run.w1]
    return float(np.percentile(ms, 50)) if ms else None
