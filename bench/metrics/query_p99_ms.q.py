"""99th percentile, over queries answered in the window, of submission to
the caller holding its prediction.  Recorded, not judged: the tail is set
by the queries that meet a slowed instance, or a stall of the host."""
import numpy as np


def read(run):
    ms = [1e3 * (r.t_done - r.t_submit) for r in run.requests
          if r.output is not None and run.w0 <= r.t_done <= run.w1]
    return float(np.percentile(ms, 99)) if ms else None
