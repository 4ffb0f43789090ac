"""90th percentile of submission to admission, over requests admitted in
the window (the program stamps admission as the first entry of a
request's times)."""
import numpy as np


def read(run):
    waits = [1e3 * (r.times[0] - r.t_submit) for r in run.requests
             if r.times and run.w0 <= r.times[0] <= run.w1]
    return float(np.percentile(waits, 90)) if waits else None
