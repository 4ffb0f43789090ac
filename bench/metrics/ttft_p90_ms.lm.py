"""90th percentile, over requests whose first token falls in the window,
of submission to first token.  Recorded, not judged: a window holds five
to seven waves of 16, so one wave whose admission stalls (behind a slowed
member's backlog, or a stall of the host) moves the percentile."""
import numpy as np


def read(run):
    waits = [1e3 * (r.times[1] - r.t_submit) for r in run.requests
             if len(r.times) > 1 and run.w0 <= r.times[1] <= run.w1]
    return float(np.percentile(waits, 90)) if waits else None
