"""99th percentile of every inter-token gap, from token 1 on (token 0 comes
from prefill: its wait is time to first token), whose later token falls in
the window.  Recorded, not judged: the steps that stall every stream at
once (under stragglers, the steps no parity can rescue; in calm runs,
stalls of the host) number about one in a hundred, so the percentile falls
either among them or among ordinary steps, from run to run."""
import numpy as np


def read(run):
    gaps = [1e3 * (b - a) for r in run.requests
            for a, b in zip(r.times[1:], r.times[2:])
            if run.w0 <= b <= run.w1]
    return float(np.percentile(gaps, 99)) if gaps else None
