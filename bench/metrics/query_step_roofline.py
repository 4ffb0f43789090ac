"""Share (%) of the device's busy time in the traced part of the window
that its forwards would take at the roofline: every execution of the
configuration's forward programs (members' and parity's alike) that began
in the traced part, each at the larger of its FLOPs over peak and its bytes
(every weight read once, the input and the logits) over bandwidth."""


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    n = sum(run.trace.modules.get(p, 0) for p in run.model.FORWARD_PROGRAMS)
    if not n:
        return None
    least = run.least_time_s(*run.model.forward_work(run.cfg))
    return 100.0 * n * least / run.trace.busy_s
