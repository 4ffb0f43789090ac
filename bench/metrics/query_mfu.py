"""Share (%) of the chip's bf16 peak that the useful work of the window
needs: one member forward's FLOPs (the configuration's count) for every
prediction returned in the window, however it was produced (parity work is
redundancy, not counted), over window x peak."""


def read(run):
    peaks = run.peaks()
    if peaks is None:
        return None
    n = sum(1 for r in run.requests if r.output is not None
            and run.w0 <= r.t_done <= run.w1)
    flops = n * run.model.forward_work(run.cfg)[0]
    return 100.0 * flops / (run.seconds * peaks["bf16_flops_per_s"])
