"""Share (%) of the chip's bf16 peak that the useful work of the window
needs: every member prefill that finished in the window and every token
decoded in it, at the configuration's FLOPs per token for its context
(parity work is redundancy, not counted), over window x peak."""


def read(run):
    peaks = run.peaks()
    if peaks is None:
        return None
    flops = 0.0
    for r in run.requests:
        P = len(r.prompt)
        for j, t in enumerate(r.times[1:]):
            if not run.w0 <= t <= run.w1:
                continue
            if j == 0:
                flops += run.model.prefill_work(run.cfg, P)[0]
            else:
                flops += run.model.token_flops(run.cfg, P + j - 1)
    return 100.0 * flops / (run.seconds * peaks["bf16_flops_per_s"])
