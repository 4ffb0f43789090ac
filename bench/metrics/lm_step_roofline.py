"""Share (%) of the device's busy time in the traced part of the window
that the required work would take at the roofline.  The work is every
member and parity decode step and every prefill of that part: a step of
an instance reads its weights once and the caches of its streams over
their occupied positions; a parity stream stands at the longest position
of its slot column; a wave's parity rebuild is one prefill per column at
its longest prompt.  Each piece costs the larger of FLOPs over peak and
bytes over bandwidth."""
from collections import defaultdict


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    m, cfg, dep = run.model, run.cfg, run.cfg["deployment"]
    slots = dep["slots"]
    inside = lambda t: run.tw0 <= t <= run.tw1
    steps = defaultdict(list)       # emission time -> [(member, slot, pos)]
    least = 0.0
    waves = defaultdict(list)
    for r in run.requests:
        waves[r.wave].append(r)
        member, slot, P = r.index // slots, r.index % slots, len(r.prompt)
        for j, t in enumerate(r.times[1:]):
            if not inside(t):
                continue
            if j == 0:
                least += run.least_time_s(*m.prefill_work(cfg, P))
            else:
                steps[t].append((member, slot, P + j - 1))
    for reqs in waves.values():
        if inside(max(r.times[1] for r in reqs if len(r.times) > 1)):
            cols = defaultdict(int)
            for r in reqs:
                cols[r.index % slots] = max(cols[r.index % slots],
                                            len(r.prompt))
            least += sum(run.least_time_s(*m.prefill_work(cfg, n))
                         for n in cols.values())
    for streams in steps.values():
        per_member = defaultdict(list)
        column = defaultdict(int)
        for member, slot, pos in streams:
            per_member[member].append(pos)
            column[slot] = max(column[slot], pos)
        for pos in per_member.values():
            least += run.least_time_s(*m.decode_work(cfg, pos))
        least += dep["r"] * run.least_time_s(
            *m.decode_work(cfg, list(column.values())))
    if not steps and not least:
        return None
    return 100.0 * least / run.trace.busy_s
