"""Reduce a profiler trace (``.xplane.pb``) to device busy time, idle share,
the device operations that took most time, and the longest idle gaps named
by what the host was doing.

The traced window is bounded by two host marks that the harness writes
(``BEGIN`` and ``END``, zero-length ``TraceAnnotation`` spans), so device and
host events are read on the trace's own clock.  A device's busy time is the
union of the intervals of its ``XLA Ops`` events inside the window;
asynchronous copies (``Async XLA Ops``) overlap compute and are not counted.
"""
from __future__ import annotations

import glob
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

BEGIN, END = "bench.trace_begin", "bench.trace_end"
SPAN_PREFIX, SPAN_END = "bench.", "/end"


@dataclass
class TraceSummary:
    busy_s: float                       # mean over devices
    window_s: float
    t0_ns: float
    device_ops: List[Tuple[str, float]] = field(default_factory=list)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)
    n_devices: int = 0
    # executions of each device program that started in the window, over
    # all devices, by program name ("jit_resnet_fwd")
    modules: Dict[str, int] = field(default_factory=dict)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(log_dir) -> Path:
    files = sorted(glob.glob(str(Path(log_dir) / "plugins" / "profile" / "*"
                                 / "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return Path(files[-1])


def union(intervals):
    """Merged, sorted [(start, end)] of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _op_name(hlo_text: str) -> str:
    """'%fusion.12 = bf16[...] fusion(...)' -> 'fusion.12'."""
    head = hlo_text.split(" = ", 1)[0]
    return head.lstrip("%")


def _self_times(events):
    """[(name, start, self time)]: an op that encloses others (a loop
    around its body) keeps only the time no nested op covers."""
    out, stack = [], []           # stack: [index in out, end]
    for n, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            parent = out[stack[-1][0]]
            parent[2] -= min(e, stack[-1][1]) - s
        out.append([n, s, e - s])
        stack.append((len(out) - 1, e))
    return [(n, s, max(t, 0.0)) for n, s, t in out]


def _spans(events, end=None):
    """One thread's events, with the harness's span marks (``name`` at
    the start, ``name/end`` at the end) joined into spans; a span still
    open when the trace stops runs to ``end`` (the ``END`` mark), or,
    without one, to the thread's last event."""
    out, open_ = [], {}
    last = max((e for _, _, e in events), default=0.0) if end is None \
        else end
    for n, s, e in sorted(events, key=lambda x: x[1]):
        if not n.startswith(SPAN_PREFIX) or n in (BEGIN, END):
            out.append((n, s, e))
        elif n.endswith(SPAN_END):
            name = n[:-len(SPAN_END)]
            if name in open_:
                out.append((name, open_.pop(name), s))
        else:
            open_[n] = s
    out.extend((n, s, max(s, last)) for n, s in open_.items())
    return out


def _program(module_name: str) -> str:
    """A device program's name without the fingerprint that the trace
    appends: 'jit_resnet_fwd(1234)' or 'jit_resnet_fwd_1234_' ->
    'jit_resnet_fwd'."""
    return re.sub(r"(\(\d+\)|_\d+_)$", "", module_name)


def _events(line):
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def read(path):
    """(host threads, devices) of one ``.xplane.pb``: each host thread a
    list of (name, start, end) events, each device (plane name, its
    ``XLA Ops`` events, its ``XLA Modules`` events), times in ns."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    threads, devices = [], []
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith("/device:") and "XLA Ops" in lines:
            devices.append((plane.name, _events(lines["XLA Ops"]),
                            _events(lines["XLA Modules"])
                            if "XLA Modules" in lines else []))
        elif plane.name.startswith("/host:"):
            threads.extend([ev for ev in _events(ln)
                            if not ev[0].startswith("$")]
                           for ln in plane.lines)
    return threads, devices


def reduce(path, top: int = 10) -> Optional[TraceSummary]:
    """Read one ``.xplane.pb`` and reduce it; see the module docstring.
    None when the trace holds no device plane (a CPU run)."""
    return summarize(*read(path), top=top)


def summarize(threads, devices, top: int = 10) -> Optional[TraceSummary]:
    """The reduction of what ``read`` returns."""
    if not devices:
        return None
    marks = {n: s for evs in threads for n, s, _ in evs if n in (BEGIN, END)}
    host = [sp for evs in threads for sp in _spans(evs, marks.get(END))]
    if BEGIN in marks and END in marks:
        t0, t1 = marks[BEGIN], marks[END]
    else:
        spans = [(s, e) for _, ops, _ in devices for _, s, e in ops]
        t0, t1 = min(s for s, _ in spans), max(e for _, e in spans)
    window = (t1 - t0) / 1e9
    if window <= 0:
        raise ValueError(f"{path}: empty traced window")

    busy, per_op, runs = [], {}, {}
    gaps0 = None
    for _, ops, modules in devices:
        for n, s, _ in modules:
            if t0 <= s < t1:
                runs[_program(n)] = runs.get(_program(n), 0) + 1
        inside = [(n, max(s, t0), min(e, t1)) for n, s, e in ops
                  if e > t0 and s < t1]
        merged = union((s, e) for _, s, e in inside)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        mods = sorted((s, e, n) for n, s, e in modules)
        j = 0
        for n, s, t in _self_times(inside):
            while j < len(mods) and mods[j][1] < s:
                j += 1
            mod = mods[j][2] if j < len(mods) and mods[j][0] <= s else "?"
            key = f"{mod}/{_op_name(n)}"
            per_op[key] = per_op.get(key, 0.0) + t / 1e9
        if gaps0 is None:
            edges = [t0] + [x for iv in merged for x in iv] + [t1]
            gaps0 = [(edges[i], edges[i + 1])
                     for i in range(0, len(edges), 2)
                     if edges[i + 1] > edges[i]]
    device_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    longest = sorted(gaps0, key=lambda g: g[0] - g[1])[:top]
    idle = [(_host_label(host, s, e), (e - s) / 1e9) for s, e in longest]
    return TraceSummary(busy_s=sum(busy) / len(busy), window_s=window,
                        t0_ns=t0, device_ops=device_ops, idle_gaps=idle,
                        n_devices=len(devices), modules=runs)


def _host_label(host, s, e):
    """What the host was doing during the device gap [s, e]: the harness
    span that overlaps it most, and the other host event that does."""
    best = {True: ("", 0.0), False: ("", 0.0)}
    for n, hs, he in host:
        ov = min(e, he) - max(s, hs)
        if ov <= 0 or n in (BEGIN, END):
            continue
        mine = n.startswith(SPAN_PREFIX)
        if ov > best[mine][1]:
            best[mine] = (n, ov)
    span = best[True][0] or "outside harness spans"
    call = best[False][0] or "no traced host call"
    return f"{span}: {call}"
