"""The program's own spans and records as the benchmark reads them: named
in a profiler trace beside the named device programs, naming the device
gaps they cover, and turned into the per-layer metrics that read the
program's recorder."""
import sys

import jax
import pytest

from bench import harness, rehearsal, trace
from bench.harness import ROOT

LM_CELLS = ["olmo1b-batch-straggle", "olmo1b-batch-calm"]
SEED = 2 ** 33 + 777
RECORDER_METRICS = ["step_host_ms_p50.lm", "dispatch_ms_p90.lm",
                    "admit_share.lm", "stalled_step_share.lm"]
HOST_WORK = {"lm.step.inputs", "lm.step.embed", "lm.step.encode",
             "lm.step.emit", "lm.member.dispatch", "lm.member.fetch",
             "lm.parity.dispatch", "lm.parity.fetch", "lm.admit.request",
             "lm.admit.rebuild", "lm.slot_write"}
PROGRAMS = {f"PjitFunction({p})" for p in
            ("member_decode", "parity_decode", "member_prefill",
             "parity_prefill")}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced CPU-sized run of each LM cell: {cell: (line, trace dir)}."""
    root = rehearsal.make_root(tmp_path_factory.mktemp("spans"))
    out = {}
    for cell in LM_CELLS:
        line, _, _ = harness.run_cell(root, cell, SEED, 2.0, True,
                                      devices=jax.devices(),
                                      trace_dir=root / "trace" / cell)
        out[cell] = (line, root / "trace" / cell)
    return out


def _python_events(trace_dir):
    """Every event of the trace's Python threads, the harness's marks
    joined into spans as the reduction joins them."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(trace.find_xplane(trace_dir)))
    host = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                if ln.name == "python":
                    host.extend(trace._spans(trace._events(ln)))
    return host


@pytest.mark.parametrize("cell", LM_CELLS)
def test_traced_line_reads_the_recorder(traced, cell):
    line, _ = traced[cell]
    assert line["correct"] is True, line["compared"]
    got = line["metrics"]
    want = set(RECORDER_METRICS) - ({"stalled_step_share.lm"}
                                    if cell.endswith("calm") else set())
    assert want <= set(got)
    if cell.endswith("calm"):
        assert "stalled_step_share.lm" not in got
    else:
        assert 0 <= got["stalled_step_share.lm"]["value"] <= 100
    assert got["step_host_ms_p50.lm"]["value"] > 0
    assert got["dispatch_ms_p90.lm"]["value"] > 0
    assert 0 < got["admit_share.lm"]["value"] <= 100


def test_trace_names_host_work_and_device_programs(traced):
    _, trace_dir = traced["olmo1b-batch-straggle"]
    host = _python_events(trace_dir)
    names = {n for n, _, _ in host}
    assert HOST_WORK <= names
    assert PROGRAMS <= names
    assert "PjitFunction(reconstruct)" in names
    assert not any("<lambda>" in n for n in names)
    # a device gap inside a decode step's embedding pull is put down to a
    # program span, not to the JAX calls nested in it
    n, s, e = max((ev for ev in host if ev[0] == "lm.step.embed"),
                  key=lambda ev: ev[2] - ev[1])
    label = trace._host_label(host, s + 1, e - 1)
    assert label.split(": ", 1)[1].startswith("lm."), label


def test_host_label_prefers_the_covering_program_span():
    host = [("bench.wait", 0, 100), ("lm.step.encode", 10, 40),
            ("PjitFunction(parity_decode)", 12, 20),
            ("CommonPjRtBuffer::Await", 20, 39), ("lm.member.fetch", 38, 90)]
    assert trace._host_label(host, 15, 35) == "bench.wait: lm.step.encode"
    assert trace._host_label(host, 50, 60) == "bench.wait: lm.member.fetch"


# ----------------------------------------------- readers, by hand ---
def _run(w0=100.0, w1=110.0):
    cell = harness.Cell.__new__(harness.Cell)
    return harness.Run(cell, 1, "cpu", "cpu", w0=w0, w1=w1)


@pytest.fixture
def recorder(monkeypatch):
    """A fresh recorder in the program's place, filled by hand."""
    from repro.serving import tracing
    rec = tracing.Recorder()
    monkeypatch.setattr(tracing, "RECORDER", rec)

    def span(name, t0, t1, **ids):
        rec.append(tracing.Span(name, "t", t0, t1, ids))
    return rec, span, tracing


def _read(name, run):
    return harness.reader(ROOT, name)(run)


def test_step_host_ms_sums_a_steps_scheduler_spans(recorder):
    rec, span, tracing = recorder
    # steps 1-3 end in the window with 3, 5 and 7 ms of scheduler spans;
    # step 0 ends before it
    for sid, (t0, ms) in enumerate([(98.0, 90.0), (101.0, 3.0),
                                    (102.0, 5.0), (103.0, 7.0)]):
        rec.append(tracing.StepRecord(sid, t0, t0 + 0.5))
        span("lm.step.inputs", t0, t0 + 1e-3, step=sid)
        span("lm.step.emit", t0 + 0.1, t0 + 0.1 + (ms - 1) / 1e3, step=sid)
        # executor spans of the step are not the scheduler's host time
        span("lm.member.dispatch", t0, t0 + 0.2, step=sid, member=0)
    assert _read("step_host_ms_p50.lm", _run()) == pytest.approx(5.0)


def test_dispatch_ms_reads_decode_dispatches_only(recorder):
    rec, span, _ = recorder
    for ms in range(1, 11):
        name = "lm.member.dispatch" if ms % 2 else "lm.parity.dispatch"
        span(name, 101.0, 101.0 + ms / 1e3, step=ms, member=0)
        span("lm.member.fetch", 102.0, 102.5, step=ms, member=0)
    span("lm.member.dispatch", 103.0, 103.5, rid=0, admit=1)   # a prefill
    span("lm.member.dispatch", 98.0, 99.0, step=0, member=0)   # before
    assert _read("dispatch_ms_p90.lm", _run()) == pytest.approx(9.1)


def test_admit_share_counts_the_part_inside_the_window(recorder):
    rec, _, tracing = recorder
    rec.append(tracing.AdmitRecord(0, 99.0, 101.0, admitted=16))
    rec.append(tracing.AdmitRecord(1, 105.0, 106.5, rebuilt=8))
    rec.append(tracing.AdmitRecord(2, 111.0, 112.0, admitted=16))
    assert _read("admit_share.lm", _run()) == pytest.approx(25.0)


def test_stalled_step_share(recorder):
    rec, _, tracing = recorder
    rec.append(tracing.StepRecord(0, 98.0, 99.0, stalled=True))
    for sid in range(1, 5):
        rec.append(tracing.StepRecord(sid, 100.0 + sid, 100.5 + sid,
                                      missed=(0, 1) if sid == 2 else (),
                                      stalled=sid == 2))
    assert _read("stalled_step_share.lm", _run()) == pytest.approx(25.0)


@pytest.mark.parametrize("name", RECORDER_METRICS)
def test_reader_finds_nothing_quietly(recorder, monkeypatch, name):
    """An empty window, and a program without the recorder (the parent of
    the change that brought it), read as no value, not as an error."""
    assert _read(name, _run()) is None
    monkeypatch.setitem(sys.modules, "repro.serving.tracing", None)
    assert _read(name, _run()) is None
