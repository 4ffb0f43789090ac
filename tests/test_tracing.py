"""Spans and records of coded generation (serving/tracing.py) as the threads
engine writes them: bounded, linked by step and request ids, and on the
clock of the futures' token stamps."""
import threading
import time

import numpy as np
import pytest

from repro.serving.api import BatchingPolicy, deploy_lm
from repro.serving.scenarios import instance_id
from repro.serving.tracing import (RECORDER, AdmitRecord, Recorder, Span,
                                   StepRecord)
from test_generation import _linear_substrate, _prompts, _spec


def test_recorder_is_bounded_and_reads_by_time():
    rec = Recorder(spans=3, records=2)
    for t in range(5):
        with rec.span("lm.step.emit", step=t):
            pass
        rec.append(StepRecord(rec.next_id(), float(t), float(t) + 0.5))
        rec.append(AdmitRecord(rec.next_id(), float(t), float(t) + 0.1))
    every = rec.window(float("-inf"), float("inf"))
    assert [s.ids["step"] for s in every.spans] == [2, 3, 4]
    assert [s.t0 for s in every.steps] == [3.0, 4.0]
    assert len(every.admissions) == 2
    # what meets the window: steps [3, 3.5] and [4, 4.5] against [3.2, 3.9]
    assert [s.t0 for s in rec.window(3.2, 3.9).steps] == [3.0]
    assert rec.window(10.0, 11.0).steps == []


def test_span_is_timed_on_the_monotonic_clock_in_its_thread():
    rec = Recorder()
    before = time.monotonic()

    def work():
        with rec.span("lm.member.fetch", step=7, member=1):
            time.sleep(0.01)
    t = threading.Thread(target=work, name="lm-member-1")
    t.start()
    t.join(5.0)
    assert not t.is_alive()
    [s] = rec.window(before, time.monotonic()).spans
    assert isinstance(s, Span)
    assert (s.name, s.thread, s.ids) == ("lm.member.fetch", "lm-member-1",
                                         {"step": 7, "member": 1})
    assert before <= s.t0 and s.seconds >= 0.01
    assert s.t1 <= time.monotonic()


def _serve(spec, prompts):
    t0 = time.monotonic()
    with deploy_lm(spec, engine="threads") as sess:
        futs = [sess.submit(p) for p in prompts]
        assert sess.wait_all(60.0)
    return futs, RECORDER.window(t0, time.monotonic())


def test_records_link_tokens_steps_and_requests():
    params, fns = _linear_substrate(seed=1)
    futs, w = _serve(_spec(params, fns), _prompts(6, seed=5))
    steps = {r.id: r for r in w.steps}
    for f in futs:
        assert f.submitted_at <= f.admitted_at <= f.first_token_at
        assert f.admitted_at == f._times[0] and f.first_token_at == f._times[1]
        ids = f.token_steps
        assert len(ids) == len(f.result()) == 5 and ids[0] is None
        for sid, t in zip(ids[1:], f._times[2:]):
            # a token is stamped inside the step that emitted it
            assert steps[sid].t0 <= t <= steps[sid].t1
        # the request's admission spans carry its rid
        mine = [s for s in w.spans if s.ids.get("rid") == f.rid
                and s.t0 >= f.submitted_at]
        assert {"lm.admit.request", "lm.member.dispatch",
                "lm.slot_write", "lm.member.fetch"} <= {s.name for s in mine}
    for s in w.spans:
        sid = s.ids.get("step")
        if sid is None or sid not in steps:
            continue
        if s.name.startswith("lm.step."):
            # a step's scheduler spans fall inside its record
            assert s.thread == "lm-scheduler"
            assert steps[sid].t0 <= s.t0 <= s.t1 <= steps[sid].t1
        else:
            assert s.thread.startswith(("lm-member-", "lm-parity-"))
    used = {sid for f in futs for sid in f.token_steps[1:]}
    names = {s.name for s in w.spans if s.ids.get("step") in used}
    assert {"lm.step.inputs", "lm.step.embed", "lm.step.encode",
            "lm.step.emit", "lm.member.dispatch", "lm.member.fetch",
            "lm.parity.dispatch", "lm.parity.fetch"} <= names
    for sid in used:
        r = steps[sid]
        assert 1 <= r.active <= 4 and r.wait_s >= 0
        assert not r.stalled and r.missed == ()
    assert sum(a.admitted for a in w.admissions) == len(futs)
    assert all(a.t0 <= a.t1 and a.wait_s >= 0 for a in w.admissions)
    assert any(a.rebuilt for a in w.admissions)


def test_irrecoverable_step_is_marked_stalled():
    """One request on member 0 of k=2, r=1: both members late in decode
    step 2 is more than one parity covers, so that step, and only that
    one, waits for the stragglers; member 0 alone late in step 3 is
    reconstructed."""
    params, fns = _linear_substrate(seed=2)
    late = 0.6
    members = [instance_id("main", 0), instance_id("main", 1)]
    # member 0's first job is the prefill; member 1 decodes from step 1
    plan = {members[0]: {3: late, 4: late}, members[1]: {2: late}}
    calls = {iid: 0 for iid in members}

    def delay(iid):
        if iid not in calls:
            return 0.0
        calls[iid] += 1
        return plan[iid].get(calls[iid], 0.0)

    spec = _spec(params, fns, batching=BatchingPolicy(max_size=1),
                 straggle_ms=150.0, delay_fn=delay)
    [fut], w = _serve(spec, _prompts(1, seed=9))
    steps = {r.id: r for r in w.steps}
    mine = [steps[sid] for sid in fut.token_steps[1:]]
    assert len(mine) == 4
    assert [r.stalled for r in mine].count(True) == 1
    stalled = mine[1]
    assert stalled.stalled and stalled.missed == (0, 1)
    assert stalled.reconstructed == () and stalled.wait_s >= late / 2
    assert mine[2].missed == (0,) and mine[2].reconstructed == (0,)
    assert not mine[2].stalled
    assert fut.reconstructed_steps >= 1
    # the injected delays are spans of the executors, tied to their step
    faults = [s for s in w.spans if s.name == "lm.fault_delay"
              and s.ids.get("step") == stalled.id]
    assert sorted(s.ids["member"] for s in faults) == [0, 1]
    assert all(s.seconds >= late * 0.9 for s in faults)


@pytest.mark.parametrize("n", [1, 3])
def test_stats_count_decode_gaps_and_every_token(n):
    params, fns = _linear_substrate(seed=3)
    with deploy_lm(_spec(params, fns), engine="threads") as sess:
        futs = [sess.submit(p) for p in _prompts(n, seed=4)]
        assert sess.wait_all(60.0)
        report = sess.stats()
    gaps = [g for f in futs for g in f.inter_token_ms[1:]]
    assert report.n == len(gaps) == n * 4
    assert report.completed_by == {"model": n * 4}
    assert report.median_ms == pytest.approx(float(np.percentile(gaps, 50)))
    span = max(f._times[-1] for f in futs) - min(f._times[0] for f in futs)
    assert report.tokens_per_s == pytest.approx(n * 5 / span)
