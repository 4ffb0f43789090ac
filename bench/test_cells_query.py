"""CPU rehearsal of the query cells through the harness's own functions at
reduced sizes, and the faults that must turn ``correct`` false."""
import math

import jax
import numpy as np
import pytest

from bench import harness, rehearsal

QUERY_CELLS = ["resnet18-query-straggle", "resnet18-query-calm"]
SEED = 2 ** 33 + 4242            # seeds may exceed 32 bits


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearsal.make_root(tmp_path_factory.mktemp("bench"))


def _run(root, cell, trace=False, seconds=2.0, seed=SEED, precision=None):
    return harness.run_cell(root, cell, seed, seconds, trace,
                            devices=jax.devices(),
                            trace_dir=root / "trace" / cell,
                            precision=precision)


@pytest.mark.parametrize("cell", QUERY_CELLS)
def test_query_cell_end_to_end(root, cell):
    line, checked, run = _run(root, cell)
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    names = {m["name"] for m in harness.Cell.load(root, cell)
             .metrics("end_to_end")}
    assert set(line["metrics"]) == names == {"queries_per_s", "setup_s"}
    assert line["metrics"]["queries_per_s"]["value"] > 0
    notes = line["notes"]
    # warm-up reconstructed a query at each member position of a group,
    # so the window compiles nothing
    assert notes["warmup_reconstructed"] == [0, 1]
    assert notes["compiled_in_window"] == []
    assert checked["predictions_compared"] > 0
    assert list(line)[-1] == "compared"
    # the clients' queries form the window's groups: no warm-up query is
    # among them, and the first starts a group
    assert run.requests[0].index % 2 == 0
    assert all(r.t_submit >= run.w0 for r in run.requests)
    if cell.endswith("straggle"):
        assert notes["faults_injected"] > 0
        assert checked["reconstructed_compared"] > 0
        assert line["compared"]["reconstructed_compared"]["at_least"]
        assert "recon_logit_err" in line["compared"]
        assert notes["answers_slowed"] + notes["answers_unslowed"] > 0
    else:
        assert notes["faults_injected"] == 0
        assert "reconstructed_compared" not in line["compared"]
        assert "answers_slowed" not in notes


def test_query_cell_traced(root):
    line, _, _ = _run(root, "resnet18-query-straggle", trace=True)
    assert line["correct"] is True, line["compared"]
    got = line["metrics"]
    # a CPU run reports no device metric
    for name in ("query_mfu", "query_step_roofline", "device_idle_share.q"):
        assert name not in got
    assert got["compiles_in_window.q"]["value"] == 0
    assert 0 < got["recon_share.q"]["value"] <= 100
    assert 0 < got["query_p50_ms.q"]["value"] <= got["query_p99_ms.q"]["value"]


@pytest.mark.parametrize("cell", QUERY_CELLS)
def test_query_control_is_not_correct(root, cell, monkeypatch):
    """The control: the program served at the configuration's
    ``control_precision`` "high", three bfloat16 passes (on the CPU, which
    ignores the precision, the stand-in computes its convolutions so).  Its
    run comes out not correct, with every limit it is compared with failed;
    the same run at the configuration's own precision meets them."""
    assert harness.Cell.load(root, cell).cfg["correct"][
        "control_precision"] == "high"
    rehearsal.cpu_high_precision(monkeypatch)
    line, _, _ = _run(root, cell, seed=SEED + 1)
    assert line["correct"] is True, line["compared"]
    line, checked, _ = _run(root, cell, seed=SEED + 1, precision="high")
    assert line["correct"] is False
    assert line["failed"] == 0 and checked["predictions_compared"] > 0
    limits = {n: c for n, c in line["compared"].items()
              if not c.get("at_least")}
    assert limits and all(c["value"] > c["limit"]
                          for c in limits.values()), limits


def test_query_wrong_reconstruction_is_caught(root, monkeypatch):
    """A decode with a wrong coefficient on the available member's output:
    only reconstructed predictions change, and correct turns false."""
    from repro.core.scheme import LinearScheme
    real = LinearScheme.decode_one

    def decode_one(self, parity_out, outputs, missing_idx):
        return real(self, parity_out, 2 * np.asarray(outputs), missing_idx)
    monkeypatch.setattr(LinearScheme, "decode_one", decode_one)
    line, checked, _ = _run(root, "resnet18-query-straggle")
    assert checked["reconstructed_compared"] > 0
    assert line["correct"] is False
    assert line["compared"]["recon_logit_err"]["value"] > \
        line["compared"]["recon_logit_err"]["limit"]
    assert line["compared"]["member_logit_err"]["value"] <= \
        line["compared"]["member_logit_err"]["limit"]


def test_query_altered_answer_is_caught(root, monkeypatch):
    """Every other member answer altered where the instance produces it:
    its logits reversed."""
    from repro.serving import runtime
    real = runtime.Query.fulfill

    def fulfill(self, result, how, now=None):
        if how == "model" and self.qid % 2 == 0:
            result = np.asarray(result)[..., ::-1]
        real(self, result, how, now)
    monkeypatch.setattr(runtime.Query, "fulfill", fulfill)
    line, _, _ = _run(root, "resnet18-query-calm")
    assert line["correct"] is False
    assert line["compared"]["member_logit_err"]["value"] > \
        line["compared"]["member_logit_err"]["limit"]


# ------------------------------------------------------ by hand ---
def _fake_run(how, seed=7):
    """A window of queries completed as ``how`` lists them."""
    cell = harness.Cell.__new__(harness.Cell)
    run = harness.Run(cell, seed, "cpu", "cpu", w0=0.0, w1=10.0)
    for i, h in enumerate(how):
        run.requests.append(harness.QueryRecord(
            i, 1.0 + i / 100, t_done=2.0 + i / 100, completed_by=h,
            output=None if h in ("error", "flushed") else np.zeros((1, 3))))
    return run


def test_query_sample_holds_every_reconstruction():
    how = ["model"] * 40 + ["parity"] * 3 + ["model"] * 40
    members, recons = harness.query_sample(_fake_run(how), 10, 8)
    assert [r.index for r in recons] == [40, 41, 42]
    assert len(members) == 10 and all(r.completed_by == "model"
                                      for r in members)
    again, _ = harness.query_sample(_fake_run(how), 10, 8)
    other, _ = harness.query_sample(_fake_run(how, seed=8), 10, 8)
    assert [r.index for r in again] == [r.index for r in members]
    assert [r.index for r in other] != [r.index for r in members]
    # more reconstructions than the sample takes: a seeded subset
    _, some = harness.query_sample(_fake_run(["parity"] * 20), 10, 8)
    assert len(some) == 8


def test_forced_delay_withdrawn_when_parity_answers_first():
    """A forced query that a parity reconstruction answers before any
    member instance takes it: its member job is dropped at dequeue, so the
    warm-up stops waiting and withdraws the delay."""
    import threading
    from bench import faults
    plan = faults.FaultPlan({"kind": "none"}, [0, 1], seed=1, horizon_s=1)
    taken = plan.force_next([0, 1], 0.5)
    answered = threading.Event()
    answered.set()
    rec = harness.QueryRecord(0, 0.0, future=type(
        "Future", (), {"done": lambda self: answered.is_set()})())
    harness._await_force(taken, rec, plan)
    assert not taken.is_set() and plan.delay(0) == 0.0


def test_recon_by_slowing_by_hand():
    """Answers split by whether a member's fault window overlapped the
    query in flight: here the first two queries' times."""
    run = _fake_run(["parity", "parity", "model", "model", "parity",
                     "error"])
    run.requests[4].t_done = 10.5        # answered after the window
    plan = type("Plan", (), {"slowed": lambda self, iids, t0, t1:
                             iids == [0] and t0 < 1.015})()
    got = harness.recon_by_slowing(run, plan, [0])
    assert got == {"answers_slowed": 2, "recon_share_slowed": 100.0,
                   "answers_unslowed": 2, "recon_share_unslowed": 0.0}


def test_query_attempts_count_every_unanswered_query():
    run = _fake_run(["model", "parity", "default", "error", "flushed",
                     "model"])
    run.requests[3].error = "RuntimeError('instance died')"
    run.requests[2].output = None        # the default prediction
    run.requests[5].t_submit = 11.0      # sent after the window
    assert harness.query_attempts(run) == (5, 3)


def test_query_readers_by_hand():
    run = _fake_run(["model"] * 6 + ["parity"] * 2 + ["error"])
    run.requests[0].t_done = 10.5        # answered after the window
    read = lambda name: harness.reader(harness.ROOT, name)(run)
    assert read("queries_per_s") == pytest.approx(7 / 10)
    assert read("recon_share.q") == pytest.approx(100 * 2 / 7)
    # every latency in the window is 1000 ms
    assert read("query_p50_ms.q") == pytest.approx(1000.0)
    assert read("query_p99_ms.q") == pytest.approx(1000.0)
    assert math.isnan(harness.QueryRecord(0, 0.0).t_done)
