"""CPU rehearsal of the LM cells through the harness's own functions at
reduced sizes, and the faults that must turn ``correct`` false."""
import time

import jax
import pytest

from bench import harness, rehearsal

LM_CELLS = ["olmo1b-batch-straggle", "olmo1b-batch-calm"]
SEED = 2 ** 33 + 12345           # seeds may exceed 32 bits


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearsal.make_root(tmp_path_factory.mktemp("bench"))


def _run(root, cell, trace=False, seconds=2.0, control=False):
    return harness.run_cell(root, cell, SEED, seconds, trace,
                            devices=jax.devices(),
                            trace_dir=root / "trace" / cell,
                            control=control)


@pytest.mark.parametrize("cell", LM_CELLS)
def test_lm_cell_end_to_end(root, cell):
    line, checked, run = _run(root, cell)
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    names = {m["name"] for m in harness.Cell.load(root, cell)
             .metrics("end_to_end")}
    assert set(line["metrics"]) == names
    assert line["metrics"]["setup_s"]["value"] > 0
    assert line["metrics"]["tokens_per_s"]["value"] > 0
    assert line["notes"]["split_waves"] == 0
    assert line["notes"]["compiled_in_window"] == []
    assert checked["tokens_compared"] > 0
    assert list(line)[-1] == "compared"
    if cell.endswith("straggle"):
        # the sample holds reconstructed tokens, and correct demands them
        assert checked["tokens_reconstructed"] > 0
        assert line["compared"]["reconstructed_compared"]["at_least"]
    else:
        assert "reconstructed_compared" not in line["compared"]


def test_lm_cell_traced(root):
    line, _, run = _run(root, "olmo1b-batch-straggle", trace=True)
    assert line["correct"] is True
    got = line["metrics"]
    # a CPU run reports no device metric
    assert "lm_mfu" not in got and "device_idle_share.lm" not in got
    assert got["compiles_in_window.lm"]["value"] == 0
    assert 0 <= got["recon_share.lm"]["value"] <= 100
    assert got["queue_wait_p90_ms.lm"]["value"] >= 0


@pytest.fixture
def fresh_substrate():
    """Serving programs traced anew, before and after the test."""
    from repro.serving import generation
    generation._transformer_substrate.cache_clear()
    yield
    generation._transformer_substrate.cache_clear()


def test_lm_state_unchanged_is_caught(root, monkeypatch, fresh_substrate):
    from repro.models import transformer as T
    real = T.decode_step

    def stale(cfg, params, cache, pos, **kw):
        logits, _ = real(cfg, params, cache, pos, **kw)
        return logits, cache                 # the step forgets its token
    monkeypatch.setattr(T, "decode_step", stale)
    line, _, _ = _run(root, "olmo1b-batch-calm")
    assert line["correct"] is False


def test_lm_altered_token_is_caught(root, monkeypatch):
    from repro.serving import generation
    real = generation.GenerationFuture._emit

    def emit(self, token, now, reconstructed):
        if len(self._tokens) == 2:           # the third token, as emitted
            token = (token + 1) % 256
        real(self, token, now, reconstructed)
    monkeypatch.setattr(generation.GenerationFuture, "_emit", emit)
    line, _, _ = _run(root, "olmo1b-batch-calm")
    assert line["correct"] is False


def test_lm_wrong_reconstruction_is_caught(root, monkeypatch):
    """A decode with a wrong coefficient on the available members' rows:
    only reconstructed tokens change, and correct turns false."""
    from repro.core.scheme import LinearScheme
    real = LinearScheme.decode

    def decode(self, parity_outs, outputs, missing_mask, parity_avail=None):
        return real(self, parity_outs, 2 * outputs, missing_mask,
                    parity_avail)
    monkeypatch.setattr(LinearScheme, "decode", decode)
    line, checked, _ = _run(root, "olmo1b-batch-straggle")
    assert checked["tokens_reconstructed"] > 0
    assert line["correct"] is False
    assert checked["recon_gap"] > line["compared"]["token_gap"]["limit"]


def _fake_run(recon):
    """A finished wave of 2 members x 2 slots whose requests carry the
    given reconstructed-step counts, in submission order."""
    cell = harness.Cell.__new__(harness.Cell)
    cell.cfg = {"deployment": {"slots": 2, "k": 2}}
    run = harness.Run(cell, 5, "cpu", "cpu")
    for i, n in enumerate(recon):
        run.requests.append(harness.LMRequest(
            0, i, [1] * (8 if i == 0 else 4), 4, 0.0, None,
            tokens=[1, 2, 3, 4], reconstructed=n))
    return run


@pytest.mark.parametrize("recon,want", [((0, 0, 0, 3), [0, 1]),
                                        ((0, 0, 0, 0), [0])])
def test_lm_columns_hold_longest_and_most_reconstructed(recon, want):
    run = _fake_run(recon)
    cols = harness.lm_columns(run, 1)
    # column s holds requests s (member 0) and 2 + s (member 1)
    assert [c[0].index for c in cols] == want


def test_control_is_not_correct(root):
    """The reference one precision below the configuration's, put in the
    program's place, fails a limit that the program's own run meets."""
    line, checked, _ = _run(root, "olmo1b-batch-straggle", control=True)
    assert line["correct"] is True
    control = checked["control"]
    limits = {c.name: c.limit for c in checked["checks"]}
    assert any(control[name] > limit for name, limit in limits.items())


def _run_cell_before_dispatch(root, cell_name, seed, seconds):
    """``run_cell`` as it was before the traffic mix's loop picked the
    driver: ``serve_lm`` and ``check_lm`` called by name, the requests not
    served in full counted as failed."""
    cell = harness.Cell.load(root, cell_name)
    devs = jax.devices()
    run = harness.Run(cell, seed, devs[0].device_kind, devs[0].platform)
    plan = harness.faults.load(cell.fault_path, cell.model.instances(cell.cfg),
                               seed, seconds + harness.FAULT_TAIL_SECONDS)
    log = harness.CompileLog()
    jax.monitoring.register_event_duration_secs_listener(log)
    try:
        harness.serve_lm(run, plan, seconds, None, time.monotonic())
        run.compiles = [e for e in log.events if run.w0 <= e[0] <= run.w1]
        run.notes["compiled_in_window"] = sorted({e[1] for e in run.compiles})
        run.notes["faults_injected"] = plan.injected
        run.notes["fault_windows"] = plan.n_windows
        checked = harness.check_lm(run)
    finally:
        jax.monitoring.unregister_event_duration_listener(log)
    return run, checked


def _before_attempts(run):
    reqs = [r for r in run.requests if r.t_submit < run.w1]
    return len(reqs), sum(1 for r in reqs
                          if r.error or len(r.tokens) != r.max_new)


def test_lm_dispatch_changes_nothing_it_reads(root):
    """The same seed through the driver picked by the mix's loop and
    through the LM driver called by name: every field that is not a
    timing agrees, and on one and the same run the line's metric values
    and compared numbers are those the LM path computes."""
    cell, seed = "olmo1b-batch-calm", SEED + 1
    old_run, old_checked = _run_cell_before_dispatch(root, cell, seed, 2.0)
    line, checked, run = harness.run_cell(root, cell, seed, 2.0, False,
                                          devices=jax.devices())
    # the same work: the first wave's prompts, lengths and (where no
    # step was reconstructed in either run) its tokens
    first = lambda r: [q for q in r.requests if q.wave == 0]
    for a, b in zip(first(old_run), first(run), strict=True):
        assert (a.prompt, a.max_new) == (b.prompt, b.max_new)
        if not a.reconstructed and not b.reconstructed:
            assert a.tokens == b.tokens
    assert line["correct"] is True and all(c.ok for c in old_checked["checks"])
    assert [(c.name, c.limit, c.at_least) for c in checked["checks"]] == \
        [(c.name, c.limit, c.at_least) for c in old_checked["checks"]]
    assert run.notes["compiled_in_window"] == \
        old_run.notes["compiled_in_window"] == []
    assert harness.attempts(old_run)[1] == _before_attempts(old_run)[1] == 0
    # one run, read both ways
    again = harness.check_lm(run)
    assert {c.name: c.value for c in again["checks"]} == \
        {n: v["value"] for n, v in line["compared"].items()}
    assert harness.attempts(run) == _before_attempts(run) == \
        (line["attempted"], line["failed"])
    for name, got in line["metrics"].items():
        if name != "setup_s":
            assert harness.reader(root, name)(run) == got["value"]
