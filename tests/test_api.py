"""The declarative serving surface (repro.serving.api): DeploymentSpec
validation, deploy() engine selection, PredictionFuture semantics, the
typed ServingReport, and the legacy-constructor shims."""
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.serving.api import (BatchingPolicy, DeploymentSpec, SimSession,
                               Trace, deploy)
from repro.serving.report import ServingReport
from repro.serving.runtime import ParMFrontend


def _linear_fwd(p, x):
    return x @ p


def _spec(**kw):
    rng = np.random.default_rng(0)
    W = jnp.asarray(rng.normal(size=(8, 5)).astype(np.float32))
    base = dict(fwd=_linear_fwd, params=W, parity_params=W, strategy="parm",
                k=2, m=2)
    base.update(kw)
    return DeploymentSpec(**base)


# ------------------------------------------------------------ validation ----
def test_spec_is_frozen_and_replace_copies():
    spec = _spec()
    with pytest.raises(AttributeError):
        spec.m = 12
    spec2 = spec.replace(m=12, batching=BatchingPolicy(max_size=4))
    assert spec2.m == 12 and spec2.batching.max_size == 4
    assert spec.m == 2 and spec.batching.max_size == 1    # original untouched


def test_spec_rejects_bad_values():
    with pytest.raises(ValueError, match="k and m"):
        _spec(k=0)
    with pytest.raises(TypeError, match="BatchingPolicy"):
        _spec(batching=4)
    with pytest.raises(ValueError, match="max_size"):
        BatchingPolicy(max_size=0)
    with pytest.raises(ValueError, match="max_delay_ms"):
        BatchingPolicy(max_delay_ms=-1.0)


def test_deploy_rejects_unknown_engine_and_non_spec():
    with pytest.raises(ValueError, match="unknown engine"):
        deploy(_spec(), engine="cloud")
    with pytest.raises(TypeError, match="DeploymentSpec"):
        deploy({"strategy": "parm"})


def test_threads_engine_requires_model():
    with pytest.raises(ValueError, match="fwd= and params="):
        deploy(DeploymentSpec(strategy="parm"), engine="threads")
    # ... but the sim engine deliberately does not
    rep = deploy(DeploymentSpec(strategy="parm", k=2, m=4),
                 engine="sim").replay(Trace(n_queries=200, qps=200, seed=0))
    assert rep["n"] == 200


# ------------------------------------------------------- threads session ----
def test_threads_session_submit_futures_and_context_manager():
    rng = np.random.default_rng(0)
    spec = _spec()
    with deploy(spec) as sess:
        assert sess.engine == "threads"
        xs = [rng.normal(size=(1, 8)).astype(np.float32) for _ in range(4)]
        futs = [sess.submit(x) for x in xs]
        assert [f.qid for f in futs] == [0, 1, 2, 3]     # auto-assigned qids
        for f, x in zip(futs, xs):
            np.testing.assert_allclose(
                f.result(timeout=10.0),
                np.asarray(_linear_fwd(spec.params, x)), atol=1e-4)
            assert f.done() and f.completed_by in ("model", "parity")
            assert f.latency_ms > 0
        rep = sess.stats()
        assert isinstance(rep, ServingReport) and rep.engine == "threads"
        workers = sess.frontend.workers
    # the with-block shut the session down: every worker retired
    assert all(not w.is_alive() for w in workers)


def test_future_result_timeout_raises():
    spec = _spec(strategy="none", m=1,
                 delay_fn=lambda i: 0.5)         # the lone worker is stuck
    with deploy(spec) as sess:
        fut = sess.submit(np.ones((1, 8), np.float32))
        assert not fut.done()
        with pytest.raises(TimeoutError, match="unanswered"):
            fut.result(timeout=0.05)
        np.testing.assert_allclose(
            fut.result(timeout=10.0),
            np.asarray(_linear_fwd(spec.params, np.ones((1, 8)))), atol=1e-3)


def test_future_deadline_state_with_slo():
    default = np.zeros((1, 5), np.float32)
    spec = _spec(strategy="default_slo", m=1, slo_ms=50.0,
                 default_prediction=default, delay_fn=lambda i: 0.4)
    with deploy(spec) as sess:
        fut = sess.submit(np.ones((1, 8), np.float32))
        assert fut.deadline_exceeded is False            # still pending
        res = fut.result(timeout=5.0)
        np.testing.assert_allclose(res, default)
        assert fut.completed_by == "default"
        assert fut.deadline_exceeded is True


def test_future_deadline_not_exceeded_for_fast_query():
    spec = _spec(strategy="none", slo_ms=5000.0)
    with deploy(spec) as sess:
        fut = sess.submit(np.ones((1, 8), np.float32))
        fut.result(timeout=10.0)
        assert fut.deadline_exceeded is False


# ----------------------------------------------------------- sim session ----
def test_sim_session_replay_and_stats():
    spec = DeploymentSpec(strategy="parm", k=2, m=12)
    sess = deploy(spec, engine="sim")
    assert isinstance(sess, SimSession)
    with pytest.raises(RuntimeError, match="no replay has run"):
        sess.stats()
    with pytest.raises(RuntimeError, match="trace-driven"):
        sess.submit(np.ones((1, 8)))
    rep = sess.replay(Trace(n_queries=2000, qps=270, seed=1))
    assert rep is sess.stats()
    assert rep.engine == "sim" and rep.strategy == "parm"
    assert rep.n == 2000 and rep.reconstructions > 0
    # keyword overrides patch the trace for one-off replays
    rep2 = sess.replay(Trace(n_queries=2000, qps=270, seed=1), qps=150)
    assert rep2.median_ms <= rep.median_ms


def test_sim_session_consumes_spec_knobs():
    """m/k/r, slo and the batching policy must reach the SimConfig."""
    spec = DeploymentSpec(strategy="parm", k=2, r=2, m=12,
                          batching=BatchingPolicy(max_size=4))
    rep = deploy(spec, engine="sim").replay(
        Trace(n_queries=2000, qps=520, seed=1))
    assert rep.mean_batch_size > 1.0            # overload formed batches
    slo_spec = DeploymentSpec(strategy="default_slo", k=2, m=2, slo_ms=40.0)
    rep = deploy(slo_spec, engine="sim").replay(
        Trace(n_queries=2000, qps=400, seed=1))
    assert rep.completed_by.get("default", 0) > 0
    assert rep.max_ms <= 40.0 + 1e-6            # every late answer defaulted


# -------------------------------------------------------------- report ------
def test_report_mapping_protocol():
    rep = ServingReport(engine="sim", strategy="parm", n=3,
                        completed_by={"model": 3})
    assert rep["strategy"] == "parm" and rep["n"] == 3
    assert "p999_ms" in rep and "nope" not in rep
    with pytest.raises(KeyError):
        rep["nope"]
    assert set(rep) >= {"engine", "strategy", "cancelled_queries",
                        "mean_batch_size"}
    assert len(rep) == len(list(rep))
    assert dict(rep)["completed_by"] == {"model": 3}
    assert rep.cancellations == 0
    assert "parm" in rep.summary()


def test_report_equality_is_field_wise():
    a = ServingReport(engine="sim", strategy="parm", n=1)
    b = ServingReport(engine="sim", strategy="parm", n=1)
    assert a == b
    assert a != ServingReport(engine="threads", strategy="parm", n=1)


# ------------------------------------------------------------ legacy shims --
def test_frontend_legacy_kwargs_fold_into_spec():
    W = jnp.ones((4, 3), jnp.float32)
    fe = ParMFrontend(_linear_fwd, W, parity_params=W, k=2, m=2,
                      strategy="parm")
    try:
        assert isinstance(fe.spec, DeploymentSpec)
        assert fe.spec.k == 2 and fe.spec.m == 2
        assert fe.spec.batching.max_size == 1
    finally:
        fe.shutdown()


def test_frontend_rejects_spec_plus_legacy_kwargs():
    W = jnp.ones((4, 3), jnp.float32)
    spec = _spec()
    with pytest.raises(TypeError, match="not both"):
        ParMFrontend(_linear_fwd, W, spec=spec)


def test_frontend_mode_kwarg_raises_through_spec_path():
    W = jnp.ones((4, 3), jnp.float32)
    with pytest.raises(TypeError, match="strategy="):
        ParMFrontend(_linear_fwd, W, k=2, m=1, mode="none")


def test_threads_and_sim_sessions_share_one_spec_object():
    """The core redesign contract in miniature: one spec object, two
    engines, coherent reports."""
    spec = _spec(m=2)
    sim = deploy(spec, engine="sim").replay(
        Trace(n_queries=100, qps=300, seed=0, n_shuffles=0))
    with deploy(spec, engine="threads") as sess:
        futs = [sess.submit(np.ones((1, 8), np.float32)) for _ in range(4)]
        assert sess.wait_all(timeout=20)
        del futs
        rt = sess.stats()
    assert (sim.strategy, sim.scheme) == (rt.strategy, rt.scheme)
    assert sim.engine == "sim" and rt.engine == "threads"


def test_threads_batching_respects_max_delay_budget():
    """max_delay_ms bounds how long a worker holds a batch open: a lone
    query must not wait out a large max_size."""
    spec = _spec(strategy="none", m=1,
                 batching=BatchingPolicy(max_size=64, max_delay_ms=30.0))
    with deploy(spec) as sess:
        t0 = time.perf_counter()
        fut = sess.submit(np.ones((1, 8), np.float32))
        fut.result(timeout=10.0)
        # one query, batch held open <= ~30ms + inference, not unbounded
        assert time.perf_counter() - t0 < 2.0
        assert sess.stats().completed_by == {"model": 1}


# ------------------------------------------------- review-hardening cases ---
def test_submit_rejects_duplicate_qid_and_counter_skips_past_explicit():
    spec = _spec(strategy="none")
    with deploy(spec) as sess:
        f3 = sess.submit(np.ones((1, 8), np.float32), qid=3)
        assert f3.qid == 3
        with pytest.raises(ValueError, match="already submitted"):
            sess.submit(np.ones((1, 8), np.float32), qid=3)
        f4 = sess.submit(np.ones((1, 8), np.float32))
        assert f4.qid == 4                  # auto counter skipped past 3
        assert f3.result(10.0) is not None and f4.result(10.0) is not None


def test_frontend_requires_model_at_construction():
    """A missing fwd/params must fail at construction, not as a silent
    worker-thread crash with futures hanging until timeout."""
    with pytest.raises(ValueError, match="fwd= and"):
        ParMFrontend(_linear_fwd)           # deployed_params forgotten
    with pytest.raises(ValueError, match="fwd= and"):
        ParMFrontend(spec=DeploymentSpec(strategy="none"))


def test_frontend_rejects_any_stray_legacy_kwarg_next_to_spec():
    spec = _spec(strategy="none")
    with pytest.raises(TypeError, match="slo_ms"):
        ParMFrontend(spec=spec, slo_ms=100.0)
    with pytest.raises(TypeError, match="strategy"):
        ParMFrontend(spec=spec, strategy="default_slo")


def test_trace_defaults_are_simconfig_defaults():
    """The calibration constants live in ONE place: Trace's defaults must
    track SimConfig's field for field."""
    from dataclasses import fields
    from repro.serving.simulator import SimConfig
    sim_defaults = {f.name: f.default for f in fields(SimConfig)}
    for f in fields(Trace):
        assert f.default == sim_defaults[f.name], f.name


def test_report_is_hashable():
    """The frozen report is a value object: hashing must work (the dict
    field is excluded from the generated __hash__, not from equality)."""
    a = ServingReport(engine="sim", strategy="parm", n=1,
                      completed_by={"model": 1})
    b = ServingReport(engine="sim", strategy="parm", n=1,
                      completed_by={"model": 1})
    assert hash(a) == hash(b)
    assert len({a, b}) == 1
    assert a != ServingReport(engine="sim", strategy="parm", n=1,
                              completed_by={"parity": 1})


def test_slo_none_disables_deadline_on_both_engines():
    """default_slo with slo_ms left None must behave identically on both
    engines: NO deadline (the threads engine arms no timers, so the sim
    must not invent the SimConfig default)."""
    spec = DeploymentSpec(strategy="default_slo", k=2, m=2)
    rep = deploy(spec, engine="sim").replay(
        Trace(n_queries=500, qps=300, seed=0, n_shuffles=0))
    assert "default" not in rep.completed_by
    assert rep.completed_by["model"] == 500
    # plain SimConfig users keep the calibrated 200 ms default
    from repro.serving.simulator import SimConfig, simulate
    direct = simulate(SimConfig(n_queries=500, qps=300, m=2, k=2, seed=0,
                                service_ms=300.0, n_shuffles=0),
                      "default_slo")
    assert direct.completed_by.get("default", 0) > 0


def test_report_mapping_view_is_fields_plus_cancellations_only():
    rep = ServingReport(engine="sim", strategy="parm",
                        cancelled_queries=2, cancelled_parities=1)
    assert rep["cancellations"] == 3
    assert "cancellations" in rep and dict(rep)["cancellations"] == 3
    for not_a_key in ("summary", "keys", "items", "_key_names"):
        assert not_a_key not in rep
        with pytest.raises(KeyError):
            rep[not_a_key]


def test_submit_after_shutdown_fails_fast():
    """No futures that hang until timeout: a closed session/frontend must
    reject new work immediately."""
    spec = _spec(strategy="none")
    sess = deploy(spec)
    sess.submit(np.ones((1, 8), np.float32)).result(timeout=10.0)
    sess.shutdown()
    with pytest.raises(RuntimeError, match="shut down"):
        sess.submit(np.ones((1, 8), np.float32))
    fe = ParMFrontend(_linear_fwd, jnp.ones((4, 3), jnp.float32), k=2, m=1,
                      strategy="none")
    fe.shutdown()
    with pytest.raises(RuntimeError, match="shut down"):
        fe.submit(0, np.ones((1, 4), np.float32))


def test_batching_mixed_shapes_serve_per_shape_group():
    """A mixed-shape backlog must not kill the batching worker: same-shape
    queries stack into one call, the odd one out gets its own call, and
    every future resolves exactly."""

    def sum_fwd(p, x):                      # shape-polymorphic model
        del p
        return np.asarray(x).sum(axis=1, keepdims=True)

    spec = DeploymentSpec(fwd=sum_fwd, params=np.zeros(1), strategy="none",
                          m=1, delay_fn=lambda i: 0.15,
                          batching=BatchingPolicy(max_size=8))
    with deploy(spec) as sess:
        xs = [np.ones((1, 8), np.float32), np.ones((1, 8), np.float32),
              np.ones((1, 4), np.float32), np.ones((1, 8), np.float32)]
        futs = [sess.submit(x) for x in xs]
        for f, x in zip(futs, xs):
            np.testing.assert_allclose(f.result(timeout=15.0),
                                       x.sum(axis=1, keepdims=True))
        assert sess.stats().completed_by == {"model": 4}


def test_backend_validated_identically_by_both_engines():
    """spec.backend reaches get_scheme on BOTH engines: a bogus backend must
    fail the same way, and a valid one must deploy on both."""
    bad = _spec(backend="nope")
    with pytest.raises(ValueError, match="backend"):
        deploy(bad, engine="threads")
    with pytest.raises(ValueError, match="backend"):
        deploy(bad, engine="sim").replay(Trace(n_queries=50, qps=200))
    ok = DeploymentSpec(strategy="parm", k=2, m=4, backend="pallas")
    rep = deploy(ok, engine="sim").replay(Trace(n_queries=200, qps=200,
                                                seed=0, n_shuffles=0))
    assert rep.scheme == "sum" and rep.n == 200
    # ... including under a NON-coded strategy, where the code is never
    # used: an undeployable spec must not replay silently
    for bad_noncoded in (DeploymentSpec(strategy="none", backend="bogus"),
                         DeploymentSpec(strategy="none", scheme="nope")):
        with pytest.raises((ValueError, KeyError)):
            deploy(bad_noncoded, engine="sim").replay(
                Trace(n_queries=50, qps=200))


def test_legacy_kwarg_surface_warns_toward_deploy():
    W = jnp.ones((4, 3), jnp.float32)
    with pytest.warns(DeprecationWarning, match="DeploymentSpec"):
        fe = ParMFrontend(_linear_fwd, W, k=2, m=1, strategy="none")
    fe.shutdown()
    # the canonical spec path stays silent
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        fe = ParMFrontend(spec=_spec(strategy="none"))
    fe.shutdown()


def test_flushed_future_never_reports_deadline_exceeded():
    """A shutdown-flushed query's finish time is a teardown artifact: the
    future must not turn it into a phantom SLO violation."""
    spec = _spec(slo_ms=0.001, delay_fn=lambda i: 0.3, m=1)
    sess = deploy(spec)
    fut = sess.submit(np.ones((1, 8), np.float32))  # partial group of 1
    sess.shutdown()
    assert fut.completed_by == "flushed"
    assert fut.deadline_exceeded is False


def test_fwd_error_surfaces_from_future_wait_all_and_shutdown():
    """An instance whose fwd raises fails its queries with the original
    exception — the worker survives and nothing waits out a timeout."""
    def broken_fwd(p, x):
        raise ValueError("fwd exploded")

    sess = deploy(_spec(fwd=broken_fwd))
    x = np.ones((1, 8), np.float32)
    futs = [sess.submit(x) for _ in range(2)]
    t0 = time.perf_counter()
    with pytest.raises(ValueError, match="fwd exploded"):
        futs[0].result(timeout=10.0)
    with pytest.raises(ValueError, match="fwd exploded"):
        sess.wait_all(timeout=10.0)
    assert time.perf_counter() - t0 < 2.0
    assert all(f.completed_by == "error" for f in futs)
    assert sess.stats().n == 0            # failures carry no latency
    with pytest.raises(ValueError, match="fwd exploded"):
        sess.shutdown()
