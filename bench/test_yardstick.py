"""The yardstick's arithmetic: traffic and fault draws, work counts,
peaks, and the reduction of a profiler trace recorded on a TPU v5e."""
import json
import math
from pathlib import Path

import numpy as np
import pytest

from bench import faults, harness, peaks, trace, traffic
from bench.harness import ROOT, load_module

DATA = Path(__file__).parent / "data"


def _cfg(name):
    path = ROOT / "bench" / "configs" / f"{name}.json"
    return json.loads(path.read_text()), load_module(path.with_suffix(".py"))


# ------------------------------------------------------------ traffic ---
@pytest.mark.parametrize("dist", [
    {"dist": "uniform", "lo": 300, "hi": 700},
    {"dist": "lognormal", "mean": 161.31, "sigma": 1.0},
    {"dist": "lognormal", "mean": 337.99, "sigma": 0.5}])
def test_stratified_same_set_every_seed(dist):
    a = traffic.stratified(np.random.default_rng(1), 64, dist)
    b = traffic.stratified(np.random.default_rng(2), 64, dist)
    assert not np.array_equal(a, b)
    np.testing.assert_allclose(np.sort(a), np.sort(b))
    if dist["dist"] == "uniform":
        assert dist["lo"] < a.min() and a.max() < dist["hi"]
    else:
        assert abs(a.mean() - dist["mean"]) / dist["mean"] < 0.05
        # the median of a log-normal lies below its mean by exp(sigma^2/2)
        want = dist["mean"] * math.exp(-dist["sigma"] ** 2 / 2)
        assert np.median(a) == pytest.approx(want, rel=0.05)


def test_to_bucket():
    sizes = [64, 128, 256, 512]
    got = traffic.to_bucket(np.array([1, 64, 64.2, 129, 512, 900]), sizes)
    assert list(got) == [64, 64, 128, 256, 512, 512]


@pytest.mark.parametrize("mix", ["batch-calm", "batch-straggle"])
def test_waves_fill_evenly(mix):
    mix = harness.load_mix(ROOT / "bench" / "traffic" / f"{mix}.json")
    out = mix["output_len"]
    it = traffic.waves(mix, np.random.default_rng(7))
    cycle = [next(it) for _ in range(out["strata"])]
    for wave in cycle:
        assert len(wave) == mix["wave_size"]
        assert len({r.max_new for r in wave}) == 1
        lens, counts = np.unique([r.prompt_len for r in wave],
                                 return_counts=True)
        # the published shape, served at the warmed lengths: 16 mid-quantile
        # draws of a log-normal of mean 161.31 and sigma 1
        assert dict(zip(lens.tolist(), counts.tolist())) == \
            {64: 5, 128: 5, 256: 3, 512: 3}
    outs = sorted(w[0].max_new for w in cycle)
    assert outs == [65, 149, 282, 511]
    # the longest prompt and output fit the pool
    assert max(traffic.buckets(mix)) + out["hi"] < 1024
    # the next cycle holds the same lengths in another order
    again = [next(it)[0].max_new for _ in range(out["strata"])]
    assert sorted(again) == outs


# ------------------------------------------------------------- faults ---
def test_fault_plan_seeded_and_stratified():
    spec = json.loads((ROOT / "bench" / "faults" / "shuffle.json")
                      .read_text())
    ids = [0, 1, 1000]
    a = faults.FaultPlan(spec, ids, seed=5, horizon_s=60)
    b = faults.FaultPlan(spec, ids, seed=5, horizon_s=60)
    c = faults.FaultPlan(spec, ids, seed=2 ** 33 + 6, horizon_s=60)
    assert a._windows == b._windows and a._windows != c._windows
    for plan in (a, c):
        n = {i: len(w) for i, w in plan._windows.items()}
        assert max(n.values()) - min(n.values()) <= spec["n_tenants"]
        for w in plan._windows.values():
            for t0, t1, lo, hi in w:
                assert 300 <= t1 - t0 <= 700 and (lo, hi) == (10, 40)
    # each seed draws its windows' times, not only which instance they hit
    starts = [sorted(t0 for w in p._windows.values() for t0, *_ in w)
              for p in (a, c)]
    assert starts[0][:8] != starts[1][:8]


def test_fault_plan_delays_only_once_armed():
    spec = {"kind": "tenant_windows", "n_tenants": 1,
            "duration_ms": [1e6, 1e6], "gap_ms": [1, 1],
            "delay_ms": [10, 40], "first_ms": [0, 0]}
    plan = faults.FaultPlan(spec, [0], seed=1, horizon_s=10)
    plan.force(0, 2, 0.5)
    assert [plan.delay(0) for _ in range(3)] == [0.0, 0.5, 0.0]
    plan.arm()
    d = plan.delay(0)
    assert 0.010 <= d <= 0.040 and plan.injected == 1
    none = faults.FaultPlan({"kind": "none"}, [0], seed=1, horizon_s=10)
    none.arm()
    assert none.delay(0) == 0.0
    with pytest.raises(ValueError):
        faults.FaultPlan({"kind": "gremlins"}, [0], seed=1, horizon_s=10)


# -------------------------------------------------------------- peaks ---
def test_peaks_lookup():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
    # bandwidth-bound and compute-bound sides of the roofline
    assert peaks.least_time_s(1e9, 819e9, "TPU v5 lite") == 1.0
    assert peaks.least_time_s(197e12, 1.0, "TPU v5 lite") == 1.0


# -------------------------------------------------------- work counts ---
def _small_lm():
    cfg, mod = _cfg("olmo-1b")
    cfg["model"].update(d_model=8, n_layers=2, n_heads=2, n_kv_heads=2,
                        head_dim=4, d_ff=16, vocab=32, dtype="bfloat16")
    return cfg, mod


def test_lm_counts_by_hand():
    cfg, mod = _small_lm()
    # per layer: q, k, v, o 8x8 each; w1, w3 8x16, w2 16x8
    per_layer = 4 * 64 + 3 * 128
    weights = 2 * per_layer + 32 * 8              # + tied embedding
    assert mod.weight_bytes(cfg) == 2 * weights
    # a step of streams holding 3 and 5 tokens: projections and head per
    # stream, attention 2 x 2 x heads x head_dim per attended position
    flops, nbytes = mod.decode_work(cfg, [3, 5])
    proj, head = 2 * 2 * per_layer, 2 * 8 * 32
    attn = 4 * 2 * 2 * 4 * (4 + 6)
    assert flops == 2 * (proj + head) + attn
    kv_pos = 2 * 2 * 2 * 4 * 2                    # K and V, layers, bytes
    assert nbytes == 2 * weights + kv_pos * (3 + 5 + 2)
    # prefill of 4: causal attention over 1+2+3+4 positions, head once
    flops, nbytes = mod.prefill_work(cfg, 4)
    assert flops == 4 * proj + head + 4 * 2 * 2 * 4 * 10
    assert nbytes == 2 * weights + kv_pos * 4
    assert mod.token_flops(cfg, 5) == proj + head + 4 * 2 * 2 * 4 * 6


# -------------------------------------------------------------- trace ---
def test_union():
    assert trace.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]
    assert trace.union([]) == []


def test_harness_spans_from_marks():
    marks = [("bench.wait", 10, 10), ("x", 12, 15), ("bench.wait/end", 20, 20),
             ("bench.submit", 25, 25), ("y", 30, 31)]
    got = sorted(trace._spans(marks), key=lambda e: e[1])
    assert got == [("bench.wait", 10, 20), ("x", 12, 15),
                   ("bench.submit", 25, 31), ("y", 30, 31)]


def test_open_span_runs_to_the_end_mark():
    # a wait still open when the trace stops, alone on its thread: it runs
    # to the END mark, not to its own start
    marks = [("bench.wait", 10, 10)]
    assert trace._spans(marks) == [("bench.wait", 10, 10)]
    assert trace._spans(marks, end=50) == [("bench.wait", 10, 50)]
    closed = [("bench.wait", 10, 10), ("bench.wait/end", 20, 20)]
    assert trace._spans(closed, end=50) == [("bench.wait", 10, 20)]


def test_self_times_of_nested_ops():
    # a loop op around two body ops, then an op after it
    got = trace._self_times([("loop", 0, 10), ("b", 6, 8), ("a", 2, 5),
                             ("after", 11, 12)])
    assert got == [("loop", 0, 5), ("a", 2, 3), ("b", 6, 2),
                   ("after", 11, 1)]


def test_reduce_recorded_trace():
    """A trace of coded OLMo-1B decode steps recorded on one TPU v5e:
    the reduction against a direct count of the same events."""
    from jax.profiler import ProfileData
    path = next(DATA.glob("*.xplane.pb"))
    got = trace.reduce(path)
    pd = ProfileData.from_file(str(path))
    dev = next(p for p in pd.planes if p.name == "/device:TPU:0")
    ops = next(ln for ln in dev.lines if ln.name == "XLA Ops")
    host = [e for p in pd.planes if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events]
    t0 = next(e.start_ns for e in host if e.name == trace.BEGIN)
    t1 = next(e.start_ns for e in host if e.name == trace.END)
    # busy by a sweep over a millisecond-fine grid of the window
    grid = np.zeros(int(math.ceil((t1 - t0) / 1e3)) + 1, bool)
    for e in ops.events:
        s, f = max(e.start_ns, t0), min(e.start_ns + e.duration_ns, t1)
        if f > s:
            grid[int((s - t0) // 1e3):int(math.ceil((f - t0) / 1e3))] = True
    assert got.n_devices == 1
    assert got.window_s == pytest.approx((t1 - t0) / 1e9)
    assert 0 < got.busy_s <= got.window_s
    assert got.busy_s == pytest.approx(grid.sum() / 1e6, rel=0.02)
    assert len(got.device_ops) == 10 and len(got.idle_gaps) == 10
    times = [t for _, t in got.device_ops]
    assert times == sorted(times, reverse=True)
    assert sum(times) <= got.busy_s * 1.0001
    gaps = [t for _, t in got.idle_gaps]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) <= got.window_s - got.busy_s + 1e-9
    assert all(": " in name for name, _ in got.idle_gaps)


# ------------------------------------------------------------ queries ---
def test_query_inputs_same_shape_every_seed():
    mix = harness.load_mix(ROOT / "bench" / "traffic" / "query-straggle.json")
    mix = dict(mix, inputs=dict(mix["inputs"], distinct=8))
    a = traffic.inputs(mix, (32, 32, 3), np.random.default_rng(1))
    b = traffic.inputs(mix, (32, 32, 3), np.random.default_rng(2))
    assert a.shape == b.shape == (8, 1, 32, 32, 3)
    assert a.dtype == np.float32 and not np.array_equal(a, b)
    assert abs(a.std() - 1) < 0.05 and abs(a.mean()) < 0.05
    with pytest.raises(ValueError):
        harness.load_mix(ROOT / "bench" / "faults" / "shuffle.json")


def test_fault_plan_forces_the_next_job_of_a_set():
    plan = faults.FaultPlan({"kind": "none"}, [0, 1, 1000], seed=1,
                            horizon_s=10)
    taken = plan.force_next([0, 1], 0.5)
    assert plan.delay(1000) == 0.0 and not taken.is_set()
    assert plan.delay(1) == 0.5 and taken.is_set()
    assert plan.delay(0) == 0.0          # one job only
    assert plan.forced_until > 0
    plan.force_next([0], 0.5)
    plan.unforce()                       # withdrawn before any job took it
    assert plan.delay(0) == 0.0
    plan.force_next([0], 0.5)
    plan.arm()                           # the window forces nothing
    assert plan.delay(0) == 0.0


def test_fault_plan_slowed_reads_its_windows():
    plan = faults.FaultPlan({"kind": "none"}, [0, 1], seed=1, horizon_s=1)
    plan._windows = {0: [(100.0, 200.0, 10.0, 10.0)], 1: []}
    plan.arm()
    t = plan._origin_mono
    assert plan.slowed([0], t + 0.15, t + 0.16)
    assert plan.slowed([0, 1], t + 0.05, t + 0.12)     # overlaps its start
    assert not plan.slowed([0], t, t + 0.05)
    assert not plan.slowed([0], t + 0.201, t + 0.3)    # after its end
    assert not plan.slowed([1], t + 0.15, t + 0.16)


def _small_cnn():
    cfg, mod = _cfg("resnet18-cifar")
    cfg["model"].update(image_shape=[4, 4, 1], stages=[2, 4],
                        blocks_per_stage=1, n_classes=3)
    return cfg, mod


def test_resnet_counts_by_hand():
    cfg, mod = _small_cnn()
    # stem 3x3x1x2 at 4x4; stage 0 block: two 3x3x2x2 at 4x4; stage 1
    # block (stride 2, 2x2 out): 3x3x2x4, 3x3x4x4 and a 1x1x2x4 projection;
    # head 4x3
    macs = 16 * 9 * 2 + 2 * 16 * 9 * 4 + 4 * (9 * 8 + 9 * 16 + 8) + 12
    weights = 18 + 2 * 36 + 72 + 144 + 8 + 12 + 3
    flops, nbytes = mod.forward_work(cfg)
    assert flops == 2 * macs
    assert mod.param_count(cfg) == weights
    assert nbytes == 4 * (weights + 16 + 3)


def test_resnet18_published_sizes():
    cfg, mod = _cfg("resnet18-cifar")
    flops, nbytes = mod.forward_work(cfg)
    # ResNet-18's 11,173,962 parameters at CIFAR's 10 classes, less the
    # 9,600 scales and shifts of the BatchNorm it does not have; ~0.56 GMAC
    # at 32x32
    assert mod.param_count(cfg) == 11_173_962 - 9_600
    assert 1.10e9 < flops < 1.12e9
    assert nbytes == pytest.approx(4 * 11_164_362, rel=1e-3)


def test_resnet_weights_in_the_programs_layout():
    import jax
    from repro.models import cnn
    cfg, mod = _cfg("resnet18-cifar")
    m = cfg["model"]
    want = jax.eval_shape(lambda k: cnn.init_resnet(
        k, tuple(m["image_shape"]), stages=tuple(m["stages"]),
        n_out=m["n_classes"], blocks_per_stage=m["blocks_per_stage"]),
        jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       mod.init_weights(cfg, 3))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert jax.tree.leaves(got) == jax.tree.leaves(want)


def test_resnet_reference_against_the_program(monkeypatch):
    """The reference and the program's forward agree to float32 rounding at
    a small size; the program at the control's precision "high" (three
    bfloat16 passes, the CPU stand-in) does not."""
    import jax
    from bench import rehearsal
    from repro.models import cnn
    cfg, mod = _cfg("resnet18-cifar")
    cfg["model"].update(stages=[8, 16, 16, 32])
    w = mod.init_weights(cfg, 11)
    x = np.random.default_rng(0).standard_normal((4, 32, 32, 3),
                                                 dtype=np.float32)
    rehearsal.cpu_high_precision(monkeypatch)
    fwd = {}
    for precision in ("highest", "high"):
        with jax.default_matmul_precision(precision):
            fwd[precision] = np.asarray(
                jax.jit(lambda w, x: cnn.resnet_fwd(w, x))(w, x), np.float64)
    got, low = fwd["highest"], fwd["high"]
    want = np.asarray(mod.reference_logits(cfg, w, x), np.float64)
    scale = np.abs(want).max(-1)
    err = (np.abs(got - want).max(-1) / scale).max()
    ctl = (np.abs(low - want).max(-1) / scale).min()
    assert err < 1e-6 and ctl > 5 * err, (err, ctl)


def test_recorded_trace_gaps_in_an_open_wait():
    """The recorded trace with a harness wait opened on the submitting
    thread and never closed, as in a wave longer than the traced part:
    every idle gap after it is named by the wait.  (The trace was recorded
    when ``bench.submit`` was one annotation; here it is the two marks the
    harness writes now.)"""
    path = next(DATA.glob("*.xplane.pb"))
    threads, devices = trace.read(path)
    host = next(evs for evs in threads
                if any(n == "bench.submit" for n, _, _ in evs))
    t_wait = next(e for n, _, e in host if n == "bench.submit")
    host += [("bench.submit" + trace.SPAN_END, t_wait, t_wait),
             ("bench.wait", t_wait, t_wait)]
    got = trace.summarize(threads, devices)
    t1 = next(s for evs in threads for n, s, _ in evs if n == trace.END)
    assert got.idle_gaps and all(n.startswith("bench.wait: ")
                                 for n, _ in got.idle_gaps)
    assert all(e <= t1 for n, s, e in trace._spans(host, t1)
               if n == "bench.wait")


def test_recorded_trace_counts_program_runs():
    from jax.profiler import ProfileData
    path = next(DATA.glob("*.xplane.pb"))
    got = trace.reduce(path)
    pd = ProfileData.from_file(str(path))
    host = [e for p in pd.planes if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events]
    t0 = next(e.start_ns for e in host if e.name == trace.BEGIN)
    t1 = next(e.start_ns for e in host if e.name == trace.END)
    dev = next(p for p in pd.planes if p.name == "/device:TPU:0")
    mods = next(ln for ln in dev.lines if ln.name == "XLA Modules")
    want = {}
    for e in mods.events:
        if t0 <= e.start_ns < t1:
            name = e.name.split("(")[0]
            want[name] = want.get(name, 0) + 1
    assert got.modules == want and sum(want.values()) > 0
    assert all("(" not in name for name in got.modules)
    # programs loaded from the persistent cache carry the fingerprint as
    # a suffix of their own
    assert trace._program("jit_member_decode_8544231769555158965_") == \
        trace._program("jit_member_decode(8544231769555158965)") == \
        "jit_member_decode"
