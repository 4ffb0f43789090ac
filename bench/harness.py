"""The benchmark harness: set up one cell, measure its window, check what
the timed path produced against the plain reference, and report.

``run.py`` is the command; this module holds everything it does, as plain
functions that the tests drive on the CPU at reduced sizes.  The loop that a
cell's traffic mix names picks its driver and its check (``LOOPS``): a coded
generation deployment (``deploy_lm``) fed in closed waves
(``closed_waves``), or a coded classification deployment (``deploy``) that
callers keep a fixed number of queries in (``closed_queries``).  Times are
on ``time.monotonic``, the clock the program stamps its tokens with.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import sys
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from bench import faults, traffic
from bench.peaks import least_time_s, peaks
from bench.trace import (BEGIN, END, SPAN_END, TraceSummary, find_xplane,
                         reduce)

ROOT = Path(__file__).resolve().parents[1]
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
TRACE_SECONDS = 5.0          # the traced part of a --trace 1 window
DRAIN_SECONDS = 600.0        # longest wait for work in flight at the close
FAULT_TAIL_SECONDS = 120.0   # faults go on while the last wave finishes
WARMUP_TRIES = 4             # attempts at each forced reconstruction
# a reconstructed query's group partner is looked for this many places per
# caller either side of it in the order the callers sent their queries
PARTNER_REACH = 2


# ------------------------------------------------------------- loading ---
def load_benchmark(root: Path) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}; have "
                   f"{[e['name'] for e in entries]}")


def load_module(path: Path):
    """Import a file by path (file names may hold '-' and '.')."""
    name = "bench_" + "".join(c if c.isalnum() else "_"
                              for c in str(path.relative_to(path.parents[1])))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_mix(path: Path) -> dict:
    """A traffic mix, whose ``loop`` has to be one of ``LOOPS``."""
    mix = json.loads(Path(path).read_text())
    if mix.get("loop") not in LOOPS:
        raise ValueError(f"{path}: loop must be one of {sorted(LOOPS)}, "
                         f"got {mix.get('loop')!r}")
    return mix


def load_config(root: Path, entry: dict):
    """(sizes from the JSON file, the model module beside it)."""
    path = root / entry["file"]
    return json.loads(path.read_text()), load_module(path.with_suffix(".py"))


@dataclass
class Cell:
    """Everything one cell is made of, found by name."""
    root: Path
    bench: dict
    workload: dict
    cfg: dict
    model: Any
    mix: dict
    fault_path: Path

    @classmethod
    def load(cls, root: Path, name: str) -> "Cell":
        bench = load_benchmark(root)
        w = find(bench["workloads"], name, "workload")
        cfg, model = load_config(root, find(bench["configs"], w["config"],
                                            "config"))
        mix = load_mix(root / "bench" / "traffic" / f"{w['traffic']}.json")
        return cls(root, bench, w, cfg, model, mix,
                   root / "bench" / "faults" / f"{mix['faults']}.json")

    def metrics(self, section: str) -> List[dict]:
        """The cell's metrics of ``end_to_end`` or ``per_layer``: those
        that list it, and those that list no cells and apply to all (a
        per-layer one: to every cell that reports the metric it moves)."""
        name = self.workload["name"]
        e2e = [m for m in self.bench["end_to_end"]
               if name in m.get("workloads", [name])]
        if section == "end_to_end":
            return e2e
        reported = {m["name"] for m in e2e}
        return [m for m in self.bench["per_layer"]
                if name in m.get("workloads", [name])
                and ("workloads" in m or m["moves"] in reported)]


def reader(root: Path, name: str):
    return load_module(root / "bench" / "metrics" / f"{name}.py").read


# -------------------------------------------------------------- device ---
def require_chips(n: int):
    """The devices of a chip run; exits (no result line) on anything but a
    TPU with at least ``n`` chips."""
    import jax
    devs = jax.devices()
    d = devs[0]
    print(f"device: platform={d.platform} device_kind={d.device_kind} "
          f"count={len(devs)}", file=sys.stderr, flush=True)
    if d.platform != "tpu":
        raise SystemExit(f"platform {d.platform!r} is not 'tpu': the "
                         "benchmark measures the chip only")
    if len(devs) < n:
        raise SystemExit(f"{n} chips asked for, {len(devs)} present")
    return devs[:n]


def describe(devs) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


class CompileLog:
    """Backend compilations and persistent-cache loads, with their time,
    as JAX reports them (``jax.monitoring``); ``misses`` counts programs
    the persistent cache did not hold."""

    def __init__(self):
        self.events = []
        self.misses = 0
        self._lock = threading.Lock()

    def event(self, event, **kw):
        if event == CACHE_MISS_EVENT:
            with self._lock:
                self.misses += 1

    def __call__(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            with self._lock:
                self.events.append((time.monotonic(),
                                    str(kw.get("fun_name", "?")), duration))


class Tracer:
    """Profiler trace of the first ``seconds`` of the window, bounded by the
    two marks ``trace.reduce`` looks for; python calls are not traced."""

    def __init__(self, log_dir: Path, seconds: float):
        import jax
        self.log_dir = log_dir
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(log_dir), profiler_options=opts)
        with jax.profiler.TraceAnnotation(BEGIN):
            self.t0 = time.monotonic()
        self.t1 = None
        self._timer = threading.Timer(seconds, self.stop)
        self._timer.start()
        self._lock = threading.Lock()

    def stop(self):
        import jax
        with self._lock:
            if self.t1 is not None:
                return
            with jax.profiler.TraceAnnotation(END):
                self.t1 = time.monotonic()
            jax.profiler.stop_trace()

    def finish(self) -> TraceSummary:
        self._timer.cancel()
        self.stop()
        return reduce(find_xplane(self.log_dir))


@contextmanager
def span(name, on):
    """A harness span in the trace, written as two instant marks (``name``
    and ``name/end``): a span still open when the trace stops is then
    not lost, as a ``TraceAnnotation`` around it would be."""
    if not on:
        yield
        return
    import jax
    with jax.profiler.TraceAnnotation(name):
        pass
    try:
        yield
    finally:
        with jax.profiler.TraceAnnotation(name + SPAN_END):
            pass


# ------------------------------------------------------------ the run ---
@dataclass
class Run:
    """What one run measured; the metric readers take it."""
    cell: Cell
    seed: int
    device_kind: str
    platform: str
    setup_s: float = 0.0
    w0: float = 0.0                   # window, on the driver's clock
    w1: float = 0.0
    requests: list = field(default_factory=list)
    compiles: list = field(default_factory=list)
    trace: Optional[TraceSummary] = None
    tw0: float = 0.0                  # traced part of the window
    tw1: float = 0.0
    notes: Dict[str, Any] = field(default_factory=dict)
    inputs: Any = None                # a query cell's input pool

    @property
    def cfg(self):
        return self.cell.cfg

    @property
    def model(self):
        return self.cell.model

    @property
    def seconds(self):
        return self.w1 - self.w0

    def peaks(self):
        """The chip's peaks; None on the CPU, whose runs report no device
        metric."""
        return None if self.platform == "cpu" else peaks(self.device_kind)

    def least_time_s(self, flops, nbytes):
        return least_time_s(flops, nbytes, self.device_kind)


def rngs(seed: int, stream: int):
    """Independent generators per use of one seed."""
    return np.random.default_rng([seed & 0xFFFFFFFFFFFFFFFF, stream])


# ------------------------------------------------------------------ lm ---
@dataclass
class LMRequest:
    wave: int
    index: int                        # submission order within the wave
    prompt: list
    max_new: int
    t_submit: float
    future: Any
    tokens: list = field(default_factory=list)
    times: list = field(default_factory=list)   # [admit, token 0, token 1..]
    reconstructed: int = 0
    error: Optional[str] = None


def lm_warmup_prompts(mix, slots):
    """Prompt lengths of the warm-up wave: every length in every member
    slot, and every length as the longest of some slot column, so every
    prefill and parity rebuild shape the window uses is compiled."""
    lens = traffic.buckets(mix)[::-1]      # longest first, as
    cols = [(x, x) for x in lens] + list(zip(lens, reversed(lens)))  # sent
    cols = (cols * slots)[:slots]
    return [a for a, _ in cols] + [b for _, b in cols]


def serve_lm(run: Run, plan, seconds: float, trace_dir: Optional[Path],
             t_start: float):
    import jax
    cell, cfg, model = run.cell, run.cfg, run.model
    dep = cfg["deployment"]
    slots, k = dep["slots"], dep["k"]
    vocab = cfg["model"]["vocab"]
    weights = model.init_weights(cfg, run.seed)
    sess = model.deploy(cfg, weights, plan.delay)
    del weights
    rng = rngs(run.seed, 1)
    if cell.mix["wave_size"] != k * slots:
        raise ValueError("a wave must fill every member slot once")

    def submit_wave(wi, reqs, on):
        # the longest prompt first: its prefill covers the others' submits,
        # so the whole wave is queued before the first admission ends
        order = sorted(range(len(reqs)), key=lambda i: -reqs[i][0])
        order = order[:1] + sorted(order[1:])
        prompts = [(rng.integers(0, vocab, reqs[i][0]).tolist(), reqs[i][1])
                   for i in order]
        out = []
        with span("bench.submit", on):
            for i, (prompt, max_new) in enumerate(prompts):
                t = time.monotonic()
                out.append(LMRequest(wi, i, prompt, max_new, t,
                                     sess.submit(prompt,
                                                 max_new_tokens=max_new)))
        return out

    def wait(reqs, on):
        with span("bench.wait", on):
            for r in reqs:
                try:
                    r.future.result(DRAIN_SECONDS)
                except Exception as e:      # recorded, counted as failed
                    r.error = repr(e)

    try:
        # warm-up: every prompt length through member prefill in every slot
        # and through parity rebuild, the decode programs, and one
        # reconstruction of each member (forced past the deadline)
        late = dep["straggle_ms"] / 1e3 + 0.05
        for i, iid in enumerate(model.instances(cfg)[:k]):
            plan.force(iid, slots + 2 + i, late)
        wave = [(p, 2 + k) for p in lm_warmup_prompts(cell.mix, slots)]
        wait(submit_wave(-1, wave, False), False)
        run.setup_s = time.monotonic() - t_start

        on = trace_dir is not None
        plan.arm()
        run.w0 = time.monotonic()
        run.w1 = run.w0 + seconds
        tracer = Tracer(trace_dir, min(TRACE_SECONDS, seconds)) if on \
            else None
        waves = traffic.waves(cell.mix, rng)
        wi = 0
        while time.monotonic() < run.w1:
            reqs = submit_wave(wi, [(r.prompt_len, r.max_new)
                                    for r in next(waves)], on)
            wait(reqs, on)
            run.requests.extend(reqs)
            wi += 1
        if tracer is not None:
            run.trace = tracer.finish()
            run.tw0, run.tw1 = tracer.t0, tracer.t1
        run.notes["device"] = describe(jax.devices()[:cell.workload["chips"]])
    finally:
        sess.shutdown()
    for r in run.requests:
        f = r.future
        r.tokens = list(f.tokens_so_far)
        r.times = list(f._times)
        r.reconstructed = f.reconstructed_steps
    run.notes["waves"] = wi
    run.notes["split_waves"] = sum(
        1 for w in range(wi) if _split(
            [r for r in run.requests if r.wave == w]))


def _split(reqs):
    """Was a wave admitted in more than one pass (a decode step between
    two of its admissions)?"""
    if not reqs or any(len(r.times) < 3 for r in reqs):
        return False
    return max(r.times[1] for r in reqs) > min(r.times[2] for r in reqs)


# --------------------------------------------------------- correctness ---
@dataclass
class Check:
    """One number compared with its limit: at most the limit, or with
    ``at_least`` at least the limit."""
    name: str
    value: float
    limit: float
    at_least: bool = False

    @property
    def ok(self) -> bool:
        if self.at_least:
            return bool(self.value >= self.limit)
        return bool(self.value <= self.limit)

    def __str__(self):
        side = "at least" if self.at_least else "limit"
        return f"{self.name}: {self.value!r} ({side} {self.limit!r})"


def _pad(seq, n):
    seq = np.asarray(seq)
    if len(seq) > n:
        raise ValueError(f"sequence of {len(seq)} exceeds the reference "
                         f"length {n}")
    pad = [(0, n - len(seq))] + [(0, 0)] * (seq.ndim - 1)
    return np.pad(seq, pad)


def lm_columns(run: Run, n: int):
    """A sample of ``n`` slot columns, drawn from the seed, that holds the
    column of the longest finished request and the column with the most
    reconstructed steps: [(member requests of one column)]."""
    dep = run.cfg["deployment"]
    slots, k = dep["slots"], dep["k"]
    waves = {}
    for r in run.requests:
        waves.setdefault(r.wave, []).append(r)
    cols = []
    for reqs in waves.values():
        if len(reqs) != k * slots or any(r.error or not r.tokens
                                         for r in reqs):
            continue
        by_index = sorted(reqs, key=lambda r: r.index)
        cols += [tuple(by_index[i * slots + s] for i in range(k))
                 for s in range(slots)]
    if not cols:
        return []
    size = [max(len(r.prompt) + len(r.tokens) for r in c) for c in cols]
    recon = [sum(r.reconstructed for r in c) for c in cols]
    must = sorted({int(np.argmax(size)), int(np.argmax(recon))})
    rest = [i for i in range(len(cols)) if i not in must]
    rng = rngs(run.seed, 4)
    pick = rng.choice(len(rest), size=max(0, min(n - len(must), len(rest))),
                      replace=False)
    return [cols[i] for i in must + sorted(rest[j] for j in pick)]


def check_lm(run: Run, control: bool = False) -> Dict[str, Any]:
    """Served tokens of a sample of slot columns against the plain
    reference: each member-path token (prefill's first token and decode
    steps a member served) by the gap between the reference's best logit
    and its logit of the served token, each reconstructed token the same
    against the reference's own encode, parity forward and decode over the
    same histories.  The number compared is the widest gap of either kind;
    the notes keep each kind's.  Where the fault plan slows instances, at
    least one reconstructed token has to be among those compared.
    ``control``: the same readings for the int8 reference put in the
    program's place."""
    import jax
    import jax.numpy as jnp
    cfg, model = run.cfg, run.model
    lim = cfg["correct"]
    n_ref = lim["reference_len"]
    coeffs = model.code_coeffs(cfg)
    if coeffs.shape[0] != 1:
        raise ValueError("the reference decode covers r=1 codes")
    c = coeffs[0]
    weights = model.init_weights(cfg, run.seed)
    ref = jax.jit(lambda w, x: model.reference_logits(cfg, w, x))
    low = jax.jit(lambda w, x: model.reference_logits(cfg, w, x, int8=True))
    emb = jax.jit(model.reference_embeds)
    gap = jax.jit(lambda rows, tok: rows.max(-1)
                  - jnp.take_along_axis(rows, tok[:, None], 1)[:, 0])
    first = jax.jit(lambda rows: jnp.argmax(rows, -1))
    member_gaps, recon_gaps, ctl_member, ctl_recon = [], [], [], []
    n_tokens = 0
    for col in lm_columns(run, lim["sample_columns"]):
        t_r = max(r.times[1] for r in col)
        n_pre = [sum(t <= t_r for t in r.times[1:]) for r in col]
        hists = [r.prompt + r.tokens[:n - 1] for r, n in zip(col, n_pre)]
        L = max(len(h) for h in hists)
        T = min(len(r.tokens) - n for r, n in zip(col, n_pre))
        D = cfg["model"]["d_model"]
        enc = np.zeros((L + T, D), np.float32)

        def embed(tokens):          # one program for every length
            return np.asarray(emb(weights, _pad(tokens, n_ref)))[:len(tokens)]
        for ci, r, n, h in zip(c, col, n_pre, hists):
            enc[L - len(h):L] += ci * embed(h)
            if T:
                enc[L:] += ci * embed(r.tokens[n - 1:n - 1 + T])
        enc = jnp.asarray(_pad(enc, n_ref))
        seqs = [jnp.asarray(_pad(r.prompt + r.tokens[:-1], n_ref), jnp.int32)
                for r in col]
        outs = [ref(weights, s) for s in seqs]
        par = ref(weights, enc)
        if control:
            louts = [low(weights, s) for s in seqs]
            lpar = low(weights, enc)
        for x, (r, n) in enumerate(zip(col, n_pre)):
            P, J = len(r.prompt), len(r.tokens)
            tok = jnp.asarray(_pad(r.tokens, n_ref), jnp.int32)
            idx = np.clip(np.arange(n_ref) + P - 1, 0, n_ref - 1)
            rows = outs[x][idx]
            g_m = np.asarray(gap(rows, tok))[:J]
            # reconstruction of token j = n + i from parity step i
            i = np.clip(np.arange(n_ref) - n, 0, max(T - 1, 0))

            def rec(outs_, par_):
                acc = par_[np.clip(L + i, 0, n_ref - 1)]
                for y, (ry, ny) in enumerate(zip(col, n_pre)):
                    if y != x:
                        oi = np.clip(len(ry.prompt) - 1 + ny + i, 0,
                                     n_ref - 1)
                        acc = acc - c[y] * outs_[y][oi]
                return acc / c[x]
            g_r = np.full(J, np.inf)
            has_rec = (np.arange(J) >= n) & (np.arange(J) < n + T)
            rrows = rec(outs, par) if T else None
            if T:
                g_r[has_rec] = np.asarray(gap(rrows, tok))[:J][has_rec]
            # which decode steps were reconstructed: the program counts
            # them per request; they are the ones the reconstruction
            # reference explains better than the member reference
            cand = [j for j in range(1, J) if np.isfinite(g_r[j])]
            cand.sort(key=lambda j: g_r[j] - g_m[j])
            recon = set(cand[:r.reconstructed])
            member = [j for j in range(J) if j not in recon]
            member_gaps.extend(g_m[member])
            recon_gaps.extend(g_r[sorted(recon)])
            n_tokens += J
            if control:
                pick = np.asarray(first(louts[x][idx]))[:J]
                gl = np.asarray(gap(rows, jnp.asarray(_pad(pick, n_ref),
                                                      jnp.int32)))[:J]
                ctl_member.extend(gl[member])
                if recon:
                    lrows = rec(louts, lpar)
                    pick = np.asarray(first(lrows))
                    gr = np.asarray(gap(rrows, jnp.asarray(pick)))[:J]
                    ctl_recon.extend(gr[sorted(recon)])
    gaps = member_gaps + recon_gaps
    checks = [Check("token_gap", float(max(gaps, default=np.inf)),
                    lim["max_token_gap"])]
    if run.notes.get("fault_windows"):
        checks.append(Check("reconstructed_compared", len(recon_gaps), 1,
                            at_least=True))
    out = {"tokens_compared": n_tokens,
           "tokens_reconstructed": len(recon_gaps),
           "member_gap": float(max(member_gaps, default=np.nan)),
           "recon_gap": float(max(recon_gaps, default=np.nan)),
           "checks": checks}
    if control:
        out["control"] = {"token_gap": float(max(ctl_member + ctl_recon,
                                                 default=np.nan)),
                          "member_gap": float(max(ctl_member,
                                                  default=np.nan)),
                          "recon_gap": float(max(ctl_recon,
                                                 default=np.nan))}
    return out


# ------------------------------------------------------------- queries ---
@dataclass
class QueryRecord:
    index: int                        # submission order, warm-up included
    t_submit: float
    future: Any = None
    t_done: float = math.nan          # when the caller had its answer
    completed_by: str = ""
    output: Optional[np.ndarray] = None
    error: Optional[str] = None


class QueryDriver:
    """Sends queries to a deployment, query ``i`` with input ``i`` of the
    pool (in turn), and keeps a record of each.  Callers on several threads
    do not wait for one another: as at a frontend that many clients call,
    which queries form a coding group is the order in which the calls reach
    it."""

    def __init__(self, sess, inputs):
        self.sess, self.inputs = sess, inputs
        self.records: List[QueryRecord] = []
        self._lock = threading.Lock()

    def submit(self, on: bool = False, until: float = math.inf):
        """Send the next query; None once ``until`` has passed."""
        with self._lock:
            t = time.monotonic()
            if t >= until:
                return None
            rec = QueryRecord(len(self.records), t)
            self.records.append(rec)
        with span("bench.submit", on):
            rec.future = self.sess.submit(
                self.inputs[rec.index % len(self.inputs)])
        return rec

    @staticmethod
    def wait(rec: QueryRecord, on: bool = False):
        with span("bench.wait", on):
            try:
                rec.output = rec.future.result(DRAIN_SECONDS)
            except Exception as e:          # recorded, counted as failed
                rec.error = repr(e)
        rec.t_done = time.monotonic()
        rec.completed_by = rec.future.completed_by


def warm_up_queries(driver: QueryDriver, plan, members, k: int,
                    delay: float = 0.25):
    """Warm-up of every program the window uses: two plain groups (the
    forward of members and parity, the encode), then one reconstruction of
    each member position of a group, forced by slowing whichever member
    instance takes that query (the decode; a parity answer that comes
    before any member takes the query does as well).  Returns the
    positions reconstructed, once every slowed instance is free again."""
    for _ in range(2):
        for rec in [driver.submit() for _ in range(k)]:
            driver.wait(rec)
    done = []
    for j in range(k):
        for attempt in range(WARMUP_TRIES):
            group = []
            for i in range(k):
                if i == j:
                    taken = plan.force_next(members, delay * 2 ** attempt)
                group.append(driver.submit())
                if i == j:
                    _await_force(taken, group[-1], plan)
                if i < j:           # answered before the slow one is sent
                    driver.wait(group[-1])
            for rec in group:
                driver.wait(rec)
            if group[j].completed_by == "parity":
                done.append(j)
                break
        else:
            raise RuntimeError(f"warm-up reconstructed no query at member "
                               f"position {j} in {WARMUP_TRIES} tries")
    time.sleep(max(0.0, plan.forced_until - time.monotonic()))
    return done


def _await_force(taken, rec: QueryRecord, plan):
    """Wait until a member instance has taken the forced delay, or the
    query was answered without one (a member job whose query a parity
    answered first is dropped at dequeue): then the delay is withdrawn."""
    deadline = time.monotonic() + DRAIN_SECONDS
    while not taken.wait(0.01):
        if rec.future.done():
            plan.unforce()
            return
        if time.monotonic() > deadline:
            raise RuntimeError("no member instance took a query")


def serve_queries(run: Run, plan, seconds: float,
                  trace_dir: Optional[Path], t_start: float):
    import jax
    cell, cfg, model, mix = run.cell, run.cfg, run.model, run.cell.mix
    pair_coeff(model, cfg)              # refused before anything is served
    phases = run.notes["setup_phases_s"]
    t = time.monotonic()
    weights = model.init_weights(cfg, run.seed)
    sess = model.deploy(cfg, weights, plan.delay)
    del weights
    phases["weights_deploy"] = time.monotonic() - t
    run.inputs = traffic.inputs(mix, model.input_shape(cfg),
                                rngs(run.seed, 1))
    driver = QueryDriver(sess, run.inputs)
    errors = []

    def client(on):
        try:
            while (rec := driver.submit(on, run.w1)) is not None:
                driver.wait(rec, on)
        except Exception as e:              # raised again below
            errors.append(e)

    try:
        run.notes["warmup_reconstructed"] = warm_up_queries(
            driver, plan, model.member_instances(cfg),
            cfg["deployment"]["k"])
        n_warm = len(driver.records)
        run.setup_s = time.monotonic() - t_start
        phases["warm_up"] = run.setup_s - sum(phases.values())

        on = trace_dir is not None
        plan.arm()
        run.w0 = time.monotonic()
        run.w1 = run.w0 + seconds
        tracer = Tracer(trace_dir, min(TRACE_SECONDS, seconds)) if on \
            else None
        clients = [threading.Thread(target=client, args=(on,),
                                    name=f"bench-client-{c}")
                   for c in range(mix["clients"])]
        for t in clients:
            t.start()
        for t in clients:
            t.join()
        if errors:
            raise errors[0]
        if tracer is not None:
            run.trace = tracer.finish()
            run.tw0, run.tw1 = tracer.t0, tracer.t1
        run.notes["device"] = describe(jax.devices()[:cell.workload["chips"]])
    finally:
        sess.shutdown()
    run.requests = driver.records[n_warm:]
    by = {}
    for r in run.requests:
        by[r.completed_by] = by.get(r.completed_by, 0) + 1
    run.notes["completed_by"] = by
    if plan.n_windows:
        run.notes.update(recon_by_slowing(run, plan,
                                          model.member_instances(cfg)))


def recon_by_slowing(run: Run, plan, members) -> Dict[str, Any]:
    """Answers in the window split by whether a fault window on a member
    instance overlapped the query's time in flight: how many of each, and
    the share of each that parity answered (%)."""
    out = {}
    done = [r for r in run.requests
            if r.t_done <= run.w1 and r.completed_by in ("model", "parity")]
    for key, want in (("slowed", True), ("unslowed", False)):
        part = [r for r in done
                if plan.slowed(members, r.t_submit, r.t_done) == want]
        out[f"answers_{key}"] = len(part)
        out[f"recon_share_{key}"] = 100.0 * sum(
            r.completed_by == "parity" for r in part) / max(len(part), 1)
    return out


def query_sample(run: Run, n_members: int, n_recon: int):
    """A sample, drawn from the seed, of the predictions of queries sent in
    the window: (members' own, reconstructed ones).  Every reconstructed
    prediction is in it, up to ``n_recon`` of them."""
    done = [r for r in run.requests
            if r.t_submit < run.w1 and r.output is not None]
    rng = rngs(run.seed, 4)

    def pick(recs, n):
        idx = rng.choice(len(recs), size=min(n, len(recs)), replace=False)
        return [recs[i] for i in sorted(idx)]
    return (pick([r for r in done if r.completed_by == "model"], n_members),
            pick([r for r in done if r.completed_by == "parity"], n_recon))


def _reference_rows(model, cfg, weights, rows, block):
    """The reference's logits of each input in ``rows`` ([batch, *shape]
    each): [len(rows), batch, classes], computed in blocks of ``block``
    inputs, one compiled program for every block."""
    import jax
    ref = jax.jit(lambda w, x: model.reference_logits(cfg, w, x))
    x = np.concatenate(rows)
    out = []
    for s in range(0, len(x), block):
        part = x[s:s + block]
        got = np.asarray(ref(weights, _pad(part, block)))[:len(part)]
        out.append(got.astype(np.float64))
    return np.concatenate(out).reshape(len(rows), len(rows[0]), -1)


def _err(got, want, scale):
    """Widest gap between two logit vectors, over ``scale``."""
    return float(np.abs(np.asarray(got, np.float64) - want).max() / scale)


def pair_coeff(model, cfg) -> float:
    """The coefficient c of a code whose one parity is c * (x_a + x_b): the
    sum code with k=2, r=1, the only code whose reconstructions
    ``check_queries`` can follow (it searches for one partner per
    reconstructed query); any other is refused."""
    coeffs = model.code_coeffs(cfg)
    if coeffs.shape != (1, 2) or coeffs[0, 0] != coeffs[0, 1]:
        raise ValueError(f"the query check follows reconstructions of the "
                         f"sum code with k=2, r=1 only, not coefficients "
                         f"{coeffs.tolist()}")
    return float(coeffs[0, 0])


def check_queries(run: Run) -> Dict[str, Any]:
    """Logit vectors of a sample of the window's predictions against the
    plain reference: a member's prediction against the reference forward of
    its input, over the reference's largest logit; a reconstructed
    prediction against the reference's own chain — the sum encode of its
    group's inputs, the parity forward, and the decode (the parity output
    less the other member's reference output) — over the largest logit of
    the terms the decode combines.  The callers do not wait for one
    another, so the other member of a reconstructed query's group is not
    known from outside: it is the query, among those sent within
    ``PARTNER_REACH`` x clients places of it and answered by their own
    instance, whose chain the prediction meets best.  Where the fault plan
    slows instances, at least one reconstructed prediction has to be among
    those compared.  The control is a run of its own, served at the
    configuration's ``control_precision`` (``run_cell(precision=...)``)."""
    cfg, model = run.cfg, run.model
    lim = cfg["correct"]
    c = pair_coeff(model, cfg)
    members, recons = query_sample(run, lim["sample_members"],
                                   lim["sample_reconstructed"])
    inputs = run.inputs
    by_index = {r.index: r for r in run.requests}
    reach = PARTNER_REACH * run.cell.mix["clients"]
    rows, at = [], {}

    def need(key, x):
        if key not in at:
            at[key] = len(rows)
            rows.append(np.asarray(x, np.float32))
        return at[key]

    def x_of(i):
        return inputs[i % len(inputs)]
    m_rows = [need(("x", r.index), x_of(r.index)) for r in members]
    chains = []                 # per reconstruction: [(partner, P, F)]
    for r in recons:
        cands = [by_index.get(i) for i in range(r.index - reach,
                                                r.index + reach + 1)]
        chains.append([
            (p, need(("p",) + tuple(sorted((r.index, p.index))),
                     (c * x_of(r.index).astype(np.float64)
                      + c * x_of(p.index)).astype(np.float32)),
             need(("x", p.index), x_of(p.index)))
            for p in cands if p is not None and p is not r
            and p.completed_by == "model"])
    if not rows:
        return {"predictions_compared": 0, "reconstructed_compared": 0,
                "checks": [Check("member_logit_err", math.inf,
                                 lim["max_member_logit_err"])]}
    weights = model.init_weights(cfg, run.seed)
    base = _reference_rows(model, cfg, weights, rows, lim["reference_block"])

    def recon_err(got, par, oth):
        """A reconstruction ``got`` against the reference chain of one
        group."""
        want = (base[par] - c * base[oth]) / c
        scale = max(np.abs(base[par]).max(), np.abs(c * base[oth]).max())
        return _err(got, want, scale / c)

    m_err = [_err(r.output, base[i], np.abs(base[i]).max())
             for r, i in zip(members, m_rows)]
    r_err, picked = [], []
    for r, cands in zip(recons, chains):
        errs = [recon_err(r.output, par, oth) for _, par, oth in cands]
        best = int(np.argmin(errs)) if errs else None
        r_err.append(errs[best] if errs else math.inf)
        picked.append(None if best is None else cands[best])
    checks = [Check("member_logit_err", max(m_err, default=math.inf),
                    lim["max_member_logit_err"])]
    if r_err:
        checks.append(Check("recon_logit_err", max(r_err),
                            lim["max_recon_logit_err"]))
    if run.notes.get("fault_windows"):
        checks.append(Check("reconstructed_compared", len(r_err), 1,
                            at_least=True))
    return {"predictions_compared": len(m_err) + len(r_err),
            "reconstructed_compared": len(r_err),
            "member_logit_err": max(m_err, default=math.nan),
            "recon_logit_err": max(r_err, default=math.nan),
            "partner_offset_max": max((abs(pk[0].index - r.index)
                                       for r, pk in zip(recons, picked)
                                       if pk), default=0),
            "checks": checks}


def query_attempts(run: Run):
    """(queries sent in the window, those that errored or got no
    prediction of the model's or the parity's)."""
    reqs = [r for r in run.requests if r.t_submit < run.w1]
    failed = sum(1 for r in reqs if r.error or r.output is None
                 or r.completed_by not in ("model", "parity"))
    return len(reqs), failed


# ------------------------------------------------------------- the run ---
def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, *, devices=None, trace_dir: Optional[Path] = None,
             t_start: Optional[float] = None, control: bool = False,
             precision: Optional[str] = None):
    """Set up, measure and check one cell; returns (result line, checks,
    run).  ``devices``: the devices to report (default: demand the
    chips the cell asks for).  ``control``: also read the control, where
    the check reads it beside the program's outputs (an LM cell's).
    ``precision``: serve at this matmul precision in place of the
    configuration's: a query cell's control (see ``calibrate.py``)."""
    import jax
    t_start = time.monotonic() if t_start is None else t_start
    cell = Cell.load(root, workload)
    devs = devices if devices is not None else \
        require_chips(cell.workload["chips"])
    run = Run(cell, seed, devs[0].device_kind, devs[0].platform)
    # set-up by phase: process start to here (imports, the backend)
    run.notes["setup_phases_s"] = {"backend": time.monotonic() - t_start}
    plan = faults.load(cell.fault_path, cell.model.instances(cell.cfg),
                       seed, seconds + FAULT_TAIL_SECONDS)
    log = CompileLog()
    precision = precision or cell.cfg.get("matmul_precision")
    old = jax.config.jax_default_matmul_precision
    if trace and trace_dir is None:
        raise ValueError("a traced run needs a trace directory")
    jax.monitoring.register_event_duration_secs_listener(log)
    jax.monitoring.register_event_listener(log.event)
    try:
        if precision:
            jax.config.update("jax_default_matmul_precision", precision)
        loop = LOOPS[cell.mix["loop"]]
        loop.serve(run, plan, seconds, trace_dir if trace else None, t_start)
        run.compiles = [e for e in log.events if run.w0 <= e[0] <= run.w1]
        run.notes["compiled_in_window"] = sorted({e[1] for e in run.compiles})
        run.notes["cache_misses"] = log.misses
        run.notes["faults_injected"] = plan.injected
        run.notes["fault_windows"] = plan.n_windows
        gc.collect()        # the deployment's state goes before the reference
        checked = loop.check(run, control=True) if control \
            else loop.check(run)
    finally:
        jax.config.update("jax_default_matmul_precision", old)
        jax.monitoring.unregister_event_duration_listener(log)
        jax.monitoring.unregister_event_listener(log.event)
    return result_line(run, checked, trace), checked, run


def result_line(run: Run, checked: dict, trace: bool) -> dict:
    cell = run.cell
    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell.metrics(section):
        if m["name"] == "setup_s":
            value = run.setup_s
        else:
            value = reader(cell.root, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    attempted, failed = attempts(run)
    checks = checked["checks"]
    device = dict(run.notes["device"])
    line = {"correct": bool(checks) and all(c.ok for c in checks)
            and failed == 0,
            "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        line["breakdown"] = {"device_ops": run.trace.device_ops,
                             "idle_gaps": run.trace.idle_gaps}
    line["notes"] = {k: v for k, v in run.notes.items() if k != "device"}
    line["notes"].update({k: v for k, v in checked.items()
                          if k not in ("checks", "control")})
    line["compared"] = {c.name: {"value": c.value, "limit": c.limit,
                                 **({"at_least": True} if c.at_least else {})}
                        for c in checks}
    return line


def lm_attempts(run: Run):
    """(requests sent in the window, those not served in full)."""
    reqs = [r for r in run.requests if r.t_submit < run.w1]
    failed = sum(1 for r in reqs if r.error or len(r.tokens) != r.max_new)
    return len(reqs), failed


def attempts(run: Run):
    return LOOPS[run.cell.mix["loop"]].attempts(run)


@dataclass(frozen=True)
class Loop:
    """What a traffic mix's ``loop`` runs: the driver that serves the
    window, the check of what it produced, and the count of attempts."""
    serve: Callable
    check: Callable
    attempts: Callable


LOOPS = {"closed_waves": Loop(serve_lm, check_lm, lm_attempts),
         "closed_queries": Loop(serve_queries, check_queries,
                                query_attempts)}
