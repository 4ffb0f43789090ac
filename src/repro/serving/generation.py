"""Coded autoregressive LM serving: token-level continuous batching with
per-step parity reconstruction.  DESIGN.md §13 is the authoring guide.

ParM codes one-shot queries; this module extends the same framework to
*generation*.  A ``GenerationSpec`` deploys k member instances plus r parity
instances of a decode-capable model (``prefill`` / ``decode_step`` /
``init_cache``).  Each member serves ``n_slots`` independent token streams
out of one fixed-shape KV-cache pool (continuous batching: streams join and
leave at token boundaries; the pool never reshapes, so resident streams are
never recompiled or perturbed).  The coding group is a *slot column*: slot s
of every member plus slot s of every parity instance.

Reconstruction semantics per decode step (the ``make_joint_parity_train_step``
LM substrate from PR 3, ApproxIFER's model-agnostic stance for the default
parity params):

* encode over input EMBEDDINGS — each step the parity stream consumes
  ``sum_i C[j,i] * embed(token_i)`` and advances its own KV cache;
* decode over LOGITS — a member that misses the per-step straggle deadline
  has its logits row recovered by the scheme's existing linear decoders from
  the parity logits and the on-time members' logits.

The recovered stream never stalls: the emitted token is the argmax of the
*reconstructed* logits, and because a decode step's cache update depends
only on its INPUT token (never on which logits won the race), the
straggler's still-running step repairs its own cache in the background —
its executor queue serializes the late step before the next one, so by the
time the next decode wants the cache it is exact.  That is the cache-repair
rule: repair-by-completion + canonical token feedback.

Scheduler states per stream: WAITING (queued) -> ADMITTED (prefill into a
free (member, slot), first token emitted from prefill logits, parity slot
column rebuilt from the encoded prompt) -> DECODING (one coded step per
token) -> FINISHED (future fulfilled, slot freed, parity column rebuilt for
the remaining occupants).

Engines:

* ``deploy_lm(spec, engine="threads")`` — real JAX inference on executor
  threads, wall-clock straggle deadlines, scenario delay adapters;
* ``deploy_lm(spec, engine="sim")``     — every decode step becomes one DES
  query at a service time calibrated from ``launch/roofline.py``
  (``decode_token_cost``), so 10M-token tail studies of the big configs
  (qwen3_moe_235b, jamba_1_5_large_398b, mamba2_780m) run on the
  simulator's fast path unchanged.
"""
from __future__ import annotations

import functools
import queue
import threading
import time
from dataclasses import dataclass, field, replace
from typing import Any, Callable, List, Optional, Union

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.scheme import get_scheme
from repro.serving.api import (BatchingPolicy, DeploymentSpec, Trace, deploy,
                               exit_session)
from repro.serving.report import ServingReport
from repro.serving.scenarios import get_scenario, instance_id
from repro.serving.tracing import RECORDER, AdmitRecord, StepRecord

_SHUTDOWN = object()
_span = RECORDER.span


# --------------------------------------------------------------------------
# Spec
# --------------------------------------------------------------------------
@dataclass(frozen=True)
class GenerationSpec:
    """Frozen description of one coded LM deployment.

    ``cfg`` / ``params`` drive the default transformer substrate
    (``repro.models.transformer``); ``parity_params`` defaults to the
    deployed params (ApproxIFER-style model-agnostic parity — retraining a
    parity model per token position is a non-starter, and for linear
    substrates the deployed model already satisfies the code exactly).
    ``prefill_fn`` / ``decode_fn`` / ``embed_fn`` / ``init_cache_fn``
    override the substrate (tests inject exactly-linear stubs).

    The threads engine sizes its cache pools from
    ``batching.max_size`` (= slots per member) and ``max_seq_len``;
    ``straggle_ms`` is the per-step deadline after which a missing member
    row is reconstructed from parity.  ``m`` / ``utilization`` / ``kv_len``
    / ``tp`` calibrate the sim engine's token-level service model.
    """

    cfg: Any = None
    params: Any = None
    parity_params: Any = None            # None -> params (model-agnostic)
    scheme: Union[str, Any] = "sum"
    strategy: Union[str, Any] = "parm"   # sim engine strategy
    k: int = 2
    r: int = 1
    batching: BatchingPolicy = field(
        default_factory=lambda: BatchingPolicy(max_size=4))
    max_seq_len: int = 64
    max_new_tokens: int = 8
    straggle_ms: float = 200.0

    # fault injection (threads engine wall-clock adapters; the sim engine
    # realizes the same scenario hazards in simulated time)
    scenario: Any = None
    scenario_seed: int = 0
    scenario_time_scale: float = 1.0
    scenario_horizon_ms: float = 600_000.0
    delay_fn: Optional[Callable] = None  # iid -> seconds, composes

    # substrate overrides (tests / non-transformer models)
    prefill_fn: Optional[Callable] = None
    decode_fn: Optional[Callable] = None
    embed_fn: Optional[Callable] = None
    init_cache_fn: Optional[Callable] = None

    # distributed placement: a jax Mesh puts params on the inference layout
    # (distributed/sharding.py, fsdp_params=False — weights replicated over
    # the data axis, tensor-parallel over the model axis)
    mesh: Any = None

    # sim-engine calibration: m member streams at `utilization` of the
    # roofline decode-step service time for cfg at kv_len / tensor-parallel
    # degree tp
    m: int = 12
    utilization: float = 0.7
    kv_len: int = 4096
    tp: int = 1

    def __post_init__(self):
        if self.k < 1 or self.r < 1:
            raise ValueError(f"k and r must be >= 1, got k={self.k} "
                             f"r={self.r}")
        if not isinstance(self.batching, BatchingPolicy):
            raise TypeError(
                f"batching must be a BatchingPolicy, got {self.batching!r}")

    def replace(self, **changes) -> "GenerationSpec":
        return replace(self, **changes)


# --------------------------------------------------------------------------
# Futures and stream state
# --------------------------------------------------------------------------
class GenerationFuture:
    """Async handle for one generation request: the emitted token ids, how
    many steps were served from a parity reconstruction, and the per-token
    emission timestamps.  ``result()`` re-raises the exception that stopped
    the session, if one did.

    Timestamps are ``time.monotonic()`` seconds: ``submitted_at``,
    ``admitted_at`` and ``first_token_at``; ``token_steps`` holds the id of
    the decode-step record (``repro.serving.tracing``) that emitted each
    token."""

    def __init__(self, rid):
        self.rid = rid
        self.submitted_at = time.monotonic()
        self._event = threading.Event()
        self._lock = threading.Lock()
        self._tokens: List[int] = []
        self._recon_steps = 0
        self._times: List[float] = []    # [admitted, token 0, token 1, ...]
        self._steps: List[Optional[int]] = []
        self._error: Optional[BaseException] = None
        self.completed_by = None         # "model" | "error"

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None) -> List[int]:
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"request {self.rid} unfinished after {timeout}s")
        if self._error is not None:
            raise self._error
        return list(self._tokens)

    @property
    def tokens_so_far(self) -> List[int]:
        with self._lock:
            return list(self._tokens)

    @property
    def reconstructed_steps(self) -> int:
        return self._recon_steps

    @property
    def inter_token_ms(self) -> List[float]:
        with self._lock:
            t = self._times
            return [1e3 * (b - a) for a, b in zip(t, t[1:])]

    @property
    def admitted_at(self) -> Optional[float]:
        with self._lock:
            return self._times[0] if self._times else None

    @property
    def first_token_at(self) -> Optional[float]:
        with self._lock:
            return self._times[1] if len(self._times) > 1 else None

    @property
    def token_steps(self) -> List[Optional[int]]:
        """Per emitted token, the id of the decode step that emitted it;
        None for token 0, which comes from the prefill at admission."""
        with self._lock:
            return list(self._steps)

    def _emit(self, token, now, reconstructed):
        with self._lock:
            self._tokens.append(int(token))
            self._times.append(now)
            if reconstructed:
                self._recon_steps += 1

    def _link(self, step):
        with self._lock:
            self._steps.append(step)

    def _finish(self, how="model"):
        self.completed_by = how
        self._event.set()

    def _fail(self, error):
        if not self._event.is_set():
            self._error = error
            self._finish("error")

    def __repr__(self):
        state = (self.completed_by or "done") if self.done() else "pending"
        return f"GenerationFuture(rid={self.rid}, {state})"


class _Stream:
    """One admitted request living in (member, slot)."""

    __slots__ = ("rid", "prompt", "max_new", "pos", "next_token", "future",
                 "t_admit")

    def __init__(self, rid, prompt, max_new, future):
        self.rid = rid
        self.prompt = prompt             # list[int], inputs already consumed
        self.max_new = max_new
        self.pos = len(prompt)           # cache fill == next write position
        self.next_token = None           # canonical feedback token
        self.future = future
        self.t_admit = time.monotonic()

    @property
    def history(self):
        """All input tokens consumed so far (prompt + fed-back emissions)."""
        return self.prompt + self.future.tokens_so_far[:-1] \
            if self.future.tokens_so_far else self.prompt


class _Executor(threading.Thread):
    """One model instance: a worker thread draining a FIFO job queue.

    FIFO order IS the cache-repair rule: a straggling decode step finishes
    (and updates this instance's cache) before the next step dequeues."""

    def __init__(self, name):
        super().__init__(name=name, daemon=True)
        self.jobs = queue.Queue()

    def submit(self, fn):
        evt, out = threading.Event(), {}
        self.jobs.put((fn, evt, out))
        return evt, out

    def run(self):
        while True:
            job = self.jobs.get()
            if job is _SHUTDOWN:
                break
            fn, evt, out = job
            try:
                out["result"] = fn()
            except Exception as e:        # re-raised by the scheduler
                out["error"] = e
            evt.set()

    def stop(self):
        self.jobs.put(_SHUTDOWN)


# --------------------------------------------------------------------------
# Default substrate: repro.models.transformer
# --------------------------------------------------------------------------
def _transformer_fns(spec):
    return _transformer_substrate(spec.cfg, spec.mesh)


@functools.lru_cache(maxsize=16)
def _transformer_substrate(cfg, mesh):
    """The jitted substrate of one (config, mesh): shared by every session
    that serves it, so each shape compiles once per process."""
    from repro.models import transformer as T

    # jitted: an eager prefill re-traces its layer scan, and so recompiles
    # the whole stack, on every call.  Each program is named for what it
    # serves (``jit_member_decode`` etc. in a profiler trace).
    def member_prefill(params, tokens, cache_len):
        return T.prefill(cfg, params, tokens=tokens, cache_len=cache_len)

    def parity_prefill(params, embeds, cache_len):
        return T.prefill(cfg, params, embeds=embeds, cache_len=cache_len)

    member_prefill = jax.jit(member_prefill, static_argnums=2)
    parity_prefill = jax.jit(parity_prefill, static_argnums=2)

    def prefill_fn(params, tokens=None, embeds=None, cache_len=0):
        if embeds is not None:
            return parity_prefill(params, embeds, cache_len)
        return member_prefill(params, tokens, cache_len)

    def step(params, cache, pos, **inp):
        logits, new = T.decode_step(cfg, params, cache, pos, **inp)
        if mesh is not None:
            # the new cache keeps the pool layout the step was compiled for
            new = jax.lax.with_sharding_constraint(
                new, cache_shardings(mesh, new))
        return logits, new

    # the pool is donated: the step writes one row per stream into it in
    # place, and the caller replaces its pool with the one returned
    @functools.partial(jax.jit, donate_argnums=1)
    def member_decode(params, cache, pos, token):
        return step(params, cache, pos, token=token)

    @functools.partial(jax.jit, donate_argnums=1)
    def parity_decode(params, cache, pos, embed):
        return step(params, cache, pos, embed=embed)

    def decode_fn(params, cache, pos, token=None, embed=None):
        if embed is not None:
            return parity_decode(params, cache, pos, embed)
        return member_decode(params, cache, pos, token)
    # the jitted programs themselves, for compile-level checks
    decode_fn.programs = {"member_decode": member_decode,
                          "parity_decode": parity_decode}

    def embed_fn(params, tokens):
        return T.embed_tokens(cfg, params, jnp.asarray(tokens))

    def init_cache_fn(params, batch, cache_len):
        return T.init_cache(cfg, batch, cache_len)

    return prefill_fn, decode_fn, embed_fn, init_cache_fn


def _resolve_fns(spec):
    if spec.prefill_fn is not None:
        return (spec.prefill_fn, spec.decode_fn, spec.embed_fn,
                spec.init_cache_fn)
    if spec.cfg is None or spec.params is None:
        raise ValueError(
            "GenerationSpec needs cfg= and params= (or a full "
            "prefill_fn/decode_fn/embed_fn/init_cache_fn substrate)")
    return _transformer_fns(spec)


def place_inference_params(params, mesh):
    """Put a param tree on the inference layout of ``mesh``:
    ``ShardingRules(mesh, fsdp_params=False)`` — tensor-parallel over the
    model axis, replicated over the data axis (every member instance holds
    a full replica; see DESIGN.md §13)."""
    from repro.distributed.sharding import ShardingRules
    rules = ShardingRules(mesh, fsdp_params=False)
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.asarray(x).dtype),
        params)
    shardings = rules.params(shapes)
    return jax.tree.map(jax.device_put, params, shardings)


def cache_shardings(mesh, cache):
    """The inference layout of a cache pool on ``mesh``
    (``ShardingRules.cache_specs``); ``cache`` may hold arrays or tracers."""
    from repro.distributed.sharding import ShardingRules
    return ShardingRules(mesh, fsdp_params=False).cache_specs(cache)


# --------------------------------------------------------------------------
# Threads engine
# --------------------------------------------------------------------------
class GenerationSession:
    """Token-level continuous batching with per-step coded redundancy.

    ``submit(prompt)`` -> ``GenerationFuture``; ``stats()`` ->
    ``ServingReport`` whose completions are decode steps (so ``median_ms``
    etc. ARE inter-token latencies) plus the per-token fields
    (``tokens_per_s``, ``inter_token_p50/p999_ms``, ``reconstructed_steps``).
    """

    engine = "threads"

    def __init__(self, spec: GenerationSpec):
        self.spec = spec
        self.scheme = get_scheme(spec.scheme, k=spec.k, r=spec.r)
        self.coeffs = np.asarray(self.scheme.coeffs, np.float32)  # [r, k]
        fns = _resolve_fns(spec)
        self._prefill, self._decode, self._embed, self._init_cache = fns
        self.k, self.r = spec.k, spec.r
        self.n_slots = spec.batching.max_size
        self.max_seq = spec.max_seq_len

        params = spec.params
        pparams = spec.parity_params if spec.parity_params is not None \
            else params
        if spec.mesh is not None:
            params = place_inference_params(params, spec.mesh)
            pparams = place_inference_params(pparams, spec.mesh)
        self.params, self.parity_params = params, pparams

        # one fixed-shape cache pool per instance; slots never reshape
        self._caches = [self._lay_out(self._init_cache(params, self.n_slots,
                                                       self.max_seq))
                        for _ in range(self.k)]
        self._pcaches = [self._lay_out(self._init_cache(pparams, self.n_slots,
                                                        self.max_seq))
                         for _ in range(self.r)]
        self._ppos = np.zeros((self.r, self.n_slots), np.int64)

        # (member, slot) occupancy
        self._slots: List[List[Optional[_Stream]]] = [
            [None] * self.n_slots for _ in range(self.k)]
        self._dirty = set()              # slot columns needing parity rebuild

        # fault adapters: scenario delays compose with the user delay_fn
        delay_fn = spec.delay_fn
        self.scenario = None
        if spec.scenario is not None:
            self.scenario = get_scenario(spec.scenario)
            pool_sizes = {"main": self.k}
            for j in range(self.r):
                pool_sizes[f"parity{j}"] = 1
            delay_fn, _ = self.scenario.adapters(
                pool_sizes, seed=spec.scenario_seed,
                horizon_ms=spec.scenario_horizon_ms,
                time_scale=spec.scenario_time_scale, extra=delay_fn)
        self._delay_fn = delay_fn
        self._member_iids = [instance_id("main", i) for i in range(self.k)]
        self._parity_iids = [instance_id(f"parity{j}", 0)
                             for j in range(self.r)]

        # warm the decode paths (jit compile) before any deadline is armed —
        # a first-step compile would otherwise read as a multi-second
        # straggle on every instance at once, which no code survives.  The
        # decode may consume (donate) the pool it is given, so the returned
        # pools are kept: they differ only in rows that admission overwrites.
        tok0 = jnp.zeros((self.n_slots, 1), jnp.int32)
        pos0 = jnp.zeros((self.n_slots,), jnp.int32)
        logits0, self._caches[0] = self._decode(self.params, self._caches[0],
                                                pos0, token=tok0)
        _, self._pcaches[0] = self._decode(
            self.parity_params, self._pcaches[0], pos0,
            embed=self._embed(self.params, tok0))
        # ... and the reconstruction decode (its shapes never change), whose
        # first call would otherwise stall a step past its deadline
        def reconstruct(parity_outs, outputs, missing, parity_avail):
            return self.scheme.decode(parity_outs, outputs, missing,
                                      parity_avail)
        self._reconstruct = jax.jit(reconstruct)
        zeros = jnp.zeros(np.shape(logits0), jnp.float32)
        self._reconstruct(jnp.stack([zeros] * self.r),
                          jnp.stack([zeros] * self.k),
                          jnp.arange(self.k) == 0, jnp.ones((self.r,), bool))

        self._members = [_Executor(f"lm-member-{i}") for i in range(self.k)]
        self._parities = [_Executor(f"lm-parity-{j}") for j in range(self.r)]
        for ex in self._members + self._parities:
            ex.start()

        self._waiting: "queue.Queue" = queue.Queue()
        self._lock = threading.Lock()
        self._stopping = False
        self._idle = threading.Event()   # set while nothing queued/active
        self._idle.set()
        self._error: Optional[BaseException] = None
        self._futures: List[GenerationFuture] = []
        self._next_rid = 0
        self._scheduler = threading.Thread(target=self._loop,
                                           name="lm-scheduler", daemon=True)
        self._scheduler.start()

    # -- public surface ----------------------------------------------------
    def submit(self, prompt, max_new_tokens=None) -> GenerationFuture:
        """Queue one generation request (prompt: sequence of token ids)."""
        with self._lock:
            if self._error is not None:
                raise RuntimeError("session failed") from self._error
            if self._stopping:
                raise RuntimeError("session is shut down")
            rid = self._next_rid
            self._next_rid += 1
            # queued under the lock: an abort either sees this request in
            # its drain or this submit already raised
            fut = GenerationFuture(rid)
            self._futures.append(fut)
            self._idle.clear()
            self._waiting.put((rid, [int(t) for t in prompt],
                               max_new_tokens or self.spec.max_new_tokens,
                               fut))
        return fut

    def wait_all(self, timeout: float = 120.0) -> bool:
        """Block until every submitted request has finished; re-raises the
        exception that stopped the scheduler, as soon as it stops."""
        done = self._idle.wait(timeout)
        if self._error is not None:
            raise self._error
        return done

    def stats(self) -> ServingReport:
        """Inter-token gaps from token 1 on (token 0's wait is time to first
        token), and every emitted token over first admission to last
        emission, from the futures' stamps."""
        with self._lock:
            futs = list(self._futures)
        gaps, n_tokens, recon, t0, t1 = [], 0, 0, [], []
        for f in futs:
            with f._lock:
                times, n, r = list(f._times), len(f._tokens), f._recon_steps
            gaps.extend(1e3 * (b - a) for a, b in zip(times[1:], times[2:]))
            n_tokens += n
            recon += r
            if n:
                t0.append(times[0])
                t1.append(times[-1])
        gaps = np.asarray(gaps, float)
        n = len(gaps)
        span = max(t1) - min(t0) if t0 else 0.0
        pct = (lambda q: float(np.percentile(gaps, q))) if n else \
            (lambda q: float("nan"))
        by = {"model": n - recon, "parity": recon}
        return ServingReport(
            engine="threads", strategy="parm",
            scheme=getattr(self.scheme, "name", str(self.spec.scheme)),
            scenario=getattr(self.scenario, "name", None),
            n=n, median_ms=pct(50), p99_ms=pct(99), p999_ms=pct(99.9),
            mean_ms=float(gaps.mean()) if n else float("nan"),
            max_ms=float(gaps.max()) if n else float("nan"),
            completed_by={k: v for k, v in by.items() if v},
            reconstructions=recon,
            tokens_per_s=(n_tokens / span) if span > 0 else 0.0,
            inter_token_p50_ms=pct(50), inter_token_p999_ms=pct(99.9),
            reconstructed_steps=recon)

    def shutdown(self):
        """Stop the scheduler and the executors; re-raises the exception
        that stopped the scheduler, if one did."""
        with self._lock:
            stopped, self._stopping = self._stopping, True
        if not stopped:
            self._scheduler.join(timeout=60.0)
            for ex in self._members + self._parities:
                ex.stop()
            for ex in self._members + self._parities:
                ex.join(timeout=10.0)
        if self._error is not None:
            raise self._error

    def __enter__(self):
        return self

    def __exit__(self, exc_type, *exc):
        return exit_session(self, exc_type)

    def _lay_out(self, cache):
        """Put a cache pool on the mesh's inference layout (a no-op without
        a mesh): the pools start there and every slot write lands back
        there, so the decode step never sees a new input layout."""
        if self.spec.mesh is None:
            return cache
        return jax.device_put(cache, cache_shardings(self.spec.mesh, cache))

    # -- scheduler ---------------------------------------------------------
    def _active(self):
        return [(i, s) for i in range(self.k) for s in range(self.n_slots)
                if self._slots[i][s] is not None]

    def _loop(self):
        try:
            self._serve()
        except Exception as e:
            self._abort(e)

    def _abort(self, error):
        """The scheduler hit an exception (its own, or an executor's it
        re-raised): fail every admitted and queued request with it and wake
        ``wait_all`` — nothing is left to wait out a timeout."""
        with self._lock:
            self._error = error
        for row in self._slots:
            for st in row:
                if st is not None:
                    st.future._fail(error)
        while True:
            try:
                self._waiting.get_nowait()[3]._fail(error)
            except queue.Empty:
                break
        self._idle.set()

    def _serve(self):
        while True:
            self._admit()
            active = self._active()
            if not active:
                with self._lock:
                    stop = self._stopping
                if self._waiting.empty():
                    self._idle.set()
                    if stop:
                        break
                    time.sleep(1e-3)
                    continue
            else:
                self._step(active)
        # flush: nothing active remains by construction

    def _fault_delay(self, iid, **ids):
        """Sleep for the delay injected on instance ``iid``: it stands for a
        slow instance's extra work, so it is a span of its own."""
        if self._delay_fn is None:
            return
        try:
            d = float(self._delay_fn(iid) or 0.0)
        except TypeError:
            return
        if d:
            with _span("lm.fault_delay", **ids):
                time.sleep(d)

    @staticmethod
    def _wait(job, rec, deadline=None):
        """Wait for an executor job (until ``deadline``, if given) and add
        the time waited to ``rec.wait_s``; True when the job finished.
        Re-raises the job's exception."""
        evt, out = job
        t = time.monotonic()
        done = evt.wait(None if deadline is None else max(0.0, deadline - t))
        rec.wait_s += time.monotonic() - t
        if done and "error" in out:
            raise out["error"]
        return done

    def _admit(self):
        """Fill free (member, slot) pairs from the waiting queue; rebuild
        parity columns whose occupancy changed.  A pass that does either is
        kept as one ``AdmitRecord``."""
        rec = None
        while True:
            free = [(i, s) for i in range(self.k)
                    for s in range(self.n_slots)
                    if self._slots[i][s] is None]
            if not free:
                break
            try:
                rid, prompt, max_new, fut = self._waiting.get_nowait()
            except queue.Empty:
                break
            if rec is None:
                rec = AdmitRecord(RECORDER.next_id(), time.monotonic())
            i, s = free[0]
            ids = {"rid": rid, "admit": rec.id}
            with _span("lm.admit.request", **ids):
                stream = _Stream(rid, prompt, max_new, fut)
                self._slots[i][s] = stream
                toks = jnp.asarray([prompt], jnp.int32)            # [1, P]

                def job(toks=toks, i=i, s=s, ids=ids):
                    self._fault_delay(self._member_iids[i], **ids)
                    with _span("lm.member.dispatch", **ids):
                        logits, one = self._prefill(self.params, tokens=toks,
                                                    cache_len=self.max_seq)
                    with _span("lm.slot_write", **ids):
                        self._caches[i] = self._lay_out(jax.tree.map(
                            lambda pool, new: pool.at[:, s:s + 1].set(new),
                            self._caches[i], one))
                    with _span("lm.member.fetch", **ids):
                        return np.asarray(logits[0, -1])

                pending = self._members[i].submit(job)
            self._wait(pending, rec)
            # first token comes from the prefill logits (admission path,
            # uncoded); decode steps from here on are coded
            tok = int(np.argmax(pending[1]["result"]))
            now = time.monotonic()
            stream.future._times.append(stream.t_admit)
            stream.future._emit(tok, now, reconstructed=False)
            stream.future._link(None)
            stream.next_token = tok
            self._dirty.add(s)
            rec.admitted += 1
            if stream.max_new <= 1:
                self._finish(i, s)
        if self._dirty:
            if rec is None:
                rec = AdmitRecord(RECORDER.next_id(), time.monotonic())
            for s in sorted(self._dirty):
                self._rebuild_parity(s, rec)
            self._dirty.clear()
            rec.t1 = time.monotonic()
            if rec.admitted or rec.rebuilt:
                RECORDER.append(rec)

    def _rebuild_parity(self, s, rec):
        """Re-prefill parity slot column s from the encoded histories of its
        current occupants (right-aligned; empty members contribute zeros).

        Occupants admitted at different times sit at different positions;
        right-alignment matches the newest suffix, which is exact for
        position-independent substrates and the trained-parity
        approximation otherwise (DESIGN.md §13)."""
        hists = []
        for i in range(self.k):
            st = self._slots[i][s]
            hists.append(st.history if st is not None else [])
        L = max((len(h) for h in hists), default=0)
        if L == 0:
            for j in range(self.r):
                self._ppos[j, s] = 0
            return
        ids = {"admit": rec.id, "slot": s}
        jobs = []
        with _span("lm.admit.rebuild", **ids):
            # encoded prompt embeddings [1, L, D]
            embs = []
            for h in hists:
                if h:
                    e = np.asarray(self._embed(self.params,
                                               jnp.asarray([h], jnp.int32)))
                else:
                    e = None
                embs.append(e)
            D = next(e.shape[-1] for e in embs if e is not None)
            dt = next(e.dtype for e in embs if e is not None)
            for j in range(self.r):
                enc = np.zeros((1, L, D), np.float32)
                for i, e in enumerate(embs):
                    if e is not None:
                        enc[:, L - e.shape[1]:] += self.coeffs[j, i] * \
                            e.astype(np.float32)
                enc = jnp.asarray(enc.astype(dt))

                def job(enc=enc, j=j):
                    with _span("lm.parity.dispatch", **ids):
                        _, one = self._prefill(self.parity_params, embeds=enc,
                                               cache_len=self.max_seq)
                    with _span("lm.slot_write", **ids):
                        self._pcaches[j] = self._lay_out(jax.tree.map(
                            lambda pool, new: pool.at[:, s:s + 1].set(new),
                            self._pcaches[j], one))

                jobs.append(self._parities[j].submit(job))
        for j, job in enumerate(jobs):
            self._wait(job, rec)
            self._ppos[j, s] = L
        rec.rebuilt += 1

    def _step(self, active):
        """One coded decode step for every active stream, kept as one
        ``StepRecord``."""
        k, n_slots = self.k, self.n_slots
        rec = StepRecord(RECORDER.next_id(), time.monotonic(),
                         active=len(active))
        ids = {"step": rec.id}
        with _span("lm.step.inputs", **ids):
            tok = np.zeros((k, n_slots, 1), np.int32)
            pos = np.zeros((k, n_slots), np.int32)
            occ = np.zeros((k, n_slots), bool)
            for i, s in active:
                st = self._slots[i][s]
                tok[i, s, 0] = st.next_token
                pos[i, s] = st.pos
                occ[i, s] = True

            # member jobs: full fixed-shape batch, per-slot positions
            member_out = []
            for i in range(k):
                ti, pi = jnp.asarray(tok[i]), jnp.asarray(pos[i])
                mids = {**ids, "member": i}

                def job(i=i, ti=ti, pi=pi, mids=mids):
                    self._fault_delay(self._member_iids[i], **mids)
                    with _span("lm.member.dispatch", **mids):
                        logits, self._caches[i] = self._decode(
                            self.params, self._caches[i], pi, token=ti)
                    with _span("lm.member.fetch", **mids):
                        return np.asarray(logits)          # [n_slots, 1, V]

                member_out.append(self._members[i].submit(job))

        # parity jobs: encoded input embedding, own cache column positions.
        # Unoccupied (member, slot) cells carry token 0 only for shape — mask
        # their embeddings to zero so they contribute nothing to the code.
        with _span("lm.step.embed", **ids):
            embs = np.asarray(self._embed(
                self.params, jnp.asarray(tok.reshape(k * n_slots, 1)))
            ).reshape(k, n_slots, 1, -1)
        with _span("lm.step.encode", **ids):
            embs = embs * occ[:, :, None, None]
            parity_out = []
            active_slots = sorted({s for _, s in active})
            for j in range(self.r):
                enc = np.einsum("i,ind->nd", self.coeffs[j],
                                embs[:, :, 0]).astype(embs.dtype)[:, None]
                enc_j = jnp.asarray(enc)
                ppos_j = jnp.asarray(self._ppos[j].astype(np.int32))
                pids = {**ids, "parity": j}

                def pjob(j=j, enc_j=enc_j, ppos_j=ppos_j, pids=pids):
                    self._fault_delay(self._parity_iids[j], **pids)
                    with _span("lm.parity.dispatch", **pids):
                        logits, self._pcaches[j] = self._decode(
                            self.parity_params, self._pcaches[j], ppos_j,
                            embed=enc_j)
                    with _span("lm.parity.fetch", **pids):
                        return np.asarray(logits)

                parity_out.append(self._parities[j].submit(pjob))
                self._ppos[j][active_slots] += 1

        # collect with the per-step straggle deadline
        deadline = time.monotonic() + self.spec.straggle_ms / 1e3
        logits = [None] * k
        missing = []
        for i, job in enumerate(member_out):
            if self._wait(job, rec, deadline):
                logits[i] = job[1]["result"]
            else:
                missing.append(i)

        reconstructed = set()
        if missing:
            pavail = np.zeros((self.r,), bool)
            plogits = [None] * self.r
            for j, job in enumerate(parity_out):
                if self._wait(job, rec, deadline):
                    plogits[j] = job[1]["result"]
                    pavail[j] = True
            if len(missing) <= int(pavail.sum()):
                with _span("lm.step.reconstruct", **ids):
                    V = next(x for x in logits if x is not None).shape[-1] \
                        if any(x is not None for x in logits) else \
                        plogits[int(np.argmax(pavail))].shape[-1]
                    outs = np.stack([
                        x if x is not None else
                        np.zeros((n_slots, 1, V), np.float32)
                        for x in logits])                   # [k, n, 1, V]
                    # an available member's unoccupied slots decoded garbage
                    # (token 0) that the parity never encoded — mask them so
                    # the residual subtraction stays exact
                    outs = outs * occ[:, :, None, None]
                    pouts = np.stack([
                        p if p is not None else
                        np.zeros((n_slots, 1, V), np.float32)
                        for p in plogits])                  # [r, n, 1, V]
                    mask = np.zeros((k,), bool)
                    mask[missing] = True
                    fixed = np.asarray(self._reconstruct(
                        jnp.asarray(pouts, jnp.float32),
                        jnp.asarray(outs, jnp.float32),
                        jnp.asarray(mask), jnp.asarray(pavail)))
                    for i in missing:
                        logits[i] = fixed[i]
                        reconstructed.add(i)
            else:
                # irrecoverable this step: block for the stragglers
                for i in missing:
                    self._wait(member_out[i], rec)
                    logits[i] = member_out[i][1]["result"]

        # emit canonical tokens; feed them back regardless of which side
        # (member or parity decode) produced the logits
        with _span("lm.step.emit", **ids):
            now = time.monotonic()
            for i, s in active:
                st = self._slots[i][s]
                tok_out = int(np.argmax(logits[i][s, 0]))
                st.future._emit(tok_out, now, reconstructed=i in reconstructed)
                st.future._link(rec.id)
                st.next_token = tok_out
                st.pos += 1
                if len(st.future.tokens_so_far) >= st.max_new or \
                        st.pos >= self.max_seq - 1:
                    self._finish(i, s)
        rec.t1 = time.monotonic()
        rec.missed = tuple(missing)
        rec.reconstructed = tuple(sorted(reconstructed))
        rec.stalled = bool(missing) and not reconstructed
        RECORDER.append(rec)

    def _finish(self, i, s):
        st = self._slots[i][s]
        self._slots[i][s] = None
        self._dirty.add(s)
        st.future._finish("model")


# --------------------------------------------------------------------------
# Sim engine: roofline-calibrated token-level DES
# --------------------------------------------------------------------------
def token_service_ms(spec: GenerationSpec) -> float:
    """Roofline decode-step service time (ms) for the spec's config."""
    from repro.launch.roofline import decode_token_cost
    if spec.cfg is None:
        raise ValueError("sim engine calibration needs spec.cfg")
    return 1e3 * decode_token_cost(spec.cfg, batch=spec.batching.max_size,
                                   kv_len=spec.kv_len, tp=spec.tp)


def _tokenize_report(report: ServingReport, tokens_per_s: float):
    """Surface a DES report's completions under their per-token names: each
    DES query was one decode step, so median/p999 ARE inter-token
    latencies."""
    from dataclasses import replace as drep
    return drep(report, tokens_per_s=tokens_per_s,
                inter_token_p50_ms=report.median_ms,
                inter_token_p999_ms=report.p999_ms,
                reconstructed_steps=report.reconstructions)


class LMSimSession:
    """Token-level DES: every decode step of ``m`` member streams is one
    simulated query at the roofline-calibrated service time, so the
    existing simulator (fast path included) prices 10M-token tail studies
    of the big configs without running a single matmul."""

    engine = "sim"

    def __init__(self, spec: GenerationSpec):
        self.spec = spec
        self._last: Optional[ServingReport] = None

    def replay(self, n_tokens: int = 100_000, *, seed: int = 0,
               service_cv: float = 0.1, **trace_overrides) -> ServingReport:
        spec = self.spec
        step_ms = token_service_ms(spec)
        qps = spec.utilization * spec.m * 1e3 / step_ms
        dspec = DeploymentSpec(
            strategy=spec.strategy, scheme=spec.scheme, k=spec.k, r=spec.r,
            m=spec.m, scenario=spec.scenario,
            batching=BatchingPolicy(max_size=1))
        trace = Trace(n_queries=int(n_tokens), qps=qps, service_ms=step_ms,
                      service_cv=service_cv, seed=seed, **trace_overrides)
        report = deploy(dspec, engine="sim").replay(trace)
        self._last = _tokenize_report(report, tokens_per_s=qps)
        return self._last

    def stats(self) -> ServingReport:
        if self._last is None:
            raise RuntimeError("no replay has run yet — call "
                               "session.replay(n_tokens=...) first")
        return self._last

    def shutdown(self):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def deploy_lm(spec: GenerationSpec, engine: str = "threads"):
    """Bring a ``GenerationSpec`` up on one of the two serving engines."""
    if not isinstance(spec, GenerationSpec):
        raise TypeError(f"deploy_lm() takes a GenerationSpec, got {spec!r}")
    if engine == "threads":
        return GenerationSession(spec)
    if engine == "sim":
        return LMSimSession(spec)
    raise ValueError(f"unknown engine {engine!r}; one of ('threads', 'sim')")
