"""Fault plans: per-instance injected delays, realized from the seed.

A plan is a JSON file under ``faults/``.  Kinds:

* ``none`` — no delay, ever.
* ``tenant_windows`` — the paper's §5.1 background traffic: each of
  ``n_tenants`` tenants repeatedly congests one instance for a window of
  ``duration_ms``, then pauses for ``gap_ms``; every job an instance starts
  inside a window on it waits an extra ``delay_ms`` (uniform).  Everything
  is drawn from the run's seed.  Windows and gaps are stratified
  (``traffic.stratified``), so every seed gets the same set of them in its
  own order, and each run of as many windows as there are instances hits
  every instance once; which windows overlap, and so how many steps no
  parity can rescue, changes with the seed.

The plan's clock starts at ``arm()``: set-up runs without faults, except the
delays that ``force`` and ``force_next`` ask for to warm up reconstruction.
"""
from __future__ import annotations

import json
import math
import random
import threading
import time
from pathlib import Path

import numpy as np

from bench.traffic import stratified


class FaultPlan:
    """``delay(iid) -> seconds``, the ``delay_fn`` the deployments take."""

    def __init__(self, spec: dict, instances, seed: int, horizon_s: float):
        self.spec = spec
        self._windows = {iid: [] for iid in instances}
        kind = spec.get("kind")
        if kind == "tenant_windows":
            self._realize(spec, list(instances), seed, horizon_s)
        elif kind != "none":
            raise ValueError(f"unknown fault plan kind {kind!r}")
        self._jitter = random.Random(f"{seed}/jitter")
        self._origin = None
        self._origin_mono = None     # the same instant on time.monotonic
        self._forced = {}
        self._next = None            # (iids, seconds, taken): force_next
        self.forced_until = 0.0      # monotonic end of the last forced delay
        self._lock = threading.Lock()
        self.injected = 0

    def _realize(self, spec, instances, seed, horizon_s):
        rng = np.random.default_rng([seed & 0xFFFFFFFFFFFFFFFF, 2])
        (d_lo, d_hi), (g_lo, g_hi) = spec["duration_ms"], spec["gap_ms"]
        horizon_ms = 1e3 * horizon_s
        n = math.ceil(horizon_ms / (d_lo + g_lo)) + 1
        lo, hi = spec["delay_ms"]
        for _ in range(spec["n_tenants"]):
            durs = stratified(rng, n, {"dist": "uniform", "lo": d_lo,
                                       "hi": d_hi})
            gaps = stratified(rng, n, {"dist": "uniform", "lo": g_lo,
                                       "hi": g_hi})
            # each run of len(instances) windows hits every instance once
            hit = np.concatenate([rng.permutation(len(instances))
                                  for _ in range(-(-n // len(instances)))])
            t = float(rng.uniform(*spec["first_ms"]))
            for d, g, i in zip(durs, gaps, hit):
                if t > horizon_ms:
                    break
                self._windows[instances[i]].append(
                    (t, t + d, lo, hi))
                t += d + g

    @property
    def n_windows(self) -> int:
        return sum(len(w) for w in self._windows.values())

    def force(self, iid, job: int, seconds: float):
        """Before ``arm``: delay ``iid``'s ``job``-th job (counted from 1) by
        ``seconds``, to drive a reconstruction during warm-up."""
        self._forced.setdefault(iid, {"n": 0, "jobs": {}})["jobs"][job] = \
            seconds

    def force_next(self, iids, seconds: float) -> threading.Event:
        """Before ``arm``: the next job that any of ``iids`` starts waits
        ``seconds``; the event returned is set once a job has taken it."""
        taken = threading.Event()
        with self._lock:
            self._next = (frozenset(iids), seconds, taken)
        return taken

    def unforce(self):
        """Drop a ``force_next`` that no job has taken."""
        with self._lock:
            self._next = None

    def arm(self):
        """Start the plan's clock; forced warm-up delays end here."""
        self._forced = {}
        self._next = None
        self._origin = time.perf_counter()
        self._origin_mono = time.monotonic()

    def slowed(self, iids, t0: float, t1: float) -> bool:
        """Did a window on any of ``iids`` overlap [t0, t1] (monotonic
        seconds, after ``arm``)?"""
        a = 1e3 * (t0 - self._origin_mono)
        b = 1e3 * (t1 - self._origin_mono)
        return any(w0 <= b and a < w1 for iid in iids
                   for w0, w1, _, _ in self._windows.get(iid, ()))

    def delay(self, iid) -> float:
        if self._origin is None:
            if self._next is not None:
                with self._lock:
                    nxt = self._next
                    if nxt is not None and iid in nxt[0]:
                        self._next = None
                        self.forced_until = max(self.forced_until,
                                                time.monotonic() + nxt[1])
                        nxt[2].set()
                        return nxt[1]
            f = self._forced.get(iid)
            if f is None:
                return 0.0
            with self._lock:
                f["n"] += 1
                return f["jobs"].get(f["n"], 0.0)
        now_ms = 1e3 * (time.perf_counter() - self._origin)
        ms = 0.0
        for t0, t1, lo, hi in self._windows.get(iid, ()):
            if t0 <= now_ms < t1:
                ms += self._jitter.uniform(lo, hi)
        if ms:
            with self._lock:
                self.injected += 1
        return ms / 1e3


def load(path: Path, instances, seed: int, horizon_s: float) -> FaultPlan:
    return FaultPlan(json.loads(Path(path).read_text()), instances, seed,
                     horizon_s)
