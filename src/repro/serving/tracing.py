"""Spans and records of coded generation, kept in memory on one clock.

Two kinds of entry, both timed on ``time.monotonic`` (the clock of a
``GenerationFuture``'s token stamps):

* a **span** is a leaf of host work — ``(name, thread, t0, t1, ids)`` —
  such as building a decode step's inputs or one executor's dispatch of a
  jitted program.  The same interval is wrapped in a
  ``jax.profiler.TraceAnnotation(name)``, so in a profiler trace it sits on
  the device's clock beside the device operations it caused.  Waits are
  never spans: a parent or a wait around a phase would be named for every
  device gap it overlaps.
* a **record** is one decode step (``StepRecord``) or one admission pass
  (``AdmitRecord``): start, end, the seconds spent waiting and the step's
  counters.  Spans point to their record by id (``step=`` / ``admit=``);
  spans of one request carry its ``rid``.

Recording is always on and costs a few microseconds a span.  The buffers
are bounded (the oldest entries go first).  One process-wide ``RECORDER``
holds every session's entries, as the profiler is process-wide too: a
reader selects its part by time (``RECORDER.window(t0, t1)``).
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import List, NamedTuple, Tuple

import jax

SPANS_KEPT = 1 << 16       # about three minutes of decode steps
RECORDS_KEPT = 1 << 14


class Span(NamedTuple):
    name: str
    thread: str
    t0: float
    t1: float
    ids: dict

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


@dataclass
class StepRecord:
    """One coded decode step: ``missed`` members passed the deadline,
    ``reconstructed`` were served from parity; ``stalled`` when a member
    missed and no parity could cover it, so the step waited for it."""
    id: int
    t0: float
    t1: float = float("nan")
    active: int = 0                   # streams decoded in this step
    wait_s: float = 0.0               # the scheduler's waits on executors
    missed: Tuple[int, ...] = ()
    reconstructed: Tuple[int, ...] = ()
    stalled: bool = False


@dataclass
class AdmitRecord:
    """One admission pass: from the first request dequeued (or the first
    parity column rebuilt, when streams only left) to the last parity
    column rebuilt."""
    id: int
    t0: float
    t1: float = float("nan")
    admitted: int = 0                 # requests prefilled into a slot
    rebuilt: int = 0                  # parity slot columns re-prefilled
    wait_s: float = 0.0               # the scheduler's waits on executors


class Window(NamedTuple):
    spans: List[Span]
    steps: List[StepRecord]
    admissions: List[AdmitRecord]


class _SpanContext:
    __slots__ = ("_out", "_name", "_ids", "_t0", "_note")

    def __init__(self, out, name, ids):
        self._out, self._name, self._ids = out, name, ids

    def __enter__(self):
        self._note = jax.profiler.TraceAnnotation(self._name)
        self._note.__enter__()
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        t1 = time.monotonic()
        self._note.__exit__(*exc)
        self._out.append(Span(self._name, threading.current_thread().name,
                              self._t0, t1, self._ids))
        return False


class Recorder:
    """Bounded buffers of spans and records (see the module docstring)."""

    def __init__(self, spans: int = SPANS_KEPT, records: int = RECORDS_KEPT):
        self._spans = deque(maxlen=spans)
        self._steps = deque(maxlen=records)
        self._admissions = deque(maxlen=records)
        self._ids = itertools.count()
        self._lock = threading.Lock()      # readers copy while others add

    def span(self, name: str, **ids) -> _SpanContext:
        """``with recorder.span("lm.step.emit", step=7): ...``"""
        return _SpanContext(self, name, ids)

    def next_id(self) -> int:
        """A record id, unique in the process."""
        return next(self._ids)

    def append(self, entry):
        """Keep a finished span or record."""
        if isinstance(entry, Span):
            out = self._spans
        elif isinstance(entry, StepRecord):
            out = self._steps
        else:
            out = self._admissions
        with self._lock:
            out.append(entry)

    def window(self, t0: float, t1: float) -> Window:
        """Every span and record whose interval meets [t0, t1]."""
        with self._lock:
            spans, steps, adm = (list(self._spans), list(self._steps),
                                 list(self._admissions))

        def meets(x):
            return x.t1 >= t0 and x.t0 <= t1
        return Window([s for s in spans if meets(s)],
                      [s for s in steps if meets(s)],
                      [a for a in adm if meets(a)])


RECORDER = Recorder()
