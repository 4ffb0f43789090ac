"""The one traffic generator: turns a mix's parameters into requests.

A mix is a JSON file under ``traffic/``; its ``loop`` names how it is sent:

* ``closed_waves`` (batch jobs): a wave of ``wave_size`` requests is sent at
  once and the next wave goes when the last request of this one finished.
  Each request draws its prompt length from ``prompt_len`` and serves it at
  the smallest of ``prompt_len.buckets`` that holds it (the longest bucket
  for longer ones); each wave draws one output length, shared by its
  requests, from ``output_len``, clipped to [``lo``, ``hi``].
* ``closed_queries`` (one-shot queries): ``clients`` callers each keep one
  query of ``batch`` inputs outstanding and send the next when its answer
  comes.  Inputs come from a pool of ``inputs.distinct`` arrays of the
  configuration's input shape, each element N(0, 1), sent in turn.

Every seed gets the same multiset of lengths, in its own order
(``stratified``): the seed reorders the work, it does not change how much
there is, so runs with different seeds differ no more than runs of one seed.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Iterator, List

import numpy as np


def quantiles(dist: dict, u: np.ndarray) -> np.ndarray:
    """The quantiles ``u`` of a distribution: ``uniform`` over [lo, hi], or
    ``lognormal`` of the given ``mean`` with log-scale ``sigma``."""
    kind = dist["dist"]
    if kind == "uniform":
        return dist["lo"] + (dist["hi"] - dist["lo"]) * u
    if kind == "lognormal":
        s = dist["sigma"]
        mu = math.log(dist["mean"]) - s * s / 2
        return np.exp([mu + s * NormalDist().inv_cdf(float(p)) for p in u])
    raise ValueError(f"unknown distribution {kind!r}")


def stratified(rng, n: int, dist: dict) -> np.ndarray:
    """``n`` draws at the mid-quantiles ``(i + 0.5) / n`` of ``dist``, in the
    order of a permutation from ``rng``."""
    return quantiles(dist, (np.arange(n) + 0.5) / n)[rng.permutation(n)]


@dataclass(frozen=True)
class Request:
    prompt_len: int
    max_new: int


def buckets(mix: dict) -> List[int]:
    """The prompt lengths a mix serves, shortest first."""
    return sorted(mix["prompt_len"]["buckets"])


def to_bucket(lengths, sizes: List[int]) -> np.ndarray:
    """Each length served at the smallest bucket that holds it."""
    i = np.searchsorted(sizes, np.ceil(lengths), side="left")
    return np.asarray(sizes)[np.minimum(i, len(sizes) - 1)]


def waves(mix: dict, rng) -> Iterator[List[Request]]:
    """Endless waves of a ``closed_waves`` mix.  Output lengths come in
    cycles of ``output_len.strata`` waves, each cycle the same set of lengths
    in a fresh order; every wave holds the same set of prompt lengths."""
    size, out = mix["wave_size"], mix["output_len"]
    sizes = buckets(mix)
    while True:
        for m in stratified(rng, out["strata"], out):
            m = int(round(min(max(m, out["lo"]), out["hi"])))
            prompts = to_bucket(stratified(rng, size, mix["prompt_len"]),
                                sizes)
            yield [Request(int(p), m) for p in prompts]


def inputs(mix: dict, shape, rng) -> np.ndarray:
    """The input pool of a ``closed_queries`` mix: [distinct, batch,
    *shape] float32.  Every seed draws as many of the same shape."""
    return rng.standard_normal(
        (mix["inputs"]["distinct"], mix["batch"]) + tuple(shape),
        dtype=np.float32)
