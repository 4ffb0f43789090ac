"""Production mesh construction.

Target hardware: TPU v5e pods, 256 chips each. Single-pod mesh is
(data=16, model=16); multi-pod is (pod=2, data=16, model=16) = 512 chips,
with the batch sharded over ('pod', 'data') — the 'pod' axis only ever
carries data-parallel gradient reductions, so the slower inter-pod links see
one all-reduce per step.

``make_production_mesh`` is a function (not a module constant): importing
this module never touches JAX device state. The dry-run entrypoint sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; regular tests and benches see the 1 real CPU device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes, devices):
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices, have {len(devices)} — run via "
            "repro.launch.dryrun which forces 512 host devices")
    return _mesh(shape, axes, devices)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh over the first devices: CPU integration tests (forced host
    devices) and the four chips of one TPU host (``chip_smoke.py``)."""
    n = 1
    for s in shape:
        n *= s
    return _mesh(shape, axes, jax.devices()[:n])
