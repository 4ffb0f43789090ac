"""ResNet-18 for CIFAR-10 as ParM (arXiv:1905.00863) deploys it.

Sizes come from ``resnet18-cifar.json`` beside this file.  This module makes
the weights from the seed in the program's parameter layout (that of
``repro.models.cnn.init_resnet``), hands them to the program's coded
classification deployment (``deploy``, threads engine), and holds the plain
reference and the work counts that the metrics use.  The reference imports
nothing of the program: the published architecture (arXiv:1512.03385,
Table 1, CIFAR form) written out with ``lax.conv_general_dilated`` in float32
under "highest" matmul precision — a 3x3 stem, BasicBlocks of two 3x3
convolutions with ReLU after the residual add, stride 2 and a 1x1
projection shortcut at the first block of each later stage, a global
average pool and a dense head.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# the deployed forward program, as it is named in a device trace
FORWARD_PROGRAMS = ("jit_resnet_fwd",)


# ------------------------------------------------------------- weights ---
def seed_key(seed: int):
    """A PRNG key from any whole number, 64-bit seeds included."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def _layout(m):
    """[(path, HWIO shape, stride)] of every convolution, in forward order:
    path is ("stem",) or (stage, block, name)."""
    c_in = m["image_shape"][-1]
    convs = [(("stem",), (3, 3, c_in, m["stages"][0]), 1)]
    c_in = m["stages"][0]
    for si, c in enumerate(m["stages"]):
        for bi in range(m["blocks_per_stage"]):
            stride = 2 if si > 0 and bi == 0 else 1
            first = c_in if bi == 0 else c
            convs.append(((si, bi, "c1"), (3, 3, first, c), stride))
            convs.append(((si, bi, "c2"), (3, 3, c, c), 1))
            if bi == 0 and c_in != c:
                convs.append(((si, bi, "proj"), (1, 1, c_in, c), stride))
        c_in = c
    return convs


def init_weights(cfg, seed: int):
    """Every weight, made on the device in one jitted call from the seed, in
    float32 and in the program's layout: {"stem", "stages": [[{"c1", "c2"
    [, "proj"]}]], "head", "head_b"}."""
    m = cfg["model"]
    convs = _layout(m)
    width = m["stages"][-1]

    def make(key):
        keys = jax.random.split(key, len(convs) + 1)
        p = {"stages": [[{} for _ in range(m["blocks_per_stage"])]
                        for _ in m["stages"]]}
        for k, (path, shape, _) in zip(keys[1:], convs):
            fan_in = shape[0] * shape[1] * shape[2]
            w = jax.random.normal(k, shape, jnp.float32) \
                * math.sqrt(2.0 / fan_in)
            if path == ("stem",):
                p["stem"] = w
            else:
                si, bi, name = path
                p["stages"][si][bi][name] = w
        p["head"] = jax.random.normal(keys[0], (width, m["n_classes"]),
                                      jnp.float32) * math.sqrt(2.0 / width)
        p["head_b"] = jnp.zeros((m["n_classes"],), jnp.float32)
        return p
    return jax.block_until_ready(jax.jit(make)(seed_key(seed)))


def input_shape(cfg):
    return tuple(cfg["model"]["image_shape"])


# ----------------------------------------------------------- deployment ---
def _layout_of_pools(cfg):
    from repro.serving.strategy import get_strategy
    d = cfg["deployment"]
    return get_strategy(d["strategy"]).layout(d["m"], d["k"], d["r"])


def instances(cfg):
    """Instance ids of the deployment's member and parity instances."""
    from repro.serving.scenarios import instance_id
    d, pools = cfg["deployment"], _layout_of_pools(cfg)
    return ([instance_id("main", i) for i in range(pools.main)]
            + [instance_id(f"parity{j}", i) for j in range(d["r"])
               for i in range(pools.parity)])


def member_instances(cfg):
    from repro.serving.scenarios import instance_id
    return [instance_id("main", i) for i in range(_layout_of_pools(cfg).main)]


def deploy(cfg, params, delay_fn):
    """The coded classification deployment through the program's normal
    entry point: ``deploy(DeploymentSpec(...), engine="threads")`` serving
    the program's ``resnet_fwd``; the parity model serves the deployed
    weights (see ``assumed``)."""
    from repro.models import cnn
    from repro.serving.api import BatchingPolicy, DeploymentSpec, deploy
    d = cfg["deployment"]
    spec = DeploymentSpec(
        fwd=jax.jit(cnn.resnet_fwd), params=params, parity_params=params,
        strategy=d["strategy"], scheme=d["scheme"], backend=d["backend"],
        k=d["k"], r=d["r"], m=d["m"],
        batching=BatchingPolicy(max_size=d["batch_max_size"]),
        delay_fn=delay_fn)
    return deploy(spec, engine=d["engine"])


def code_coeffs(cfg):
    """The [r, k] coefficients of the deployment's code (the sum code's
    Vandermonde rows, C[j, i] = (i + 1) ** j), written out here so the
    reference's decode does not read the program's."""
    d = cfg["deployment"]
    if d["scheme"] != "sum":
        raise ValueError(f"reference decode knows the sum code only, "
                         f"not {d['scheme']!r}")
    return np.array([[(i + 1) ** j for i in range(d["k"])]
                     for j in range(d["r"])], np.float64)


# ------------------------------------------------------------ reference ---
def _conv(x, w, stride):
    """NHWC x HWIO convolution, "SAME" padding, in float32."""
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)


def reference_logits(cfg, weights, images):
    """Logits [B, n_classes] of a plain float32 forward over images
    [B, H, W, C]."""
    m = cfg["model"]
    x = jnp.asarray(images, jnp.float32)
    x = jax.nn.relu(_conv(x, weights["stem"], 1))
    for si, blocks in enumerate(weights["stages"]):
        for bi, blk in enumerate(blocks):
            stride = 2 if si > 0 and bi == 0 else 1
            h = jax.nn.relu(_conv(x, blk["c1"], stride))
            h = _conv(h, blk["c2"], 1)
            short = _conv(x, blk["proj"], stride) \
                if "proj" in blk else x[:, ::stride, ::stride]
            x = jax.nn.relu(h + short)
    x = x.mean(axis=(1, 2))
    if x.shape[-1] != m["stages"][-1]:
        raise ValueError("feature width differs from the last stage")
    return jnp.matmul(x, weights["head"],
                      precision=jax.lax.Precision.HIGHEST) + weights["head_b"]


# --------------------------------------------------------- work counts ---
def forward_work(cfg):
    """(FLOPs, bytes) of one batch-1 forward: every convolution's and the
    head's multiply-adds (2 FLOPs each; ReLU, adds and the pool are left
    out), and the bytes it cannot avoid: every weight read once, the image
    read and the logits written.  Activations are not counted, so the bytes
    are a floor."""
    m = cfg["model"]
    H, W, C = m["image_shape"]
    flops, h, w = 0.0, H, W
    for path, (kh, kw, ci, co), stride in _layout(m):
        if path[-1] == "c1":        # a block's output size ("SAME" padding)
            h, w = -(-h // stride), -(-w // stride)
        flops += 2.0 * h * w * kh * kw * ci * co
    flops += 2.0 * m["stages"][-1] * m["n_classes"]
    itemsize = jnp.dtype(m["dtype"]).itemsize
    return flops, itemsize * (param_count(cfg) + H * W * C + m["n_classes"])


def param_count(cfg):
    m = cfg["model"]
    n = sum(math.prod(shape) for _, shape, _ in _layout(m))
    return n + (m["stages"][-1] + 1) * m["n_classes"]
