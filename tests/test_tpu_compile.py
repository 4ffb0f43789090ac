"""Every Pallas kernel builder of ``repro.kernels``, compiled by Mosaic for a
described TPU v5e chip at serving widths.

Interpret mode accepts block shapes the chip's compiler refuses (rank-1
blocks of size 1, rows of a tiny table that are not (8, 128)-aligned) and
VMEM working sets that do not fit.  These compiles run the TPU compiler for
a chip that is described, not attached, so they guard the device path from
a CPU-only machine; nothing runs and no result is checked here.

The topology is described inside a fixture (never at import): only one
process at a time may load the TPU library, and the test workers all import
this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.berrut_encoder import berrut_encode
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_encode_forward import fused_encode_forward
from repro.kernels.learned_encoder import learned_project
from repro.kernels.multigroup_decode import multigroup_decode
from repro.kernels.parity_decode import parity_decode
from repro.kernels.parity_encode import parity_encode

# chip_smoke.py's kernel widths: 224x224x3 queries, the OLMo-1B vocabulary
# and attention shape (16 heads x 128, MHA), k = 2 members.
F, V, B, K = 224 * 224 * 3, 50304, 8, 2
HEADS, HD, SEQ = 16, 128, 1024
BF, F32 = jnp.bfloat16, jnp.float32

# name -> (builder, argument shapes as (shape, dtype))
CASES = {
    "parity_encode": (parity_encode, [((K, B, F), BF), ((K,), F32)]),
    "parity_decode": (parity_decode, [((B, V), F32), ((K, B, V), F32),
                                      ((K,), F32), ((), F32)]),
    "fused_encode_forward r=1": (fused_encode_forward,
                                 [((K, B, F), BF), ((1, K), F32),
                                  ((1, F, 1024), BF)]),
    "fused_encode_forward r=2": (fused_encode_forward,
                                 [((K, B, F), BF), ((2, K), F32),
                                  ((2, F, 1024), BF)]),
    "multigroup_decode G=4": (multigroup_decode,
                              [((4, B, V), F32), ((4, K, B, V), F32),
                               ((4, K + 1), F32)]),
    "learned_project r=1": (learned_project, [((4, B, F), BF),
                                              ((4, 1), F32)]),
    "learned_project r=2": (learned_project, [((4, B, F), BF),
                                              ((4, 2), F32)]),
    "berrut_encode r=2": (berrut_encode, [((K, B, F), BF), ((2, K), F32)]),
    "flash_attention": (flash_attention, [((1, SEQ, HEADS, HD), BF)] * 3),
    "decode_attention": (decode_attention,
                         [((B, HEADS, HD), BF), ((B, SEQ, HEADS, HD), BF),
                          ((B, SEQ, HEADS, HD), BF), ((B,), jnp.int32)]),
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_compiles_for_mosaic(one_chip, name):
    builder, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    # interpret=False is every builder's default: this is the chip's lowering
    compiled = jax.jit(builder).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("program", ["member_decode", "parity_decode"])
def test_decode_program_updates_pool_in_place(one_chip, program):
    """The serving decode programs at OLMo-1B width (8 slots, 1024
    positions) alias their donated KV pool to the pool they return and hold
    no pool-sized temporary: a step writes one row per stream.  Two layers
    stand for sixteen; the layer scan keeps the program's shape the same."""
    from repro.configs.base import get_config
    from repro.models import transformer as T
    from repro.serving.generation import _transformer_substrate

    cfg = get_config("olmo-1b").replace(n_layers=2)

    def abstract(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = abstract(jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.PRNGKey(0))))
    pool = abstract(jax.eval_shape(lambda: T.init_cache(cfg, B, SEQ)))
    pool_bytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(pool))
    feed = ((B, 1), jnp.int32) if program == "member_decode" else \
        ((B, 1, cfg.d_model), BF)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in (((B,), jnp.int32), feed)]
    _, decode_fn, _, _ = _transformer_substrate(cfg, None)
    compiled = decode_fn.programs[program].lower(params, pool,
                                                 *args).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes / 16
