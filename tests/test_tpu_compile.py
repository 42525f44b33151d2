"""Compile the serving path for a described TPU v5e chip (no chip needed).

Interpret-mode kernel tests never see the TPU lowering's tiling rules or the
device's memory limit; these tests hand the chip's compiler the Pallas
kernels at qwen2-1.5b widths and the engine's full-width jitted programs, so
a kernel or program that the chip would refuse fails here first.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library at a time, and
under pytest-xdist only the worker that runs this file loads it.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.models import NOSHARD, init_cache, init_params

HBM_BYTES = 16e9            # TPU v5e: 16 GB of HBM per chip

# the chip smoke's engine shape: EngineConfig(paged=True, max_batch=32,
# max_len=4096), page 16 -> dense-equivalent pool of 32 * 256 + 1 pages
BATCH, MAX_LEN, PAGE = 32, 4096, 16
N_PAGES = BATCH * (MAX_LEN // PAGE) + 1


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A described-chip compile is written to the persistent cache but can
    never be read back without a chip; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def chip(one_chip, no_persistent_cache):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def like(tree):
        return jax.tree.map(lambda a: spec(a.shape, a.dtype), tree)

    return spec, like


def _compile(fn, *args, **kw):
    return jax.jit(fn, **kw).lower(*args).compile()


CFG = get_config("qwen2-1.5b")
HQ, KH, HD = CFG.num_heads, CFG.num_kv_heads, CFG.head_dim


def test_paged_decode_attention_compiles(chip):
    from repro.kernels import paged_decode_attention
    spec, _ = chip
    c = _compile(paged_decode_attention,
                 spec((BATCH, HQ, HD), jnp.float32),
                 spec((N_PAGES, PAGE, KH, HD), jnp.bfloat16),
                 spec((N_PAGES, PAGE, KH, HD), jnp.bfloat16),
                 spec((BATCH, MAX_LEN // PAGE), jnp.int32),
                 spec((BATCH,), jnp.int32), spec((BATCH,), jnp.bool_))
    assert "tpu_custom_call" in c.as_text()


def test_flash_attention_compiles(chip):
    from repro.kernels.flash_attention import flash_attention
    spec, _ = chip
    c = _compile(flash_attention, spec((1, HQ, 1024, HD), jnp.bfloat16),
                 spec((1, KH, 1024, HD), jnp.bfloat16),
                 spec((1, KH, 1024, HD), jnp.bfloat16))
    assert "tpu_custom_call" in c.as_text()


def test_rmsnorm_compiles(chip):
    from repro.kernels.rmsnorm import rmsnorm
    spec, _ = chip
    c = _compile(rmsnorm, spec((BATCH, 1024, CFG.d_model), jnp.bfloat16),
                 spec((CFG.d_model,), jnp.float32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("window", [0, 512])
def test_decode_attention_compiles(chip, window):
    from repro.kernels.decode_attention import decode_attention
    spec, _ = chip
    c = _compile(lambda q, k, v, kp, qp: decode_attention(
        q, k, v, kp, qp, window=window),
        spec((BATCH, HQ, HD), jnp.bfloat16),
        spec((BATCH, KH, 2048, HD), jnp.bfloat16),
        spec((BATCH, KH, 2048, HD), jnp.bfloat16),
        spec((BATCH, 2048), jnp.int32), spec((BATCH,), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


def test_decode_attention_q8_compiles(chip):
    from repro.kernels.decode_attention_q8 import decode_attention_q8
    spec, _ = chip
    kv = spec((BATCH, KH, 2048, HD), jnp.int8)
    sc = spec((BATCH, KH, 2048, 1), jnp.float32)
    c = _compile(decode_attention_q8, spec((BATCH, HQ, HD), jnp.bfloat16),
                 kv, sc, kv, sc, spec((BATCH, 2048), jnp.int32),
                 spec((BATCH,), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


@pytest.fixture(scope="module")
def engine_state(chip):
    """Full-width params and the chip smoke's paged pool, as shapes."""
    _, like = chip
    params = like(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), CFG)))
    caches = like(jax.eval_shape(
        lambda: init_cache(CFG, BATCH, MAX_LEN, paged_pool=(N_PAGES, PAGE))))
    return params, caches


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("sampled", [False, True])
def test_paged_decode_block_program_fits_one_chip(chip, engine_state,
                                                  sampled):
    from repro.serving.engine import _paged_decode_block_kernel
    spec, _ = chip
    params, caches = engine_state
    rows = spec((BATCH,), jnp.int32)
    c = _paged_decode_block_kernel.lower(
        CFG, NOSHARD, 16, sampled, params, rows, caches,
        spec((BATCH, MAX_LEN // PAGE), jnp.int32), rows,
        spec((BATCH,), jnp.bool_), spec((BATCH, 2), jnp.uint32),
        spec((BATCH,), jnp.float32), rows,
        spec((BATCH,), jnp.float32)).compile()
    m = c.memory_analysis()
    # the donated pool is updated in place, not copied
    assert m.alias_size_in_bytes > 0
    assert _device_bytes(c) < HBM_BYTES


def test_prefill_program_fits_one_chip(chip, engine_state):
    from repro.serving.engine import _prefill_kernel
    spec, _ = chip
    params, caches = engine_state
    rows = spec((BATCH,), jnp.int32)
    scalar = spec((), jnp.int32)
    c = _prefill_kernel.lower(
        CFG, NOSHARD, True, params, spec((1, 1024), jnp.int32), scalar,
        caches, scalar, spec((1, 1024 // PAGE), jnp.int32), rows, rows,
        spec((BATCH, 2), jnp.uint32), spec((BATCH,), jnp.float32), rows,
        spec((BATCH,), jnp.float32)).compile()
    assert c.memory_analysis().alias_size_in_bytes > 0
    assert _device_bytes(c) < HBM_BYTES


def test_paged_decode_block_program_attends_through_the_kernel(chip,
                                                               engine_state):
    """Lowered for the v5e, the decode block takes the Pallas paged kernel
    (the platform choice is made inside the program) and no longer makes
    the dense (B, bucket, KH, hd) K and V gather: its temporaries are
    smaller than that gather alone."""
    from repro.serving.engine import _paged_decode_block_kernel
    spec, _ = chip
    params, caches = engine_state
    rows = spec((BATCH,), jnp.int32)
    c = _paged_decode_block_kernel.lower(
        CFG, NOSHARD, 16, False, params, rows, caches,
        spec((BATCH, MAX_LEN // PAGE), jnp.int32), rows,
        spec((BATCH,), jnp.bool_), spec((BATCH, 2), jnp.uint32),
        spec((BATCH,), jnp.float32), rows,
        spec((BATCH,), jnp.float32)).compile()
    assert "tpu_custom_call" in c.as_text()
    # the gather path gathered K and V in the query's dtype, float32
    dense_gather = 2 * BATCH * MAX_LEN * KH * HD * 4
    assert c.memory_analysis().temp_size_in_bytes < dense_gather
