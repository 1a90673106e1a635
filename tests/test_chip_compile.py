"""Compile the serving path's Pallas kernels for one TPU v5e chip that is
described, not attached, at real model widths.

Interpret mode (every other kernel test) never checks block alignment or
VMEM limits; the chip's compiler does, and it is installed here.  Nothing is
run: these tests only prove that Mosaic accepts the kernels as the engine
calls them — ``paged_attention_decode_kernel`` on every pure-decode step,
``paged_prefill_chunk_kernel`` on every chunk step — with the engine's
cache dtype (``ServeConfig.cache_dtype``, float32) and the model's compute
dtype (bfloat16) for the step's new queries and KV.

``w2a8_gemv`` is left out: no served path calls it (``packed_matmul``
reaches it only through ``QuantConfig.use_kernel``, which nothing sets), and
it does not compile for the chip today — it reads the scalar ``delta_ref[0]``
from a ``pl.ANY`` operand, and placed in SMEM instead, Mosaic cannot lower
the int8 subtraction in ``_unpack`` (ROADMAP A2).

The topology is described inside a module-scoped fixture and never at
import: only one process at a time may load the TPU library, and every
test worker imports this file.  Keep these tests in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention.kernel import paged_attention_decode_kernel
from repro.kernels.paged_prefill.kernel import paged_prefill_chunk_kernel

BLOCK, CHUNK = 16, 32                       # KV block size, prefill chunk
# (slots, block-table width): chip_smoke.py's served width (64 + 32 tokens),
# the engine's default max_len of 256, and a wide batch with a long table
TABLES = [(8, 6), (8, 16), (64, 256)]
CACHE_DTYPE = jnp.float32                   # ServeConfig.cache_dtype
COMPUTE_DTYPE = jnp.bfloat16                # ModelConfig.compute_dtype

WIDTHS = {                                  # (Hkv, g = Hq / Hkv, Dh)
    "qwen1.5-0.5b": (16, 1, 64),
    "qwen2.5-3b": (2, 8, 128),
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        jax.config.update("jax_enable_compilation_cache", enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, shapes, one_chip, **static):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = fn.lower(*args, interpret=False, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("b,table", TABLES, ids=lambda v: str(v))
@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_decode_kernel_compiles_for_v5e(one_chip, arch, b, table):
    hkv, g, dh = WIDTHS[arch]
    pool = ((1 + b * table, hkv, BLOCK, dh), CACHE_DTYPE)   # + trash block
    _compile(paged_attention_decode_kernel, [
        ((b, hkv, g, dh), COMPUTE_DTYPE),            # q
        ((b, hkv, 1, dh), COMPUTE_DTYPE),            # k_new
        ((b, hkv, 1, dh), COMPUTE_DTYPE),            # v_new
        pool, pool,
        ((b, table), jnp.int32),                     # block_tables
        ((b,), jnp.int32),                           # idx
    ], one_chip, scale=dh ** -0.5)


@pytest.mark.parametrize("b,table", TABLES, ids=lambda v: str(v))
@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_prefill_chunk_kernel_compiles_for_v5e(one_chip, arch, b, table):
    hkv, g, dh = WIDTHS[arch]
    pool = ((1 + b * table, hkv, BLOCK, dh), CACHE_DTYPE)   # + trash block
    _compile(paged_prefill_chunk_kernel, [
        ((b, hkv, CHUNK * g, dh), COMPUTE_DTYPE),    # q
        ((b, hkv, CHUNK, dh), COMPUTE_DTYPE),        # k_chunk
        ((b, hkv, CHUNK, dh), COMPUTE_DTYPE),        # v_chunk
        pool, pool,
        ((b, table), jnp.int32),                     # block_tables
        ((b,), jnp.int32),                           # start
        ((b,), jnp.int32),                           # lens
    ], one_chip, scale=dh ** -0.5)
