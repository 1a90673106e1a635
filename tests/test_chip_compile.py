"""Compile the serving path's Pallas kernels for one TPU v5e chip that is
described, not attached, at real model widths.

Interpret mode (every other kernel test) never checks block alignment or
VMEM limits; the chip's compiler does, and it is installed here.  Nothing is
run: these tests only prove that Mosaic accepts the kernels as the engine
calls them — ``paged_attention_decode_kernel`` on every pure-decode step,
``paged_prefill_chunk_kernel`` on every chunk step — with the engine's
cache dtype (``ServeConfig.cache_dtype``, float32) and the model's compute
dtype (bfloat16) for the step's new queries and KV.  Two more compile the
engine's whole decode and chunk step programs (two layers at Qwen2.5-3B
widths) and pin the names a profile reader matches: the programs' module
names and the kernels' instruction names declared in
``serving/tracing.py``, against the benchmark's patterns.

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
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention.kernel import paged_attention_decode_kernel
from repro.kernels.paged_prefill.kernel import paged_prefill_chunk_kernel

BLOCK, CHUNK = 16, 32                       # KV block size, prefill chunk
# (slots, block-table width): chip_smoke.py's served width (64 + 32 tokens),
# the engine's default max_len of 256, the benchmark's chat cell (64 slots,
# 128 blocks), and a wide batch with a long table
TABLES = [(8, 6), (8, 16), (64, 128), (64, 256)]
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


# -- the names the benchmark's trace reader matches ---------------------------

def _instructions(hlo_text):
    """HLO instruction names of a compiled module's text."""
    return re.findall(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", hlo_text, re.M)


@pytest.fixture(scope="module")
def served_steps(one_chip):
    """The engine's decode and chunk step programs, compiled for the
    described chip: the benchmark's packed Qwen2.5-3B configuration with
    its widths kept and two layers (vocabulary cut, which no name depends
    on), the fused kernels taken natively as on the chip."""
    import json
    import pathlib

    from bench import harness
    from repro.kernels.paged_attention import ops as pa_ops
    from repro.kernels.paged_prefill import ops as pp_ops
    from repro.models import build_model
    from repro.serving.engine import Engine, ServeConfig

    root = pathlib.Path(__file__).resolve().parents[1]
    config = json.loads(
        (root / "bench" / "configs" / "qwen2.5-3b-packed.json").read_text())
    config["architecture"].update(num_hidden_layers=2, vocab_size=2048)
    cfg = harness.model_config(config)
    b, width = 8, 16
    eng = Engine(cfg, None, ServeConfig(
        max_batch=b, max_len=width * BLOCK, kv_block_size=BLOCK,
        num_kv_blocks=1 + b * width, prefill_chunk=CHUNK, attn_impl="fused"))
    params = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda p: eng.model.init_cache(
        p, eng.scfg.pool_blocks(), BLOCK, jnp.dtype(eng.scfg.cache_dtype)),
        params)

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                    sharding=one_chip)

    params, cache = placed(params), placed(cache)
    vec, f32 = sd((b,), jnp.int32), sd((b,), jnp.float32)
    keys, bt = sd((b, 2), jnp.uint32), sd((b, width), jnp.int32)
    saved = pa_ops._interpret_default, pp_ops._interpret_default
    pa_ops._interpret_default = pp_ops._interpret_default = lambda: False
    try:
        decode = eng._decode.lower(params, vec, cache, vec, keys, f32, f32,
                                   bt).compile().as_text()
        chunk = eng._chunk.lower(params, sd((b, CHUNK), jnp.int32), cache,
                                 vec, vec, sd((b,), bool), keys, f32, f32,
                                 bt).compile().as_text()
    finally:
        pa_ops._interpret_default, pp_ops._interpret_default = saved
    return {"decode": decode, "chunk": chunk}


@pytest.mark.parametrize("kind", ["decode", "chunk"])
def test_trace_reader_names_match_the_compiled_steps(served_steps, kind):
    from bench import harness
    from bench.metrics import paged_attention_roofline as pa
    from bench.metrics import paged_prefill_roofline as pp
    from repro.serving import tracing

    program, declared, pattern, kernel = {
        "decode": (harness.DECODE_PROGRAM, tracing.DECODE_PROGRAM, pa.KERNEL,
                   tracing.DECODE_KERNEL),
        "chunk": (harness.CHUNK_PROGRAM, tracing.CHUNK_PROGRAM, pp.KERNEL,
                  tracing.PREFILL_KERNEL),
    }[kind]
    text = served_steps[kind]
    module = re.match(r"HloModule ([\w.\-]+)", text).group(1)
    assert module == declared
    assert re.search(program, module)
    hits = [n for n in _instructions(text) if re.search(pattern, n)]
    assert hits, f"no instruction of the {kind} step matches {pattern}"
    assert {n.split(".")[0] for n in hits} == {kernel}
