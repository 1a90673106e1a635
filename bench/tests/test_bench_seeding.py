"""Seeding one matrix at a time: a tensor of rank above 2 is drawn matrix by
matrix, each from its own key; the program's packed leaves are what the
reference draws; and at published expert widths the seeding program holds
one layer's tensors beside the packed parameters, not an unpacked stack."""
import importlib.util

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, weights
from bench.tests.conftest import DATA


def _experts():
    spec = importlib.util.spec_from_file_location("bench_test_experts",
                                                  DATA / "experts.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


EXPERTS = _experts()
DRAWS = {
    "ternary": weights.ternary,
    "spread_bf16": lambda k, s: weights.spread_bf16(k, s, 0.05),
    "steps_bf16": lambda k, s: weights.steps_bf16(k, s, 8, 1 / 64),
}


def _key():
    return weights.tensor_key(weights.base_key(2 ** 33 + 7), 3, "up")


@pytest.mark.parametrize("lead", [(5,), (2, 3)], ids=["rank3", "rank4"])
@pytest.mark.parametrize("draw", sorted(DRAWS))
def test_higher_rank_draw_is_one_matrix_per_key(draw, lead):
    draw, key = DRAWS[draw], _key()
    got = np.asarray(draw(key, lead + (8, 12)))
    assert got.shape == lead + (8, 12)
    for i, at in enumerate(np.ndindex(*lead)):
        np.testing.assert_array_equal(
            got[at], np.asarray(draw(jax.random.fold_in(key, i), (8, 12))))
    assert not np.array_equal(got[(0,) * len(lead)], got[(1,) * len(lead)])


@pytest.mark.parametrize("shape", [(12,), (8, 12)], ids=["rank1", "rank2"])
def test_lower_rank_draws_keep_their_key_and_bits(shape):
    eq, key = np.testing.assert_array_equal, _key()
    eq(np.asarray(weights.ternary(key, shape)),
       np.asarray(jax.random.randint(key, shape, -1, 2, jnp.int8)))
    k = jax.random.randint(key, shape, -8, 9, jnp.int32)
    eq(np.asarray(weights.steps_bf16(key, shape, 8, 1 / 64)),
       np.asarray((k.astype(jnp.float32) / 64).astype(jnp.bfloat16)))


TINY = dict(hidden_size=8, num_hidden_layers=4, first_k_dense_replace=1,
            intermediate_size=16, n_routed_experts=3, moe_intermediate_size=12,
            vocab_size=32)


def test_expert_leaves_unpack_to_the_reference_draw():
    """Every packed matrix of the program's leaves is the reference's draw
    of that layer, matrix ``e`` of an expert tensor comes from
    ``fold_in(tensor key, e)``, and each has its own scale."""
    from repro.core.quant import pack_ternary, unpack_ternary
    seed = 2 ** 40 + 9
    p = weights.program_params(seed, EXPERTS, TINY, "packed",
                               EXPERTS.program_shapes(TINY), pack_ternary)
    key = weights.base_key(seed)
    eq = np.testing.assert_array_equal
    kinds = EXPERTS.layer_kinds(TINY)
    moe = p["stack"]["pos0"]
    for layer, kind in enumerate(kinds):
        ref = reference._draw(EXPERTS, key, jnp.int32(layer), TINY, "packed",
                              kind)
        if kind == "dense":
            mlp = p[f"dense{layer}"]["mlp"]
            for name, (k, _) in EXPERTS.linear_shapes(TINY, kind).items():
                eq(np.asarray(unpack_ternary(mlp[name]["w_packed"], k)),
                   np.asarray(ref[name]))
                assert mlp[name]["delta"] == np.float32(
                    weights.ternary_delta(k))
            continue
        r = layer - TINY["first_k_dense_replace"]
        for name, (e, k, n) in EXPERTS.linear_shapes(TINY, kind).items():
            leaf = moe["experts"][name]
            eq(np.asarray(leaf["delta"][r]),
               np.full(e, weights.ternary_delta(k), np.float32))
            for i in range(e):
                mat = np.asarray(unpack_ternary(leaf["w_packed"][r, i], k))
                eq(mat, np.asarray(ref[name][i]))
                eq(mat, np.asarray(weights.ternary(jax.random.fold_in(
                    weights.tensor_key(key, jnp.int32(layer), name), i),
                    (k, n))))
        eq(np.asarray(moe["router"]["w"][r]),
           np.asarray(ref["router"], np.float32))
        eq(np.asarray(moe["norm"]["scale"][r]),
           np.asarray(ref["norm"], np.float32))
    eq(np.asarray(p["embed"]["table"]),
       np.asarray(EXPERTS.top_tensors(key, TINY, "packed")["embed"]))


# Expert stacks alone (no embedding, no dense layer), at published widths:
# Moonlight-16B-A3B (hf:moonshotai/Moonlight-16B-A3B config.json) and the
# 30B-A3B block of Qwen3-30B-A3B (hf:JetLM/SDAR-30B-A3B-Chat config.json
# gives the same widths).
PUBLISHED = {
    "moonlight-16b-a3b": dict(hidden_size=2048, num_hidden_layers=26,
                              n_routed_experts=64, moe_intermediate_size=1408),
    "30b-a3b": dict(hidden_size=2048, num_hidden_layers=48,
                    n_routed_experts=128, moe_intermediate_size=768),
}


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_seeding_holds_one_layer_at_published_widths(name):
    """Lowered and compiled for the CPU, not run: the seeding program's
    scratch memory stays under 1.5 GB beside a packed output of 3.6 and
    7.3 GB (an unpacked stack of layers took 25.5 and 41.1 GB)."""
    from repro.core.quant import pack_ternary
    arch = dict(PUBLISHED[name], first_k_dense_replace=0,
                intermediate_size=0, vocab_size=0)
    shapes = EXPERTS.program_shapes(arch)
    make = weights.generator(EXPERTS, arch, "packed", shapes, pack_ternary)
    mem = jax.jit(make).lower(
        jax.ShapeDtypeStruct((2,), jnp.uint32)).compile().memory_analysis()
    packed = sum(int(np.prod(s.shape)) * s.dtype.itemsize
                 for s in jax.tree_util.tree_leaves(shapes))
    assert mem.output_size_in_bytes >= packed > 3.5e9
    assert mem.temp_size_in_bytes < 1.5e9
