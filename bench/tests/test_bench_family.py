"""The architecture module a configuration names: the Qwen2 module reads
exactly what the benchmark read before there were modules, and the leaf
walk fills any program layout a module maps (layers of different kinds,
several pattern positions, tensors outside the stack, expert tensors)."""
import hashlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, reference, weights
from bench.tests.conftest import tiny_cell

SEED = 2 ** 33 + 5

# Recorded on the CPU with the benchmark's code as it stood before the
# architecture modules (weights and reference written for Qwen2 alone), by
# the same calls as below: the sha256 of every program leaf (path, dtype,
# shape, bytes, in tree order), and of the reference's normed hidden states;
# the widest logit gaps of ``reference.compare``.
BEFORE = {
    "packed": {
        "params_sha256": "8d43394bcea81515cd45bfd2ace90985694db510e4c2c01f1"
                         "50f60747a75f8c8",
        "gap": 0.7083151340484619,
        "control_gap": 0.12815189361572266,
        "hidden_sha256": "0712d615ca20aa4276666de233ee4527b2082f84dbf7a49b0"
                         "c8dc4945b6838fe",
        "control_hidden_sha256": "6482734451e8c7fcbdbfa05e484c4455db3b52b49"
                                 "80c7344c3f3b0a58c69b2e7",
    },
    "bf16": {
        "params_sha256": "5423d0c0de9dd145a38209d6bae375b0382d2d00281e1e847"
                         "24a43220fef5b25",
        "gap": 0.7542998790740967,
        "control_gap": 0.028120309114456177,
        "hidden_sha256": "fdfe0b4531280ea15a9adc9cd52f38b820480999707a76436"
                         "800a1e4d32af583",
        "control_hidden_sha256": "26a2154f5c1855efe1e804cc63018492034cddb25"
                                 "d13e7ff5e217c4c1b42e97c",
    },
}


def _family(form):
    config = tiny_cell(f"tiny-{form}.chat").config
    family = harness.family_of(config)
    return config, family, family.architecture(config)


@pytest.mark.parametrize("form", ["packed", "bf16"])
def test_program_params_are_the_values_before_the_modules(form):
    from repro.core.quant import pack_ternary
    from repro.models import build_model
    config, family, arch = _family(form)
    shapes = jax.eval_shape(build_model(harness.model_config(config)).init,
                            jax.random.PRNGKey(0))
    params = weights.program_params(SEED, family, arch, form, shapes,
                                    pack_ternary)
    h = hashlib.sha256()
    for p, leaf in jax.tree_util.tree_leaves_with_path(params):
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(p), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    assert h.hexdigest() == BEFORE[form]["params_sha256"]


@pytest.mark.parametrize("form", ["packed", "bf16"])
def test_reference_reads_what_it_read_before_the_modules(form):
    _, family, arch = _family(form)
    rng = np.random.default_rng(7)
    seqs = [(rng.integers(0, 512, n).tolist(),
             rng.integers(0, 512, m).tolist())
            for n, m in ((20, 9), (33, 16), (8, 4))]
    res = reference.compare(SEED, family, arch, form, seqs, pad_len=64,
                            pad_out=16, control=True)
    assert res["gap"] == BEFORE[form]["gap"]
    assert res["control_gap"] == BEFORE[form]["control_gap"]
    tokens = np.zeros((3, 64), np.int32)
    for i, (p, o) in enumerate(seqs):
        tokens[i, :len(p) + len(o)] = p + o
    idx = np.tile(np.arange(16, dtype=np.int32), (3, 1))
    for control, key in ((False, "hidden_sha256"),
                         (True, "control_hidden_sha256")):
        h, _ = reference.forward_hidden(SEED, family, arch, form, tokens, idx,
                                        control=control)
        assert (hashlib.sha256(np.asarray(h).tobytes()).hexdigest()
                == BEFORE[form][key])


# -- the leaf walk -------------------------------------------------------------

E, K, N, D, V = 4, 8, 16, 8, 32
KINDS = ["dense", "experts", "experts", "experts", "experts"]


def _layer(key, layer, arch, form, kind):
    if kind == "dense":
        return {"w1": weights.ternary(weights.tensor_key(key, layer, "w1"),
                                      (K, N))}
    return {"experts": weights.ternary(
                weights.tensor_key(key, layer, "experts"), (E, K, N)),
            "norm": 1 + weights.steps_bf16(
                weights.tensor_key(key, layer, "norm"), (D,), 8, 1 / 64)}


def _top(key, arch, form):
    return {"embed": weights.spread_bf16(weights.tensor_key(key, -1, "embed"),
                                         (V, D), 0.02)}


# A leading dense layer outside the scan, then expert layers scanned as two
# pattern positions (layers 1, 3 at pos0; 2, 4 at pos1).
LEAVES = {
    "embed/table": ((V, D), jnp.bfloat16, (None, "embed")),
    "first/mlp/w_packed": ((K // 4, N), jnp.uint8, (0, "w1")),
    "first/mlp/delta": ((), jnp.float32, (0, "w1")),
    "stack/pos0/moe/w_packed": ((2, E, K // 4, N), jnp.uint8,
                                ([1, 3], "experts")),
    "stack/pos0/moe/delta": ((2, E), jnp.float32, ([1, 3], "experts")),
    "stack/pos1/moe/w_packed": ((2, E, K // 4, N), jnp.uint8,
                                ([2, 4], "experts")),
    "stack/pos1/moe/delta": ((2, E), jnp.float32, ([2, 4], "experts")),
    "stack/pos1/norm/scale": ((2, D), jnp.float32, ([2, 4], "norm")),
}


def _shapes(paths):
    tree = {}
    for path in paths:
        shape, dtype, _ = LEAVES.get(path, ((1,), jnp.float32, None))
        node = tree
        *head, last = path.split("/")
        for part in head:
            node = node.setdefault(part, {})
        node[last] = jax.ShapeDtypeStruct(shape, dtype)
    return tree


SYNTHETIC = types.SimpleNamespace(
    layer_kinds=lambda arch: KINDS, layer_tensors=_layer, top_tensors=_top,
    program_leaf=lambda path, arch: (LEAVES[path][2] if path in LEAVES
                                     else None))


def test_leaf_walk_fills_any_layout_the_module_maps():
    from repro.core.quant import pack_ternary, unpack_ternary
    p = weights.program_params(11, SYNTHETIC, {}, "packed", _shapes(LEAVES),
                               pack_ternary)
    key = weights.base_key(11)
    drawn = [_layer(key, jnp.int32(i), {}, "packed", k)
             for i, k in enumerate(KINDS)]
    eq = np.testing.assert_array_equal
    eq(np.asarray(p["embed"]["table"]),
       np.asarray(_top(key, {}, "packed")["embed"]))
    eq(np.asarray(unpack_ternary(p["first"]["mlp"]["w_packed"], K)),
       np.asarray(drawn[0]["w1"]))
    assert p["first"]["mlp"]["delta"] == np.float32(weights.ternary_delta(K))
    for pos, layers in (("pos0", [1, 3]), ("pos1", [2, 4])):
        moe = p["stack"][pos]["moe"]
        assert moe["delta"].shape == (2, E)          # one per expert
        eq(np.asarray(moe["delta"]),
           np.full((2, E), weights.ternary_delta(K), np.float32))
        for r, layer in enumerate(layers):
            for e in range(E):
                eq(np.asarray(unpack_ternary(moe["w_packed"][r, e], K)),
                   np.asarray(drawn[layer]["experts"][e]))
    for r, layer in enumerate([2, 4]):
        eq(np.asarray(p["stack"]["pos1"]["norm"]["scale"][r]),
           np.asarray(drawn[layer]["norm"], np.float32))


def test_leaf_walk_refuses_a_leaf_the_module_does_not_name():
    from repro.core.quant import pack_ternary
    with pytest.raises(KeyError, match="stack/pos1/router/w"):
        weights.program_params(11, SYNTHETIC, {}, "packed",
                               _shapes(list(LEAVES) + ["stack/pos1/router/w"]),
                               pack_ternary)


def test_configuration_without_a_module_is_an_error():
    config = dict(tiny_cell("tiny-packed.chat").config)
    del config["arch"]
    with pytest.raises(harness.BadCell, match="architecture module"):
        harness.model_config(config)
    with pytest.raises(harness.BadCell, match="no architecture module"):
        harness.model_config(dict(config, arch="no-such-family"))
