"""Inputs from the seed: the served model's weights equal the reference's,
and traffic offers the same work on every seed, in another order."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import traffic, weights
from bench.tests.conftest import tiny_cell


@pytest.mark.parametrize("form", ["packed", "bf16"])
def test_program_weights_are_the_reference_weights(form):
    """The one jitted call that fills the program's layout draws exactly
    what the reference draws layer by layer from the same seed."""
    from repro.core.quant import pack_ternary, unpack_ternary
    from repro.models import build_model
    from bench import harness

    config = tiny_cell(f"tiny-{form}.chat").config
    qwen2 = harness.family_of(config)
    arch = qwen2.architecture(config)
    shapes = jax.eval_shape(build_model(harness.model_config(config)).init,
                            jax.random.PRNGKey(0))
    seed = 2 ** 33 + 5
    params = weights.program_params(seed, qwen2, arch, form, shapes,
                                    pack_ternary)
    key = weights.base_key(seed)
    stack = params["stack"]["pos0"]
    for layer in range(arch["num_hidden_layers"]):
        ref = qwen2.layer_tensors(key, jnp.int32(layer), arch, form)
        for mod, name in (("attn", "wq"), ("attn", "wo"), ("mlp", "down")):
            p = stack[mod][name]
            got = (unpack_ternary(p["w_packed"][layer], ref[name].shape[0])
                   if form == "packed" else p["w"][layer])
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref[name]))
        np.testing.assert_array_equal(np.asarray(stack["attn"]["wk"]["b"][layer]),
                                      np.asarray(ref["bk"]))
        np.testing.assert_array_equal(np.asarray(stack["norm2"]["scale"][layer]),
                                      np.asarray(ref["norm2"]))
    top = qwen2.top_tensors(key, arch, form)
    np.testing.assert_array_equal(np.asarray(params["embed"]["table"]),
                                  np.asarray(top["embed"]))


def test_seed_beyond_32_bits_gives_its_own_weights():
    a, b = weights.base_key(5), weights.base_key(2 ** 32 + 5)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError):
        weights.base_key(-1)


def test_open_loop_same_work_every_seed():
    """Every seed gets the same arrival times and sizes, with its own
    tokens."""
    mix = tiny_cell("tiny-packed.chat").mix
    segments = (5.0, 20.0, 5.0)
    a = traffic.open_loop(mix, 4.0, segments, 11, 512)
    b = traffic.open_loop(mix, 4.0, segments, 2 ** 40 + 3, 512)
    again = traffic.open_loop(mix, 4.0, segments, 11, 512)
    assert [x.prompt for x in a] == [x.prompt for x in again]
    assert [x.due for x in a] == [x.due for x in b]
    assert all(0 <= x.due < sum(segments) for x in a)
    assert [x.due for x in a] == sorted(x.due for x in a)
    size = lambda r: [(len(x.prompt), x.max_tokens) for x in r]
    assert size(a) == size(b)
    assert [x.prompt for x in a] != [x.prompt for x in b]
    # the rate scales the arrival times and nothing else
    c = traffic.open_loop(mix, 2.0, (10.0, 40.0, 10.0), 11, 512)
    assert [x.due for x in c[:20]] == pytest.approx(
        [2 * x.due for x in a[:20]])
    assert size(c[:20]) == size(a[:20])


def test_closed_sessions_share_their_document():
    mix = tiny_cell("tiny-packed.docqa").mix
    sessions = traffic.closed_sessions(mix, 7, 512)
    assert len(sessions) == mix["sessions"]
    for s in sessions[:20]:
        lo, hi = mix["asks"]["min"], mix["asks"]["max"]
        assert lo <= len(s) <= hi
        doc = min(len(a.prompt) for a in s) - mix["question"]["max"]
        assert all(a.prompt[:doc] == s[0].prompt[:doc] for a in s)


def test_warm_start_same_work_every_seed():
    """The requests in flight at the load's start come first, due at 0,
    the same sizes on every seed, each within what one request of the mix
    can hold, about as many as the rate times a request's lifetime."""
    mix = tiny_cell("tiny-packed.chat").mix
    token_s = 0.05
    a = traffic.open_loop(mix, 4.0, (5.0, 20.0), 11, 512, token_s)
    b = traffic.open_loop(mix, 4.0, (5.0, 20.0), 2 ** 40 + 3, 512, token_s)
    cold = traffic.open_loop(mix, 4.0, (5.0, 20.0), 11, 512)
    warm = [x for x in a if x.warm]
    assert warm and a[:len(warm)] == warm
    assert all(x.due == 0.0 for x in warm)
    size = lambda r: [(len(x.prompt), x.max_tokens) for x in r]
    assert size(a) == size(b)
    assert size(a[len(warm):]) == size(cold)
    assert [x.due for x in a[len(warm):]] == [x.due for x in cold]
    assert all(len(x.prompt) + x.max_tokens <= traffic.longest(mix)
               and x.max_tokens >= 1 for x in warm)
    assert any(len(x.prompt) > mix["prompt"]["max"] for x in warm) or \
        any(x.max_tokens < mix["output"]["min"] for x in warm)
    mean_out = (mix["output"]["min"] + mix["output"]["max"]) / 2
    assert 0.3 < len(warm) / (4.0 * mean_out * token_s) < 3.0
