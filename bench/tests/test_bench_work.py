"""The operations and bytes the step and kernel metrics count, from the
shapes the engine records for each launched step."""
import jax
import pytest

from bench import harness, work
from bench.metrics import (chunk_mfu, decode_mfu, paged_attention_roofline,
                           paged_prefill_roofline)
from bench.tests.conftest import tiny_cell


def _engine(attn_impl="gather", cache_dtype="float32"):
    from repro.serving.engine import Engine, ServeConfig
    config = tiny_cell("tiny-packed.chat").config
    eng = harness.build_engine(config, 3)
    scfg = ServeConfig(**dict(config["serve"], attn_impl=attn_impl,
                              cache_dtype=cache_dtype))
    return Engine(eng.cfg, eng.params, scfg), config["architecture"]


# the tiny configurations' architecture module
QWEN2 = harness.family_of(tiny_cell("tiny-packed.chat").config)


def _shapes(eng, prompts):
    """Every launched step's recorded shape, serving ``prompts`` to the end
    through the engine's own step."""
    from repro.serving.api import SamplingParams
    for p in prompts:
        eng.submit(p, SamplingParams(max_tokens=5, ignore_eos=True))
    out = []
    while eng.has_pending():
        eng.step()
        if eng.last_decode is not None:
            out.append(dict(eng.last_decode))
    return out


PROMPTS = [list(range(1, 21)), list(range(3, 40)), list(range(5, 14))]


def test_kv_bytes_follow_the_pool_dtype():
    for dtype, size in (("float32", 4), ("bfloat16", 2)):
        eng, arch = _engine(cache_dtype=dtype)
        eng._ensure_state()
        leaf = jax.tree_util.tree_leaves(eng._cache)[0]
        assert leaf.dtype.itemsize == size
    shape = {"active": [0, 2], "positions": [40, 0, 7, 0], "chunks": None}
    o4, b4 = paged_attention_roofline.step_work(shape, QWEN2, arch, 4)
    o2, b2 = paged_attention_roofline.step_work(shape, QWEN2, arch, 2)
    assert o4 == o2
    kv = (40 + 2) + (7 + 2)                       # resident + new, two rows
    ctx = 2 * (arch["num_attention_heads"] * arch["head_dim"] * 2
               * arch["num_hidden_layers"])       # context out, pool dtype
    assert b4 - b2 == kv * QWEN2.kv_bytes_per_token(arch, 2) + ctx


def test_only_rows_that_carry_a_request_count():
    arch = tiny_cell("tiny-packed.chat").config["architecture"]
    one = {"active": [1], "positions": [500, 9, 500, 500], "chunks": None}
    lone = {"active": [0], "positions": [9], "chunks": None}
    assert (paged_attention_roofline.step_work(one, QWEN2, arch, 4)
            == paged_attention_roofline.step_work(lone, QWEN2, arch, 4))
    assert (decode_mfu.step_ops(one, QWEN2, arch)
            == decode_mfu.step_ops(lone, QWEN2, arch))
    chunk = {"active": [0, 3], "positions": [16, 99, 99, 5],
             "chunks": {0: 8}}
    ops, nbytes = paged_prefill_roofline.step_work(chunk, QWEN2, arch, 4)
    per_tok = QWEN2.kv_bytes_per_token(arch, 4)
    qo = (arch["num_attention_heads"] * arch["head_dim"] * 6
          * arch["num_hidden_layers"])
    assert nbytes == (16 + 8) * per_tok + 8 * qo + (5 + 1) * per_tok + qo
    assert ops == sum(QWEN2.attention_ops(arch, 16 + j + 1) for j in range(8)) \
        + QWEN2.attention_ops(arch, 6)
    lin, _ = chunk_mfu.step_ops(chunk, QWEN2, arch)
    assert lin == 2 * QWEN2.linear_params(arch) * 9


def test_fused_and_gather_paths_count_the_same_work():
    fused, arch = _engine("fused")
    gather, _ = _engine("gather")
    a, b = _shapes(fused, PROMPTS), _shapes(gather, PROMPTS)
    assert len(a) == len(b) and any(s["chunks"] for s in a) \
        and any(s["chunks"] is None for s in a)
    for sa, sb in zip(a, b):
        if sa["chunks"] is None:
            assert (paged_attention_roofline.step_work(sa, QWEN2, arch, 4)
                    == paged_attention_roofline.step_work(sb, QWEN2, arch, 4))
            assert (decode_mfu.step_ops(sa, QWEN2, arch)
                    == decode_mfu.step_ops(sb, QWEN2, arch))
        else:
            assert (paged_prefill_roofline.step_work(sa, QWEN2, arch, 4)
                    == paged_prefill_roofline.step_work(sb, QWEN2, arch, 4))
            assert (chunk_mfu.step_ops(sa, QWEN2, arch)
                    == chunk_mfu.step_ops(sb, QWEN2, arch))


@pytest.mark.parametrize("form_peak", [("packed", "int8_ops_per_s"),
                                       ("bf16", "bf16_flops_per_s")])
def test_linear_layers_at_the_forms_peak(v5e_peaks, form_peak):
    form, peak = form_peak
    t = work.least_seconds(1e12, 0.0, form, v5e_peaks)
    assert t == pytest.approx(1e12 / v5e_peaks[peak])
