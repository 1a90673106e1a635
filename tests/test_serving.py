"""Serving: continuous-batching engine, request lifecycle, sampling, packed
conversion.

The load-bearing test is mixed-depth parity: requests admitted mid-stream
into a running batch must produce token-for-token identical greedy outputs to
running each request alone (per-slot cache indices, ISSUE acceptance
criterion).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant as Q
from repro.models import build_model, get_config
from repro.serving.api import (FinishReason, GenerationRequest, SamplingParams,
                               StepOutput)
from repro.serving.engine import (Engine, Request, ServeConfig, ServingEngine,
                                  convert_to_packed)
from repro.serving.sampling import greedy, sample_batch, sample_top_p
from repro.serving.scheduler import Scheduler, bucket_length


@pytest.fixture(scope="module")
def small_lm():
    cfg = get_config("qwen1.5-0.5b").reduced().replace(
        compute_dtype="float32", param_dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def qat_lm():
    cfg = get_config("qwen1.5-0.5b").reduced().replace(
        compute_dtype="float32", param_dtype="float32").with_quant(Q.QAT)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def run_alone(eng: Engine, prompt, sp: SamplingParams):
    """Reference: one request at a time through the same engine."""
    req = eng.submit(list(prompt), sp)
    for _ in eng.stream():
        pass
    return req


class TestSampling:
    def test_greedy_is_argmax(self):
        logits = jax.random.normal(jax.random.PRNGKey(0), (4, 100))
        np.testing.assert_array_equal(np.asarray(greedy(logits)),
                                      np.asarray(jnp.argmax(logits, -1)))

    def test_top_p_zero_temp_is_greedy(self):
        logits = jax.random.normal(jax.random.PRNGKey(1), (4, 50))
        got = sample_top_p(jax.random.PRNGKey(2), logits, 0.9, 0.0)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(greedy(logits)))

    def test_top_p_restricts_support(self):
        logits = jnp.log(jnp.array([[0.7, 0.2, 0.05, 0.05]]))
        for seed in range(20):
            s = sample_top_p(jax.random.PRNGKey(seed), logits, 0.75, 1.0)
            assert int(s[0]) in (0, 1)

    def test_sample_batch_mixed_rows(self):
        """One step can mix greedy rows (temp 0) with stochastic rows."""
        logits = jax.random.normal(jax.random.PRNGKey(3), (3, 64))
        keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(3, dtype=jnp.uint32))
        temps = jnp.array([0.0, 1.0, 0.0], jnp.float32)
        tops = jnp.array([1.0, 0.9, 1.0], jnp.float32)
        got = np.asarray(sample_batch(keys, logits, temps, tops))
        ref = np.asarray(greedy(logits))
        assert got[0] == ref[0] and got[2] == ref[2]

    def test_sample_batch_top_p_restricts_support(self):
        logits = jnp.log(jnp.array([[0.7, 0.2, 0.05, 0.05]]))
        for seed in range(20):
            s = sample_batch(jax.random.PRNGKey(seed)[None], logits,
                             jnp.ones((1,)), jnp.full((1,), 0.75))
            assert int(s[0]) in (0, 1)


class TestScheduler:
    def test_bucket_length_pow2(self):
        assert bucket_length(3, 8, 64) == 8
        assert bucket_length(9, 8, 64) == 16
        assert bucket_length(33, 8, 64) == 64
        assert bucket_length(60, 8, 64) == 64   # clamped to max_len

    def test_admit_and_free(self):
        sc = Scheduler(n_slots=2, max_len=16, eos_id=99)
        for uid in range(3):
            sc.submit(GenerationRequest(uid=uid, prompt=[1, 2, 3],
                                        params=SamplingParams(max_tokens=2)))
        admitted, rejected = sc.admit()
        assert [s for s, _ in admitted] == [0, 1] and not rejected
        # admission parks the prompt for chunked prefill; nothing filled yet
        assert sc.positions[0] == 0 and sc.prefill_remaining(0) == 3
        assert sc.next_chunks() == {0: 3, 1: 3}   # default: whole prompt
        assert sc.advance_prefill(0, 3)
        assert sc.positions[0] == 3            # next write = prompt_len
        out = sc.record(0, token=7)            # 1st generated token
        assert not out.finished and sc.positions[0] == 3
        out = sc.record(0, token=8)            # hits max_tokens=2
        assert out.finished and out.finish_reason == FinishReason.LENGTH
        assert sc.slots[0] is None             # slot freed for re-admission
        admitted, _ = sc.admit()
        assert [s for s, _ in admitted] == [0]  # third request backfills

    def test_oversized_prompt_aborted(self):
        sc = Scheduler(n_slots=1, max_len=8, eos_id=99)
        req = GenerationRequest(uid=0, prompt=list(range(8)))
        sc.submit(req)
        admitted, rejected = sc.admit()
        assert not admitted and rejected[0].finish_reason == FinishReason.ABORTED
        assert req.done and not sc.has_work()

    def test_eos_stop(self):
        sc = Scheduler(n_slots=1, max_len=16, eos_id=42)
        sc.submit(GenerationRequest(uid=0, prompt=[1],
                                    params=SamplingParams(max_tokens=10)))
        sc.admit()
        out = sc.record(0, token=42)
        assert out.finished and out.finish_reason == FinishReason.STOP


class TestContinuousBatching:
    def test_mixed_depth_matches_single(self, small_lm):
        """Requests admitted mid-stream into a running batch generate
        token-for-token what they generate alone (greedy)."""
        cfg, model, params = small_lm
        prompts = [[1, 2, 3], [5, 6, 7, 8, 9], [11, 12], [3, 1, 4, 1, 5, 9]]
        sp = SamplingParams(max_tokens=8, ignore_eos=True)
        eng = Engine(cfg, params, ServeConfig(max_batch=3, max_len=24))
        refs = [run_alone(eng, p, sp).output_tokens for p in prompts]

        eng2 = Engine(cfg, params, ServeConfig(max_batch=3, max_len=24))
        r0 = eng2.submit(prompts[0], sp)
        eng2.step(); eng2.step()                       # r0 is 2 tokens deep
        r1 = eng2.submit(prompts[1], sp)
        eng2.step()                                    # r1 admitted mid-stream
        r2 = eng2.submit(prompts[2], sp)
        r3 = eng2.submit(prompts[3], sp)               # queues until a slot frees
        for _ in eng2.stream():
            pass
        got = [r.output_tokens for r in (r0, r1, r2, r3)]
        assert got == refs

    def test_streaming_order_and_finish(self, small_lm):
        cfg, model, params = small_lm
        eng = Engine(cfg, params, ServeConfig(max_batch=2, max_len=16))
        sp = SamplingParams(max_tokens=4, ignore_eos=True)
        ra, rb = eng.submit([1, 2], sp), eng.submit([3, 4, 5], sp)
        outs = list(eng.stream())
        for r in (ra, rb):
            mine = [o for o in outs if o.uid == r.uid]
            assert [o.index for o in mine] == list(range(4))
            assert [o.token for o in mine] == r.output_tokens
            assert [o.finished for o in mine] == [False, False, False, True]
            assert mine[-1].finish_reason == FinishReason.LENGTH

    def test_callback_streaming(self, small_lm):
        cfg, model, params = small_lm
        eng = Engine(cfg, params, ServeConfig(max_batch=2, max_len=16))
        got = []
        r = eng.submit([1, 2, 3], SamplingParams(max_tokens=3, ignore_eos=True),
                       on_token=lambda o: got.append((o.index, o.token)))
        for _ in eng.stream():
            pass
        assert got == list(enumerate(r.output_tokens))

    def test_max_tokens_counts_generated_only(self, small_lm):
        """max_tokens bounds *generated* tokens exactly — the first
        prefill-sampled token counts, the prompt does not."""
        cfg, model, params = small_lm
        eng = Engine(cfg, params, ServeConfig(max_batch=2, max_len=16))
        for n in (1, 3):
            r = eng.submit([1, 2, 3, 4],
                           SamplingParams(max_tokens=n, ignore_eos=True))
            for _ in eng.stream():
                pass
            assert r.num_generated == n
            assert r.finish_reason == FinishReason.LENGTH

    def test_eos_finishes_with_stop(self, small_lm):
        cfg, model, params = small_lm
        # probe the greedy continuation, then rig eos_id to its 2nd token
        eng = Engine(cfg, params, ServeConfig(max_batch=1, max_len=16))
        probe = eng.submit([9, 8, 7], SamplingParams(max_tokens=4,
                                                     ignore_eos=True))
        for _ in eng.stream():
            pass
        eos = probe.output_tokens[1]
        eng2 = Engine(cfg, params, ServeConfig(max_batch=1, max_len=16,
                                               eos_id=eos))
        r = eng2.submit([9, 8, 7], SamplingParams(max_tokens=10))
        for _ in eng2.stream():
            pass
        assert r.finish_reason == FinishReason.STOP
        assert r.output_tokens == probe.output_tokens[:2]

    def test_cache_capacity_finishes_with_length(self, small_lm):
        cfg, model, params = small_lm
        eng = Engine(cfg, params, ServeConfig(max_batch=1, max_len=8))
        r = eng.submit([1, 2, 3, 4, 5],
                       SamplingParams(max_tokens=50, ignore_eos=True))
        for _ in eng.stream():
            pass
        assert r.finish_reason == FinishReason.LENGTH
        # prompt fills 0..4; decode writes at 5,6,7 produce one token each and
        # the final sampled token needs no cache write: 8 - 5 + 1 generated
        assert r.num_generated == 4

    def test_seeded_sampling_reproducible(self, small_lm):
        cfg, model, params = small_lm
        eng = Engine(cfg, params, ServeConfig(max_batch=3, max_len=16))
        sp7 = SamplingParams(max_tokens=6, temperature=1.0, seed=7,
                             ignore_eos=True)
        sp8 = SamplingParams(max_tokens=6, temperature=1.0, seed=8,
                             ignore_eos=True)
        a, b, c = (eng.submit([1, 2, 3], sp) for sp in (sp7, sp7, sp8))
        for _ in eng.stream():
            pass
        assert a.output_tokens == b.output_tokens
        assert a.output_tokens != c.output_tokens

    def test_quantized_paths_through_scheduler(self, qat_lm):
        """QAT and packed students both serve mixed-depth batches identically
        to single-request runs (the decode-bandwidth story needs the packed
        path correct under continuous batching)."""
        qcfg, _, qparams = qat_lm
        pcfg, pparams = convert_to_packed(qcfg, qparams)
        prompts = [[1, 2, 3], [5, 6, 7, 8, 9]]
        sp = SamplingParams(max_tokens=5, ignore_eos=True)
        for cfg, params in ((qcfg, qparams), (pcfg, pparams)):
            eng = Engine(cfg, params, ServeConfig(max_batch=2, max_len=20))
            refs = [run_alone(eng, p, sp).output_tokens for p in prompts]
            ra = eng.submit(prompts[0], sp)
            eng.step()                              # stagger depths
            rb = eng.submit(prompts[1], sp)
            for _ in eng.stream():
                pass
            assert [ra.output_tokens, rb.output_tokens] == refs


class TestEngineCompat:
    def test_generate_wrapper_legacy_requests(self, small_lm):
        cfg, model, params = small_lm
        eng = ServingEngine(cfg, params, ServeConfig(max_batch=4, max_len=16))
        reqs = [Request(uid=i, prompt=[1, 2, 3 + i], max_tokens=6)
                for i in range(6)]
        out = eng.generate(reqs)
        assert set(out) == {0, 1, 2, 3, 4, 5}
        for r in reqs:
            assert r.done and r.output == out[r.uid]
            assert 1 <= len(r.output) <= 6
            assert all(0 <= t < cfg.padded_vocab for t in r.output)

    def test_deterministic_greedy(self, small_lm):
        cfg, model, params = small_lm
        eng = ServingEngine(cfg, params, ServeConfig(max_batch=2, max_len=12))
        r1 = eng.generate([Request(uid=0, prompt=[5, 6, 7], max_tokens=5)])
        r2 = eng.generate([Request(uid=0, prompt=[5, 6, 7], max_tokens=5)])
        assert r1[0] == r2[0] and len(r1[0]) == 5

    def test_generate_rejects_oversized_legacy_prompt(self, small_lm):
        """Legacy Requests can't surface FinishReason.ABORTED, so generate()
        fails fast instead of silently returning an empty output."""
        cfg, model, params = small_lm
        eng = ServingEngine(cfg, params, ServeConfig(max_batch=1, max_len=8))
        with pytest.raises(ValueError, match="cache"):
            eng.generate([Request(uid=0, prompt=list(range(12)))])

    def test_default_config_not_shared(self, small_lm):
        cfg, model, params = small_lm
        e1, e2 = Engine(cfg, params), Engine(cfg, params)
        e1.scfg.max_len = 999
        assert e2.scfg.max_len != 999


class TestPacked:
    def test_packed_conversion_preserves_logits(self, qat_lm):
        cfg, model, params = qat_lm
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab)
        logits_qat, _, _ = model.apply(params, toks)

        pcfg, pparams = convert_to_packed(cfg, params)
        pmodel = build_model(pcfg)
        logits_packed, _, _ = pmodel.apply(pparams, toks)
        # int32-accumulate-then-scale vs dequantize-then-fp32-matmul round
        # differently; agreement to ~1e-2 logits is exact-quantization-level
        np.testing.assert_allclose(np.asarray(logits_packed),
                                   np.asarray(logits_qat),
                                   rtol=1e-2, atol=1e-2)

    def test_packed_conversion_of_trained_stacked_weights(self, qat_lm):
        """Weights that are not the template's own init (a trained
        student's) must be converted, per scanned layer, biases kept — at
        any layer count, including the 24 of the full-width config."""
        cfg, model, _ = qat_lm
        params = model.init(jax.random.PRNGKey(7))
        params = jax.tree_util.tree_map(lambda a: a + 0.01, params)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab)
        logits_qat, _, _ = model.apply(params, toks)
        pcfg, pparams = convert_to_packed(cfg, params)
        logits_packed, _, _ = build_model(pcfg).apply(pparams, toks)
        np.testing.assert_allclose(np.asarray(logits_packed),
                                   np.asarray(logits_qat),
                                   rtol=1e-2, atol=1e-2)
        full = get_config("qwen1.5-0.5b").with_quant(Q.QAT)
        shapes = jax.eval_shape(
            lambda p: convert_to_packed(full, p)[1],
            jax.eval_shape(build_model(full).init, jax.random.PRNGKey(0)))
        wq = shapes["stack"]["pos0"]["attn"]["wq"]
        assert wq["w_packed"].shape == (24, 1024 // 4, 1024)
        assert wq["delta"].shape == (24,) and wq["b"].shape == (24, 1024)

    def test_packed_weight_bytes_8x_smaller_than_bf16(self):
        cfg = get_config("qwen1.5-0.5b").reduced().with_quant(Q.QAT)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        _, pparams = convert_to_packed(cfg, params)

        def linear_bytes(tree, key):
            tot = 0
            if isinstance(tree, dict):
                for k, v in tree.items():
                    if k == key and hasattr(v, "nbytes"):
                        tot += v.nbytes
                    else:
                        tot += linear_bytes(v, key)
            return tot

        full = linear_bytes(params, "w")
        packed = linear_bytes(pparams, "w_packed")
        assert packed > 0
        assert packed * 7 < full  # fp32 w -> uint8/4: ~16x; vs bf16: 8x


class TestPrefillBudget:
    """Per-step prefill token budget: caps the *sum* of chunk tokens across
    slots per step; unfunded slots stall (stay admitted, resume next step)."""

    def _sched(self, chunk, budget, n_slots=3):
        from repro.serving.paged import BlockAllocator
        alloc = BlockAllocator(num_blocks=33, block_size=4)
        return Scheduler(n_slots=n_slots, max_len=32, eos_id=99,
                         allocator=alloc, prefill_chunk=chunk,
                         prefill_budget=budget)

    def test_budget_caps_sum_across_slots(self):
        sc = self._sched(chunk=8, budget=10)
        for uid in range(3):
            sc.submit(GenerationRequest(uid=uid, prompt=list(range(1, 13)),
                                        params=SamplingParams()))
        sc.admit()
        # slot 0 gets its full chunk (8), slot 1 the clipped remainder (2),
        # slot 2 stalls entirely
        assert sc.next_chunks() == {0: 8, 1: 2}

    def test_stalled_slots_resume_next_step(self):
        sc = self._sched(chunk=8, budget=10)
        for uid in range(3):
            sc.submit(GenerationRequest(uid=uid, prompt=list(range(1, 13)),
                                        params=SamplingParams()))
        sc.admit()
        for slot, n in sc.next_chunks().items():
            sc.advance_prefill(slot, n)
        # next step: planning restarts at slot 0's backlog, slot 2 is funded
        # once earlier slots shrink
        assert sc.next_chunks() == {0: 4, 1: 6}
        for slot, n in {0: 4, 1: 6}.items():
            sc.advance_prefill(slot, n)
        assert sc.next_chunks() == {1: 4, 2: 6}

    def test_unchunked_prefill_also_budgeted(self):
        # chunk=0 means "whole remainder", still clipped by the budget
        sc = self._sched(chunk=0, budget=10)
        for uid in range(2):
            sc.submit(GenerationRequest(uid=uid, prompt=list(range(1, 13)),
                                        params=SamplingParams()))
        sc.admit()
        assert sc.next_chunks() == {0: 10}

    def test_budget_validation(self, small_lm):
        cfg, _, params = small_lm
        with pytest.raises(ValueError, match="prefill_budget"):
            Engine(cfg, params, ServeConfig(max_batch=2, max_len=32,
                                            prefill_budget=0))

    def test_budget_outputs_match_unbudgeted(self, small_lm):
        """Stalled rows ride the fused step as emit-less pad rows — they must
        not perturb anyone's tokens (greedy parity vs no budget)."""
        cfg, _, params = small_lm
        prompts = [list(range(1, 14)), list(range(3, 12)),
                   list(range(5, 17))]
        sp = SamplingParams(max_tokens=4, ignore_eos=True)

        def run(budget):
            eng = Engine(cfg, params, ServeConfig(
                max_batch=3, max_len=48, kv_block_size=4, paged=True,
                prefill_chunk=4, prefill_budget=budget))
            reqs = [eng.submit(p, sp) for p in prompts]
            for _ in eng.stream():
                pass
            assert eng.allocator.blocks_in_use() == 0
            return eng, [r.output_tokens for r in reqs]

        eng_b, got = run(budget=6)       # forces stalls: 3 slots x chunk 4
        _, want = run(budget=None)
        assert got == want


class TestEngineStats:
    def test_latency_and_counter_fields(self, small_lm):
        cfg, _, params = small_lm
        eng = Engine(cfg, params, ServeConfig(max_batch=2, max_len=32,
                                              kv_block_size=4))
        sp = SamplingParams(max_tokens=3, ignore_eos=True)
        reqs = [eng.submit([1, 2, 3], sp), eng.submit([4, 5], sp)]
        for _ in eng.stream():
            pass
        st = eng.stats()
        assert st.tokens_generated == sum(r.num_generated for r in reqs) == 6
        assert st.queue_depth == 0
        assert st.steps_committed > 0
        assert st.steps_overlapped == 0          # sync loop never overlaps
        for sample in (st.queue_wait_ms, st.e2e_latency_ms, st.ttft_ms,
                       st.step_gap_ms):
            assert sample is not None
            assert set(sample) == {"mean", "p50", "p95", "p99"}
        assert st.e2e_latency_ms["p50"] >= st.queue_wait_ms["p50"]
        assert st.cancellations == 0 and st.deadline_expirations == 0
