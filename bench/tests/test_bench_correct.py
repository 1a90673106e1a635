"""``correct`` on the CPU at the tiny size: a sound run passes, and the
whole run with the timed path broken underneath, or the reference in the
lower precision put in the program's place, comes out not correct."""
import time

import jax.numpy as jnp
import pytest

from bench import harness, reference
from bench.tests.conftest import tiny_cell

SEED = 2 ** 33 + 17


def _run(workload, peaks=None, control=False):
    return harness.run_cell(tiny_cell(workload), SEED, 2.0, False,
                            time.perf_counter(), require_chip=False,
                            peaks=peaks, control=control)


def test_sound_run_is_correct():
    out = _run("tiny-packed.chat")
    gap = out["checks"]["logit_gap"]
    assert out["correct"], gap
    assert out["notes"]["checked"]["requests"] >= 2
    assert out["notes"]["checked"]["tokens_in_window"] > 0
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms",
                                   "output_tok_s", "setup_s"}


def _token_altered(impl):
    """The decode step's sampled token, moved one id on where it is made."""
    def faulty(self, *a, **k):
        nxt, cache, keys = impl(self, *a, **k)
        return (nxt + 1) % self.cfg.vocab, cache, keys
    return faulty


def _state_unchanged(impl):
    """The decode step hands back the KV pool it was given: its writes are
    lost."""
    def faulty(self, params, tokens, cache, *a, **k):
        nxt, _, keys = impl(self, params, tokens, cache, *a, **k)
        return nxt, cache, keys
    return faulty


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged])
def test_broken_decode_step_is_not_correct(monkeypatch, fault):
    from repro.serving.engine import Engine
    monkeypatch.setattr(Engine, "_decode_impl", fault(Engine._decode_impl))
    out = _run("tiny-packed.chat")
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged])
def test_broken_decode_step_of_the_teacher_is_not_correct(monkeypatch, fault):
    """The same faults under the bf16 teacher's configuration."""
    from repro.serving.engine import Engine
    monkeypatch.setattr(Engine, "_decode_impl", fault(Engine._decode_impl))
    out = _run("tiny-bf16.chat")
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("workload", ["tiny-packed.chat", "tiny-bf16.chat"])
def test_lower_precision_control_is_not_correct(workload):
    """Teacher-forced on greedy continuations, the reference in the
    precision one step below the configuration's picks tokens that lie
    further below the reference's best than the cell's limit allows."""
    cell = tiny_cell(workload)
    family = harness.family_of(cell.config)
    arch, form = family.architecture(cell.config), cell.config["form"]
    seqs = _sequences(cell)
    res = reference.compare(SEED, family, arch, form, seqs, pad_len=64,
                            pad_out=16, control=True)
    limit = cell.cell["limits"]["logit_gap"]
    assert res["gap"] <= limit < res["control_gap"], res


def test_control_in_the_programs_place_is_not_correct():
    """The whole run with the lower-precision reference's tokens judged in
    place of the served tokens, by the same comparison and limit: the
    program's run is correct and the control's is not."""
    out = _run("tiny-packed.chat", control=True)
    assert out["correct"], out["checks"]
    ctrl = out["control"]
    assert not ctrl["correct"], ctrl
    assert ctrl["checks"]["logit_gap"]["limit"] == \
        out["checks"]["logit_gap"]["limit"]


def test_checked_requests_prefer_tokens_served_in_the_window():
    from bench.client import Record
    cell = tiny_cell("tiny-packed.chat")
    w = (10.0, 20.0)

    def rec(i, due, times):
        return Record(i, [1] * 8, len(times), due, tokens=[0] * len(times),
                      times=list(times), finish="length", done=times[-1])
    early = [rec(i, 0.0, [1.0 + i, 2.0 + i]) for i in range(6)]
    inside = [rec(10 + i, 5.0, [8.0, 12.0 + i]) for i in range(2)]
    late = rec(20, 21.0, [22.0, 23.0])        # due after the window
    got = harness.sample_checked(early + inside + [late], cell, SEED, w,
                                 30.0)
    assert len(got) == cell.mix["check"]["requests"]
    assert got[:2] == inside or got[:2] == inside[::-1]
    assert late not in got


def _sequences(cell):
    """Prompts with greedy reference continuations, as a served run gives."""
    from bench import traffic
    asks = traffic.open_loop(cell.mix, 4.0, (10.0,), SEED, 512)[:6]
    seqs = []
    family = harness.family_of(cell.config)
    arch, form = family.architecture(cell.config), cell.config["form"]
    for a in asks:
        toks = list(a.prompt)
        for _ in range(8):
            h, table = reference.forward_hidden(
                SEED, family, arch, form,
                jnp.asarray([toks + [0] * (64 - len(toks))]),
                jnp.asarray([[len(toks) - 1]]))
            logits = h[0, 0] @ table.astype(jnp.float32).T
            toks.append(int(jnp.argmax(logits)))
        seqs.append((a.prompt, toks[len(a.prompt):]))
    return seqs
