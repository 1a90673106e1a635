"""Step-work counters and engine phases on the profiler clock.

Each fused step's work is counted on the host from its plan at launch —
positions every linear layer computes against those carrying a request,
KV blocks of the table handed to the attention kernel against those holding
live rows and those the kernel fetches — and added once per committed step
to ``EngineStats``, the metrics registry, ``Tracer.step_work`` and
``Tracer.step_scored``.  These tests recompute the counters from each
launch's step shape (``Engine.last_decode``, read when ``launch_span``
fires, as the serving benchmark reads it), in the sync and the async loop,
under supervisor retries and restarts, and check that a ``jax.profiler``
capture holds the engine's ``serve.*`` phases with the committed step ids.
"""
import asyncio
import glob
import math

import jax
import numpy as np
import pytest

from repro.analysis import check_trace
from repro.kernels.paged_prefill.ops import fetched_blocks
from repro.models import build_model, get_config
from repro.serving.api import SamplingParams
from repro.serving.async_engine import AsyncEngine, drive_requests
from repro.serving.engine import Engine, ServeConfig
from repro.serving.faults import Fault, FaultPlan
from repro.serving.supervisor import ServingSupervisor
from repro.serving.tracing import COMMIT, LAUNCH, PLAN, SYNC, Tracer

SCFG = dict(max_batch=3, max_len=48, kv_block_size=4, prefill_chunk=4)
COUNTERS = ("chunk_positions_run", "chunk_positions_useful",
            "kv_blocks_walked", "kv_blocks_live", "kv_blocks_scored")


@pytest.fixture(scope="module")
def lm():
    cfg = get_config("qwen1.5-0.5b").reduced(layers=2).replace(
        compute_dtype="float32", param_dtype="float32")
    return cfg, build_model(cfg).init(jax.random.PRNGKey(0))


class ShapeTracer(Tracer):
    """Keeps each launch's step shape, and the block table it was handed,
    under the step id it commits as; a retried step's relaunch replaces its
    first launch."""

    def __init__(self, engine_of, clock):
        super().__init__(clock=clock)
        self.engine_of = engine_of      # the live engine (restarts swap it)
        self.launched = 0
        self.shapes = {}

    def launch_span(self, t0, t1, step, spec=False):
        super().launch_span(t0, t1, step, spec)
        self.launched += 1
        eng = self.engine_of()
        shape = dict(eng.last_decode)
        shape["table"] = eng.sched.block_tables[:, :shape["table_width"]].copy()
        self.shapes[step] = shape


def recount(shapes, max_batch, block, fused=False):
    """The five counters, from launch shapes and tables alone: a fused
    chunk step fetches what the prefill kernel's rule names, any other
    step the whole table."""
    out = dict.fromkeys(COUNTERS, 0)
    for s in shapes:
        active = s["active"]
        walked = max_batch * s["table_width"]
        out["kv_blocks_walked"] += walked
        if s["chunks"] is None:                      # decode step
            ends = [s["positions"][i] + 1 for i in active]
            out["kv_blocks_scored"] += walked
        else:
            out["chunk_positions_run"] += max_batch * s["chunk_t"]
            out["chunk_positions_useful"] += sum(s["lens"][i]
                                                 for i in active)
            ends = [s["starts"][i] + s["lens"][i] for i in active]
            out["kv_blocks_scored"] += (
                sum(int(m.sum()) for m in fetched_blocks(
                    s["starts"], s["lens"], s["table"], block))
                if fused else walked)
        out["kv_blocks_live"] += sum(math.ceil(e / block) for e in ends)
    return out


def _workload(seed, n=3, max_tokens=6):
    rng = np.random.default_rng(seed)
    sp = SamplingParams(max_tokens=max_tokens, ignore_eos=True)
    return [(rng.integers(0, 64, int(rng.integers(5, 14))).tolist(), sp)
            for _ in range(n)]


def _serve_async(aeng, work):
    async def main():
        async with aeng:
            return await drive_requests(
                aeng, [(0.0, p, sp, None) for p, sp in work])
    return asyncio.run(main())


def _check(eng, tracer):
    """Stats, registry and tracer agree with the launches' recount."""
    st = eng.stats()
    assert sorted(tracer.shapes) == list(range(st.steps_committed))
    want = recount([tracer.shapes[k] for k in sorted(tracer.shapes)],
                   eng.scfg.max_batch, eng.scfg.kv_block_size,
                   fused=eng.attn_impl == "fused")
    assert {k: getattr(st, k) for k in COUNTERS} == want
    snap = eng.metrics.snapshot()
    assert {k: snap[f"serving_{k}_total"] for k in COUNTERS} == want
    # Tracer.work: one record per committed step, summing to the stats
    assert [w[0] for w in tracer.work] == list(range(st.steps_committed))
    chunk = [w for w in tracer.work if w[1] == "chunk"]
    assert sum(w[4] for w in chunk) == st.chunk_positions_run
    assert sum(w[5] for w in chunk) == st.chunk_positions_useful
    assert sum(w[6] for w in tracer.work) == st.kv_blocks_walked
    assert sum(w[7] for w in tracer.work) == st.kv_blocks_live
    # Tracer.scored: one record per committed step, beside its work record
    assert [w[:3] for w in tracer.scored] == [w[:3] for w in tracer.work]
    assert sum(w[3] for w in tracer.scored) == st.kv_blocks_scored
    kinds = {s["chunks"] is None for s in tracer.shapes.values()}
    assert kinds == {True, False}                    # decode and chunk ran
    assert 0 < st.chunk_positions_useful <= st.chunk_positions_run
    assert 0 < st.kv_blocks_live <= st.kv_blocks_walked
    return st


@pytest.mark.parametrize("loop", ["sync", "async"])
def test_counters_equal_the_launch_shapes(lm, loop):
    cfg, params = lm
    eng = Engine(cfg, params, ServeConfig(**SCFG))
    tracer = ShapeTracer(lambda: eng, eng.clock)
    eng.tracer = tracer
    work = _workload(0)
    if loop == "sync":
        for p, sp in work:
            eng.submit(p, sp)
        for _ in eng.stream():
            pass
    else:
        _serve_async(AsyncEngine(eng), work)
        assert eng.stats().steps_overlapped > 0      # speculation ran
    st = _check(eng, tracer)
    assert tracer.launched == st.steps_committed


@pytest.mark.parametrize("loop", ["sync", "async"])
def test_a_retried_step_is_counted_once(lm, loop):
    cfg, params = lm
    plan = FaultPlan([Fault("commit", "raise", at=2),
                      Fault("commit", "raise", at=7)])

    def factory():
        e = Engine(cfg, params, ServeConfig(**SCFG))
        e.fault_hook = plan.engine_hook
        return e

    sup = ServingSupervisor(factory)
    eng = factory()
    tracer = ShapeTracer(lambda: sup.engine, eng.clock)
    eng.tracer = tracer
    work = _workload(1)
    if loop == "sync":
        sup.attach(eng)
        for p, sp in work:
            eng.submit(p, sp)
        sup.drive()
    else:
        _serve_async(AsyncEngine(eng, supervisor=sup), work)
    assert plan.unfired() == []
    st = _check(sup.engine, tracer)
    assert st.step_retries == 2
    assert tracer.launched > st.steps_committed      # relaunches happened


def test_counters_carry_across_a_restart(lm):
    cfg, params = lm

    def factory():
        return Engine(cfg, params, ServeConfig(**SCFG))

    sup = ServingSupervisor(factory)
    eng = factory()
    sup.attach(eng)
    tracer = ShapeTracer(lambda: sup.engine, eng.clock)
    eng.tracer = tracer
    for p, sp in _workload(2, max_tokens=8):
        eng.submit(p, sp)
    for _ in range(4):
        sup.run_step()
    before = eng.stats()
    new = sup.restart()
    assert new is not eng and new.tracer is tracer
    assert {k: getattr(new.stats(), k) for k in COUNTERS} == \
        {k: getattr(before, k) for k in COUNTERS}
    sup.drive()
    _check(new, tracer)


def test_fused_chunk_steps_count_the_kernels_fetch(lm):
    """With the fused kernels a chunk step counts the blocks the prefill
    kernel fetches (its rule, from the table it was handed): the idle
    slot's row fetches nothing, so a chunk step fetches fewer blocks than
    the table holds and at least the blocks its rows' positions occupy."""
    cfg, params = lm
    eng = Engine(cfg, params, ServeConfig(**SCFG, attn_impl="fused"))
    tracer = ShapeTracer(lambda: eng, eng.clock)
    eng.tracer = tracer
    for p, sp in _workload(6, n=SCFG["max_batch"] - 1, max_tokens=4):
        eng.submit(p, sp)
    for _ in eng.stream():
        pass
    _check(eng, tracer)
    chunk = [(w, sc) for w, sc in zip(tracer.work, tracer.scored)
             if w[1] == "chunk"]
    assert chunk and all(w[7] <= sc[3] < w[6] for w, sc in chunk)


def test_budget_stalled_rows_are_not_useful(lm):
    """A mid-prefill row past the step's prefill budget rides the chunk
    step as a pad row: computed, walked, but not useful."""
    cfg, params = lm
    eng = Engine(cfg, params, ServeConfig(**SCFG, prefill_budget=4))
    plans = []
    launch = eng.launch_step

    def spy(plan, feed=None):
        inflight = launch(plan, feed)
        plans.append((plan, inflight.work))
        return inflight

    eng.launch_step = spy
    for p, sp in _workload(3):
        eng.submit(p, sp)
    for _ in eng.stream():
        pass
    stalled = 0
    for plan, w in plans:
        if w is None or w.kind != "chunk":
            continue
        decoding = [s for s in plan.active
                    if s not in plan.chunks and s not in plan.stalled]
        assert w.positions_useful == sum(plan.chunks.values()) + len(decoding)
        assert w.rows == len(plan.active)
        stalled += len(plan.stalled)
    assert stalled > 0
    st = eng.stats()
    assert st.chunk_positions_useful == sum(
        w.positions_useful for _, w in plans if w and w.kind == "chunk")


def test_export_writes_the_work_as_counter_events(lm):
    cfg, params = lm
    eng = Engine(cfg, params, ServeConfig(**SCFG))
    tracer = Tracer(clock=eng.clock)
    eng.tracer = tracer
    for p, sp in _workload(4):
        eng.submit(p, sp)
    for _ in eng.stream():
        pass
    doc = tracer.export()
    assert check_trace(doc) == []
    counters = [e for e in doc["traceEvents"] if e["ph"] == "C"]
    work = [e for e in counters if e["name"] == "step_work"]
    scored = [e for e in counters if e["name"] == "kv_scored"]
    assert len(work) == len(scored) == eng.stats().steps_committed
    assert len(counters) == len(work) + len(scored)
    for e, w in zip(work, tracer.work):
        run, useful, walked, live = w[4:]
        assert e["args"]["useful_pct"] == pytest.approx(100 * useful / run)
        assert e["args"]["live_pct"] == pytest.approx(100 * live / walked)
    for e, w, sc in zip(scored, tracer.work, tracer.scored):
        assert e["args"]["scored_pct"] == pytest.approx(100 * sc[3] / w[6])
    assert len([e for e in doc["traceEvents"] if e["ph"] != "M"]) == \
        tracer.num_events()


def _annotations(trace_dir):
    """(name, {stat: value}) of every ``serve.*`` host event captured."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))
    data = ProfileData.from_file(path[-1])
    return [(e.name, dict(e.stats)) for plane in data.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("serve.")]


def test_profiler_capture_holds_the_engine_phases(lm, tmp_path):
    cfg, params = lm
    eng = Engine(cfg, params, ServeConfig(**SCFG))
    tracer = Tracer(clock=eng.clock)
    eng.tracer = tracer
    with jax.profiler.trace(str(tmp_path)):
        _serve_async(AsyncEngine(eng), _workload(5))
    st = eng.stats()
    events = _annotations(tmp_path)
    steps = {name: sorted(a["step"] for n, a in events if n == name)
             for name in (PLAN, LAUNCH, SYNC, COMMIT)}
    committed = list(range(st.steps_committed))
    assert steps[LAUNCH] == committed                # one launch per step
    assert steps[SYNC] == committed
    assert steps[COMMIT] == committed
    assert set(committed) <= set(steps[PLAN])
    kinds = {(n, a["kind"]) for n, a in events}
    assert {(LAUNCH, "chunk"), (LAUNCH, "decode")} <= kinds
    # the launch annotations carry the kinds the committed work records
    launch_kind = {a["step"]: a["kind"] for n, a in events if n == LAUNCH}
    assert {w[0]: w[1] for w in tracer.work} == launch_kind
