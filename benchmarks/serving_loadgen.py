"""Serving front-end benchmarks: async-loop overlap, goodput under
deadlines, and closed-loop saturation (BENCH_serving.json sections).

Three benches over the PR-6 async serving stack, all on the reduced
2-layer student in interpret mode (CPU CI — wall numbers are the loop
*structure*, not TPU perf; the step-gap and overlap metrics are
backend-independent host-side facts):

* ``async_overlap_bench`` — the same engine driven by the synchronous
  ``Engine.step()`` loop and then by ``AsyncEngine``'s double-buffered host
  loop, same fuzzed workload.  Gates on token parity (greedy outputs must be
  identical) and on the async loop actually overlapping (speculative
  launches dispatched before the previous step's sync).  Reports the
  step-gap (host dispatch gap) distribution for both — the async loop's p50
  is 0 by construction on overlapped steps.
* ``goodput_bench`` — arrival-rate sweep through the TCP front-end
  (serving/frontend.py), one connection per request, with per-request
  deadlines, explicit mid-stream cancellations, and a bounded queue:
  goodput (requests finishing within deadline per second) vs arrival rate.
* ``saturation_bench`` — closed-loop many-client sweep: N clients each
  sending requests back-to-back; throughput vs N gives the saturation
  curve.

Run standalone (writes/merges BENCH_serving.json):

    PYTHONPATH=src python -m benchmarks.serving_loadgen

CI smoke (seconds, exercises server + deadline + cancellation end-to-end):

    PYTHONPATH=src python -m benchmarks.serving_loadgen --smoke

Fault-injected chaos soak (PR 8: seeded FaultPlan + ServingSupervisor;
gates on full fault coverage, zero leaked blocks, token parity for
unaffected requests, and the snapshot-restore resuming in-flight work —
now run with tracing and the flight recorder attached: every recovery
action must leave a recorder dump, span trees must close, and tokens
must be byte-identical to the telemetry-off baseline):

    PYTHONPATH=src python -m benchmarks.serving_loadgen --smoke --chaos \
        --sanitize

Telemetry benches (PR 9):

* ``trace_bench`` (``--smoke --trace``) — fuzzed-arrival async run with a
  :class:`~repro.serving.tracing.Tracer` attached; validates the emitted
  Chrome trace JSON against ``repro.analysis.tracecheck`` and gates span
  accounting against ``EngineStats`` *exactly* (request spans ==
  requests_submitted, commit spans == steps_committed, chunk spans ==
  prefill_chunks, no unclosed spans).
* ``telemetry_overhead_bench`` — tok/s with tracer + flight recorder
  attached vs. detached (the registry itself is always on), token parity
  required, gated at < 2% regression; writes
  BENCH_serving.json["telemetry"].
"""
from __future__ import annotations

import asyncio
import gc
import json
import os
import tempfile
import time
from collections import Counter
from typing import Dict, List, Optional

import jax
import numpy as np

from benchmarks.common import telemetry_section, write_bench_serving
from repro.models import build_model, get_config
from repro.serving.api import SamplingParams
from repro.serving.async_engine import AsyncEngine, drive_requests
from repro.serving.engine import Engine, ServeConfig
from repro.serving.frontend import FrontendServer, ServeClient
from repro.serving.telemetry import FlightRecorder
from repro.serving.tracing import Tracer


def _build_engine(sanitize: bool = False) -> Engine:
    cfg = get_config("qwen1.5-0.5b").reduced(layers=2).replace(
        compute_dtype="float32", param_dtype="float32")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    return Engine(cfg, params, ServeConfig(
        max_batch=4, max_len=64, kv_block_size=8, prefill_chunk=16,
        sanitize=sanitize))


def _fuzzed_schedule(rng, n, max_tokens, jitter_s=0.005):
    prompts = [rng.integers(0, 64, int(rng.integers(4, 20))).tolist()
               for _ in range(n)]
    sp = SamplingParams(max_tokens=max_tokens, ignore_eos=True)
    gaps = rng.uniform(0.0, jitter_s, n)
    return [(float(g), p, sp, None) for g, p in zip(gaps, prompts)]


def _gap_delta(eng: Engine, snap) -> Optional[Dict[str, float]]:
    """Percentiles of the step-gap samples observed since ``snap`` was
    taken (``Histogram.since`` — no raw sample lists to slice)."""
    d = eng._step_gap_ms.since(snap)
    return d.percentiles() if d.count else None


def async_overlap_bench(n_requests: int = 8, max_tokens: int = 12) -> dict:
    """Sync vs async host loop on one engine (jits shared, so the comparison
    is loop structure only): token parity gate + step-gap / overlap report."""
    eng = _build_engine()
    rng = np.random.default_rng(0)
    sched = _fuzzed_schedule(rng, n_requests, max_tokens)

    def run_sync(uid_base: int) -> Dict[int, List[int]]:
        reqs = [eng.submit(p, sp, uid=uid_base + i)
                for i, (_, p, sp, _) in enumerate(sched)]
        for _ in eng.stream():
            pass
        return {r.uid - uid_base: list(r.output_tokens) for r in reqs}

    run_sync(0)                                   # warm-up: compiles
    # measured sync pass: diff histogram snapshots (Histogram.since), the
    # fixed-memory replacement for slicing the old cumulative stat lists
    g0, t0 = eng._step_gap_ms.snapshot(), time.perf_counter()
    c0, o0, n0 = eng._steps_committed, eng._steps_overlapped, \
        eng._tokens_generated
    sync_out = run_sync(1000)
    sync = {"wall_s": time.perf_counter() - t0,
            "tok_per_s": (eng._tokens_generated - n0)
            / max(time.perf_counter() - t0, 1e-9),
            "steps": eng._steps_committed - c0,
            "steps_overlapped": eng._steps_overlapped - o0,
            "step_gap_ms": _gap_delta(eng, g0)}

    async def run_async(uid_base: int):
        async with AsyncEngine(eng) as aeng:
            res = await drive_requests(
                aeng, [(g, p, sp, d) for (g, p, sp, d) in sched])
        return {uid - uid_base: [o.token for o in outs if o.token >= 0]
                for uid, outs in res.items()}

    g0, t0 = eng._step_gap_ms.snapshot(), time.perf_counter()
    c0, o0, n0 = eng._steps_committed, eng._steps_overlapped, \
        eng._tokens_generated
    # align uids: drive_requests submits with uid=None -> engine counter
    eng._uid_counter = 2000
    async_out = asyncio.run(run_async(2000))
    wall = time.perf_counter() - t0
    steps = eng._steps_committed - c0
    overlapped = eng._steps_overlapped - o0
    a = {"wall_s": wall,
         "tok_per_s": (eng._tokens_generated - n0) / max(wall, 1e-9),
         "steps": steps, "steps_overlapped": overlapped,
         "overlapped_frac": overlapped / max(steps, 1),
         "step_gap_ms": _gap_delta(eng, g0)}

    if async_out != sync_out:
        raise RuntimeError(
            "async host loop diverged from the synchronous Engine "
            f"(greedy parity): {async_out} vs {sync_out}")
    if overlapped == 0:
        raise RuntimeError(
            "async loop never overlapped a launch with the previous step's "
            "sync — speculative decode launch is not engaging")
    out = {
        "config": {"arch": "qwen1.5-0.5b reduced(2)", "max_batch": 4,
                   "n_requests": n_requests, "max_tokens": max_tokens},
        "sync": sync, "async": a,
        "step_gap_p50_reduction_ms": (sync["step_gap_ms"]["p50"]
                                      - a["step_gap_ms"]["p50"]),
        "token_parity": True,
        "note": "same Engine object drives both loops (shared jits); "
                "step-gap = host time between a step's device sync and the "
                "next dispatch; overlapped steps dispatched before the "
                "previous sync (gap 0)",
    }
    write_bench_serving({"async_overlap": out})
    return out


def trace_bench(n_requests: int = 8, max_tokens: int = 12,
                out_path: Optional[str] = None) -> dict:
    """``--trace`` mode: fuzzed-arrival async workload with a
    :class:`Tracer` attached.  Validates the exported Chrome trace JSON
    against ``repro.analysis.tracecheck`` and gates span accounting
    *exactly* against ``EngineStats``: one root span per submitted
    request, one commit span per committed step, one chunk span per
    prefill chunk, zero unclosed spans after drain."""
    from repro.analysis.tracecheck import validate_trace

    eng = _build_engine()
    eng.tracer = Tracer(clock=eng.clock)
    rng = np.random.default_rng(5)
    sched = _fuzzed_schedule(rng, n_requests, max_tokens)

    async def run() -> None:
        async with AsyncEngine(eng) as aeng:
            await drive_requests(aeng, sched)

    asyncio.run(run())
    st = eng.stats()
    tr = eng.tracer

    if out_path is None:
        fd, out_path = tempfile.mkstemp(suffix=".json", prefix="trace_")
        os.close(fd)
    doc = tr.export(out_path)
    validate_trace(out_path)          # schema-check the file as written

    for name, got, want in (
            ("request", tr.counts["request"], st.requests_submitted),
            ("step", tr.counts["step"], st.steps_committed),
            ("prefill_chunk", tr.counts["prefill_chunk"],
             st.prefill_chunks)):
        if got != want:
            raise RuntimeError(
                f"span accounting broken: {name} spans = {got}, "
                f"EngineStats says {want}")
    if tr.open_requests():
        raise RuntimeError(
            f"unclosed request spans after drain: {tr.open_requests()}")

    out = {
        "config": {"arch": "qwen1.5-0.5b reduced(2)", "max_batch": 4,
                   "n_requests": n_requests, "max_tokens": max_tokens},
        "trace_path": out_path,
        "events": len(doc["traceEvents"]),
        "counts": dict(tr.counts),
        "engine": {"requests_submitted": st.requests_submitted,
                   "steps_committed": st.steps_committed,
                   "prefill_chunks": st.prefill_chunks,
                   "steps_overlapped": st.steps_overlapped},
        "reconciled": True,
        "note": "span counts reconcile exactly with EngineStats; trace "
                "validated by repro.analysis.tracecheck and loadable in "
                "Perfetto / chrome://tracing",
    }
    write_bench_serving({"trace": out})
    print(f"trace bench OK: {out['events']} events -> {out_path}; "
          f"requests={tr.counts['request']} steps={tr.counts['step']} "
          f"prefill_chunks={tr.counts['prefill_chunk']} all reconciled, "
          "0 unclosed spans")
    return out


def telemetry_overhead_bench(n_requests: int = 8, max_tokens: int = 64,
                             repeats: int = 10) -> dict:
    """Per-step cost with tracer + flight recorder attached vs. detached,
    on one engine (shared jits).

    Token parity is the hard gate: both arms must produce byte-identical
    outputs.  The overhead gate is <2% and *noise-calibrated*.  The
    workload is deterministic, so step k of an "on" pass and step k of an
    "off" pass run identical device work — the statistic is the median of
    per-step paired time deltas, which a stalled step (scheduler quantum
    stolen from the VM) cannot move.  The same statistic computed between
    the two *off* halves (an A/A test, true overhead zero by
    construction) measures the run's noise floor; the gate fails only
    when the on/off overhead exceeds 2% *plus* that floor, so a machine
    that cannot resolve 2% (shared CI runners routinely show multi-%
    A/A deltas) does not flake, while a real regression — an allocation
    per token, a sync per span — lands far above any floor and still
    trips.  Both numbers are reported in BENCH_serving.json.  The
    metrics registry itself is always on — it is part of both arms by
    design."""
    # own engine: longer max_len than the shared bench config so the
    # decode tail (where the arms differ per step) dominates each pass
    cfg = get_config("qwen1.5-0.5b").reduced(layers=2).replace(
        compute_dtype="float32", param_dtype="float32")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    eng = Engine(cfg, params, ServeConfig(
        max_batch=4, max_len=96, kv_block_size=8, prefill_chunk=16))
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, 64, int(rng.integers(4, 20))).tolist()
               for _ in range(n_requests)]
    sp = SamplingParams(max_tokens=max_tokens, ignore_eos=True)

    def run_once(uid_base: int):
        """One drained pass; returns (tokens, per-step wall times)."""
        reqs = [eng.submit(p, sp, uid=uid_base + i)
                for i, p in enumerate(prompts)]
        steps: List[float] = []
        while eng.has_pending():
            t0 = time.perf_counter()
            eng.commit_step(eng.launch_step(eng.plan_step()))
            steps.append(time.perf_counter() - t0)
        return [list(r.output_tokens) for r in reqs], steps

    run_once(0)                                   # warm-up: compiles
    run_once(5000)                                # second warm-up: caches
    state = {"uid_base": 10_000, "expected": None}
    passes = {"off": [], "on": []}                # per-pass step-time lists
    gc_was_on = gc.isenabled()
    gc.disable()                                  # no mid-pass GC jitter
    try:
        for rep in range(repeats):
            # alternate arm order so slow drift splits evenly
            order = ("off", "on") if rep % 2 == 0 else ("on", "off")
            for arm in order:
                if arm == "on":
                    eng.tracer = Tracer(clock=eng.clock)
                    eng.recorder = FlightRecorder(clock=eng.clock)
                else:
                    eng.tracer = None
                    eng.recorder = None
                eng.sched.recorder = eng.recorder
                toks, steps = run_once(state["uid_base"])
                state["uid_base"] += 1000
                if state["expected"] is None:
                    state["expected"] = toks
                elif toks != state["expected"]:
                    raise RuntimeError(
                        f"telemetry changed tokens (arm={arm}): "
                        f"{toks} vs {state['expected']}")
                passes[arm].append(steps)
    finally:
        if gc_was_on:
            gc.enable()

    def paired_delta_pct(a_passes, b_passes) -> float:
        """Median per-step (b - a) across step-index-aligned pass pairs,
        as a percent of the median step time."""
        deltas = [b - a
                  for pa, pb in zip(a_passes, b_passes)
                  for a, b in zip(pa, pb)]
        base = float(np.median([s for p in a_passes for s in p]))
        return 100.0 * float(np.median(deltas)) / base

    overhead_pct = paired_delta_pct(passes["off"], passes["on"])
    # A/A null between the two off halves: by construction zero overhead,
    # so whatever it reads is this run's measurement noise floor
    half = len(passes["off"]) // 2
    null_pct = abs(paired_delta_pct(passes["off"][:half],
                                    passes["off"][half:2 * half]))
    step_ms = {arm: 1e3 * float(np.median([s for p in passes[arm]
                                           for s in p]))
               for arm in passes}
    if overhead_pct >= 2.0 + null_pct:
        raise RuntimeError(
            f"telemetry overhead {overhead_pct:.2f}% >= 2% + "
            f"{null_pct:.2f}% A/A noise floor (step {step_ms['off']:.3f} "
            f"-> {step_ms['on']:.3f} ms)")
    out = {
        "config": {"arch": "qwen1.5-0.5b reduced(2)", "max_batch": 4,
                   "n_requests": n_requests, "max_tokens": max_tokens,
                   "repeats": repeats},
        "step_ms_off": step_ms["off"],
        "step_ms_on": step_ms["on"],
        "overhead_pct": overhead_pct,
        "aa_null_pct": null_pct,
        "token_parity": True,
        **telemetry_section(eng),
        "note": "median per-step paired delta over a deterministic "
                "workload (step k is identical device work in both "
                "arms); 'on' = tracer + flight recorder attached (the "
                "metrics registry is always on in both arms); gate: "
                "overhead < 2% + the A/A noise floor measured between "
                "the two off halves",
    }
    write_bench_serving({"telemetry": out})
    print(f"telemetry overhead OK: step {step_ms['off']:.3f} -> "
          f"{step_ms['on']:.3f} ms ({overhead_pct:+.2f}%, A/A floor "
          f"{null_pct:.2f}%), token parity held")
    return out


async def _rate_run(eng: Engine, arrival_rate: float, n_requests: int,
                    deadline_ms: Optional[float], max_queue: Optional[int],
                    rng, cancel_clients: int = 0,
                    expired_clients: int = 0) -> dict:
    """One open-loop pass through the TCP front-end: Poisson arrivals at
    ``arrival_rate`` req/s, one connection per request.  ``cancel_clients``
    send an explicit cancel after their first streamed token;
    ``expired_clients`` carry an already-expired deadline (deterministic
    deadline-path coverage on any machine speed)."""
    arrivals = np.cumsum(rng.exponential(1.0 / arrival_rate, n_requests))
    prompts = [rng.integers(0, 64, int(rng.integers(6, 16))).tolist()
               for _ in range(n_requests)]
    results: List[Optional[List[Dict]]] = [None] * n_requests

    async with AsyncEngine(eng, max_queue=max_queue) as aeng:
        async with FrontendServer(aeng) as srv:
            t0 = time.perf_counter()

            async def one(i: int) -> None:
                delay = arrivals[i] - (time.perf_counter() - t0)
                if delay > 0:
                    await asyncio.sleep(delay)
                kw = {"max_tokens": 10, "temperature": 0.0}
                if i < expired_clients:
                    kw["deadline_ms"] = 0.0       # expires at first sweep
                elif deadline_ms is not None:
                    kw["deadline_ms"] = deadline_ms
                if expired_clients <= i < expired_clients + cancel_clients:
                    kw.update(max_tokens=40, ignore_eos=True, cancel_after=1)
                async with ServeClient(port=srv.port) as c:
                    results[i] = await c.request(prompts[i], **kw)

            await asyncio.gather(*(one(i) for i in range(n_requests)))
            wall = time.perf_counter() - t0

    reasons = Counter(evs[-1].get("finish_reason") for evs in results)
    n_tok = sum(sum(1 for e in evs if e.get("token", -1) >= 0)
                for evs in results)
    met = reasons.get("stop", 0) + reasons.get("length", 0)
    return {"arrival_rate": arrival_rate, "requests": n_requests,
            "wall_s": wall, "tok_per_s": n_tok / max(wall, 1e-9),
            "finish_reasons": dict(reasons),
            "deadline_met": met,
            "goodput_req_per_s": met / max(wall, 1e-9)}


def goodput_bench(n_requests: int = 12,
                  deadline_ms: float = 4000.0) -> dict:
    """Goodput-vs-arrival-rate curve with deadlines, cancellation, and
    backpressure exercised at every rate (engine shared across rates, so
    compiles are paid once)."""
    eng = _build_engine()
    rng = np.random.default_rng(1)
    # warm-up pass: compiles (tiny closed burst, no deadlines)
    asyncio.run(_rate_run(eng, 1000.0, 4, None, None, rng))
    rates = []
    for rate in (2.0, 8.0, 32.0):
        rates.append(asyncio.run(_rate_run(
            eng, rate, n_requests, deadline_ms, max_queue=6, rng=rng,
            cancel_clients=2, expired_clients=1)))
    st = eng.stats()
    if st.deadline_expirations == 0:
        raise RuntimeError("goodput bench never exercised deadline expiry")
    if st.cancellations == 0:
        raise RuntimeError("goodput bench never exercised cancellation")
    out = {
        "config": {"arch": "qwen1.5-0.5b reduced(2)", "max_batch": 4,
                   "requests_per_rate": n_requests,
                   "deadline_ms": deadline_ms, "max_queue": 6,
                   "cancel_clients_per_rate": 2,
                   "expired_clients_per_rate": 1},
        "rates": rates,
        "engine": {"cancellations": st.cancellations,
                   "deadline_expirations": st.deadline_expirations,
                   "preemptions": st.preemptions,
                   "steps_overlapped": st.steps_overlapped,
                   "steps_committed": st.steps_committed},
        "note": "goodput = requests finishing (stop/length) within their "
                "deadline per wall second; cancelled / expired / rejected "
                "requests are goodput misses by construction",
    }
    write_bench_serving({"goodput": out})
    return out


def saturation_bench(requests_per_client: int = 3,
                     max_tokens: int = 8) -> dict:
    """Closed-loop client sweep: N clients each keep exactly one request in
    flight (submit, drain, repeat).  Throughput vs N; the knee is the
    engine's saturation point (max_batch slots on this config)."""
    eng = _build_engine()
    rng = np.random.default_rng(2)

    async def run_level(n_clients: int) -> dict:
        async with AsyncEngine(eng) as aeng:
            async with FrontendServer(aeng) as srv:
                t0 = time.perf_counter()
                toks = [0] * n_clients

                async def client(i: int) -> None:
                    async with ServeClient(port=srv.port) as c:
                        for _ in range(requests_per_client):
                            p = rng.integers(0, 64,
                                             int(rng.integers(6, 16))).tolist()
                            evs = await c.request(p, max_tokens=max_tokens,
                                                  temperature=0.0)
                            toks[i] += sum(1 for e in evs
                                           if e.get("token", -1) >= 0)

                await asyncio.gather(*(client(i) for i in range(n_clients)))
                wall = time.perf_counter() - t0
        return {"clients": n_clients, "wall_s": wall,
                "tokens": sum(toks),
                "tok_per_s": sum(toks) / max(wall, 1e-9)}

    asyncio.run(run_level(2))                     # warm-up: compiles
    levels = [asyncio.run(run_level(n)) for n in (1, 2, 4, 8)]
    out = {
        "config": {"arch": "qwen1.5-0.5b reduced(2)", "max_batch": 4,
                   "requests_per_client": requests_per_client,
                   "max_tokens": max_tokens},
        "levels": levels,
        "saturation_tok_per_s": max(lv["tok_per_s"] for lv in levels),
        "note": "closed loop: each client holds exactly one request in "
                "flight; throughput saturates once clients >= max_batch",
    }
    write_bench_serving({"saturation": out})
    return out


def chaos_soak(smoke: bool = False, sanitize: bool = False,
               seed: int = 0) -> dict:
    """Fault-injected soak (PR 8): the full client workload runs against an
    engine wired to a seeded :class:`FaultPlan` covering every injection
    seam — device-step raises at plan/launch/commit, NaN logits driven to
    quarantine, slow/hung steps, allocator exhaustion spikes, malformed /
    oversized / disconnecting clients, and a host-loop crash that forces a
    supervisor snapshot-restore.  Gates:

    * every scheduled fault actually fired (``FaultPlan.unfired() == []``);
    * the drain is clean — zero leaked blocks, shadow census agrees when
      ``sanitize=True``;
    * token parity: every request not directly hit by a fault (quarantined /
      disconnected / shed) streams exactly the tokens of a fault-free
      greedy baseline — retries and the snapshot-restore are invisible;
    * the restart really resumed in-flight work (>= 1 restart and the
      resumed requests completing with parity), and >= 1 quarantine and
      >= 1 step retry were exercised.

    Reports recovery latency and goodput-under-faults to
    BENCH_serving.json["chaos"]."""
    from repro.serving.faults import FaultPlan
    from repro.serving.supervisor import ServingSupervisor, SupervisorConfig

    n_requests = 10 if smoke else 12
    max_tokens = 12 if smoke else 16
    rng = np.random.default_rng(seed + 7)
    prompts = [rng.integers(0, 64, int(rng.integers(8, 14))).tolist()
               for _ in range(n_requests)]
    sp = SamplingParams(max_tokens=max_tokens, ignore_eos=True)

    # fault-free greedy baseline: the parity reference (sync engine; PR-6
    # benches gate sync/async parity, so this is the ground truth)
    base = _build_engine()
    reqs = [base.submit(p, sp) for p in prompts]
    for _ in base.stream():
        pass
    expected = [list(r.output_tokens) for r in reqs]

    plan = FaultPlan.chaos(seed=seed, n_requests=n_requests,
                           quarantine_after=2, restarts=1)

    def factory() -> Engine:
        e = _build_engine(sanitize=sanitize)
        e.fault_hook = plan.engine_hook
        if e.allocator is not None:
            e.allocator.fault_hook = plan.alloc_hook
        return e

    flight_dir = tempfile.mkdtemp(prefix="flight_")
    sup = ServingSupervisor(factory, SupervisorConfig(
        quarantine_after=2, flight_dir=flight_dir))
    eng = factory()
    # telemetry rides along (PR 9): tracing + flight recorder on, while the
    # parity baseline above ran telemetry-off — the parity gate below is
    # therefore also the byte-identical-tokens telemetry-on/off check
    eng.tracer = Tracer(clock=eng.clock)
    results: List[Optional[List[Dict]]] = [None] * n_requests
    affected = set()        # request indices a fault hit directly
    t0 = time.perf_counter()

    async def main() -> Engine:
        async with AsyncEngine(eng, supervisor=sup) as aeng:
            aeng.loop_fault_hook = plan.loop_hook
            async with FrontendServer(aeng, max_line_bytes=2048) as srv:

                async def one(i: int) -> None:
                    kind = plan.client_fault(i)
                    kw = dict(max_tokens=max_tokens, temperature=0.0,
                              ignore_eos=True)
                    async with ServeClient(port=srv.port) as c:
                        if kind == "malformed":
                            # junk line first: typed error, connection lives
                            await c.send_raw(b"}{ not json\n")
                            err = await c._recv()
                            if "error" not in err:
                                raise RuntimeError(
                                    f"no typed error for bad json: {err}")
                        if kind == "disconnect":
                            await c._send({"prompt": prompts[i], **kw})
                            await c._recv()          # ack
                            await c._recv()          # one streamed token
                            affected.add(i)
                            return                   # close = disconnect
                        if kind == "oversized":
                            # over max_line_bytes: the server answers with a
                            # typed error (the cleared buffer's tail may add
                            # a bad-json error), then serves the real request
                            await c.send_raw(b"x" * 8192 + b"\n")
                            await c._send({"prompt": prompts[i], **kw})
                            saw_err, ack = 0, None
                            while ack is None:
                                line = await c._recv()
                                if "uid" in line:
                                    ack = line
                                elif "error" in line:
                                    saw_err += 1
                                else:
                                    raise RuntimeError(
                                        f"unexpected line: {line}")
                            if not saw_err:
                                raise RuntimeError(
                                    "no typed error for oversized line")
                            evs: List[Dict] = []
                            while True:
                                out = await c._recv()
                                evs.append(out)
                                if out.get("finished"):
                                    break
                            results[i] = evs
                            return
                        results[i] = await c.request(prompts[i], **kw)

                await asyncio.gather(*(one(i) for i in range(n_requests)))
                # the disconnected request cancels server-side; let it drain
                for _ in range(200):
                    if not aeng.engine._requests:
                        break
                    await asyncio.sleep(0.05)
            return aeng.engine

    final = asyncio.run(main())
    wall = time.perf_counter() - t0
    st = final.stats()

    missing = plan.unfired()
    if missing:
        raise RuntimeError(f"chaos schedule not fully delivered: {missing}")
    if st.engine_restarts < 1:
        raise RuntimeError("scheduled host-loop crash did not restart")
    if st.quarantines < 1:
        raise RuntimeError("nan fault run did not quarantine its request")
    if st.step_retries < 1:
        raise RuntimeError("no failed step was ever retried")
    leaked = final.allocator.blocks_in_use()
    if leaked != 0:
        raise RuntimeError(f"leaked blocks after chaos drain: {leaked}")
    if final.shadow is not None:
        final.shadow.assert_drained()

    # -- telemetry gates (PR 9) ----------------------------------------------
    # every recovery action left a flight-recorder dump (in memory AND on
    # disk), and the dump counts reconcile with the recovery counters
    dump_reasons = Counter(sup.recorder.dump_reasons())
    for reason, want in (("step-retry", st.step_retries),
                         ("quarantine", st.quarantines),
                         ("engine-restart", st.engine_restarts),
                         ("hung-step", st.hung_steps)):
        if dump_reasons.get(reason, 0) != want:
            raise RuntimeError(
                f"flight recorder missed recovery events: {reason} dumps "
                f"= {dump_reasons.get(reason, 0)}, stats say {want}")
    on_disk = [f for f in os.listdir(flight_dir)
               if f.startswith("flight-") and f.endswith(".json")]
    if len(on_disk) != len(sup.recorder.dumps):
        raise RuntimeError(
            f"flight dumps on disk ({len(on_disk)}) != dumps taken "
            f"({len(sup.recorder.dumps)})")
    # span trees well-formed across retries/quarantines/restart: no orphan
    # or unclosed spans, counts reconcile exactly, trace schema-valid
    tr = final.tracer
    if tr.open_requests():
        raise RuntimeError(
            f"unclosed request spans after chaos drain: {tr.open_requests()}")
    for name, got, want in (
            ("request", tr.counts["request"], st.requests_submitted),
            ("step", tr.counts["step"], st.steps_committed),
            ("prefill_chunk", tr.counts["prefill_chunk"],
             st.prefill_chunks)):
        if got != want:
            raise RuntimeError(
                f"span accounting broken under chaos: {name} spans = "
                f"{got}, EngineStats says {want}")
    from repro.analysis.tracecheck import validate_trace
    validate_trace(tr.export())

    # token parity for every request no fault hit directly
    completed_ok, mismatched = 0, []
    for i, evs in enumerate(results):
        if i in affected or evs is None:
            continue
        reason = evs[-1].get("finish_reason")
        if reason in ("error", "aborted"):       # quarantined / shed
            affected.add(i)
            continue
        if reason not in ("stop", "length"):
            raise RuntimeError(f"request {i} ended {reason!r} under chaos")
        toks = [e["token"] for e in evs if e.get("token", -1) >= 0]
        if toks != expected[i]:
            mismatched.append(i)
        completed_ok += 1
    if mismatched:
        raise RuntimeError(
            "token parity broken for fault-free requests "
            f"{mismatched} (retries/restore must be invisible)")
    if completed_ok == 0:
        raise RuntimeError("no request survived the chaos soak unaffected")

    out = {
        "config": {"arch": "qwen1.5-0.5b reduced(2)", "max_batch": 4,
                   "n_requests": n_requests, "max_tokens": max_tokens,
                   "seed": seed, "sanitize": sanitize,
                   "faults_scheduled": len(plan.faults)},
        "wall_s": wall,
        "fault_classes": sorted({f"{s}:{k}" for s, k, _ in plan.fired}),
        "injections_delivered": len(plan.fired),
        "counters": {"step_failures": st.step_failures,
                     "step_retries": st.step_retries,
                     "quarantines": st.quarantines,
                     "engine_restarts": st.engine_restarts,
                     "load_sheds": st.load_sheds,
                     "hung_steps": st.hung_steps,
                     "degrade_tier": st.degrade_tier},
        "recovery_ms": st.recovery_ms,
        "flight_dumps": dict(dump_reasons),
        "trace_events": tr.num_events(),
        "warm_restore": bool(sup.last_restart_warm),
        "affected_requests": sorted(affected),
        "completed_unaffected": completed_ok,
        "token_parity_unaffected": True,
        "goodput_req_per_s": completed_ok / max(wall, 1e-9),
        "note": "parity gate: requests not directly hit by a fault stream "
                "exactly the fault-free greedy baseline's tokens — step "
                "retries and the snapshot-restore are invisible to them",
    }
    write_bench_serving({"chaos": out})
    print(f"chaos soak OK: {len(plan.fired)} injections "
          f"({len(out['fault_classes'])} classes), "
          f"retries={st.step_retries} quarantines={st.quarantines} "
          f"restarts={st.engine_restarts} "
          f"(warm={out['warm_restore']}) hung={st.hung_steps}; "
          f"{completed_ok}/{n_requests} unaffected with token parity, "
          f"0 leaked blocks; {len(sup.recorder.dumps)} flight dumps, "
          f"{tr.num_events()} trace events, 0 unclosed spans")
    return out


def _journal_tokens(journal_dir: str) -> int:
    """Committed-token count in a journal directory (parent-side progress
    probe while the child serve process is writing — torn tails are fine,
    a mid-compaction read just reports the previous count)."""
    from repro.serving.journal import JournalCorruption, read_records
    try:
        recs, _ = read_records(journal_dir)
    except (JournalCorruption, FileNotFoundError, OSError):
        return -1
    return sum(len(v) for r in recs if r.get("t") == "tokens"
               for v in r.get("k", {}).values())


def crash_child(journal_dir: str, port_file: str) -> None:
    """The ``--crash-child`` entrypoint: a self-contained serve process the
    crash soak SIGKILLs.  Builds a sanitized, checksummed, journaled engine,
    replays whatever journal the previous incarnation left (forced-prefix
    re-submission + stream adoption), serves the TCP front-end, and
    announces readiness by atomically writing ``port_file``.  SIGTERM
    drains gracefully (journal shutdown record, sanitizer census) and
    exits 0 — SIGKILL is the whole point of the exercise."""
    import signal
    import sys

    from repro.serving.recovery import reconcile, replay_journal

    cfg = get_config("qwen1.5-0.5b").reduced(layers=2).replace(
        compute_dtype="float32", param_dtype="float32")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    eng = Engine(cfg, params, ServeConfig(
        max_batch=4, max_len=64, kv_block_size=8, prefill_chunk=16,
        sanitize=True, kv_checksums=True, journal_dir=journal_dir))
    rep = replay_journal(eng)
    reconcile(rep, eng)

    async def main() -> None:
        aeng = AsyncEngine(eng)
        for uid in rep.resumed:
            aeng.adopt_stream(uid)
        srv = FrontendServer(aeng, recovery=rep)
        await srv.start()
        aeng.start()
        stop = asyncio.Event()
        asyncio.get_running_loop().add_signal_handler(
            signal.SIGTERM, stop.set)
        tmp = port_file + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"port": srv.port, "pid": os.getpid(),
                       "resumed": rep.resumed,
                       "forced_tokens": rep.forced_tokens,
                       "replay_ms": rep.replay_ms}, f)
        os.replace(tmp, port_file)      # atomic: the parent never sees half
        await stop.wait()
        await srv.aclose()
        await aeng.shutdown(drain=True)

    asyncio.run(main())
    sys.exit(0)


def crash_soak(smoke: bool = False, seed: int = 0, kills: int = 3,
               journal_dir: Optional[str] = None) -> dict:
    """The ``--crash`` soak (PR 10): cross-process durability under SIGKILL
    plus silent device-memory corruption.

    Phase 1 — kill/relaunch cycles: a forked serve process (journaled,
    sanitized, KV-checksummed engine behind the TCP front-end) streams the
    full client workload while the parent tails its journal and delivers
    ``kills`` seeded SIGKILLs (``FaultPlan.crash``), each once the journal
    has grown by a scheduled number of committed tokens that cycle.  After
    each kill the parent relaunches the child — which replays the journal,
    re-submitting unfinished requests with their committed tokens forced as
    prefix — and every interrupted client reconnects with the ``resume``
    protocol line at its delivery offset.  Gates:

    * zero lost accepted requests: every acked uid runs to stop/length;
    * zero duplicate delivered tokens: every client asserts each streamed
      event's ``index`` equals exactly the count it already holds, across
      all reconnects (exactly-once end-to-end over TCP);
    * greedy token parity: every request's concatenated stream equals the
      fault-free baseline token-for-token — crashes are invisible;
    * a clean final drain: the last child exits 0 on SIGTERM after writing
      the journal's shutdown record (sanitizer census inside the child).

    Phase 2 — device-memory corruption: a seeded ``device_mem`` fault
    flips/garbles a resident KV block mid-decode; the shadow pool's
    checksum sweep must detect exactly the victim, targeted
    recompute-preemption must recover it, and the final tokens must still
    match the baseline (zero leaked blocks at the sanitized drain).

    Reports recovery latency (relaunch wall + in-child replay) and replay
    cost (forced-prefix tokens re-scored) to BENCH_serving.json["crash"]."""
    import signal
    import subprocess
    import sys

    from repro.serving.faults import FaultPlan

    n_requests = 6 if smoke else 8
    max_tokens = 24 if smoke else 32
    rng = np.random.default_rng(seed + 11)
    prompts = [rng.integers(0, 64, int(rng.integers(8, 14))).tolist()
               for _ in range(n_requests)]
    sp = SamplingParams(max_tokens=max_tokens, ignore_eos=True)

    plan = FaultPlan.crash(seed=seed, kills=kills, corruptions=1)
    if journal_dir is not None:          # CI: in-workspace, uploadable
        os.makedirs(journal_dir, exist_ok=True)
        jdir = journal_dir
    else:
        jdir = tempfile.mkdtemp(prefix="crashj-")
    reqstate = [{"uid": None, "toks": [], "done": False, "reason": None}
                for _ in range(n_requests)]
    relaunch_s: List[float] = []
    replay_ms: List[float] = []
    forced_total = 0
    kills_delivered = 0
    t_soak = time.perf_counter()

    def launch_child() -> tuple:
        port_file = os.path.join(jdir, "port.json")
        if os.path.exists(port_file):
            os.unlink(port_file)
        proc = subprocess.Popen(
            [sys.executable, "-m", "benchmarks.serving_loadgen",
             "--crash-child", "--journal-dir", jdir,
             "--port-file", port_file],
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            env={**os.environ, "PYTHONPATH": "src"})
        t0 = time.perf_counter()
        deadline = t0 + 300.0
        while not os.path.exists(port_file):
            if proc.poll() is not None:
                raise RuntimeError(
                    f"crash child died during startup (rc={proc.returncode})")
            if time.perf_counter() > deadline:
                proc.kill()
                raise RuntimeError("crash child never became ready")
            time.sleep(0.05)
        with open(port_file) as f:
            info = json.load(f)
        return proc, info, time.perf_counter() - t0

    async def run_cycle(port: int, fault) -> None:
        """One child lifetime: (re)attach every unfinished client; if a proc
        fault is scheduled, SIGKILL the child once its journal grows by the
        scheduled token count.  Client coroutines treat a dropped connection
        as 'resume next cycle'."""
        nonlocal kills_delivered
        acked = asyncio.Event()
        pending_acks = [i for i, st in enumerate(reqstate)
                        if not st["done"] and st["uid"] is None]
        base_tokens = max(0, _journal_tokens(jdir))

        def note_ack(i: int) -> None:
            if i in pending_acks:
                pending_acks.remove(i)
            if not pending_acks:
                acked.set()

        async def client(i: int) -> None:
            st = reqstate[i]
            try:
                c = await ServeClient(port=port).connect()
            except OSError:
                return                      # child died before we connected
            try:
                if st["uid"] is None:
                    await c._send({"prompt": prompts[i],
                                   "max_tokens": max_tokens,
                                   "temperature": 0.0, "ignore_eos": True})
                    ack = await c._recv()
                    st["uid"] = ack["uid"]
                    note_ack(i)
                else:
                    await c._send({"resume": st["uid"],
                                   "offset": len(st["toks"])})
                    ack = await c._recv()
                    if "error" in ack:
                        raise RuntimeError(
                            f"resume rejected for uid {st['uid']}: {ack}")
                while True:
                    e = await c._recv()
                    tok = e.get("token", -1)
                    if tok >= 0:
                        # the exactly-once gate: every delivered token lands
                        # at precisely the next index, across reconnects
                        if e["index"] != len(st["toks"]):
                            raise RuntimeError(
                                f"uid {st['uid']}: token index {e['index']} "
                                f"!= delivered count {len(st['toks'])} "
                                "(lost or duplicated token)")
                        st["toks"].append(tok)
                    if e.get("finished"):
                        st["done"] = True
                        st["reason"] = e.get("finish_reason")
                        return
            except (ConnectionError, asyncio.IncompleteReadError,
                    json.JSONDecodeError):
                return                      # SIGKILL landed mid-stream
            finally:
                note_ack(i)
                try:
                    await c.close()
                except (ConnectionError, OSError):
                    pass

        async def killer() -> None:
            nonlocal kills_delivered
            if fault is None:
                return
            if pending_acks:
                await acked.wait()          # every request durably accepted
            while any(not st["done"] for st in reqstate):
                n = _journal_tokens(jdir)
                if n >= 0 and n - base_tokens >= fault.arg:
                    os.kill(info["pid"], signal.SIGKILL)
                    kills_delivered += 1
                    return
                await asyncio.sleep(0.02)
            raise RuntimeError(
                "workload drained before the scheduled SIGKILL fired — "
                "schedule the kill earlier or grow the workload")

        tasks = [client(i) for i, st in enumerate(reqstate)
                 if not st["done"]]
        if not pending_acks:
            acked.set()
        await asyncio.gather(*tasks, killer())

    cycle = 0
    proc = None
    try:
        while cycle < kills + 3:
            proc, info, ready_s = launch_child()
            relaunch_s.append(ready_s)
            replay_ms.append(float(info["replay_ms"]))
            forced_total += int(info["forced_tokens"])
            if cycle > 0:
                want = sorted(st["uid"] for st in reqstate
                              if not st["done"] and st["uid"] is not None)
                got = sorted(info["resumed"])
                if got != want:
                    raise RuntimeError(
                        f"recovery resumed uids {got}, journal-accepted "
                        f"unfinished uids are {want} (lost requests)")
            fault = plan.proc_fault(cycle)
            asyncio.run(run_cycle(info["port"], fault))
            if fault is not None:
                proc.wait(timeout=60)       # SIGKILL landed: reap the child
                cycle += 1
                continue
            # no kill this cycle: everything drained — graceful shutdown
            if any(not st["done"] for st in reqstate):
                raise RuntimeError(
                    f"kill-free cycle left unfinished requests: "
                    f"{[i for i, s in enumerate(reqstate) if not s['done']]}")
            proc.send_signal(signal.SIGTERM)
            rc = proc.wait(timeout=120)
            if rc != 0:
                raise RuntimeError(
                    f"graceful child drain exited {rc}, want 0")
            proc = None
            break
        else:
            raise RuntimeError("crash soak never reached a kill-free cycle")
    finally:
        if proc is not None and proc.poll() is None:
            proc.kill()
            proc.wait(timeout=60)

    # fault-free greedy baseline (sync engine): the parity ground truth for
    # both phases.  Computed only now, once every child has exited: the
    # children need the device, and a parent that touched JAX would hold it
    base = _build_engine()
    breqs = [base.submit(p, sp) for p in prompts]
    for _ in base.stream():
        pass
    expected = [list(r.output_tokens) for r in breqs]
    del base, breqs

    # hard gates: nothing lost, nothing duplicated, greedy parity end-to-end
    if kills_delivered < kills:
        raise RuntimeError(
            f"only {kills_delivered}/{kills} scheduled SIGKILLs fired")
    mismatched = [i for i, st in enumerate(reqstate)
                  if st["toks"] != expected[i]]
    if mismatched:
        raise RuntimeError(
            f"token parity broken across crashes for requests {mismatched}")
    bad_reason = [i for i, st in enumerate(reqstate)
                  if st["reason"] not in ("stop", "length")]
    if bad_reason:
        raise RuntimeError(
            f"requests {bad_reason} did not run to completion: "
            f"{[reqstate[i]['reason'] for i in bad_reason]}")
    from repro.serving.journal import load_state
    jstate = load_state(jdir)
    if not jstate.clean_shutdown:
        raise RuntimeError("final journal carries no clean-shutdown record")

    # -- phase 2: device-memory corruption, detection, targeted recovery -----
    cfg = get_config("qwen1.5-0.5b").reduced(layers=2).replace(
        compute_dtype="float32", param_dtype="float32")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    eng = Engine(cfg, params, ServeConfig(
        max_batch=4, max_len=64, kv_block_size=8, prefill_chunk=16,
        sanitize=True, kv_checksums=True))
    creqs = [eng.submit(p, sp) for p in prompts]
    corrupted: List[int] = []
    preempted: List[int] = []
    while eng.sched.has_work():
        eng.step()
        victim = plan.device_mem_hook(eng)
        if victim is not None:
            bad = eng.check_kv_integrity()
            if bad != [victim]:
                raise RuntimeError(
                    f"checksum sweep found {bad}, injected block {victim}")
            preempted.extend(eng.recover_corrupt_blocks(bad))
            corrupted.append(victim)
    if not corrupted:
        raise RuntimeError("device_mem fault never fired")
    cmismatch = [i for i, r in enumerate(creqs)
                 if list(r.output_tokens) != expected[i]]
    if cmismatch:
        raise RuntimeError(
            "token parity broken through corruption recovery for "
            f"requests {cmismatch}")
    if eng.allocator.blocks_in_use() != 0:
        raise RuntimeError(
            f"leaked blocks after corruption drain: "
            f"{eng.allocator.blocks_in_use()}")
    eng.shadow.assert_drained()
    cst = eng.stats()

    missing = plan.unfired()
    if missing:
        raise RuntimeError(f"crash schedule not fully delivered: {missing}")

    out = {
        "config": {"arch": "qwen1.5-0.5b reduced(2)", "max_batch": 4,
                   "n_requests": n_requests, "max_tokens": max_tokens,
                   "seed": seed, "kills": kills},
        "wall_s": time.perf_counter() - t_soak,
        "sigkills": kills_delivered,
        "relaunches": len(relaunch_s),
        "relaunch_s": {"mean": float(np.mean(relaunch_s)),
                       "max": float(np.max(relaunch_s))},
        "replay_ms": {"mean": float(np.mean(replay_ms)),
                      "max": float(np.max(replay_ms))},
        "forced_prefix_tokens": forced_total,
        "journal": {"records": jstate.records,
                    "recoveries": jstate.recoveries,
                    "clean_shutdown": jstate.clean_shutdown},
        "kv_corruption": {"injected_blocks": corrupted,
                          "detected": cst.kv_corruptions,
                          "preempted_uids": sorted(set(preempted))},
        "lost_requests": 0,
        "duplicate_tokens": 0,
        "token_parity": True,
        "note": "gates: every acked request completes with exact greedy "
                "parity across >= 3 SIGKILL/replay cycles (per-event index "
                "continuity = exactly-once over TCP resume), clean journal "
                "shutdown on the final drain, and a seeded KV bit-flip "
                "detected by the checksum sweep and healed by recompute "
                "preemption with zero leaked blocks",
    }
    write_bench_serving({"crash": out})
    print(f"crash soak OK: {kills_delivered} SIGKILLs over "
          f"{len(relaunch_s)} launches, {forced_total} forced-prefix "
          f"tokens replayed, relaunch mean {out['relaunch_s']['mean']:.1f}s"
          f" (replay {out['replay_ms']['mean']:.1f}ms); "
          f"{n_requests}/{n_requests} requests exact-parity with 0 "
          f"lost/duplicate tokens; kv corruption on block"
          f" {corrupted} detected+recovered (preempted "
          f"{sorted(set(preempted))}), 0 leaked blocks")
    return out


def smoke(sanitize: bool = False) -> None:
    """CI smoke: server up, four client behaviors (normal, expired deadline,
    explicit cancel, disconnect) through the real TCP endpoint, block
    accounting back to zero.  Seconds, not minutes.  With ``sanitize=True``
    the whole run executes under the shadow block-pool (every transition and
    write-set validated; a violation raises SanitizerError)."""
    eng = _build_engine(sanitize=sanitize)

    async def main() -> None:
        async with AsyncEngine(eng, max_queue=8) as aeng:
            async with FrontendServer(aeng) as srv:
                rng = np.random.default_rng(3)

                def prompt():
                    return rng.integers(0, 64, 10).tolist()

                async def run(**kw):
                    async with ServeClient(port=srv.port) as c:
                        return await c.request(prompt(), temperature=0.0,
                                               **kw)

                normal, expired, cancelled = await asyncio.gather(
                    run(max_tokens=6),
                    run(max_tokens=6, deadline_ms=0.0),
                    run(max_tokens=40, ignore_eos=True, cancel_after=1))
                assert normal[-1]["finish_reason"] in ("stop", "length"), \
                    normal[-1]
                assert expired[-1]["finish_reason"] == "deadline", expired[-1]
                assert cancelled[-1]["finish_reason"] == "cancelled", \
                    cancelled[-1]
                # disconnect mid-stream cancels server-side
                c = await ServeClient(port=srv.port).connect()
                await c._send({"prompt": prompt(), "max_tokens": 40,
                               "ignore_eos": True})
                await c._recv()                  # ack
                await c._recv()                  # one streamed token
                await c.close()
                for _ in range(100):
                    await asyncio.sleep(0.05)
                    if not eng._requests:
                        break
        st = eng.stats()
        assert st.cancellations >= 2, st         # explicit + disconnect
        assert st.deadline_expirations >= 1, st
        assert eng.allocator.blocks_in_use() == 0, \
            f"leaked blocks: {eng.allocator.blocks_in_use()}"
        if eng.shadow is not None:
            eng.shadow.assert_drained()           # zero OWNED/SHARED blocks
        tail = ""
        if st.sanitizer is not None:
            tail = (f" sanitized(transitions={st.sanitizer['transitions']} "
                    f"write_checks={st.sanitizer['write_checks']})")
        print(f"serve smoke OK: cancellations={st.cancellations} "
              f"deadline_expirations={st.deadline_expirations} "
              f"steps_overlapped={st.steps_overlapped}/{st.steps_committed}"
              + tail)

    asyncio.run(main())


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast end-to-end server check (CI)")
    ap.add_argument("--sanitize", action="store_true",
                    help="run the smoke under the shadow block-pool "
                         "sanitizer (repro.analysis)")
    ap.add_argument("--chaos", action="store_true",
                    help="fault-injected soak: seeded FaultPlan over every "
                         "injection seam, supervised recovery, parity and "
                         "leak gates (with --smoke: CI-sized)")
    ap.add_argument("--trace", nargs="?", const="", default=None,
                    metavar="PATH",
                    help="trace bench: fuzzed-arrival run with the Tracer "
                         "attached; validates the Chrome trace JSON "
                         "(repro.analysis.tracecheck) and gates span/stats "
                         "reconciliation (PATH optional; default a temp "
                         "file)")
    ap.add_argument("--telemetry-overhead", action="store_true",
                    help="interleaved tracer-on/off A/B run: gates <2%% "
                         "tok/s overhead with byte-identical tokens")
    ap.add_argument("--crash", action="store_true",
                    help="durability soak (PR 10): SIGKILL a forked serve "
                         "process at seeded points, relaunch + journal "
                         "replay + client resume; gates zero lost / "
                         "duplicate tokens, greedy parity, and KV-"
                         "corruption detection (with --smoke: CI-sized)")
    ap.add_argument("--crash-child", action="store_true",
                    help=argparse.SUPPRESS)   # internal: the forked server
    ap.add_argument("--journal-dir", default=None, metavar="DIR",
                    help="with --crash: put the journal (and the "
                         "child's port file) under DIR instead of a "
                         "temp dir — CI uploads it on failure")
    ap.add_argument("--port-file", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--seed", type=int, default=0,
                    help="fault-schedule seed for --crash / --chaos")
    a = ap.parse_args()
    if a.crash_child:
        crash_child(a.journal_dir, a.port_file)
    elif a.crash:
        crash_soak(smoke=a.smoke, seed=a.seed, journal_dir=a.journal_dir)
    elif a.chaos:
        chaos_soak(smoke=a.smoke, sanitize=a.sanitize)
    elif a.trace is not None:
        trace_bench(out_path=a.trace or None)
    elif a.telemetry_overhead:
        telemetry_overhead_bench()
    elif a.smoke:
        smoke(sanitize=a.sanitize)
    else:
        out = {"async_overlap": async_overlap_bench(),
               "trace": trace_bench(),
               "telemetry": telemetry_overhead_bench(),
               "goodput": goodput_bench(),
               "saturation": saturation_bench(),
               "chaos": chaos_soak(),
               "crash": crash_soak()}
        print(json.dumps(out, indent=1))
        print("merged into BENCH_serving.json")
