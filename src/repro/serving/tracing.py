"""Per-request and per-step span tracing with Chrome trace-event export.

A :class:`Tracer` attached to the engine (``engine.tracer``; ``None`` by
default, so the hot path pays one attribute check when tracing is off)
records two families of spans from timestamps the engine already takes
through its :class:`~repro.serving.telemetry.Clock`:

* **engine track** (pid 1) — one span per ``plan_step`` /
  ``launch_step`` / ``commit_step`` call, on separate threads so the async
  double-buffer overlap is visible: a speculative ``launch`` of step N+1
  lands *before* step N's ``commit``.  The off-thread host sync in
  ``AsyncEngine._loop`` gets its own ``sync`` track.  Each committed step
  also records its work counts (:meth:`Tracer.step_work`), exported as a
  ``step_work`` counter track: the share of computed positions that carry
  a request, and the share of walked KV blocks that hold live data; and
  the KV blocks the attention kernels fetched (:meth:`Tracer.step_scored`),
  exported as a ``kv_scored`` counter track: their share of the walked
  blocks.
* **request track** (pid 2, one thread per request uid) — the request's
  lifecycle: a ``queued`` span (submit → admission), ``prefill_chunk``
  spans (one per chunk the scheduler advanced in a committed step), a
  ``first_token`` instant, and a root ``request`` span (submit →
  finish) whose args carry the finish reason and token count.

``export()`` produces Chrome trace-event JSON (the
``{"traceEvents": [...]}`` flavor) loadable in Perfetto / chrome://
tracing; ``repro.analysis.tracecheck`` validates the schema in CI.

Span accounting reconciles exactly with
:class:`~repro.serving.api.EngineStats`: ``counts["request"]`` ==
``requests_submitted``, ``counts["step"]`` == ``steps_committed``,
``counts["prefill_chunk"]`` == ``prefill_chunks`` (the benchmark
``--trace`` mode gates on this), and the sums over :attr:`Tracer.work`
equal the step-work counters (``chunk_positions_run``,
``chunk_positions_useful``, ``kv_blocks_walked``, ``kv_blocks_live``), and
those over :attr:`Tracer.scored` equal ``kv_blocks_scored``.
:meth:`open_requests` must be empty after a drained run — an unclosed
request span is a lifecycle bug.

Names that outside readers match are declared here once:

* the phases the engine writes into a ``jax.profiler`` capture as
  ``TraceAnnotation`` host spans (``serve.plan`` / ``serve.launch`` /
  ``serve.sync`` / ``serve.commit``, each with ``step=<id>`` and
  ``kind=chunk|decode|idle``), so a profile of a serving process shows
  the engine's phases on the device trace's own clock;
* the XLA module names of the two jitted step programs and the HLO
  instruction names of the two Pallas kernels (the kernels pass theirs as
  ``pallas_call(name=...)``), which profile readers match by name.
  ``tests/test_chip_compile.py`` pins them against a compile for the chip.

Pure stdlib; no numpy/jax (this module is reachable from the lint's hot
step path and must stay host-sync-free).  Event storage grows with the
traced run — tracing is an opt-in debugging tool, not an always-on
metric (those live in :mod:`repro.serving.telemetry`).
"""
from __future__ import annotations

import json
from typing import Dict, List, Optional

from repro.serving.telemetry import Clock

__all__ = ["Tracer", "PID_ENGINE", "PID_REQUESTS", "PLAN", "LAUNCH",
           "SYNC", "COMMIT", "DECODE_PROGRAM", "CHUNK_PROGRAM",
           "DECODE_KERNEL", "PREFILL_KERNEL"]

# host phases in a jax.profiler capture (TraceAnnotation names)
PLAN = "serve.plan"
LAUNCH = "serve.launch"
SYNC = "serve.sync"
COMMIT = "serve.commit"

# XLA module names of the jitted step programs (Engine._decode_impl,
# Engine._chunk_step_impl) and HLO instruction names of the Pallas kernels
DECODE_PROGRAM = "jit__decode_impl"
CHUNK_PROGRAM = "jit__chunk_step_impl"
DECODE_KERNEL = "paged_attention_decode_kernel"
PREFILL_KERNEL = "paged_prefill_chunk_kernel"

PID_ENGINE = 1
PID_REQUESTS = 2

# engine-track thread ids, ordered the way Perfetto should stack them
TID_PLAN = 1
TID_LAUNCH = 2
TID_SYNC = 3
TID_COMMIT = 4

_ENGINE_THREADS = {
    TID_PLAN: "plan",
    TID_LAUNCH: "launch",
    TID_SYNC: "sync",
    TID_COMMIT: "commit",
}


class Tracer:
    """Records spans as Chrome trace events.  All ``t*`` arguments are
    engine-clock seconds; the tracer rebases them to microseconds from
    the first event so traces start at t=0."""

    def __init__(self, clock: Optional[Clock] = None):
        self.clock = clock or Clock()
        self._events: List[dict] = []
        self._epoch: Optional[float] = None
        # span accounting, reconciled against EngineStats by the bench
        self.counts: Dict[str, int] = {
            "request": 0, "step": 0, "prefill_chunk": 0,
        }
        # uid -> {"tid", "submit", "admitted"} for requests still in flight
        self._open: Dict[int, dict] = {}
        self._req_tid: Dict[int, int] = {}
        self._next_req_tid = 1
        # one tuple per committed step (step_work's arguments, in order)
        self.work: List[tuple] = []
        # one tuple per committed step (step_scored's arguments, in order)
        self.scored: List[tuple] = []

    # -- time ---------------------------------------------------------------

    def _us(self, t: float) -> float:
        if self._epoch is None:
            self._epoch = t
        return (t - self._epoch) * 1e6

    def _complete(self, name: str, pid: int, tid: int,
                  t0: float, t1: float, cat: str, args: Optional[dict]) -> None:
        ts = self._us(t0)
        ev = {"name": name, "cat": cat, "ph": "X", "ts": ts,
              "dur": max(0.0, self._us(t1) - ts), "pid": pid, "tid": tid}
        if args:
            ev["args"] = args
        self._events.append(ev)

    def _instant(self, name: str, pid: int, tid: int, t: float,
                 cat: str, args: Optional[dict]) -> None:
        ev = {"name": name, "cat": cat, "ph": "i", "ts": self._us(t),
              "pid": pid, "tid": tid, "s": "t"}
        if args:
            ev["args"] = args
        self._events.append(ev)

    # -- engine track -------------------------------------------------------

    def plan_span(self, t0, t1, step, active, chunks, spec=False):
        self._complete("plan", PID_ENGINE, TID_PLAN, t0, t1, "step",
                       {"step": step, "active": active, "chunks": chunks,
                        "spec": spec})

    def launch_span(self, t0, t1, step, spec=False):
        self._complete("launch", PID_ENGINE, TID_LAUNCH, t0, t1, "step",
                       {"step": step, "spec": spec})

    def sync_span(self, t0, t1, step):
        """The off-thread ``np.asarray`` host sync in ``AsyncEngine._loop``."""
        self._complete("sync", PID_ENGINE, TID_SYNC, t0, t1, "step",
                       {"step": step})

    def commit_span(self, t0, t1, step, tokens=0, chunks=0):
        """One committed engine step (the decode-token batch): counted and
        reconciled against ``EngineStats.steps_committed``."""
        self.counts["step"] += 1
        self._complete("commit", PID_ENGINE, TID_COMMIT, t0, t1, "step",
                       {"step": step, "tokens": tokens, "chunks": chunks})

    def step_work(self, step, kind, t_launch, rows, positions_run,
                  positions_useful, kv_blocks_walked, kv_blocks_live):
        """Work counts of one committed step (``Engine``'s ``StepWork``,
        counted from its plan at launch): rows carrying a request; positions
        every linear layer computed and those that carry a request; KV
        blocks the attention kernels' grid walks per KV head per layer and
        those that hold the rows' data.  ``t_launch`` is the engine-clock
        time dispatch returned."""
        self.work.append((step, kind, t_launch, rows, positions_run,
                          positions_useful, kv_blocks_walked, kv_blocks_live))

    def step_scored(self, step, kind, t_launch, kv_blocks_scored):
        """KV blocks the attention kernels of one committed step fetch per
        KV head per layer (``StepWork.kv_blocks_scored``), beside the same
        step's :meth:`step_work` record."""
        self.scored.append((step, kind, t_launch, kv_blocks_scored))

    # -- request track ------------------------------------------------------

    def _tid_for(self, uid: int) -> int:
        tid = self._req_tid.get(uid)
        if tid is None:
            tid = self._next_req_tid
            self._next_req_tid += 1
            self._req_tid[uid] = tid
        return tid

    def request_submit(self, uid: int, t: float) -> None:
        """Open the request's root span.  Idempotent per uid: a supervisor
        restart re-submits salvaged requests into the fresh engine, and
        those must not open (or count) a second span."""
        if uid in self._open:
            return
        self.counts["request"] += 1
        self._open[uid] = {"tid": self._tid_for(uid), "submit": t,
                           "admitted": None}

    def request_admitted(self, uid: int, t: float) -> None:
        st = self._open.get(uid)
        if st is None or st["admitted"] is not None:
            return
        st["admitted"] = t
        self._complete("queued", PID_REQUESTS, st["tid"], st["submit"], t,
                       "request", {"uid": uid})

    def prefill_chunk(self, uid: int, t0: float, t1: float, n: int) -> None:
        """One prefill chunk advanced for ``uid`` in a committed step;
        reconciled against ``EngineStats.prefill_chunks``."""
        self.counts["prefill_chunk"] += 1
        st = self._open.get(uid)
        tid = st["tid"] if st is not None else self._tid_for(uid)
        self._complete("prefill_chunk", PID_REQUESTS, tid, t0, t1,
                       "request", {"uid": uid, "positions": n})

    def request_first_token(self, uid: int, t: float) -> None:
        st = self._open.get(uid)
        if st is None:
            return
        self._instant("first_token", PID_REQUESTS, st["tid"], t,
                      "request", {"uid": uid})

    def request_finish(self, uid: int, t: float, reason: str,
                       tokens: int = 0) -> None:
        """Close the root span (finish, cancel, deadline, error, abort all
        land here).  Unknown uids are ignored — a cancel can race a
        finish."""
        st = self._open.pop(uid, None)
        if st is None:
            return
        self._complete("request", PID_REQUESTS, st["tid"], st["submit"], t,
                       "request",
                       {"uid": uid, "reason": reason, "tokens": tokens})

    def open_requests(self) -> List[int]:
        """Uids with an unclosed root span — must be empty after a drained
        run (the well-formedness gate in the telemetry chaos test)."""
        return sorted(self._open)

    # -- export -------------------------------------------------------------

    def _metadata(self) -> List[dict]:
        meta = [
            {"name": "process_name", "ph": "M", "pid": PID_ENGINE,
             "args": {"name": "engine"}},
            {"name": "process_name", "ph": "M", "pid": PID_REQUESTS,
             "args": {"name": "requests"}},
        ]
        for tid, name in _ENGINE_THREADS.items():
            meta.append({"name": "thread_name", "ph": "M", "pid": PID_ENGINE,
                         "tid": tid, "args": {"name": name}})
        for uid, tid in sorted(self._req_tid.items()):
            meta.append({"name": "thread_name", "ph": "M",
                         "pid": PID_REQUESTS, "tid": tid,
                         "args": {"name": f"req {uid}"}})
        return meta

    def _counters(self) -> List[dict]:
        """One ``step_work`` and one ``kv_scored`` counter event per
        committed step, at its launch: the useful share of computed
        positions, the live share of walked KV blocks, and the share of
        walked KV blocks the kernels fetched, in percent."""
        out = []
        walked_of = {}
        for (step, _, t, _, run, useful, walked, live) in self.work:
            walked_of[step] = walked
            out.append({"name": "step_work", "cat": "step", "ph": "C",
                        "ts": self._us(t), "pid": PID_ENGINE,
                        "tid": TID_LAUNCH,
                        "args": {"useful_pct": 100.0 * useful / run,
                                 "live_pct": (100.0 * live / walked
                                              if walked else 0.0)}})
        for (step, _, t, scored) in self.scored:
            walked = walked_of.get(step, 0)
            out.append({"name": "kv_scored", "cat": "step", "ph": "C",
                        "ts": self._us(t), "pid": PID_ENGINE,
                        "tid": TID_LAUNCH,
                        "args": {"scored_pct": (100.0 * scored / walked
                                                if walked else 0.0)}})
        return out

    def num_events(self) -> int:
        return len(self._events) + len(self.work) + len(self.scored)

    def export(self, path: Optional[str] = None) -> dict:
        """Chrome trace-event JSON; written to ``path`` when given.  Safe
        to call mid-run (exports the events recorded so far)."""
        doc = {
            "traceEvents": (self._metadata() + list(self._events)
                            + self._counters()),
            "displayTimeUnit": "ms",
            "otherData": {"counts": dict(self.counts),
                          "open_requests": self.open_requests()},
        }
        if path:
            with open(path, "w") as f:
                json.dump(doc, f)
        return doc
