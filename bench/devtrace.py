"""From a JAX profiler trace to the numbers the metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps, for
every device plane, its ``XLA Modules`` events (one per program run, named
after the jitted function, e.g. ``jit__decode_impl(...)``) and its ``XLA
Ops`` events (one per operation; a Pallas kernel is one such event), plus
the host-clock anchor the harness drops right after the trace starts.  The
rest are pure functions over those events, so a recorded trace tests them.

Times are integer nanoseconds on the trace's own clock; ``Trace.to_host``
maps them to the host's ``time.perf_counter`` seconds through the anchor.
Event names are today's jit-module and kernel-function names: they are
matched by regular expression and are fragile until the program names its
steps and kernels with stable scopes.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

ANCHOR = "bench_anchor"


@dataclasses.dataclass
class Event:
    name: str
    start: int                  # ns, trace clock
    dur: int                    # ns
    text: str = ""              # name plus the event's string stats

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    devices: Dict[str, Dict[str, List[Event]]]   # plane -> {modules, ops}
    anchor_ns: Optional[int]                     # trace clock of the anchor
    anchor_host: Optional[float] = None          # perf_counter of the anchor

    def to_host(self, ns: int) -> float:
        return self.anchor_host + (ns - self.anchor_ns) * 1e-9

    def to_trace(self, host_s: float) -> int:
        return int(round(self.anchor_ns + (host_s - self.anchor_host) * 1e9))


def _text(ev) -> str:
    parts = [ev.name]
    for _, v in ev.stats:
        if isinstance(v, str):
            parts.append(v)
    return " ".join(parts)


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` file, gzipped (``.gz``) or not, or the newest
    one under a trace directory."""
    import gzip

    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(
            path, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Event]]] = {}
    anchor = None
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            kinds = {"modules": [], "ops": []}
            for line in plane.lines:
                kind = ("modules" if line.name == "XLA Modules" else
                        "ops" if line.name == "XLA Ops" else None)
                if kind is None:
                    continue
                kinds[kind].extend(
                    Event(e.name, int(e.start_ns), int(e.duration_ns),
                          _text(e)) for e in line.events)
            if kinds["modules"] or kinds["ops"]:
                for evs in kinds.values():
                    evs.sort(key=lambda e: e.start)
                devices[plane.name] = kinds
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == ANCHOR and anchor is None:
                        anchor = int(e.start_ns)
    return Trace(devices, anchor)


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge [start, end) intervals."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def busy(ops: Sequence[Event], lo: int, hi: int) -> List[Tuple[int, int]]:
    """Intervals in [lo, hi) in which some operation ran on the device."""
    return clip(union([(e.start, e.end) for e in ops]), lo, hi)


def busy_ns(ops: Sequence[Event], lo: int, hi: int) -> int:
    return sum(e - s for s, e in busy(ops, lo, hi))


def idle_gaps(ops: Sequence[Event], lo: int, hi: int
              ) -> List[Tuple[int, int]]:
    """Gaps in [lo, hi) with no operation running, longest first."""
    gaps, t = [], lo
    for s, e in busy(ops, lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return sorted(gaps, key=lambda g: g[0] - g[1])


def matching(events: Sequence[Event], pattern: str) -> List[Event]:
    """Events whose name or string stats match ``pattern`` (programs)."""
    rx = re.compile(pattern)
    return [e for e in events if rx.search(e.text)]


def op_name(e: Event) -> str:
    """An operation's own HLO instruction name, ``%fusion.3 = ...`` ->
    ``fusion.3``; an operation's text also names its operands, so a kernel
    is matched on this alone."""
    return e.name.split(" = ", 1)[0].lstrip("%")


def named(ops: Sequence[Event], pattern: str) -> List[Event]:
    """Operations whose own instruction name matches ``pattern``."""
    rx = re.compile(pattern)
    return [e for e in ops if rx.search(op_name(e))]


def leaves(ops: Sequence[Event]) -> List[Event]:
    """Operations that hold no other: a loop or call op spans the ops it
    runs, which the trace also lists."""
    s = sorted(ops, key=lambda e: (e.start, -e.end))
    return [e for e, nxt in zip(s, s[1:] + [None])
            if nxt is None or nxt.start >= e.end]


def within(events: Sequence[Event], lo: int, hi: int) -> List[Event]:
    """Events wholly inside [lo, hi)."""
    return [e for e in events if e.start >= lo and e.end <= hi]


def inside(ops: Sequence[Event], module: Event) -> List[Event]:
    return within(ops, module.start, module.end)


def top_ops(ops: Sequence[Event], lo: int, hi: int, n: int = 10,
            width: int = 160) -> List[Tuple[str, float]]:
    """Innermost operations by total device seconds in [lo, hi), named by
    the first ``width`` characters of their HLO text."""
    tot: Dict[str, int] = {}
    for e in leaves(within(ops, lo, hi)):
        tot[e.name[:width]] = tot.get(e.name[:width], 0) + e.dur
    return [(k, v * 1e-9) for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def match_steps(modules: Sequence[Event], launches: Sequence[float],
                trace: Trace, slack_s: float = 0.002) -> List[int]:
    """Index into ``launches`` (host launch times of steps of one program,
    in launch order) of the step each module event ran.  The device runs
    steps in launch order, and a step cannot start before its launch, so the
    events are the launches shifted by the largest offset that keeps every
    launch before its event (less ``slack_s`` of clock error)."""
    starts = [trace.to_host(m.start) for m in modules]
    n, k = len(starts), len(launches) - len(starts)
    while k >= 0:
        if all(launches[k + i] <= starts[i] + slack_s for i in range(n)):
            return [k + i for i in range(n)]
        k -= 1
    raise ValueError(f"{n} device runs cannot be matched to {len(launches)} "
                     "host launches")
