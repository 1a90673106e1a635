"""The benchmark's run of one cell: build, warm up, load, measure, check.

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
cell's configuration and traffic mix and lists its metrics;
``<bench>/configs/<config>.json`` holds the model and serve settings,
``<bench>/arch/<family>.py`` the architecture module the configuration
names (its ``"arch"`` key): the program's configuration, the seeded
tensors and their program leaves, the reference's layers and the work
counts, so that nothing else here reads an architecture's keys or tensor
names; ``<bench>/traffic/<mix>.json`` the mix,
``<bench>/cells/<workload>.json`` what is fixed per cell (open-loop rate
and warm start, correctness limit), and ``<bench>/metrics/<metric>.py`` one
reader per metric.  Adding a cell, a configuration, an architecture
family, a mix or a metric adds files; no code here names one.

One process owns the chip.  It builds the program's engine with weights made
from the seed on the device, warms every step shape the mix can reach, then
serves the mix through the program's own front end (TCP, JSON lines) →
``AsyncEngine`` → fused step, with a ramp before the measured window.  After
the window it reads peak memory, frees the program's state, and compares a
sample of the finished requests with the plain reference.
"""
from __future__ import annotations

import asyncio
import dataclasses
import gc
import importlib.util
import json
import math
import os
import pathlib
import re
import time
import types
from typing import Callable, Dict, List, Optional

import numpy as np

from . import devtrace as tr
from . import traffic as traffic_mod
from .client import Connection, Record

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent

DECODE_PROGRAM = r"jit__decode_impl"
CHUNK_PROGRAM = r"jit__chunk_step_impl"
TAIL_S = 60.0           # longest wait past the window for its requests
TPOT_TOKENS = 24        # tokens each request due in the window should stream
TRACE_S = 8.0           # profiled seconds, at the end of a --trace 1 window


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


class BadCell(RuntimeError):
    """The cell cannot be set up from its files."""


# -- the cell, found by name ---------------------------------------------------

@dataclasses.dataclass
class Cell:
    workload: str
    chips: int
    config_name: str
    config: Dict
    mix_name: str
    mix: Dict
    cell: Dict
    metrics: List[Dict]            # every metric entry of BENCHMARK.json
    bench: pathlib.Path

    def metric_entries(self, trace: bool) -> List[Dict]:
        """The metrics this cell reports: end to end without the trace,
        per layer with it."""
        return [m for m in self.metrics
                if m["_kind"] == ("per_layer" if trace else "end_to_end")
                and self.workload in m.get("workloads", [self.workload])]


def _json(path: pathlib.Path) -> Dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise BadCell(f"missing file {path}") from e


def load_cell(workload: str, root: pathlib.Path = ROOT,
              bench: pathlib.Path = BENCH) -> Cell:
    doc = _json(root / "BENCHMARK.json")
    w = [x for x in doc["workloads"] if x["name"] == workload]
    if not w:
        raise BadCell(f"no workload {workload!r} in BENCHMARK.json")
    w = w[0]
    conf = [c for c in doc["configs"] if c["name"] == w["config"]]
    if not conf:
        raise BadCell(f"no config {w['config']!r} in BENCHMARK.json")
    metrics = ([dict(m, _kind="end_to_end") for m in doc["end_to_end"]]
               + [dict(m, _kind="per_layer") for m in doc["per_layer"]])
    return Cell(workload, int(w["chips"]), w["config"],
                _json(root / conf[0]["file"]), w["traffic"],
                _json(bench / "traffic" / f"{w['traffic']}.json"),
                _json(bench / "cells" / f"{workload}.json"), metrics, bench)


def load_reader(bench: pathlib.Path, name: str) -> Callable:
    path = bench / "metrics" / f"{name}.py"
    if not path.is_file():
        raise BadCell(f"no reader {path} for metric {name!r}")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


_FAMILIES: Dict[pathlib.Path, types.ModuleType] = {}


def load_family(arch_dir: pathlib.Path, name: str) -> types.ModuleType:
    """The architecture module ``<arch_dir>/<name>.py``, loaded by path once
    per process (a module may load a sibling it builds on the same way).

    It gives ``architecture(config)``, the architecture's numbers
    (``arch``) in the configuration file; ``program_config(config)``, the
    program's ModelConfig for them (before the form's dtypes and
    quantization); ``layer_kinds(arch)``, the kind of each layer;
    ``layer_tensors(key, layer, arch, form, kind)`` and ``top_tensors(key,
    arch, form)``, the seeded tensors by the architecture's names;
    ``program_leaf(path, arch)``, the layers and tensor name of a program
    leaf; the float32 reference's ``embed``, ``reference_layer``,
    ``final_norm`` and ``head_table``; and the work counts
    ``linear_params``, ``attention_ops``, ``head_ops``,
    ``kv_bytes_per_token`` and ``attention_io_bytes``."""
    if not re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.-]*", name):
        raise BadCell(f"architecture module name {name!r}")
    path = (pathlib.Path(arch_dir) / f"{name}.py").resolve()
    if path not in _FAMILIES:
        if not path.is_file():
            raise BadCell(f"no architecture module {path}")
        spec = importlib.util.spec_from_file_location(
            f"bench_arch_{name.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _FAMILIES[path] = mod
    return _FAMILIES[path]


def family_of(config: Dict, bench: pathlib.Path = BENCH) -> types.ModuleType:
    """The architecture module a configuration names; a configuration that
    names none is an error."""
    if "arch" not in config:
        raise BadCell("the configuration names no architecture module "
                      "(its \"arch\" key)")
    return load_family(bench / "arch", config["arch"])


# -- the device ----------------------------------------------------------------

def check_chip(chips: int) -> List:
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX finds no accelerator: {e}") from e
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU, only {len(devices)} "
                     f"{devices[0].platform} device(s)")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds "
                     f"{len(devices)}")
    return devices


CACHE_DIR = ".bench_cache"     # JAX's persistent compilation cache, in-tree


def enable_compile_cache(root: pathlib.Path) -> str:
    """JAX's persistent compilation cache in a fixed directory inside the
    checkout (the path is part of what a later run must find again), with
    no size limit, whatever the environment names: a cache shared with
    another checkout, or one too small for every step program, would make
    set-up depend on what else ran.  The program is handed the same
    directory through ``JAX_COMPILATION_CACHE_DIR``.  Every program is
    cached, however quick its compile."""
    import jax
    path = str(root / CACHE_DIR)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileLog:
    """Times of backend compiles and persistent-cache reads, from JAX's
    monitoring events, so compiles inside the window can be counted."""

    def __init__(self):
        import jax
        self.compiles: List[float] = []
        self.cache_reads: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append(time.perf_counter())

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_reads.append(time.perf_counter())

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t < t1 for t in self.compiles + self.cache_reads)


# -- the program ---------------------------------------------------------------

def model_config(config: Dict, bench: pathlib.Path = BENCH):
    """The program's ModelConfig for a configuration file: the architecture
    module's, in the file's dtypes and form."""
    from repro.core import quant as Q

    cfg = family_of(config, bench).program_config(config).replace(
        param_dtype=config["dtypes"]["params"],
        compute_dtype=config["dtypes"]["compute"])
    if config["form"] == "packed":
        cfg = cfg.with_quant(Q.PACKED)
    elif config["form"] != "bf16":
        raise BadCell(f"unknown form {config['form']!r}")
    return cfg


def build_engine(config: Dict, seed: int, bench: pathlib.Path = BENCH):
    import jax
    from repro.core.quant import pack_ternary
    from repro.models import build_model
    from repro.serving.engine import Engine, ServeConfig

    from .weights import program_params

    cfg = model_config(config, bench)
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    family = family_of(config, bench)
    params = program_params(seed, family, family.architecture(config),
                            config["form"], shapes, pack_ternary)
    return Engine(cfg, params, ServeConfig(**config["serve"]))


def step_shapes(eng, longest: int):
    """Every (program, table width, chunk bucket) a request of at most
    ``longest`` tokens can make the engine launch."""
    from repro.serving.scheduler import bucket_length
    cols = eng.sched.block_tables.shape[1]
    top = bucket_length(eng.allocator.blocks_for(longest), 1, cols)
    widths = sorted({bucket_length(2 ** i, 1, cols)
                     for i in range(int(math.log2(top)) + 1)})
    chunk = eng.scfg.prefill_chunk
    ts = sorted({bucket_length(2 ** i, 1, chunk)
                 for i in range(int(math.log2(chunk)) + 1)})
    return widths, ts


def _signatures(eng, widths, ts):
    """(jitted step, argument shapes) of every program to warm, built as
    ``Engine._launch_decode`` / ``_launch_chunk`` build their arguments."""
    import jax
    import jax.numpy as jnp
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, jnp.dtype(dt))
    like = lambda tree: jax.tree_util.tree_map(
        lambda a: sd(a.shape, a.dtype), tree)
    b = eng.scfg.max_batch
    params, cache, keys = like(eng.params), like(eng._cache), like(eng._keys)
    vec, f32 = sd((b,), np.int32), sd((b,), np.float32)
    out = []
    for w in widths:
        bt = sd((b, w), np.int32)
        out.append((eng._decode, (params, vec, cache, vec, keys, f32, f32,
                                  bt)))
        out += [(eng._chunk, (params, sd((b, t), np.int32), cache, vec, vec,
                              sd((b,), bool), keys, f32, f32, bt))
                for t in ts]
    return out


def precompile(eng, widths, ts, workers: int) -> None:
    """Compile (or read from the persistent cache) every step program.
    Lowering runs in this thread, one program after another, so that each
    program's text, and with it its cache key, is the same in every
    process; the compiles then run in parallel threads (a cold compile of
    the 63 programs of a 3B cell takes about 6 minutes in 8 threads and
    far longer in one)."""
    from concurrent.futures import ThreadPoolExecutor
    lowered = [fn.lower(*args) for fn, args in _signatures(eng, widths, ts)]
    with ThreadPoolExecutor(workers) as ex:
        list(ex.map(lambda low: low.compile(), lowered))


def warm_up(eng, widths, ts) -> int:
    """Run the decode program at every width and the chunk program at every
    (bucket, width), with the arguments built as the engine builds them, on
    rows that all point at the trash block, after ``precompile``.  Returns
    the programs run."""
    import jax.numpy as jnp
    sc, b = eng.sched, eng.scfg.max_batch
    pad = np.full((b,), eng.scfg.pad_id, np.int32)
    zeros = np.zeros((b,), np.int32)
    n = 0
    tok = None
    for w in widths:
        bt = jnp.asarray(np.zeros((b, w), np.int32))
        tok, eng._cache, eng._keys = eng._decode(
            eng.params, jnp.asarray(pad), eng._cache, jnp.asarray(zeros),
            eng._keys, jnp.asarray(sc.temperatures), jnp.asarray(sc.top_ps),
            bt)
        n += 1
        for t in ts:
            tok, eng._cache, eng._keys = eng._chunk(
                eng.params, jnp.asarray(np.full((b, t), eng.scfg.pad_id,
                                                np.int32)),
                eng._cache, jnp.asarray(zeros), jnp.asarray(np.ones(
                    (b,), np.int32)), jnp.asarray(np.zeros((b,), bool)),
                eng._keys, jnp.asarray(sc.temperatures),
                jnp.asarray(sc.top_ps), bt)
            n += 1
    np.asarray(tok)
    return n


# -- what the traced run records from the engine -------------------------------

def make_tracer(eng):
    """A Tracer that also keeps, on the host clock, each launched step's
    shape (``Engine.last_decode`` at launch), each request's queue wait and
    the engine's plan/launch/sync/commit spans."""
    from repro.serving.tracing import Tracer

    class StepTracer(Tracer):
        def __init__(self):
            super().__init__(clock=eng.clock)
            self.eng = eng              # dropped once the load has run
            self.launches: List[Dict] = []
            self.waits: List[tuple] = []        # (admitted, seconds)
            self.spans: List[tuple] = []        # (name, t0, t1)

        def plan_span(self, t0, t1, *a, **k):
            self.spans.append(("plan", t0, t1))

        def launch_span(self, t0, t1, step, spec=False):
            shape = dict(self.eng.last_decode or {})
            shape["t"] = t1
            shape["kind"] = "decode" if shape.get("chunks") is None \
                else "chunk"
            self.launches.append(shape)
            self.spans.append(("launch", t0, t1))

        def device_span(self, *a, **k):
            pass

        def sync_span(self, t0, t1, step):
            self.spans.append(("sync", t0, t1))

        def commit_span(self, t0, t1, *a, **k):
            self.spans.append(("commit", t0, t1))

        def prefill_chunk(self, *a, **k):
            pass

        def request_submit(self, uid, t):
            self._open[uid] = {"submit": t}

        def request_admitted(self, uid, t):
            st = self._open.get(uid)
            if st is not None and "admitted" not in st:
                st["admitted"] = t
                self.waits.append((t, t - st["submit"]))

        def request_first_token(self, *a, **k):
            pass

        def request_finish(self, uid, *a, **k):
            self._open.pop(uid, None)

    return StepTracer()


# -- one run -------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What the metric readers see."""
    cell: Cell
    seed: int
    seconds: float
    records: List[Record]
    window: tuple                    # host perf_counter seconds [w0, w1)
    setup_s: float
    stats: Dict[str, int]            # EngineStats counters over the window
    arch: Dict                       # the configuration's architecture
    form: str
    family: types.ModuleType         # its architecture module
    serve: Dict
    kv_itemsize: int                 # bytes per element of the KV pool
    peaks: Optional[Dict] = None
    tracer: object = None
    trace: object = None             # trace.Trace, with --trace 1
    trace_window: Optional[tuple] = None   # host seconds profiled
    end: float = 0.0                 # host seconds when measuring stopped
    notes: Dict = dataclasses.field(default_factory=dict)

    def in_window(self) -> List[Record]:
        w0, w1 = self.window
        return [r for r in self.records if w0 <= r.due < w1]

    def modules(self, program: str):
        """Device runs of ``program`` wholly inside the profiled window."""
        if self.trace is None:
            return []
        lo, hi = (self.trace.to_trace(t) for t in self.trace_window)
        return [m for planes in self.trace.devices.values()
                for m in tr.within(tr.matching(planes["modules"], program),
                                   lo, hi)]

    def device_steps(self, program: str, kind: str):
        """[(module event, step shape, ops inside it)] for every run of
        ``program`` wholly inside the profiled window, matched in order to
        the host launches of ``kind`` steps."""
        if self.trace is None:
            return []
        lo, hi = (self.trace.to_trace(t) for t in self.trace_window)
        out = []
        launches = [s for s in self.tracer.launches if s["kind"] == kind]
        for planes in self.trace.devices.values():
            mods = tr.within(tr.matching(planes["modules"], program), lo, hi)
            if not mods:
                continue
            idx = tr.match_steps(mods, [s["t"] for s in launches], self.trace)
            for m, i in zip(mods, idx):
                out.append((m, launches[i], tr.inside(planes["ops"], m)))
        return out


def _percentile(values, q) -> Optional[float]:
    return float(np.percentile(np.asarray(values, float), q)) \
        if len(values) else None


async def _drive(eng, cell: Cell, seed: int, seconds: float, trace_dir,
                 t_start: float, compile_log: CompileLog):
    """Serve the mix through the front end; returns records and timings."""
    import jax
    from repro.serving.async_engine import AsyncEngine
    from repro.serving.frontend import FrontendServer

    mix, loop_kind = cell.mix, cell.mix["loop"]
    ramp = float(mix["ramp_s"])
    records: List[Record] = []
    stop = asyncio.Event()
    timing: Dict = {}
    stats0 = {}
    prof = {}

    async def window_events(w0, w1):
        await asyncio.sleep(max(0.0, w0 - time.perf_counter()))
        stats0.update(_counters(eng))
        timing["waiting"] = [len(eng.sched.waiting)]
        timing["live"] = [_live(eng)]
        if trace_dir is not None:
            await asyncio.sleep(max(0.0, w1 - TRACE_S - time.perf_counter()))
            jax.profiler.start_trace(str(trace_dir))
            with jax.profiler.TraceAnnotation("bench_anchor"):
                prof["anchor"] = time.perf_counter()
            prof["t0"] = prof["anchor"]
        await asyncio.sleep(max(0.0, w1 - time.perf_counter()))
        timing["stats1"] = _counters(eng)
        timing["waiting"].append(len(eng.sched.waiting))
        timing["live"].append(_live(eng))
        if trace_dir is not None:
            prof["t1"] = time.perf_counter()
            jax.profiler.stop_trace()

    aeng = AsyncEngine(eng)
    async with aeng:
        async with FrontendServer(aeng) as srv:
            t_load = time.perf_counter()
            timing["setup_s"] = t_load - t_start
            w0, w1 = t_load + ramp, t_load + ramp + seconds
            tasks = [asyncio.ensure_future(window_events(w0, w1))]
            if loop_kind == "open":
                asks = traffic_mod.open_loop(
                    mix, float(cell.cell["rate_rps"]),
                    (ramp, seconds, TAIL_S), seed, eng.cfg.vocab,
                    cell.cell.get("token_s"))

                async def one(i, ask):
                    rec = Record(i, ask.prompt, ask.max_tokens,
                                 t_load + ask.due)
                    await asyncio.sleep(max(0.0, rec.due - time.perf_counter()))
                    if stop.is_set():
                        return
                    records.append(rec)
                    async with Connection(srv.port) as c:
                        await c.request(ask.prompt, rec)

                tasks += [asyncio.ensure_future(one(i, a))
                          for i, a in enumerate(asks)]
            elif loop_kind == "closed":
                sessions = traffic_mod.closed_sessions(mix, seed,
                                                       eng.cfg.vocab)
                queue = list(sessions)

                async def client():
                    async with Connection(srv.port) as c:
                        while queue and not stop.is_set():
                            for ask in queue.pop(0):
                                if stop.is_set():
                                    return
                                rec = Record(len(records), ask.prompt,
                                             ask.max_tokens,
                                             time.perf_counter(),
                                             session=ask.session, ask=ask.ask)
                                records.append(rec)
                                await c.request(ask.prompt, rec)

                tasks += [asyncio.ensure_future(client())
                          for _ in range(int(mix["clients"]))]
            else:
                raise BadCell(f"unknown loop {loop_kind!r}")

            await tasks[0]                       # the window has closed
            # the load goes on until every request due in the window has
            # streamed TPOT_TOKENS tokens (or all of its own, or ended), at
            # most TAIL_S
            due = [r for r in records if w0 <= r.due < w1]
            t_cap = time.perf_counter() + TAIL_S
            while (any(len(r.times) < min(TPOT_TOKENS, r.max_tokens)
                       and r.finish is None for r in due)
                   and time.perf_counter() < t_cap):
                await asyncio.sleep(0.05)
            timing["end"] = time.perf_counter()
            stop.set()
            timing["tail_s"] = timing["end"] - w1
            await aeng.shutdown(drain=False)
            for t in tasks[1:]:
                if not t.done():
                    t.cancel()
            await asyncio.gather(*tasks[1:], return_exceptions=True)
    timing.update(window=(w0, w1), stats0=stats0, prof=prof,
                  compiles=compile_log.between(w0, w1),
                  compiles_in_load=compile_log.between(t_load,
                                                       timing["end"]))
    return records, timing


def _live(eng) -> int:
    """Requests holding a slot."""
    return sum(r is not None for r in eng.sched.slots)


def _counters(eng) -> Dict[str, int]:
    s = eng.stats()
    return {k: getattr(s, k) for k in (
        "prefill_positions", "prefill_positions_skipped", "prefill_chunks",
        "tokens_generated", "steps_committed", "steps_overlapped",
        "preemptions", "admissions")}


def sample_checked(records: List[Record], cell: Cell, seed: int,
                   w: tuple, end: float) -> List[Record]:
    """The requests the reference checks: ones due before the window closed
    and finished before measuring stopped, drawn from the seed, from those
    that streamed tokens inside the window first (the steps with the
    window's load made them); the longest of those is always among them
    and, where the mix has sessions, one later ask (its prefix from the
    cache)."""
    n = int(cell.mix["check"]["requests"])
    done = [r for r in records if r.due < w[1] and r.complete(end)]
    if not done:
        return []
    rng = np.random.default_rng(seed ^ 0x5EED)
    served_in = [r for r in done if _window_tokens(r, w)]
    pick = [max(served_in or done,
                key=lambda r: r.prompt_len + len(r.tokens))]
    later = [r for r in done if r.ask > 0 and r is not pick[0]]
    if later:
        pick.append(later[int(rng.integers(len(later)))])
    for pool in (served_in, done):
        rest = [r for r in pool if all(r is not p for p in pick)]
        for i in rng.permutation(len(rest))[:max(0, n - len(pick))]:
            pick.append(rest[int(i)])
    return pick


def _window_tokens(r: Record, w: tuple) -> int:
    return sum(w[0] <= t < w[1] for t in r.times)


def verdict(checks: Dict[str, Dict]) -> bool:
    """``correct``: every number compared lies within its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, require_chip: bool = True,
             out_dir: Optional[pathlib.Path] = None,
             peaks: Optional[Dict] = None, control: bool = False) -> Dict:
    """One run of one cell; returns the result line's object.  With
    ``require_chip=False`` (tests only) it runs on whatever JAX finds,
    leaves JAX's compilation cache as it is, and takes the ``peaks`` given
    in place of the device's.  ``control=True`` also reads the control's
    gap on the same checked requests and judges it as the served tokens are
    judged, under the result's ``control`` key (``bench/control.py``)."""
    import jax
    if require_chip:
        devices = check_chip(cell.chips)
        enable_compile_cache(ROOT)
    else:
        devices = jax.devices()
    compile_log = CompileLog()
    dev = devices[0]
    config, mix = cell.config, cell.mix
    family = family_of(config, cell.bench)
    arch, form = family.architecture(config), config["form"]

    phases = {"imports": time.perf_counter() - t_start}
    t = time.perf_counter()
    eng = build_engine(config, seed, cell.bench)
    eng._ensure_state()
    phases["engine"] = time.perf_counter() - t
    widths, ts = step_shapes(eng, traffic_mod.longest(mix))
    t = time.perf_counter()
    precompile(eng, widths, ts, max(1, min(8, (os.cpu_count() or 2) - 2)))
    phases["compile"] = time.perf_counter() - t
    t = time.perf_counter()
    n_programs = warm_up(eng, widths, ts)
    phases["warm_up"] = time.perf_counter() - t
    tracer = None
    trace_dir = None
    if trace:
        tracer = make_tracer(eng)
        eng.tracer = tracer
        trace_dir = (out_dir or ROOT / ".bench_trace") / f"trace-{seed}"
    records, timing = asyncio.run(_drive(
        eng, cell, seed, seconds, trace_dir, t_start, compile_log))
    stats = {k: timing["stats1"][k] - timing["stats0"][k]
             for k in timing["stats1"]}
    import jax
    kv_itemsize = int(jax.tree_util.tree_leaves(eng._cache)[0].dtype.itemsize)
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    eng.tracer = None
    if tracer is not None:
        tracer.eng = None
    del eng
    gc.collect()

    run = Run(cell, seed, seconds, records, timing["window"],
              timing["setup_s"], stats, arch, form, family,
              dict(config["serve"]),
              kv_itemsize, tracer=tracer, end=timing["end"])
    run.notes.update(setup_phases_s=phases, programs_warmed=n_programs,
                     compiles_in_window=timing["compiles"],
                     compiles_in_load=timing["compiles_in_load"],
                     waiting_at_window_ends=timing["waiting"],
                     live_at_window_ends=timing["live"],
                     tail_s=round(timing["tail_s"], 3))
    late = [r.sent - r.due for r in run.in_window() if r.sent is not None]
    if late and mix["loop"] == "open":
        run.notes["generator_late_p95_ms"] = _percentile(late, 95) * 1e3
    if trace:
        run.trace = tr.load(str(trace_dir))
        run.trace.anchor_host = timing["prof"]["anchor"]
        run.trace_window = (timing["prof"]["t0"], timing["prof"]["t1"])
        run.peaks = peaks if peaks is not None else peaks_for(
            dev.device_kind)

    # correctness: the reference over a sample of finished requests
    checked = sample_checked(records, cell, seed, run.window, run.end)
    limit = float(cell.cell["limits"]["logit_gap"])
    t = time.perf_counter()
    if checked:
        from . import reference
        res = reference.compare(
            seed, family, arch, form, [(r.prompt, r.tokens) for r in checked],
            pad_len=traffic_mod.longest(mix),
            pad_out=int(_out_max(mix)), control=control)
        gap, control_gap = res["gap"], res.get("control_gap")
        run.notes["checked"] = {
            "requests": len(checked), "tokens": res["positions"],
            "tokens_in_window": sum(_window_tokens(r, run.window)
                                    for r in checked)}
    else:
        gap = control_gap = float("inf")
        run.notes["checked"] = {"requests": 0, "tokens": 0,
                                "tokens_in_window": 0}
    checks = {"logit_gap": {"value": gap, "limit": limit}}
    correct = verdict(checks)
    run.notes["reference_s"] = time.perf_counter() - t

    metrics = {}
    for m in cell.metric_entries(trace):
        value = load_reader(cell.bench, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    attempted = run.in_window()
    failed = [r for r in attempted if r.failed(run.end)]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(mem)}
    out = {"correct": correct, "attempted": len(attempted),
           "failed": len(failed), "metrics": metrics, "device": device}
    if trace:
        busy, window, breakdown = trace_summary(run)
        device.update(busy_s=busy, window_s=window)
        out["breakdown"] = breakdown
        lo, hi = run.trace_window
        run.notes["steps_profiled"] = {
            kind: sum(lo <= s["t"] < hi and s["kind"] == kind
                      for s in tracer.launches)
            for kind in ("decode", "chunk")}
    if control:
        ctrl = {"logit_gap": {"value": control_gap, "limit": limit}}
        out["control"] = {"correct": verdict(ctrl), "checks": ctrl}
    out["notes"] = run.notes
    out["checks"] = checks
    return out


def _out_max(mix: Dict) -> int:
    return int(mix["output"]["max"])


def peaks_for(kind: str) -> Dict:
    table = _json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise BadCell(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


def trace_summary(run: Run):
    """busy_s, window_s and the breakdown of the profiled window."""
    lo, hi = (run.trace.to_trace(t) for t in run.trace_window)
    busies, ops_all, gaps = [], [], []
    for planes in run.trace.devices.values():
        busies.append(tr.busy_ns(planes["ops"], lo, hi) * 1e-9)
        ops_all += planes["ops"]
        gaps += tr.idle_gaps(planes["ops"], lo, hi)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    spans = run.tracer.spans

    def host_activity(g):
        a, b = run.trace.to_host(g[0]), run.trace.to_host(g[1])
        best, label = 0.0, "no engine span"
        for name, s0, s1 in spans:
            o = min(b, s1) - max(a, s0)
            if o > best:
                best, label = o, name
        return label

    breakdown = {
        "device_ops": [[n, s] for n, s in tr.top_ops(ops_all, lo, hi)],
        "idle_gaps": [[host_activity(g), (g[1] - g[0]) * 1e-9]
                      for g in gaps],
    }
    busy = float(np.mean(busies)) if busies else 0.0
    return busy, (hi - lo) * 1e-9, breakdown
