"""The reduction from profiler trace to metrics, on hand-made events and on
a small trace recorded on a v5e chip (``record_trace.py``: the tiny packed
cell, traced)."""
import json
import types

import pytest

from bench import devtrace as tr
from bench import harness
from bench.metrics import paged_attention_roofline as pa
from bench.metrics import paged_prefill_roofline as pp
from bench.tests.conftest import DATA, tiny_cell

PB = DATA / "trace.xplane.pb.gz"
HOST = DATA / "trace_host.json"


def ev(name, start, dur, text=None):
    return tr.Event(name, start, dur, text or name)


def test_busy_is_the_union_of_operations():
    ops = [ev("a", 0, 10), ev("b", 5, 10), ev("c", 30, 5), ev("d", 31, 1)]
    assert tr.union([(e.start, e.end) for e in ops]) == [(0, 15), (30, 35)]
    assert tr.busy_ns(ops, 0, 40) == 20
    assert tr.busy_ns(ops, 10, 32) == 7             # clipped to the window
    assert tr.idle_gaps(ops, 0, 40) == [(15, 30), (35, 40)]


def test_program_and_kernel_matching():
    mods = [ev("jit__decode_impl(7)", 0, 100), ev("jit__chunk_step_impl(9)",
                                                   150, 200)]
    kern = "%paged_attention_decode_kernel.7 = f32[4,2] custom-call(...)"
    user = "%fusion.3 = f32[4] fusion(f32[4,2] %paged_attention_decode_kernel.7)"
    loop = "%while.4 = (s32[]) while(...)"
    ops = [ev(loop, 5, 40), ev(kern, 10, 5), ev(user, 20, 9),
           ev("%paged_prefill_chunk_kernel.2 = f32[4] custom-call()", 160, 5)]
    dec = tr.matching(mods, harness.DECODE_PROGRAM)
    assert [m.name for m in dec] == ["jit__decode_impl(7)"]
    # the kernel by its own name, not by an op that reads its result
    assert [e.name for e in tr.named(tr.inside(ops, dec[0]),
                                     pa.KERNEL)] == [kern]
    assert [e.name for e in tr.named(ops, pp.KERNEL)] == [ops[-1].name]
    # the loop that holds them is not an operation of its own
    assert [e.name for e in tr.leaves(ops)] == [kern, user, ops[-1].name]
    assert [n for n, _ in tr.top_ops(ops, 0, 400)][0] == user


def test_steps_match_launches_in_order():
    trace = tr.Trace({}, anchor_ns=0, anchor_host=100.0)
    mods = [ev("m", 3_000_000, 10), ev("m", 9_000_000, 10)]
    # launches at 100.001 .. 100.008 s; the device ran the last two
    launches = [100.0005, 100.001, 100.0025, 100.008]
    assert tr.match_steps(mods, launches, trace) == [2, 3]
    with pytest.raises(ValueError):
        tr.match_steps(mods, [100.02, 100.03], trace)


@pytest.fixture(scope="module")
def recorded():
    if not PB.exists():
        pytest.fail(f"{PB} is missing: record it with record_trace.py")
    host = json.loads(HOST.read_text())
    trace = tr.load(str(PB))
    trace.anchor_host = host["anchor_host"]
    cell = tiny_cell("tiny-packed.chat")
    run = harness.Run(cell, 1234, 3.0, [], tuple(host["trace_window"]), 0.0,
                      {}, host["arch"], host["form"],
                      harness.family_of(cell.config), {},
                      host["kv_itemsize"],
                      peaks=json.loads((harness.BENCH / "peaks.json")
                                       .read_text())["devices"]["TPU v5 lite"],
                      tracer=types.SimpleNamespace(
                          launches=host["launches"], spans=host["spans"],
                          waits=[]),
                      trace=trace, trace_window=tuple(host["trace_window"]))
    return run, host


def test_recorded_trace_has_the_device_and_the_anchor(recorded):
    run, _ = recorded
    assert run.trace.anchor_ns is not None
    assert len(run.trace.devices) == 1
    planes = next(iter(run.trace.devices.values()))
    assert planes["modules"] and planes["ops"]


def test_recorded_busy_and_idle(recorded):
    run, host = recorded
    busy, window, breakdown = harness.trace_summary(run)
    assert 0 < busy <= window
    assert busy == pytest.approx(host["result"]["device"]["busy_s"])
    assert window == pytest.approx(host["result"]["device"]["window_s"])
    idle = harness.load_reader(harness.BENCH, "device_idle_share")(run)
    assert idle == pytest.approx(100 * (1 - busy / window))
    assert len(breakdown["device_ops"]) <= 10
    assert len(breakdown["idle_gaps"]) <= 10


@pytest.mark.parametrize("name", ["decode_step_ms", "chunk_step_ms",
                                  "decode_mfu", "chunk_mfu",
                                  "paged_attention_roofline",
                                  "paged_prefill_roofline"])
def test_recorded_per_program_readings(recorded, name):
    """Each reading the chip run gave is read again from the recorded trace,
    and every share of a peak or roofline is a share."""
    run, host = recorded
    value = harness.load_reader(harness.BENCH, name)(run)
    assert value == pytest.approx(host["result"]["metrics"][name]["value"])
    if name.endswith(("_mfu", "_roofline")):
        assert 0 < value <= 100


def test_recorded_kernels_sit_in_their_programs(recorded):
    run, host = recorded
    layers = host["arch"]["num_hidden_layers"]
    for program, kind, kernel in ((harness.DECODE_PROGRAM, "decode", pa.KERNEL),
                                  (harness.CHUNK_PROGRAM, "chunk", pp.KERNEL)):
        steps = run.device_steps(program, kind)
        assert steps
        for _, shape, inner in steps:
            assert shape["kind"] == kind
            assert len(tr.named(inner, kernel)) == layers
