"""The readers of the program's spans and of the flash kernels, on a
hand-made ring and a hand-made trace; and the arithmetic of
``tools/span_idle.py`` on hand-made spans. No chip, no model."""
import os

import pytest

from chipbench import harness, peaks, program_spans
from chipbench import trace as tracelib
from chipbench.families import gpt2
from chipbench.tools import span_idle
from paddle_tpu import obs

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MS = 1_000_000
FN = "Trainer.__init__.<locals>.step"
PROGRAM_SPAN = ("to_static_host_ms.train", "to_static_dispatch_ms.train",
                "setup_compiling_calls_s", "setup_python_trace_s")
FLASH = ("flash_fwd_roofline", "flash_bwd_roofline")


@pytest.fixture(scope="module")
def cell():
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    return harness.Cell(bench, "train-1p3b-2k")


def _span(name, n, dur, parent=None, fn=FN, **args):
    if name == "to_static.call":
        args = dict(args, fn=fn)
    return {"name": name, "trace_id": f"{fn}:{n}", "span_id": f"{name}/{fn}/{n}",
            "parent_id": parent, "ts": float(n), "dur": dur, "ph": "X",
            "args": args}


def _call(n, dur, dispatch, traces=0, trace_s=None, fn=FN):
    """One call of the compiled step as the program records it: the legs
    first, the call last (a span is recorded when it ends)."""
    parent = f"to_static.call/{fn}/{n}"
    out = []
    if trace_s is not None:
        out.append(_span("to_static.trace", n, trace_s, parent, fn))
    out.append(_span("to_static.dispatch", n, dispatch, parent, fn))
    out.append(_span("to_static.call", n, dur, fn=fn, traces=traces, leaves=9))
    return out


@pytest.fixture
def ring():
    """The program's ring holding: another function's call, two compiling
    calls and a steady one of set-up, then a window of three calls."""
    r = obs.ring()
    r.clear()
    events = (_call(1, 9.0, 8.9, traces=1, trace_s=1.0, fn="other")
              + _call(1, 30.0, 29.5, traces=1, trace_s=6.0)
              + _call(2, 20.0, 19.5, traces=1, trace_s=4.0)
              + _call(3, 0.004, 0.001)
              + _call(4, 0.003, 0.001) + _call(5, 0.002, 0.0015)
              + _call(6, 0.009, 0.002))
    for e in events:
        r.record(e)
    yield r
    r.clear()


def _facts(**over):
    facts = {"on_chip": True, "step_s": [0.15, 0.15, 0.15], "seq": 2048,
             "batch": 1, "family": gpt2, "peaks": peaks.peaks_for("TPU v5 lite")}
    return dict(facts, **over)


def test_the_four_span_readers_on_a_hand_made_ring(cell, ring):
    facts = _facts(config=cell.config)
    read = cell.reader
    # the window is the newest three calls of the newest call's function
    assert read("to_static_host_ms.train")(facts) == pytest.approx(3.0)
    assert read("to_static_dispatch_ms.train")(facts) == pytest.approx(1.5)
    # set-up: the calls before them that traced, the other function's left out
    assert read("setup_compiling_calls_s")(facts) == pytest.approx(50.0)
    assert read("setup_python_trace_s")(facts) == pytest.approx(10.0)
    assert (read("to_static_dispatch_ms.train")(facts)
            <= read("to_static_host_ms.train")(facts))
    assert (read("setup_python_trace_s")(facts)
            <= read("setup_compiling_calls_s")(facts))


@pytest.mark.parametrize("name", PROGRAM_SPAN)
def test_a_span_reader_has_nothing_to_read(cell, ring, name):
    facts = _facts(config=cell.config)
    read = cell.reader(name)
    assert read(dict(facts, on_chip=False)) is None      # off the chip
    assert read(dict(facts, step_s=[])) is None          # no window
    assert read(dict(facts, step_s=[0.1] * 7)) is None   # more steps than calls
    for _ in range(ring._ring.maxlen):                   # the ring drops events
        ring.record({"name": "filler", "args": {}, "parent_id": None})
    assert ring.n_dropped > 0
    assert read(facts) is None
    ring.clear()                       # a program that records no such span
    assert read(facts) is None


def test_a_call_that_fell_back_is_not_a_compiled_call(cell, ring):
    # a graph break: the program marks the call whose trace failed, and
    # what then ran inside its span was the fallback, not a compile
    broken = _call(0, 40.0, 1.0, traces=1, trace_s=0.7)
    broken[-1]["args"]["fallback"] = True
    events = broken + ring.dump()
    ring.clear()
    for e in events:
        ring.record(e)
    facts = _facts(config=cell.config)
    assert cell.reader("setup_compiling_calls_s")(facts) == pytest.approx(50.0)
    assert cell.reader("setup_python_trace_s")(facts) == pytest.approx(10.0)


def test_split_and_children_by_hand():
    events = _call(1, 2.0, 1.0, traces=1, trace_s=0.5) + _call(2, 1.0, 0.5)
    setup, window = program_spans.split(events, 1)
    assert [c["trace_id"] for c in setup] == [f"{FN}:1"]
    assert [c["trace_id"] for c in window] == [f"{FN}:2"]
    legs = program_spans.children(events, setup, program_spans.TRACE)
    assert [e["dur"] for e in legs] == [0.5]
    assert program_spans.children(events, window, program_spans.TRACE) == []
    assert program_spans.split(events, 0) is None
    assert program_spans.split([], 1) is None


def _kernel(name, n, shape="bf16[1,16,2048,128]{3,2,1,0}"):
    return (f"%{name}.{n} = {shape} custom-call(bf16[1,16,2048,128]{{3,2,1,0}} "
            f"%copy.{n}), custom_call_target=\"tpu_custom_call\"")


def _trace():
    """Two layers of one step: forward 0.5 ms each, dq 0.4, dk/dv 0.6; and
    a fusion that only USES a kernel's result (its name must not count)."""
    ops, t = [], 0
    for n in (1, 2):
        for name, ms in (("jvp_flash_fwd_", 0.5),
                         ("transpose_jvp_flash_bwd_dq__", 0.4),
                         ("transpose_jvp_flash_bwd_dkv__", 0.6)):
            ops.append((_kernel(name, n), t, int(ms * MS)))
            t += MS
    ops.append(("%fusion.9 = bf16[2048,2048]{1,0} fusion(bf16[1,16,2048,128] "
                "%jvp_flash_fwd_.1, bf16[2048] %transpose_jvp_flash_bwd_dq__.1)",
                t, 3 * MS))
    return {"devices": {0: ops}, "spans": []}


def test_the_flash_readers_on_a_hand_made_trace(cell):
    facts = _facts(config=cell.config, trace=_trace())
    pairs = 2048 * 2049 // 2
    fwd = 2 * 4 * 128 * 16 * pairs              # 2 events x 17.19 GFLOP
    assert cell.reader("flash_fwd_roofline")(facts) == pytest.approx(
        100 * fwd / (197e12 * 0.001))
    bwd = 2 * 8 * 128 * 16 * pairs
    assert cell.reader("flash_bwd_roofline")(facts) == pytest.approx(
        100 * bwd / (197e12 * 0.002))
    two = dict(facts, batch=2)                  # every sequence of the batch
    assert cell.reader("flash_fwd_roofline")(two) == pytest.approx(
        2 * cell.reader("flash_fwd_roofline")(facts))


@pytest.mark.parametrize("name", FLASH)
def test_a_flash_reader_has_nothing_to_read(cell, name):
    facts = _facts(config=cell.config)
    read = cell.reader(name)
    assert read(dict(facts, trace=None)) is None          # off the chip
    unnamed = {"devices": {0: [(_kernel("jvp__", 1), 0, MS)]}, "spans": []}
    assert read(dict(facts, trace=unnamed)) is None       # no kernel events
    if name == "flash_bwd_roofline":                      # one kernel of two
        half = {"devices": {0: [(_kernel("transpose_jvp_flash_bwd_dkv__", 1),
                                 0, MS)]}, "spans": []}
        assert read(dict(facts, trace=half)) is None


def test_the_new_metrics_are_the_training_cells_alone(cell):
    mine = {m["name"]: m for m in cell.per_layer()}
    for name in PROGRAM_SPAN + FLASH:
        assert mine[name]["workloads"] == ["train-1p3b-2k"]
        assert callable(cell.reader(name))
    assert {mine[n]["source"] for n in PROGRAM_SPAN} == {"program_span"}
    assert {mine[n]["source"] for n in FLASH} == {"device_trace"}
    assert {mine[n]["moves"] for n in PROGRAM_SPAN[2:]} == {"setup_s"}


# -- tools/span_idle.py -----------------------------------------------------


def _host(name, start, end, **ids):
    return {"name": name, "start": start * MS, "end": end * MS, **ids}


def _spans():
    return [
        _host("make_batch", 0, 2),
        _host("train.step", 2, 20),
        _host("pt:to_static.call", 4, 12, trace_id="step:7", span_id="c"),
        _host("pt:to_static.read_state", 5, 7, trace_id="step:7",
              span_id="r", parent_id="c"),
        _host("pt:to_static.dispatch", 7, 11, trace_id="step:7",
              span_id="d", parent_id="c"),
    ]


def test_idle_seconds_by_innermost_program_span():
    # the device works [1,3] [6,8] [10,13] [18,22] of a window [0,22]
    trace = {"devices": {0: [("%f.1 = x", 1 * MS, 2 * MS), ("%f.2 = x", 6 * MS,
                              2 * MS), ("%f.3 = x", 10 * MS, 3 * MS),
                             ("%f.4 = x", 18 * MS, 4 * MS)]},
             "spans": [("make_batch", 0, 2 * MS), ("train.step", 2 * MS, 18 * MS)]}
    rows = dict(map(tuple, span_idle.idle_by_program_span(trace, _spans())))
    # idle: [0,1] [3,6] [8,10] [13,18]
    assert rows == pytest.approx({
        "pt:to_static.revalidate": 0.0, "pt:to_static.write_state": 0.0,
        "pt:to_static.trace": 0.0,
        "pt:to_static.read_state": 0.001,    # [5,6]
        "pt:to_static.dispatch": 0.002,      # [8,10]
        "pt:to_static.call": 0.001,          # [4,5]: the call's own time
        span_idle.BEFORE: 0.001,             # [3,4]: before the call
        span_idle.AFTER: 0.005,              # [13,18]: the device runs the step
        "train.step": 0.0,
        "make_batch": 0.001,                 # [0,1]
        "(none)": 0.0}, abs=1e-12)
    total = sum(rows.values())
    gaps = tracelib.idle_gaps(trace, tracelib.window_of(trace), 0)
    assert total == pytest.approx(tracelib.total(gaps) / 1e9)
    # the benchmark's own table books all of it to its two spans
    by_span = dict(map(tuple, tracelib.idle_by_span(
        trace, tracelib.window_of(trace), 0)))
    assert by_span["train.step"] == pytest.approx(
        total - rows["make_batch"])
    # a step with no program call in it (the parent commit) stays whole
    bare = [s for s in _spans() if not s["name"].startswith("pt:")]
    rows = dict(map(tuple, span_idle.idle_by_program_span(trace, bare)))
    assert rows["train.step"] == pytest.approx(0.010)
    assert rows[span_idle.BEFORE] == rows[span_idle.AFTER] == 0.0


def test_nesting_check_finds_a_call_outside_a_step_and_an_overhang():
    good = span_idle.nesting(_spans())
    assert good == {"train_steps": 1, "calls": 1,
                    "calls_outside_a_train_step": 0, "children": 2,
                    "children_without_parent": 0, "worst_overhang_ns": 0,
                    "children_sharing_their_call_id": 2}
    bad = _spans() + [
        _host("pt:to_static.call", 21, 23, trace_id="step:8", span_id="c2"),
        _host("pt:to_static.dispatch", 22, 24, trace_id="step:8",
              span_id="d2", parent_id="c2"),
        _host("pt:to_static.trace", 22, 23, trace_id="x", span_id="t",
              parent_id="gone")]
    got = span_idle.nesting(bad)
    assert got["calls_outside_a_train_step"] == 1
    assert got["worst_overhang_ns"] == 1 * MS
    assert got["children_without_parent"] == 1
