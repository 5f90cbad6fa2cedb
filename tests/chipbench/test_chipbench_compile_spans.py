"""The six readers of what set-up's compiling calls did (issue 36), on a
hand-made ring: a warm set-up, a cold one, a ring that dropped events, off
the chip, a program that records no such span; and their entries in
``BENCHMARK.json``. No chip, no model."""
import os

import pytest

from chipbench import compile_spans, harness
from paddle_tpu import obs

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FN = "Trainer.__init__.<locals>.step"
LAYER = "compiled step (jit/__init__.py)"
NEW = {
    "setup_step_traces": ("count", "program_span", "setup_s"),
    "setup_step_lower_s": ("s", "program_span", "setup_s"),
    "setup_step_compile_s": ("s", "program_span", "setup_s"),
    "setup_step_cache_misses": ("count", "program_counter", "setup_s"),
    "step_cold_compile_s": ("s", "program_span", "setup_s"),
    "step_compiled_gib": ("GiB", "program_counter", "train_tokens_per_s"),
}
GIB = 2 ** 30


@pytest.fixture(scope="module")
def bench():
    return harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


@pytest.fixture(scope="module")
def cell(bench):
    return harness.Cell(bench, "train-1p3b-2k")


def _span(name, n, dur, parent=None, of=FN, **args):
    return {"name": name, "trace_id": f"{of}:{n}",
            "span_id": f"{name}/{of}/{n}/{len(args)}/{dur}",
            "parent_id": parent, "ts": float(n), "dur": dur, "ph": "X",
            "args": args}


def _hit(dur, stored, fun="jit(pure)"):
    """A compile the cache served: ``stored`` is what jax kept as the
    compile's cost (whole seconds), the read takes nearly all of ``dur``."""
    read = 0.9 * dur
    return dict(dur=dur, fun=fun, cache="hit", retrieval_s=read,
                saved_s=stored - read)


def _miss(dur, fun="jit(pure)", cache="miss"):
    return dict(dur=dur, fun=fun, cache=cache)


def _call(n, dur, trace_s=None, lower_s=(), compiles=(), memory=None, fn=FN):
    """One call as the program records it: the legs first (a span is
    recorded when it ends), the call last."""
    parent = f"to_static.call/{fn}/{n}"
    out = []
    if trace_s is not None:
        out.append(_span("to_static.trace", n, trace_s, parent, fn, fn=fn))
    for k, s in enumerate(lower_s):
        out.append(_span("to_static.lower", n, s, parent, fn,
                         fun=f"jit(f{k})"))
    for c in compiles:
        c = dict(c)
        out.append(_span("to_static.compile", n, c.pop("dur"), parent, fn,
                         **c))
    out.append(_span("to_static.dispatch", n, dur * 0.9, parent, fn))
    args = {"fn": fn, "leaves": 9, "traces": int(trace_s is not None)}
    if memory is not None:
        args["memory"] = memory
    call = _span("to_static.call", n, dur, None, fn, **args)
    call["span_id"] = parent
    return out + [call]


MEMORY_1 = dict(argument=4 * GIB, output=4 * GIB, alias=0, temp=2 * GIB,
                code=GIB // 2)
MEMORY_2 = dict(argument=9 * GIB, output=9 * GIB, alias=9 * GIB - GIB // 4,
                temp=5 * GIB, code=GIB // 2)      # 14.75 GiB
WINDOW = [c for n in (4, 5, 6) for c in _call(n, 0.004)]


def _other(compiles):
    """Another compiled function's call (a model's forward at build
    time): its lowering and compile are not the trainer's."""
    return _call(1, 50.0, trace_s=5.0, lower_s=(7.0,), compiles=compiles,
                 memory=MEMORY_1, fn="other")


def _warm():
    small = _hit(0.01, 0, fun="jit(convert_element_type)")
    return (_other([_hit(1.0, 40)])
            + _call(1, 12.0, trace_s=6.0, lower_s=(2.0, 0.5),
                    compiles=[small, _hit(3.0, 200)], memory=MEMORY_1)
            + _call(2, 10.0, trace_s=4.0, lower_s=(1.5,),
                    compiles=[_hit(2.0, 250)], memory=MEMORY_2)
            + _call(3, 0.004) + WINDOW)


def _cold():
    small = _miss(0.3, fun="jit(convert_element_type)")
    return (_other([_miss(41.0)])
            + _call(1, 212.0, trace_s=6.0, lower_s=(2.0, 0.5),
                    compiles=[small, _miss(200.4)], memory=MEMORY_1)
            + _call(2, 262.0, trace_s=4.0, lower_s=(1.5,),
                    compiles=[_miss(250.7)], memory=MEMORY_2)
            + _call(3, 0.004) + WINDOW)


@pytest.fixture
def ring():
    r = obs.ring()
    r.clear()
    yield r
    r.clear()


def _fill(ring, events):
    ring.clear()
    for e in events:
        ring.record(e)


def _facts(**over):
    return dict({"on_chip": True, "step_s": [0.15, 0.15, 0.15]}, **over)


def _read_all(cell):
    return {name: cell.reader(name)(_facts()) for name in NEW}


def test_the_six_readers_on_a_warm_set_up(cell, ring):
    _fill(ring, _warm())
    got = _read_all(cell)
    assert got["setup_step_traces"] == 2
    assert got["setup_step_lower_s"] == pytest.approx(4.0)
    assert got["setup_step_compile_s"] == pytest.approx(5.01)
    assert got["setup_step_cache_misses"] == 0         # the run was warm
    # what the stored compiles had cost, not what this run paid
    assert got["step_cold_compile_s"] == pytest.approx(450.0)
    assert got["step_compiled_gib"] == pytest.approx(14.75)
    # the anatomy adds up inside the compiling calls (12 + 10 s)
    assert (got["setup_step_lower_s"] + got["setup_step_compile_s"]
            + 10.0) <= cell.reader("setup_compiling_calls_s")(_facts())


def test_the_six_readers_on_a_cold_set_up(cell, ring):
    _fill(ring, _cold())
    got = _read_all(cell)
    assert got["setup_step_traces"] == 2
    assert got["setup_step_lower_s"] == pytest.approx(4.0)
    assert got["setup_step_compile_s"] == pytest.approx(451.4)
    assert got["setup_step_cache_misses"] == 3
    # at a fresh cache a compile costs what it took
    assert got["step_cold_compile_s"] == pytest.approx(
        got["setup_step_compile_s"])
    assert got["step_compiled_gib"] == pytest.approx(14.75)


def test_a_run_half_warm_counts_each_compile_its_own_way(cell, ring):
    events = (_call(1, 12.0, trace_s=6.0, lower_s=(2.0,),
                    compiles=[_hit(3.0, 200)], memory=MEMORY_1)
              + _call(2, 262.0, trace_s=4.0, lower_s=(1.5,),
                      compiles=[_miss(250.7), _miss(0.2, cache="off")],
                      memory=MEMORY_2)
              + WINDOW)
    _fill(ring, events)
    got = _read_all(cell)
    assert got["setup_step_cache_misses"] == 2          # a miss and an "off"
    assert got["setup_step_compile_s"] == pytest.approx(253.9)
    assert got["step_cold_compile_s"] == pytest.approx(200 + 250.7 + 0.2)


def test_the_newest_compiling_call_gives_the_memory(cell, ring):
    # a recompilation inside the window is the newest executable
    late = _call(7, 30.0, trace_s=1.0, lower_s=(1.0,),
                 compiles=[_miss(20.0)],
                 memory=dict(MEMORY_2, temp=6 * GIB))
    _fill(ring, _warm() + late)
    facts = _facts(step_s=[0.15] * 4)
    assert cell.reader("step_compiled_gib")(facts) == pytest.approx(15.75)
    # and set-up's numbers do not see the window's compile
    assert cell.reader("setup_step_traces")(facts) == 2
    assert cell.reader("setup_step_compile_s")(facts) == pytest.approx(5.01)


def test_cold_seconds_by_hand():
    hit = _span("to_static.compile", 1, 2.0, "p", **{
        k: v for k, v in _hit(2.0, 250).items() if k != "dur"})
    assert compile_spans.cold_seconds(hit) == pytest.approx(250.0)
    miss = _span("to_static.compile", 1, 250.7, "p", fun="f", cache="miss")
    assert compile_spans.cold_seconds(miss) == 250.7
    off = _span("to_static.compile", 1, 0.2, "p", fun="f", cache="off")
    assert compile_spans.cold_seconds(off) == 0.2


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_reader_has_nothing_to_read(cell, ring, name):
    _fill(ring, _warm())
    read = cell.reader(name)
    assert read(_facts()) is not None
    assert read(_facts(on_chip=False)) is None          # off the chip
    assert read(_facts(step_s=[])) is None              # no window
    assert read(_facts(step_s=[0.1] * 9)) is None       # more steps than calls
    for _ in range(ring._ring.maxlen):                  # the ring drops events
        ring.record({"name": "filler", "args": {}, "parent_id": None})
    assert ring.n_dropped > 0
    assert read(_facts()) is None
    ring.clear()                        # a program that records no span at all
    assert read(_facts()) is None


@pytest.mark.parametrize("name", sorted(set(NEW) - {"setup_step_traces"}))
def test_a_program_without_the_new_spans_reads_nothing(cell, ring, name):
    """The parent commit: calls, legs and traces, but no lowering or
    compile span and no ``memory``. The reader returns nothing and does
    not raise (only ``setup_step_traces`` reads a span it has)."""
    old = [e for e in _warm()
           if e["name"] not in ("to_static.lower", "to_static.compile")]
    for e in old:
        e["args"].pop("memory", None)
    _fill(ring, old)
    assert cell.reader(name)(_facts()) is None
    assert cell.reader("setup_step_traces")(_facts()) == 2


def test_a_call_that_fell_back_is_left_out(cell, ring):
    broken = _call(0, 40.0, trace_s=0.7, lower_s=(9.0,),
                   compiles=[_miss(30.0)])
    broken[-1]["args"]["fallback"] = True
    _fill(ring, broken + _warm())
    got = _read_all(cell)
    assert got["setup_step_traces"] == 2
    assert got["setup_step_lower_s"] == pytest.approx(4.0)
    assert got["setup_step_cache_misses"] == 0


def test_the_entries_in_benchmark_json(bench):
    by_name = {m["name"]: m for m in bench["per_layer"]}
    # appended, in the issue's order, after everything that was there
    assert [m["name"] for m in bench["per_layer"]][-len(NEW):] == list(NEW)
    for name, (unit, source, moves) in NEW.items():
        assert by_name[name] == {
            "name": name, "unit": unit, "better": "lower", "source": source,
            "layer": LAYER, "moves": moves}          # and no "workloads" key
        path = os.path.join(ROOT, "chipbench", "layer_metrics", name + ".py")
        assert os.path.isfile(path)


def test_every_cell_reports_all_six(bench):
    assert len(bench["workloads"]) >= 4
    for w in bench["workloads"]:
        cell = harness.Cell(bench, w["name"])
        reported = {m["name"] for m in cell.per_layer()}
        assert set(NEW) <= reported
        for name in NEW:
            assert callable(cell.reader(name))
    # the four older set-up and host metrics stay pinned to the dense cell
    for name in ("setup_compiling_calls_s", "setup_python_trace_s",
                 "to_static_host_ms.train", "to_static_dispatch_ms.train"):
        assert [m["workloads"] for m in bench["per_layer"]
                if m["name"] == name] == [["train-1p3b-2k"]]


def test_the_readers_read_what_the_program_records(ring):
    """End to end off the chip: a real compiled train step's ring, with
    ``on_chip`` claimed by hand, gives every reader a number of the right
    kind (no time of this run is a device number: only kinds and counts
    are asserted)."""
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn

    layer = nn.Linear(4, 4)
    opt = paddle.optimizer.AdamW(learning_rate=0.1,
                                 parameters=layer.parameters())

    def step(x):
        loss = layer(x).sum()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    fn = paddle.jit.to_static(step, layers=[layer], optimizers=[opt])
    x = paddle.to_tensor(np.ones((2, 4), np.float32))
    for _ in range(5):
        fn(x)
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = harness.Cell(bench, "train-1p3b-2k")
    facts = _facts(step_s=[0.1, 0.1])
    got = {name: cell.reader(name)(facts) for name in NEW}
    assert got["setup_step_traces"] == 2
    assert got["setup_step_lower_s"] > 0 and got["setup_step_compile_s"] > 0
    assert got["setup_step_cache_misses"] >= 2      # no cache directory here
    assert got["step_cold_compile_s"] == pytest.approx(
        got["setup_step_compile_s"])
    assert 0 < got["step_compiled_gib"] < 1e-3
    assert (got["setup_step_lower_s"] + got["setup_step_compile_s"]
            + cell.reader("setup_python_trace_s")(facts)
            <= cell.reader("setup_compiling_calls_s")(facts))
