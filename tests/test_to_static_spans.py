"""What the compiled path of ``jit.to_static`` tells ``paddle_tpu.obs``:
one ``to_static.call`` span a call with its four legs as children, a
``to_static.trace`` span only while jax traces — and nothing on the eager
fallback or with recording off. What a COMPILING call adds to them is
``tests/test_jit_compile_spans.py``'s."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import obs

LEGS = ("to_static.revalidate", "to_static.read_state", "to_static.dispatch",
        "to_static.write_state")


@pytest.fixture
def ring():
    prev = obs.set_enabled(True)
    obs.ring().clear()
    yield obs.ring()
    obs.set_enabled(prev)
    obs.ring().clear()


def _forward():
    layer = nn.Linear(4, 4)

    def forward(x):
        return layer(x).sum()

    return paddle.jit.to_static(forward, layers=[layer])


def _train_step():
    layer = nn.Linear(4, 4)
    opt = paddle.optimizer.AdamW(learning_rate=0.1,
                                 parameters=layer.parameters())

    def train_step(x):
        loss = layer(x).sum()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    return paddle.jit.to_static(train_step, layers=[layer], optimizers=[opt])


def _x():
    return paddle.to_tensor(np.ones((2, 4), np.float32))


def _calls(ring):
    events = ring.dump()
    calls = [e for e in events if e["name"] == "to_static.call"]
    kids = {c["span_id"]: [e for e in events if e["parent_id"] == c["span_id"]]
            for c in calls}
    return events, calls, kids


def test_a_compiled_call_records_the_call_and_its_four_legs(ring):
    fn = _forward()
    fn(_x())
    fn(_x())
    events, calls, kids = _calls(ring)
    assert len(calls) == 2
    for n, call in enumerate(calls, start=1):
        assert call["parent_id"] is None
        assert call["trace_id"] == f"{fn._qualname}:{n}"
        mine = kids[call["span_id"]]
        assert [e["name"] for e in mine if e["name"] in LEGS] == list(LEGS)
        # one shared identifier, and every leg inside its parent's lifetime
        assert {e["trace_id"] for e in mine} == {call["trace_id"]}
        for e in mine:
            assert e["ts"] >= call["ts"] - 1e-6
            assert e["ts"] + e["dur"] <= call["ts"] + call["dur"] + 1e-6
    # beside them: what jax did inside the compiling call (lowering and
    # compile: tests/test_jit_compile_spans.py) and the compiles of
    # eager ops outside any call
    assert {e["name"] for e in events} <= set(LEGS) | {
        "to_static.call", "to_static.trace", "to_static.lower",
        "to_static.compile", "xla.lower", "xla.compile"}


def test_traces_is_one_on_the_tracing_call_then_zero(ring):
    fn = _forward()
    for _ in range(3):
        fn(_x())
    _, calls, kids = _calls(ring)
    assert [c["args"]["traces"] for c in calls] == [1, 0, 0]
    assert all(c["args"]["fn"] == fn._qualname for c in calls)
    traced = [[e["name"] for e in kids[c["span_id"]]].count("to_static.trace")
              for c in calls]
    assert traced == [1, 0, 0]          # only while jax traces


def test_a_train_step_traces_twice_and_counts_its_state(ring):
    fn = _train_step()
    for _ in range(3):
        fn(_x())
    _, calls, _ = _calls(ring)
    # the second call retraces: the optimizer's accumulators now exist
    assert [c["args"]["traces"] for c in calls] == [1, 1, 0]
    leaves = [c["args"]["leaves"] for c in calls]
    assert leaves[0] < leaves[1] == leaves[2]   # weight, bias + their moments


def test_a_call_of_the_same_function_inside_it_keeps_both_calls_whole(ring):
    layer = nn.Linear(4, 4)

    def forward(x):
        # another argument tree, so another jitted function of the same fn
        return layer(x[0]).sum() if isinstance(x, tuple) else fn((x,)) + 1

    fn = paddle.jit.to_static(forward, layers=[layer])
    fn(_x())
    _, calls, kids = _calls(ring)
    assert len(calls) == 2              # the inner call closes first
    for call in calls:
        assert call["args"]["traces"] >= 1 and "leaves" in call["args"]
        names = [e["name"] for e in kids[call["span_id"]]]
        assert [n for n in names if n in LEGS] == list(LEGS)
        assert "to_static.trace" in names


@pytest.mark.parametrize("how", ["jit_disabled", "fallback_eager"])
def test_the_eager_fallback_records_nothing(ring, how):
    fn = _forward()
    if how == "jit_disabled":
        paddle.jit.enable_to_static(False)
    else:
        fn._fallback_eager = True
    try:
        fn(_x())
    finally:
        paddle.jit.enable_to_static(True)
    # an eager op's own compile is an xla.* span, and nothing else is there
    assert {e["name"] for e in ring.dump()} <= {"xla.lower", "xla.compile"}


def test_a_graph_break_falls_back_and_later_calls_record_nothing(ring):
    layer = nn.Linear(4, 4)

    def broken(x):
        y = layer(x).sum()
        if float(y) > 1e9:          # needs the value: a graph break
            return y * 2
        return y

    fn = paddle.jit.to_static(broken, layers=[layer], full_graph=False)
    with pytest.warns(UserWarning):
        fn(_x())
    assert fn._fallback_eager or fn._piecewise is not None
    # the call that met the break says so; it is not a compiled call
    first = [e for e in ring.dump() if e["name"] == "to_static.call"
             and e["args"]["fn"] == fn._qualname]
    assert [c["args"].get("fallback") for c in first] == [True]
    before = len([e for e in ring.dump() if e["name"] == "to_static.call"
                  and e["args"]["fn"] == fn._qualname])
    fn(_x())
    after = len([e for e in ring.dump() if e["name"] == "to_static.call"
                 and e["args"]["fn"] == fn._qualname])
    assert after == before      # the fallback paths open no call span


def test_nothing_at_all_with_recording_off(ring):
    fn = _train_step()
    obs.set_enabled(False)
    for _ in range(3):
        fn(_x())
    assert ring.dump() == [] and ring.n_recorded == 0
    obs.set_enabled(True)
    fn(_x())
    assert [e["name"] for e in ring.dump()][-1] == "to_static.call"


def test_no_frame_of_its_own_lies_under_the_traced_function(ring):
    """While jax traces, ``__call__`` and ``pure`` are the only frames of
    ``jit/__init__.py`` under the user's function: the spans are with-blocks
    in them, not wrappers (a frame more under every traced op shifted the
    interpreter's frame stack and slowed set-up's tracing, PERF.md PR 24)."""
    import sys

    layer = nn.Linear(4, 4)
    seen = []

    def forward(x):
        frame, names = sys._getframe(1), []
        while frame is not None:
            if frame.f_code.co_filename.endswith("paddle_tpu/jit/__init__.py"):
                names.append(frame.f_code.co_name)
            frame = frame.f_back
        seen.append(names)
        return layer(x).sum()

    paddle.jit.to_static(forward, layers=[layer])(_x())
    assert seen == [["pure", "__call__"]]
