"""What a COMPILING call of ``jit.to_static`` tells ``paddle_tpu.obs``
beyond its legs (``tests/test_to_static_spans.py``): jax's lowering and
compile as ``to_static.lower`` / ``to_static.compile`` children with the
persistent cache's verdict, the executable's memory on the call span, and
the same two events as ``xla.lower`` / ``xla.compile`` outside any call
(``paddle_tpu/obs/compile.py``: the repository's one ``jax.monitoring``
listener)."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import monitoring

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import obs

MEMORY_KEYS = {"argument", "output", "alias", "temp", "code"}
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


@pytest.fixture
def ring():
    prev = obs.set_enabled(True)
    obs.ring().clear()
    yield obs.ring()
    obs.set_enabled(prev)
    obs.ring().clear()


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


def _calls(ring, fn):
    events = ring.dump()
    calls = [e for e in events if e["name"] == "to_static.call"
             and e["args"]["fn"] == fn._qualname]
    kids = [[e for e in events if e["parent_id"] == c["span_id"]]
            for c in calls]
    return calls, kids


def _named(events, name):
    return [e for e in events if e["name"] == name]


def test_a_compiling_call_has_lower_and_compile_children(ring):
    fn = _train_step()
    for _ in range(3):
        fn(_x())
    calls, kids = _calls(ring, fn)
    assert [c["args"]["traces"] for c in calls] == [1, 1, 0]
    for call, mine in zip(calls[:2], kids[:2]):
        lowers = _named(mine, "to_static.lower")
        compiles = _named(mine, "to_static.compile")
        # the step itself, by jax's name for the jitted pure function
        assert "jit(pure)" in [e["args"]["fun"] for e in lowers]
        assert "jit(pure)" in [e["args"]["fun"] for e in compiles]
        for e in lowers + compiles:
            assert e["trace_id"] == call["trace_id"]
            assert e["tid"] == "to_static" and e["dur"] >= 0
            # jax's clock (time.time) against the ring's anchored one
            assert e["ts"] >= call["ts"] - 1e-3
            assert e["ts"] + e["dur"] <= call["ts"] + call["dur"] + 1e-3
        assert {e["args"]["cache"] for e in compiles} <= {"hit", "miss", "off"}
        assert all("cache" not in e["args"] for e in lowers)
        # lowering comes before the compile of the same function
        low = [e for e in lowers if e["args"]["fun"] == "jit(pure)"][-1]
        comp = [e for e in compiles if e["args"]["fun"] == "jit(pure)"][-1]
        assert low["ts"] + low["dur"] <= comp["ts"] + 1e-6
    # a cached call has none, and no memory either
    names = {e["name"] for e in kids[2]}
    assert not names & {"to_static.lower", "to_static.compile",
                        "to_static.trace"}
    assert "memory" not in calls[2]["args"]


def test_the_call_that_compiled_carries_the_executables_memory(ring):
    fn = _train_step()
    for _ in range(3):
        fn(_x())
    calls, kids = _calls(ring, fn)
    for call, mine in zip(calls[:2], kids[:2]):
        memory = call["args"]["memory"]
        assert set(memory) == MEMORY_KEYS
        assert all(type(v) is int and v >= 0 for v in memory.values())
        assert memory["temp"] > 0 and memory["argument"] > 0
    # the second trace threads the optimizer's moments and donates them
    assert calls[1]["args"]["memory"]["argument"] > \
        calls[0]["args"]["memory"]["argument"]
    assert calls[1]["args"]["memory"]["alias"] > 0


def test_reading_the_memory_traces_and_compiles_nothing(ring):
    fn = _train_step()
    fn(_x())
    calls, kids = _calls(ring, fn)
    assert "memory" in calls[0]["args"]
    # the read's own lower().compile() would be a second span of the step
    for name in ("to_static.trace", "to_static.lower", "to_static.compile"):
        assert [e["args"].get("fun", "jit(pure)")
                for e in _named(kids[0], name)].count("jit(pure)") == 1
    assert fn._pure_runs == 1
    # nor does the read of a plain jit's executable record anything
    jitted = jax.jit(lambda x: jnp.cos(x) @ x)
    x = jnp.ones((4, 4))
    jitted(x)
    ring.clear()
    memory = obs.compile.executable_memory(jitted, x)
    assert set(memory) == MEMORY_KEYS
    assert ring.dump() == []


def test_a_jit_outside_any_compiled_call_is_an_xla_span(ring):
    @jax.jit
    def outside(x):
        return jnp.sin(x) @ x

    t0 = time.time()
    outside(jnp.ones((8, 8))).block_until_ready()
    t1 = time.time()
    events = ring.dump()
    for name in ("xla.lower", "xla.compile"):
        mine = [e for e in _named(events, name)
                if e["args"]["fun"] == "jit(outside)"]
        assert len(mine) == 1
        assert mine[0]["parent_id"] is None and mine[0]["tid"] == "compile"
        assert t0 - 1e-3 <= mine[0]["ts"] <= t1 + 1e-3
        assert mine[0]["ph"] == "X" and 0 <= mine[0]["dur"] <= t1 - t0 + 1e-3
    assert _named(events, "xla.compile")[-1]["args"]["cache"] in (
        "hit", "miss", "off")
    assert not [e for e in events if e["name"].startswith("to_static.")]
    outside(jnp.ones((8, 8)))           # cached: nothing more
    assert len(ring.dump()) == len(events)


def test_the_cache_says_hit_with_what_the_compile_had_cost(ring, tmp_path):
    from jax.experimental.compilation_cache import compilation_cache

    def make():
        def body(x):
            return jnp.cos(x) @ x + 3

        return jax.jit(body)

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = [getattr(jax.config, k) for k in keys]
    try:
        for k, v in zip(keys, (str(tmp_path), 0.0, -1)):
            jax.config.update(k, v)
        compilation_cache.reset_cache()
        x = jnp.ones((8, 8))
        make()(x)                       # writes the entry
        make()(x)                       # the same program again: reads it
    finally:
        for k, v in zip(keys, was):
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    first, second = [e for e in _named(ring.dump(), "xla.compile")
                     if e["args"]["fun"] == "jit(body)"]
    assert first["args"]["cache"] == "miss"
    assert "saved_s" not in first["args"]
    assert second["args"]["cache"] == "hit"
    assert second["args"]["retrieval_s"] > 0
    # what the stored compile had cost: jax's saved time plus the read;
    # jax stores it in whole seconds, so this small program reads 0
    cold = second["args"]["saved_s"] + second["args"]["retrieval_s"]
    assert cold == pytest.approx(int(first["dur"]), abs=1e-9)
    # nothing of one compile's verdict is left over for the next
    jax.jit(lambda x: x - 7)(jnp.ones((8, 8)))
    last = _named(ring.dump(), "xla.compile")[-1]
    assert "saved_s" not in last["args"] and "retrieval_s" not in last["args"]


@pytest.mark.parametrize("key, value", [
    ("jax_enable_compilation_cache", False),
    # jax 0.9.0 reports "the request used the cache" even then
    ("jax_compilation_cache_dir", None),
])
def test_a_compile_with_the_cache_disabled_or_nowhere_says_off(ring, key,
                                                               value):
    from jax.experimental.compilation_cache import compilation_cache

    was = getattr(jax.config, key)
    try:
        jax.config.update(key, value)
        compilation_cache.reset_cache()
        jax.jit(lambda x: x * 5 + 1)(jnp.ones((3,)))
    finally:
        jax.config.update(key, was)
        compilation_cache.reset_cache()
    assert _named(ring.dump(), "xla.compile")[-1]["args"]["cache"] == "off"


def test_a_fallback_call_carries_no_memory(ring):
    layer = nn.Linear(4, 4)

    def broken(x):
        y = layer(x).sum()
        if float(y) > 1e9:          # needs the value: a graph break
            return y * 2
        return y

    fn = paddle.jit.to_static(broken, layers=[layer], full_graph=False)
    with pytest.warns(UserWarning):
        fn(_x())
    calls, _ = _calls(ring, fn)
    assert [c["args"].get("fallback") for c in calls] == [True]
    assert calls[0]["args"]["traces"] >= 1
    assert "memory" not in calls[0]["args"]
    # and the thread's open calls are closed again: a jit after it is xla.*
    jax.jit(lambda x: x + 11)(jnp.ones((3,)))
    assert ring.dump()[-1]["name"] == "xla.compile"
    assert ring.dump()[-1]["parent_id"] is None


def test_the_listener_keeps_nothing_of_jaxpr_trace_duration(ring):
    t = time.time()
    monitoring.record_event_duration_secs(TRACE_EVENT, 0.5, fun_name="f")
    monitoring.record_event_time_span(TRACE_EVENT, t, t + 0.5, fun_name="f")
    assert ring.dump() == []
    # a nested jit traced inside a jitted function is such an event, and
    # only the outer function is lowered and compiled

    @jax.jit
    def inner(x):
        return x * 2

    @jax.jit
    def outer(x):
        return inner(x) + inner(x + 1)

    outer(jnp.ones((4,)))
    assert {e["args"]["fun"] for e in ring.dump()
            if e["name"] in ("xla.lower", "xla.compile")} >= {"jit(outer)"}
    assert {e["name"] for e in ring.dump()} <= {"xla.lower", "xla.compile"}


def test_one_listener_of_each_kind_is_registered():
    from jax._src import monitoring as registry

    from paddle_tpu.obs import compile as compile_events

    assert registry.get_event_time_span_listeners().count(
        compile_events._on_time_span) == 1
    assert registry.get_event_listeners().count(
        compile_events._on_event) == 1
    assert registry.get_event_duration_listeners().count(
        compile_events._on_duration) == 1


def test_nothing_with_recording_off(ring):
    obs.set_enabled(False)
    jax.jit(lambda x: x + 13)(jnp.ones((3,)))
    fn = _train_step()
    fn(_x())
    assert ring.dump() == [] and ring.n_recorded == 0


def test_multi_step_opens_a_call_span_like_any_other(ring):
    fn = _train_step()
    fn(_x())
    fn(_x())
    ring.clear()
    fn.multi_step(_x(), steps=3)
    fn.multi_step(_x(), steps=3)
    calls, kids = _calls(ring, fn)
    assert [c["args"]["traces"] >= 1 for c in calls] == [True, False]
    assert [c["trace_id"] for c in calls] == [f"{fn._qualname}:3",
                                              f"{fn._qualname}:4"]
    for mine in kids:
        assert [e["name"] for e in mine
                if e["name"] in ("to_static.dispatch",
                                 "to_static.write_state")] == [
            "to_static.dispatch", "to_static.write_state"]
    first = {e["name"] for e in kids[0]}
    assert {"to_static.trace", "to_static.lower", "to_static.compile"} <= first
    assert "jit(scanned)" in [e["args"]["fun"] for e in
                              _named(kids[0], "to_static.compile")]
    assert set(calls[0]["args"]["memory"]) == MEMORY_KEYS
    assert "memory" not in calls[1]["args"]
    assert not {e["name"] for e in kids[1]} & {
        "to_static.trace", "to_static.lower", "to_static.compile"}


def test_record_span_takes_a_start_and_a_duration(ring):
    with obs.span("parent") as parent:
        pass
    got = obs.record_span("made.before", 1700000000.25, 1.5, parent=parent,
                          tid="t", fun="f")
    event = ring.dump()[-1]
    assert event["name"] == "made.before" and event["ph"] == "X"
    assert event["ts"] == pytest.approx(1700000000.25, abs=1e-6)
    assert event["dur"] == 1.5 and event["tid"] == "t"
    assert event["parent_id"] == parent.span_id
    assert event["trace_id"] == parent.trace_id
    assert event["args"] == {"fun": "f"} and got.span_id == event["span_id"]
    doc = obs.export_chrome_trace([event])
    assert [e["dur"] for e in doc if e.get("ph") == "X"] == [1.5e6]
