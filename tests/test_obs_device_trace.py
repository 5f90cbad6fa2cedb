"""``obs.span`` has two sinks: the ring and, while a profiler session
runs, a ``pt:``-prefixed annotation on the device trace's clock. One
primitive: nothing else in ``paddle_tpu`` opens a ``TraceAnnotation``."""
import os
import re

import jax
import pytest
from jax.profiler import ProfileData

from chipbench import trace as tracelib
from paddle_tpu import obs, profiler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def xplane(tmp_path_factory):
    """One profiler session on the CPU holding a nested pair of spans (the
    outer one under a name the benchmark's readers filter on), a
    RecordEvent, an async start/finish pair, and a span with recording
    off; returns (path of the xplane, its host events, the ring's dump)."""
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    prev = obs.set_enabled(True)
    obs.ring().clear()
    jax.profiler.start_trace(trace_dir)
    try:
        with obs.span("harvest", lanes=3) as outer:
            with obs.span("to_static.call", parent=outer):
                pass
        with profiler.RecordEvent("user_step"):
            pass
        obs.finish_span(obs.start_span("submit"))
        obs.set_enabled(False)
        with obs.span("engine.step"):
            pass
        obs.set_enabled(True)
    finally:
        jax.profiler.stop_trace()
    ring = obs.ring().dump()
    obs.set_enabled(prev)
    obs.ring().clear()
    path = tracelib.find_xplane(trace_dir)
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    events.setdefault(e.name, []).append(
                        (int(e.start_ns), int(e.duration_ns), dict(e.stats)))
    return path, events, ring


def test_a_span_is_a_prefixed_event_with_its_ids(xplane):
    _, events, ring = xplane
    (s0, d0, outer), = events["pt:harvest"]
    (s1, d1, inner), = events["pt:to_static.call"]
    by_name = {e["name"]: e for e in ring}
    for name, stats in (("harvest", outer), ("to_static.call", inner)):
        assert stats["trace_id"] == by_name[name]["trace_id"]
        assert stats["span_id"] == by_name[name]["span_id"]
    assert inner["parent_id"] == outer["span_id"]
    assert inner["trace_id"] == outer["trace_id"]
    assert "parent_id" not in outer or outer["parent_id"] == ""
    # on the profiler's clock: relative to the session, and nested
    assert 0 <= s0 <= s1 and s1 + d1 <= s0 + d0
    assert s0 < 60e9                    # not an epoch time


@pytest.mark.parametrize("name", tracelib.SPAN_NAMES)
def test_no_event_bears_a_bare_name_of_the_benchmarks(xplane, name):
    _, events, _ = xplane
    assert name not in events


def test_the_benchmarks_loader_picks_up_no_program_span(xplane):
    path, _, _ = xplane
    assert tracelib.load(path)["spans"] == []


def test_the_async_pair_reaches_the_ring_only(xplane):
    _, events, ring = xplane
    assert "submit" in {e["name"] for e in ring}
    assert "pt:submit" not in events


def test_recording_off_reaches_neither_sink(xplane):
    _, events, ring = xplane
    assert "pt:engine.step" not in events
    assert "engine.step" not in {e["name"] for e in ring}


def test_record_event_goes_through_the_one_primitive(xplane):
    _, events, ring = xplane
    assert len(events["pt:profiler:user_step"]) == 1
    assert "user_step" not in events    # no annotation of its own beside it
    assert "profiler:user_step" in {e["name"] for e in ring}


def test_record_event_keeps_its_clock_and_the_summary(capsys):
    prof = profiler.Profiler(timer_only=True)
    prof.start()
    with profiler.RecordEvent("outer") as outer:
        with profiler.RecordEvent("inner") as inner:
            assert inner.name == "inner"
    prof.stop()
    assert outer.begin_ns <= inner.begin_ns <= inner.end_ns <= outer.end_ns
    prof.summary(op_detail=False)
    out = capsys.readouterr().out
    assert "UserDefined summary" in out and "outer" in out and "inner" in out


def test_without_a_session_a_span_opens_no_annotation():
    with obs.span("quiet") as _:
        pass
    ctx = obs.span("quiet")
    with ctx:
        assert ctx._annotation is None


def test_one_place_opens_a_trace_annotation():
    call = re.compile(r"TraceAnnotation\(")
    found = []
    for base, _, files in os.walk(os.path.join(ROOT, "paddle_tpu")):
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(base, name)
                with open(path, encoding="utf-8") as fh:
                    if call.search(fh.read()):
                        found.append(os.path.relpath(path, ROOT))
    assert found == [os.path.join("paddle_tpu", "obs", "trace.py")]
