"""Lay the program's own spans over the device's idle time, on one clock.

    python3 -m chipbench.tools.span_idle --workload train-1p3b-2k --seed 7 \
        [--seconds <run_seconds>]

One traced run of a training cell, as ``python3 -m chipbench ... --trace 1``
makes it, with a tracer that besides the benchmark's own spans keeps the
program's (``paddle_tpu.obs.span`` writes each as a ``pt:<name>``
annotation, with its ``trace_id`` / ``span_id`` / ``parent_id``, into the
profiler's host plane) and the device ops' statistics. Prints, and writes
to ``chiprun_out/span_idle_<cell>_<seed>.json``:

* the nesting check: every ``pt:to_static.call`` inside one ``train.step``
  span and every child inside its parent, on the trace's clock;
* the device's idle seconds of the traced part by the innermost span open
  at the time, which sum to the ``idle_gaps`` total of a result line;
* the flash kernels' and the optimizer's device time a step (the
  optimizer's ops are those whose ``op_name`` carries the
  ``optimizer.step`` scope), the ops by kind with the ``op_name`` they
  carry, and the step time inside the traced part;
* what one ``obs.span`` costs with and without a profiler session.

Not a benchmark run: its numbers go to ``PERF.md`` by hand.
"""
import argparse
import json
import os
import re
import shutil
import sys
import time
from typing import Dict, List

from chipbench import harness, stats, trace as tracelib

PREFIX = "pt:"
CALL = PREFIX + "to_static.call"
BEFORE, AFTER = "train.step before the call", "train.step after the call"
# innermost first: a gap is booked to the first of these that covers it
ORDER = (PREFIX + "to_static.revalidate", PREFIX + "to_static.read_state",
         PREFIX + "to_static.dispatch", PREFIX + "to_static.write_state",
         PREFIX + "to_static.trace", CALL, BEFORE, AFTER, "train.step",
         "make_batch")
KERNELS = {"flash_fwd": r"^%[\w.\-]*flash_fwd[\w.\-]* = ",
           "flash_bwd_dq": r"^%[\w.\-]*flash_bwd_dq[\w.\-]* = ",
           "flash_bwd_dkv": r"^%[\w.\-]*flash_bwd_dkv[\w.\-]* = ",
           "fused_adamw": r"^%[\w.\-]*fused_adamw[\w.\-]* = "}
SCOPE = "optimizer.step"


def op_names(path: str) -> Dict[str, str]:
    """``op_name`` (the jax name stack, scopes included) of every device
    op, by the op's event name. The profiler keeps it with the event's
    METADATA (statistic ``tf_op``), which ``ProfileData`` does not show:
    the xplane is read again through TensorFlow's generated protobuf
    module, loaded by its path so that TensorFlow itself is not imported.
    Empty where that module is not installed."""
    import importlib.util

    tf = importlib.util.find_spec("tensorflow")
    pb2 = tf and tf.submodule_search_locations and os.path.join(
        list(tf.submodule_search_locations)[0], "tsl", "profiler", "protobuf",
        "xplane_pb2.py")
    if not pb2 or not os.path.exists(pb2):
        return {}
    spec = importlib.util.spec_from_file_location("_chipbench_xplane_pb2", pb2)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    space = mod.XSpace()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    out: Dict[str, str] = {}
    for plane in space.planes:
        if not tracelib.DEVICE_PLANE.match(plane.name):
            continue
        keys = {k: v.name for k, v in plane.stat_metadata.items()}
        for md in plane.event_metadata.values():
            for st in md.stats:
                if keys.get(st.metadata_id) == "tf_op":
                    out[md.name] = (st.str_value or
                                    keys.get(st.ref_value, ""))
    return out


def load_full(path: str) -> Dict:
    """Host spans (the benchmark's and the program's) with their ids, and
    each device's ops as (name, start, duration, op_name)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans: List[Dict] = []
    ops: Dict[int, List] = {}
    names = op_names(path)
    for plane in data.planes:
        m = tracelib.DEVICE_PLANE.match(plane.name)
        if m:
            dev = ops.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name == tracelib.OPS_LINE:
                    dev.extend((e.name, int(e.start_ns), int(e.duration_ns),
                                names.get(e.name, "")) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if (e.name.startswith(PREFIX)
                            or e.name in tracelib.SPAN_NAMES):
                        spans.append({"name": e.name, "start": int(e.start_ns),
                                      "end": int(e.start_ns + e.duration_ns),
                                      **{k: v for k, v in e.stats
                                         if k.endswith("_id")}})
    return {"spans": spans, "ops": ops}


def ops_by_kind(dev: List, steps: int, limit: int = 30) -> List[Dict]:
    """The device ops that took most time, by kind (``trace.op_kind``):
    ms and events a step, the ms under ``SCOPE``, and the op_name most of
    the kind's events carry, numbers and the leaf's position taken out."""
    acc: Dict[str, Dict] = {}
    for name, _, dur, op_name in dev:
        row = acc.setdefault(tracelib.op_kind(name), {
            "ns": 0, "n": 0, "scoped_ns": 0, "op_names": {}})
        row["ns"] += dur
        row["n"] += 1
        row["scoped_ns"] += dur if SCOPE in op_name else 0
        key = re.sub(r"\d+", "#", op_name)[-110:]
        row["op_names"][key] = row["op_names"].get(key, 0) + 1
    rows = sorted(acc.items(), key=lambda kv: -kv[1]["ns"])[:limit]
    return [{"kind": k, "ms_a_step": r["ns"] / 1e6 / steps,
             "events_a_step": r["n"] / steps,
             "scoped_ms_a_step": r["scoped_ns"] / 1e6 / steps,
             "op_name": max(r["op_names"], key=r["op_names"].get)}
            for k, r in rows]


class SpanTracer(harness.Tracer):
    """The harness's tracer, keeping the whole of what this tool reads."""

    last = None

    def stop(self) -> None:
        if self.on:
            import jax

            jax.profiler.stop_trace()
            path = tracelib.find_xplane(self.dir)
            self.trace = tracelib.load(path)
            self.full = load_full(path)
            shutil.rmtree(self.dir, ignore_errors=True)
            SpanTracer.last = self


def nesting(spans: List[Dict]) -> Dict:
    """Every program call inside a ``train.step``, every child inside its
    parent; the worst overhang in nanoseconds (0 = all inside)."""
    steps = sorted((s["start"], s["end"]) for s in spans
                   if s["name"] == "train.step")
    by_id = {s["span_id"]: s for s in spans if "span_id" in s}
    calls = [s for s in spans if s["name"] == CALL]
    outside = [c for c in calls
               if not any(a <= c["start"] and c["end"] <= b for a, b in steps)]
    kids = [s for s in spans if s.get("parent_id")]
    orphans = [k for k in kids if k["parent_id"] not in by_id]
    worst = 0
    for k in kids:
        p = by_id.get(k["parent_id"])
        if p is not None:
            worst = max(worst, p["start"] - k["start"], k["end"] - p["end"])
    ids = {c["trace_id"] for c in calls}
    return {"train_steps": len(steps), "calls": len(calls),
            "calls_outside_a_train_step": len(outside),
            "children": len(kids), "children_without_parent": len(orphans),
            "worst_overhang_ns": worst,
            "children_sharing_their_call_id": sum(
                k.get("trace_id") in ids for k in kids)}


def around_the_call(spans: List[Dict]) -> List[Dict]:
    """A ``train.step``'s time before its one call of the program (the
    job's ``to_tensor``) and after it (``block_until_ready`` and
    ``float(loss)``: the device runs the step), as spans of their own."""
    calls = [s for s in spans if s["name"] == CALL]
    out = []
    for step in (s for s in spans if s["name"] == "train.step"):
        inside = [c for c in calls if step["start"] <= c["start"]
                  and c["end"] <= step["end"]]
        if len(inside) == 1:
            out += [{"name": BEFORE, "start": step["start"],
                     "end": inside[0]["start"]},
                    {"name": AFTER, "start": inside[0]["end"],
                     "end": step["end"]}]
    return out


def idle_by_program_span(trace: Dict, spans: List[Dict]) -> List[List]:
    """Idle seconds of the first device by innermost span, ``ORDER``'s
    order settling what covers what; the rows sum to the idle total."""
    spans = spans + around_the_call(spans)
    window = tracelib.window_of(trace)
    left = tracelib.idle_gaps(trace, window, min(trace["devices"]))
    rows = []
    for name in ORDER:
        cover = tracelib.union((s["start"], s["end"]) for s in spans
                               if s["name"] == name)
        rest = tracelib.subtract(left, cover)
        rows.append([name, (tracelib.total(left) - tracelib.total(rest)) / 1e9])
        left = rest
    rows.append(["(none)", tracelib.total(left) / 1e9])
    return rows


def span_cost(root: str, n: int = 20000) -> Dict:
    """Nanoseconds one ``obs.span`` enter and exit costs: recording off,
    on with no profiler session, and on inside one."""
    import timeit

    import jax

    from paddle_tpu import obs

    def one():
        with obs.span("span_idle.cost"):
            pass

    def per_call():
        return 1e9 * min(timeit.repeat(one, number=n, repeat=3)) / n

    out = {}
    prev = obs.set_enabled(False)
    out["recording_off_ns"] = per_call()
    obs.set_enabled(True)
    out["no_session_ns"] = per_call()
    trace_dir = os.path.join(root, ".chipbench_trace")
    jax.profiler.start_trace(trace_dir)
    out["in_session_ns"] = per_call()
    jax.profiler.stop_trace()
    shutil.rmtree(trace_dir, ignore_errors=True)
    obs.set_enabled(prev)
    return out


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    harness.Tracer = SpanTracer
    line, _ = harness.run(bench, args.workload, args.seed,
                          args.seconds or bench["run_seconds"], True,
                          t_start=t_start)
    tracer = SpanTracer.last
    if tracer is None:
        print("span_idle: no trace (no accelerator?)", file=sys.stderr)
        return 3
    trace, full = tracer.trace, tracer.full
    spans, dev = full["spans"], full["ops"][min(full["ops"])]
    steps = max(1, sum(s["name"] == "train.step" for s in spans))
    starts = sorted(s["start"] for s in spans if s["name"] == "make_batch")
    busy = sum(d for _, _, d, _ in dev)
    scoped = sum(d for _, _, d, o in dev if SCOPE in o)
    report = {
        "line": line,
        "nesting": nesting(spans),
        "idle_by_program_span_s": idle_by_program_span(trace, spans),
        "idle_total_s": tracelib.total(tracelib.idle_gaps(
            trace, tracelib.window_of(trace), min(trace["devices"]))) / 1e9,
        "kernel_ms_a_step": {
            k: [1e3 * seconds / steps, events / steps]
            for k, (seconds, events) in (
                (k, tracelib.kernel_seconds(trace, rx))
                for k, rx in KERNELS.items())},
        "optimizer_scope": {
            "ops_a_step": sum(SCOPE in o for *_, o in dev) / steps,
            "ms_a_step": scoped / 1e6 / steps,
            "share_of_device_busy": scoped / busy if busy else None},
        "ops_by_kind": ops_by_kind(dev, steps),
        "device_ms_a_step": busy / 1e6 / steps,
        "traced_step_ms": (1e-6 * stats.median(
            [b - a for a, b in zip(starts, starts[1:])])
            if len(starts) > 2 else None),
        "custom_calls": sorted({n.split(" = ")[0].rstrip("0123456789.")
                                for n, *_ in dev if " custom-call(" in n}),
        "span_cost": span_cost(harness.ROOT),
    }
    for key, value in report.items():
        print(f"[span_idle] {key}: {json.dumps(value)[:6000]}", flush=True)
    out = os.path.join(harness.ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"span_idle_{args.workload}_{args.seed}.json"),
              "w", encoding="utf-8") as fh:
        json.dump(report, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
