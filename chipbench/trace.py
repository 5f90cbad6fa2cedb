"""From a profiler trace to numbers: the reduction every PR shares.

``load`` turns the ``.xplane.pb`` jax's profiler wrote into plain
tuples; everything after it is arithmetic on ``(name, start, duration)``
lists (nanoseconds), checked in tests on hand-made traces.

Device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line
holds one event per executed HLO operation (a Pallas kernel appears
under the name of its custom call, see ``kernel_seconds``). The
benchmark's own ``jax.profiler.TraceAnnotation`` spans land on the host
plane's thread lines under the names given to them.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, int, int]            # name, start_ns, duration_ns
Interval = Tuple[int, int]              # start_ns, end_ns

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_NAMES = ("submit", "engine.step", "harvest", "wait", "train.step",
              "make_batch")


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> Dict:
    """``{"devices": {index: [Event]}, "spans": [Event]}`` — the ops of
    each device plane and the benchmark's host spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[int, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = devices.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.name, int(e.start_ns), int(e.duration_ns))
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, int(e.start_ns), int(e.duration_ns))
                             for e in line.events if e.name in SPAN_NAMES)
    return {"devices": devices, "spans": spans}


# -- arithmetic on intervals -------------------------------------------------


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, disjoint intervals."""
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals: Sequence[Interval]) -> int:
    return sum(b - a for a, b in intervals)


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of the disjoint sorted intervals ``a`` that no interval
    of the disjoint sorted ``b`` covers."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def _iv(events: Iterable[Event]) -> List[Interval]:
    return [(s, s + d) for _, s, d in events]


def window_of(trace: Dict) -> Interval:
    """The traced window: from the start of the first to the end of the
    last thing the profiler recorded, the benchmark's own spans (which
    cover its loop from the profiler's start to its stop) or a device
    operation — so idle time at either edge counts."""
    ops = [e for dev in trace["devices"].values() for e in dev]
    if not ops:
        raise ValueError("no operation ran on a device in the trace")
    marks = ops + list(trace["spans"])
    return min(s for _, s, _ in marks), max(s + d for _, s, d in marks)


def busy_seconds(trace: Dict, window: Interval) -> float:
    """Seconds in which an operation ran on the device: the union of the
    op intervals inside the window, averaged over the devices used."""
    per = [total(clip(union(_iv(ops)), *window))
           for ops in trace["devices"].values() if ops]
    return sum(per) / len(per) / 1e9


def idle_share(trace: Dict) -> float:
    """Share (0..1) of the traced window in which no operation ran on the
    device, averaged over the devices used."""
    lo, hi = window_of(trace)
    return 1.0 - busy_seconds(trace, (lo, hi)) / ((hi - lo) / 1e9)


def idle_gaps(trace: Dict, window: Interval, device: int) -> List[Interval]:
    busy = clip(union(_iv(trace["devices"][device])), *window)
    return subtract([window], busy)


def idle_by_span(trace: Dict, window: Interval, device: int,
                 limit: int = 10) -> List[List]:
    """Idle seconds of ``device`` by the benchmark span open at the time
    (innermost span wins; ``(none)`` where no span was open)."""
    left = idle_gaps(trace, window, device)
    out: Dict[str, int] = {}
    by_name: Dict[str, List[Interval]] = {}
    for name, s, d in trace["spans"]:
        by_name.setdefault(name, []).append((s, s + d))
    # the benchmark's spans do not nest; shortest first settles an overlap
    for name in sorted(by_name, key=lambda n: min(b - a for a, b in by_name[n])):
        cover = union(by_name[name])
        rest = subtract(left, cover)
        out[name] = total(left) - total(rest)
        left = rest
    out["(none)"] = total(left)
    rows = sorted(((n, ns / 1e9) for n, ns in out.items() if ns > 0),
                  key=lambda kv: -kv[1])
    return [[n, s] for n, s in rows[:limit]]


_HLO = re.compile(r"^%[\w.\-]+ = (.*?) ([\w\-]+)\(")
_LAYOUT = re.compile(r"\{[^}]*\}")


def op_kind(name: str) -> str:
    """What an op is, without which one it is: an event of the ``XLA
    Ops`` line is named by its whole HLO instruction; keep the opcode and
    the result's shapes (``fusion -> (bf16[2048,8192], bf16[2048,8192])``)
    so that the 24 layers' copies of one op add up."""
    m = _HLO.match(name)
    if not m:
        return name[:120]
    return f"{m.group(2)} -> {_LAYOUT.sub('', m.group(1))}"[:120]


def top_ops(trace: Dict, limit: int = 10) -> List[List]:
    """The device operations that took most time: seconds summed by kind
    of op (``op_kind``) with the number of events, averaged over the
    devices used."""
    devs = [ops for ops in trace["devices"].values() if ops]
    acc: Dict[str, List[int]] = {}
    for ops in devs:
        for name, _, d in ops:
            row = acc.setdefault(op_kind(name), [0, 0])
            row[0] += d
            row[1] += 1
    rows = sorted(acc.items(), key=lambda kv: -kv[1][0])[:limit]
    return [[f"{k} x{n // len(devs)}", ns / len(devs) / 1e9]
            for k, (ns, n) in rows]


def kernel_seconds(trace: Dict, pattern: str) -> Tuple[float, int]:
    """Device seconds and number of the events whose name matches
    ``pattern``, on the device where they took longest."""
    rx, best = re.compile(pattern), (0.0, 0)
    for ops in trace["devices"].values():
        found = [d for name, _, d in ops if rx.search(name)]
        best = max(best, (sum(found) / 1e9, len(found)))
    return best
