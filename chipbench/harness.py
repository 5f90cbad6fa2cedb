"""One run of one cell: find its data files by name, run its job, print
the contract's last line.

Nothing here knows a cell, a configuration, a traffic mix or a metric by
name: ``BENCHMARK.json`` names the cell's configuration and traffic, the
configuration's ``family`` names ``families/<family>.py``, the traffic's
``kind`` names ``jobs/<kind>.py``, and each per-layer metric is read by
``layer_metrics/<metric>.py``.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
import re
import shutil
import sys
import time
from typing import Dict, List, Optional

from . import peaks, trace as tracelib

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def say(msg: str) -> None:
    print(f"[chipbench] {msg}", flush=True)


def load_json(path: str) -> Dict:
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


class Cell:
    """A cell's data, found by name from ``BENCHMARK.json``. ``workload``
    is a cell's name or, for ``tools/`` alone, ``config:traffic`` — a pair
    that is not (yet) a cell, on one chip."""

    def __init__(self, bench: Dict, workload: str, root: str = ROOT):
        self.bench, self.root = bench, root
        cells = {w["name"]: w for w in bench["workloads"]}
        if ":" in workload:
            config, traffic = workload.split(":")
            cells[workload] = {"config": config, "traffic": traffic,
                               "chips": 1}
        if workload not in cells:
            raise SystemExit(f"chipbench: no workload {workload!r} in "
                             f"BENCHMARK.json (has {sorted(cells)})")
        self.spec = cells[workload]
        self.name = workload
        self.chips = int(self.spec["chips"])
        conf = {c["name"]: c for c in bench["configs"]}[self.spec["config"]]
        self.config = load_json(os.path.join(root, conf["file"]))
        self.traffic = self._find("traffic", self.spec["traffic"] + ".json",
                                  load_json)
        self.family = self._module("families", self.config["family"])
        self.job = self._module("jobs", self.traffic["kind"])

    def _dirs(self, sub: str) -> List[str]:
        """Where files of one sort may sit: ``<path>/<sub>`` of every
        benchmark path, so a later PR can bring a directory of its own."""
        return [os.path.join(self.root, p, sub) for p in self.bench["paths"]]

    def _find(self, sub: str, filename: str, load):
        for d in self._dirs(sub):
            path = os.path.join(d, filename)
            if os.path.exists(path):
                return load(path)
        raise SystemExit(f"chipbench: no {sub}/{filename} under "
                         f"{self.bench['paths']}")

    def _module(self, sub: str, name: str):
        if not _NAME.match(name):
            raise SystemExit(f"chipbench: bad {sub} name {name!r}")
        if sub != "layer_metrics":
            try:
                return importlib.import_module(f"chipbench.{sub}.{name}")
            except ModuleNotFoundError as e:
                if e.name != f"chipbench.{sub}.{name}":
                    raise

        def load(path):
            spec = importlib.util.spec_from_file_location(
                f"chipbench_{sub}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod

        return self._find(sub, name + ".py", load)

    def reports(self, metric: Dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def end_to_end(self) -> List[Dict]:
        return [m for m in self.bench["end_to_end"] if self.reports(m)]

    def per_layer(self) -> List[Dict]:
        moved = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"]
                if self.reports(m) and m["moves"] in moved]

    def reader(self, metric: str):
        return self._module("layer_metrics", metric).read


class Tracer:
    """jax's profiler around a part of the window; the trace is written
    under the checkout (``.chipbench_trace/``), reduced, and removed."""

    def __init__(self, root: str, on: bool):
        self.on, self.dir = on, os.path.join(root, ".chipbench_trace")
        self.trace: Optional[Dict] = None

    def start(self) -> None:
        if self.on:
            import jax

            shutil.rmtree(self.dir, ignore_errors=True)
            jax.profiler.start_trace(self.dir)

    def stop(self) -> None:
        if self.on:
            import jax

            jax.profiler.stop_trace()
            self.trace = tracelib.load(tracelib.find_xplane(self.dir))
            shutil.rmtree(self.dir, ignore_errors=True)

    @staticmethod
    def span(name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)


def devices_or_exit(chips: int, allow_cpu: bool):
    import jax

    devs = jax.devices()
    if devs[0].platform == "cpu" and not allow_cpu:
        print("chipbench: no accelerator (jax platform is 'cpu'); a "
              "benchmark number comes only from a chip", file=sys.stderr)
        raise SystemExit(3)
    if len(devs) < chips:
        print(f"chipbench: the cell needs {chips} chips, jax found "
              f"{len(devs)}", file=sys.stderr)
        raise SystemExit(3)
    return devs[:chips]


def enable_cache() -> str:
    """The persistent compile cache, at the fixed path the program's own
    ``enable_compile_cache`` gives (``JAX_COMPILATION_CACHE_DIR`` if set,
    else ``<checkout>/.jax_cache``), holding every program however small."""
    import jax

    from paddle_tpu.utils.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def device_record(devs, tracer: Tracer) -> Dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    rec = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": peak}
    if tracer.trace is not None:
        lo, hi = tracelib.window_of(tracer.trace)
        rec["busy_s"] = tracelib.busy_seconds(tracer.trace, (lo, hi))
        rec["window_s"] = (hi - lo) / 1e9
    return rec


def breakdown(trace: Dict) -> Dict:
    return {"device_ops": tracelib.top_ops(trace),
            "idle_gaps": tracelib.idle_by_span(
                trace, tracelib.window_of(trace), min(trace["devices"]))}


def run(bench: Dict, workload: str, seed: int, seconds: float, trace: bool,
        *, root: str = ROOT, t_start: Optional[float] = None,
        allow_cpu: bool = False, control: Optional[str] = None,
        traffic: Optional[Dict] = None,
        config: Optional[Dict] = None) -> Dict:
    """Run one cell once; returns the result line (a dict with the
    contract's keys alone) and, for ``tools/`` and the tests, the numbers
    compared, the notes and every end-to-end reading. ``allow_cpu``
    is for the tests' own calls; ``control`` (a precision below the
    configuration's) and ``traffic`` / ``config`` (changed copies of the
    cell's mix and configuration) are for ``tools/``. The command line sets none of them."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = Cell(bench, workload, root)
    devs = devices_or_exit(cell.chips, allow_cpu)
    on_chip = devs[0].platform != "cpu"
    if on_chip:
        say(f"compile cache: {enable_cache()}")
        peaks.peaks_for(devs[0].device_kind)  # unknown kind = error
    say(f"cell={workload} seed={seed} seconds={seconds} trace={int(trace)} "
        f"device={devs[0].device_kind!r} x{len(devs)}")
    tracer = Tracer(root, trace and on_chip)
    ctx = {"cell": cell, "config": config or cell.config, "control": control,
           "traffic": traffic or cell.traffic,
           "family": cell.family, "seed": int(seed),
           "seconds": float(seconds), "tracer": tracer, "devices": devs,
           "t_start": t_start, "say": say}
    res = cell.job.run(ctx)
    for c in res["checks"]:
        say(f"check {c['name']}: value={c['value']:.6g} limit={c['limit']:.6g}"
            f" {'ok' if c['ok'] else 'FAILED'}"
            + (f" at {c['at']}" if c["at"] else ""))
    for k, v in sorted(res.get("notes", {}).items()):
        say(f"note {k}={v}")
    correct = bool(res["checks"]) and all(c["ok"] for c in res["checks"])
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    metrics = {}
    if not trace:
        # off the chip (the tests' own calls) a host-clock time or rate is
        # not a device number and is never written under its name
        for m in cell.end_to_end() if on_chip else []:
            metrics[m["name"]] = res["end_to_end"][m["name"]]
    else:
        facts = dict(res["facts"], trace=tracer.trace, on_chip=on_chip,
                     chips=cell.chips, config=ctx["config"],
                     traffic=ctx["traffic"], family=cell.family,
                     end_to_end=res["end_to_end"],
                     peaks=(peaks.peaks_for(devs[0].device_kind)
                            if on_chip else None))
        for m in cell.per_layer():
            value = cell.reader(m["name"])(facts)
            if value is not None:
                metrics[m["name"]] = value
    line = {"correct": correct, "attempted": int(res["attempted"]),
            "failed": int(res["failed"]),
            "metrics": {k: {"value": float(v), "unit": units[k]}
                        for k, v in metrics.items()},
            "device": device_record(devs, tracer)}
    if tracer.trace is not None:
        line["breakdown"] = breakdown(tracer.trace)
    return line, {"checks": res["checks"], "notes": res.get("notes", {}),
                  "end_to_end": res["end_to_end"]}
