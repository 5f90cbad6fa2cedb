"""The gated norms after a linear mixer, ONE layer alone (no cell runs
this).

At ``[1, 16384, 4096]`` bfloat16 (32 heads of 128: what the three hybrid
cells' linear layers hand their output gate) it reads, on the chip, for
each way of running a gate:

- ``qwen3next.kernels``: ``ops/gated_norm.py`` (Gated DeltaNet's gate as
  the layer runs it: the norm over each head of 128, a gain of 128, SiLU);
- ``qwen3next.xla``: the same formula as XLA compiles it, ``models/
  qwen3_next.py::gdn_gate`` under ``jax.checkpoint`` (the kernels' oracle);
- ``minicpm_sala.xla``: ``models/minicpm_sala.py::lightning_gate`` under
  ``jax.checkpoint`` as ``lin.gate`` runs it (the norm over all 4,096, a
  gain of 4,096, a sigmoid gate);
- ``granite.xla``: ``models/granite_hybrid.py::ssm_gate`` under
  ``jax.checkpoint`` as ``ssm.gate`` runs it (SiLU gate first, then the
  norm over all 4,096).

Of each: wall seconds a call over ``--calls`` calls of the jitted forward
and of forward + backward (``jax.vjp`` applied to a cotangent that is an
input, so nothing of a loss fuses into the gate; every gradient and the
output are results), each loop ended by ``block_until_ready``; and the
device's busy milliseconds a call over three calls of each in one
profiler trace. The kernels' row adds the mean duration of the
``gated_norm_fwd`` / ``gated_norm_bwd`` events and the share of the bytes'
bound they make (forward: ``o``, ``z`` read and ``out`` written; backward
``o``, ``z``, ``d out`` read and ``d o``, ``d z`` written), and its
agreement with the XLA form at 2,048 tokens, in bfloat16 and in float32
(what the kernels' float32 differs by: the order of sums and the chip's
transcendentals).

``--file other/gated_norm.py`` prices that file too (a variant) in the
same process, after the repository's. Off the chip nothing is timed:
``--rehearse`` walks the same code at a toy size in interpret mode and
prints the agreement alone.

    chiprun -- python3 benchmarks/gated_norm_bench.py [--file PATH ...]
    JAX_PLATFORMS=cpu python3 benchmarks/gated_norm_bench.py --rehearse
"""
import argparse
import functools
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from benchmarks.gdn_bench import _wall, load

CELL = dict(seq=16384, heads=32, d=128)
TOY = dict(seq=256, heads=2, d=128)
EPS = 1e-6
NAMES = ("out", "d_o", "d_z", "d_w")


def inputs(seed, seq, heads, d, gain, dtype):
    """``o``, ``z`` and the output's cotangent [1, seq, heads d] with
    entries of unit variance, a gain of ``gain`` entries about one."""
    ks = jax.random.split(jax.random.key(seed), 4)
    o, z, dy = (jax.random.normal(k, (1, seq, heads * d)).astype(dtype)
                for k in ks[:3])
    w = (1.0 + 0.1 * jax.random.normal(ks[3], (gain,))).astype(dtype)
    return o, z, w, dy


def xla_forms(heads, d):
    """{name: (gate(o, z, w) on flat [B, S, heads d] arrays, the gain's
    size)}: each model's formula under ``jax.checkpoint``, as its layer
    calls it."""
    from paddle_tpu.models.granite_hybrid import ssm_gate
    from paddle_tpu.models.minicpm_sala import lightning_gate
    from paddle_tpu.models.qwen3_next import gdn_gate

    def by_head(fn):
        # the recurrences return o as [B, S, heads, d]: a reshape of the
        # flat array their kernel writes
        return lambda o, z, w: fn(o.reshape(*o.shape[:2], heads, d), z, w)

    kept = lambda fn: jax.checkpoint(functools.partial(fn, eps=EPS))
    return {"qwen3next.xla": (by_head(kept(gdn_gate)), d),
            "minicpm_sala.xla": (by_head(kept(lightning_gate)), heads * d),
            "granite.xla": (kept(ssm_gate), heads * d)}


def both(gate):
    """-> fn(o, z, w, dy) = (out, d o, d z, d w)."""
    def run(o, z, w, dy):
        out, back = jax.vjp(gate, o, z, w)
        return (out, *back(dy))
    return jax.jit(run)


def worst(got, want):
    got, want = (a.astype(jnp.float32) for a in (got, want))
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


def agreement(gate, oracle, shape, dtype):
    """{"out": .., "d_w": ..}: the largest difference over the largest
    entry, inputs of ``dtype`` on both sides."""
    args = inputs(7, **shape, gain=shape["d"], dtype=dtype)
    return {n: worst(a, b) for n, a, b in zip(
        NAMES, both(gate)(*args), both(oracle)(*args))}


def _busy_ms(fn, args, calls=3):
    """Device-busy ms a call of ``fn`` and the trace it was read from."""
    from chipbench import trace as tracelib

    with tempfile.TemporaryDirectory() as where:
        with jax.profiler.trace(where):
            for _ in range(calls):
                jax.block_until_ready(fn(*args))
        trace = tracelib.load(tracelib.find_xplane(where))
    window = tracelib.window_of(trace)
    return 1e3 * tracelib.busy_seconds(trace, window) / calls, trace


def price(gate, gain, shape, calls, kernels=()):
    """Wall and device-busy ms a call (forward; forward + backward); for
    ``kernels`` (forward's name, backward's) their mean device ms an event
    and shares of the bytes' bound."""
    from chipbench import peaks, trace as tracelib

    o, z, w, dy = inputs(11, **shape, gain=gain, dtype=jnp.bfloat16)
    forward, grad = jax.jit(gate), both(gate)
    out = {"fwd_wall_ms": 1e3 * _wall(forward, (o, z, w), calls),
           "grad_wall_ms": 1e3 * _wall(grad, (o, z, w, dy), calls)}
    out["fwd_device_ms"], _ = _busy_ms(forward, (o, z, w))
    out["grad_device_ms"], trace = _busy_ms(grad, (o, z, w, dy))
    out["grad_top_ops"] = tracelib.top_ops(trace, 6)
    chip = peaks.peaks_for(jax.devices()[0].device_kind)
    for name, arrays in zip(kernels, (3, 5)):
        seconds, events = tracelib.kernel_seconds(trace, name)
        if not events:          # a trace without the kernel's events
            continue
        bound = arrays * o.size * o.dtype.itemsize / chip.hbm_bytes_per_s
        out[f"{name}_ms"] = 1e3 * seconds / events
        out[f"{name}_events"] = events
        out[f"{name}_roofline"] = 100.0 * bound * events / seconds
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--file", action="append", default=[],
                    help="another gated_norm.py to price as well")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy size, no times: what a run off the chip may do")
    args = ap.parse_args()

    device = jax.devices()[0]
    on_chip = device.platform == "tpu"
    if not on_chip and not args.rehearse:
        sys.exit("gated_norm_bench: no chip here (a time comes only from a "
                 "chip run); --rehearse walks the code at a toy size")
    from paddle_tpu.ops import gated_norm as own

    timed = on_chip and not args.rehearse
    shape = CELL if timed else TOY
    forms = xla_forms(shape["heads"], shape["d"])
    oracle = forms["qwen3next.xla"][0]
    rows = []

    def report(row):
        # after every row: a later form that fails loses nothing before it
        print(json.dumps(row), flush=True)
        rows.append(row)
        os.makedirs("chiprun_out", exist_ok=True)
        with open(os.path.join("chiprun_out", "gated_norm_bench.json"),
                  "w") as f:
            json.dump(rows, f, indent=1)

    for label, module in [("repository", own)] + [
            (path, load(path)) for path in args.file]:
        gate = functools.partial(module.gated_rms_norm, eps=EPS)
        row = {"gate": "qwen3next.kernels", "kernels": label,
               "device": device.device_kind}
        try:
            if timed:
                row.update(price(gate, shape["d"], shape, args.calls,
                                 module.KERNELS))
            for dtype in (jnp.bfloat16, jnp.float32):
                row[f"agreement_{jnp.dtype(dtype).name}"] = agreement(
                    gate, oracle, dict(shape, seq=min(shape["seq"], 2048)),
                    dtype)
        except Exception as e:    # a variant the chip's compiler refuses
            if module is own:
                raise
            row["error"] = repr(e)[:400]
        report(row)
    if timed:
        for name, (gate, gain) in forms.items():
            report({"gate": name, "device": device.device_kind,
                    **price(gate, gain, shape, args.calls)})


if __name__ == "__main__":
    main()
