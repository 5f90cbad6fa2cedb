"""The gated delta rule's kernels, ONE layer alone (no cell runs this).

At ``train-qwen3next-4l-16k``'s shape (q, k [1, 16384, 16, 128], v
[1, 16384, 32, 128] bfloat16; g, beta float32) it reads, on the chip:

- wall: seconds a call over ``--calls`` calls of the jitted forward and of
  ``jax.grad`` over all five inputs (forward + backward + the decays'
  running sums), each loop ended by ``block_until_ready``;
- device: the mean duration of the ``gdn_fwd*`` / ``gdn_bwd*`` events of
  one profiler trace over three gradient calls, by the patterns the
  benchmark's two roofline readers use, and the share of the bytes'
  bound they make (``chipbench/shapes_qwen3next.py``);
- agreement: at 2,048 tokens, the kernels in bfloat16 against the
  recurrence token by token in float32 on the same (bfloat16-rounded)
  inputs: the largest difference over the largest entry, for ``o`` and
  each of the five gradients.

``--file other/gated_delta_rule.py`` prices that file too (the parent's,
a variant) in the same process, after the repository's. Off the chip
nothing is timed: ``--rehearse`` walks the same code at a toy size in
interpret mode and prints the agreement alone.

    chiprun -- python3 benchmarks/gdn_bench.py [--file PATH ...]
    JAX_PLATFORMS=cpu python3 benchmarks/gdn_bench.py --rehearse
"""
import argparse
import importlib.util
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

CELL = dict(seq=16384, key_heads=16, value_heads=32, d=128)
TOY = dict(seq=256, key_heads=1, value_heads=2, d=128)
NAMES = ("q", "k", "v", "g", "beta")


def load(path):
    """The module of a kernel file outside the package, imported as a
    sibling of ``paddle_tpu.ops.gated_delta_rule`` (its relative imports
    resolve there)."""
    import paddle_tpu.ops  # noqa: F401  the package the file belongs to

    name = "paddle_tpu.ops._bench_" + os.path.basename(path).split(".")[0]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def inputs(seed, seq, key_heads, value_heads, d, dtype):
    """Normalised q (scaled) and k, unit-variance v, decays that leave
    0.84-0.999 of a state a token (the cell's ``A_log`` range), beta in
    (0, 1); and the cotangent of ``o``."""
    ks = jax.random.split(jax.random.key(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (1, seq, key_heads, d))) / np.sqrt(d)
    k = unit(jax.random.normal(ks[1], (1, seq, key_heads, d)))
    v = jax.random.normal(ks[2], (1, seq, value_heads, d))
    rate = jnp.exp(jnp.linspace(np.log(0.0015), np.log(0.12), value_heads))
    g = -rate * jax.random.uniform(ks[3], (1, seq, value_heads), minval=0.5,
                                   maxval=1.5)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, seq, value_heads)))
    weight = jax.random.normal(ks[5], (1, seq, value_heads, d))
    return ((q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta),
            weight.astype(dtype))


def recurrence(q, k, v, g, beta):
    """``S <- exp(g_t) S; delta = beta_t (v_t - S^T k_t); S <- S + k_t
    delta^T; o_t = S^T q_t`` per value head in float32, state from zero."""
    hv = v.shape[2]
    rep = hv // q.shape[2]
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    q, k = jnp.repeat(q, rep, axis=2), jnp.repeat(k, rep, axis=2)

    def step(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t)[..., None, None] * state
        u = jnp.einsum("bhkv,bhk->bhv", state, k_t, precision="highest")
        delta = b_t[..., None] * (v_t - u)
        state = state + k_t[..., :, None] * delta[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t,
                                 precision="highest")

    xs = [jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)]
    zero = jnp.zeros((q.shape[0], hv, q.shape[-1], v.shape[-1]), jnp.float32)
    return jnp.moveaxis(jax.lax.scan(step, zero, xs)[1], 0, 1)


def _both(fn, weight):
    loss = lambda *a: jnp.sum(fn(*a).astype(jnp.float32)
                              * weight.astype(jnp.float32))
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)))


def agreement(rule, shape, seq):
    """{"o": .., "q": .. "beta": ..}: bfloat16 kernels against the float32
    recurrence, the largest difference over the largest entry."""
    args, weight = inputs(7, **{**shape, "seq": seq}, dtype=jnp.bfloat16)
    worst = lambda got, want: float(
        jnp.abs(got.astype(jnp.float32) - want).max() / jnp.abs(want).max())
    out = {"o": worst(jax.jit(rule)(*args), jax.jit(recurrence)(*args))}
    got, want = (_both(f, weight)(*args)[1] for f in (rule, recurrence))
    out.update({n: worst(a, b.astype(jnp.float32))
                for n, a, b in zip(NAMES, got, want)})
    return out


def _wall(fn, args, calls):
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / calls


def price(rule, shape, calls):
    """Wall seconds a call (forward; forward + backward) and the two
    kernels' mean device seconds an event, with their shares of the
    bytes' bound."""
    from chipbench import peaks, shapes_qwen3next, trace as tracelib
    from chipbench.layer_metrics import gdn_bwd_roofline, gdn_fwd_roofline

    args, weight = inputs(11, **shape, dtype=jnp.bfloat16)
    forward, both = jax.jit(rule), _both(rule, weight)
    out = {"fwd_wall_ms": 1e3 * _wall(forward, args, calls),
           "grad_wall_ms": 1e3 * _wall(both, args, calls)}
    with tempfile.TemporaryDirectory() as where:
        with jax.profiler.trace(where):
            for _ in range(3):
                jax.block_until_ready(both(*args))
        trace = tracelib.load(tracelib.find_xplane(where))
    z = {"key_dim": shape["d"], "value_dim": shape["d"],
         "key_heads": shape["key_heads"], "value_heads": shape["value_heads"]}
    chip = peaks.peaks_for(jax.devices()[0].device_kind)
    for way, reader in (("fwd", gdn_fwd_roofline), ("bwd", gdn_bwd_roofline)):
        seconds, events = tracelib.kernel_seconds(trace, reader.KERNELS)
        if not events:          # a trace without the kernel's events
            continue
        bound = shapes_qwen3next.bound_seconds(
            getattr(shapes_qwen3next, f"gdn_{way}_flops")(shape["seq"], z),
            getattr(shapes_qwen3next, f"gdn_{way}_bytes")(shape["seq"], z, 2),
            chip)
        out[f"gdn_{way}_ms"] = 1e3 * seconds / events
        out[f"gdn_{way}_events"] = events
        out[f"gdn_{way}_roofline"] = 100.0 * bound * events / seconds
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--file", action="append", default=[],
                    help="another gated_delta_rule.py to price as well")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy size, no times: what a run off the chip may do")
    args = ap.parse_args()

    device = jax.devices()[0]
    on_chip = device.platform == "tpu"
    if not on_chip and not args.rehearse:
        sys.exit("gdn_bench: no chip here (a time comes only from a chip "
                 "run); --rehearse walks the code at a toy size")
    from paddle_tpu.ops import gated_delta_rule as own

    rows = []
    for label, module in [("repository", own)] + [
            (path, load(path)) for path in args.file]:
        rule = module.gated_delta_rule
        row = {"kernels": label, "device": device.device_kind}
        if on_chip and not args.rehearse:
            row.update(price(rule, CELL, args.calls))
            row["agreement_bf16_2048"] = agreement(rule, CELL, 2048)
        else:
            row["agreement_bf16_toy"] = agreement(rule, TOY, TOY["seq"])
        print(json.dumps(row), flush=True)
        rows.append(row)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "gdn_bench.json"), "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
