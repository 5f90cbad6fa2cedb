"""Block-sparse attention's kernels, ONE layer alone (no cell runs this).

At ``train-minicpmsala-4l-16k``'s shape (q [1, 16384, 32, 128], k, v
[1, 16384, 2, 128] bfloat16, the published rule: top-64 blocks of 64
keys, 1 first block and a window of 32 forced) on a table that
``select_blocks`` makes of seeded inputs, it reads on the chip, for each
way of running the layer:

- ``columns_64``: the any-table path on the whole table (every block of a
  token goes through the per-token pass);
- ``columns_31``: the any-table path on the table cut to its 31 FREE
  columns (the forced ids dropped): what the per-token pass costs when
  its blocks halve, before anything else computes the band;
- ``band``: the rule handed to the op (``init_blocks``, ``window_blocks``):
  the band pass and the per-token pass over the free columns — only for a
  kernel file that knows the arguments.

Of each it gives the mean device ms an event of the ``sparse_attn_fwd*``
and ``sparse_attn_bwd*`` kernels over three gradient calls of one
profiler trace, by the patterns of the benchmark's two roofline readers
(all of a direction's kernels; the one that writes the result; their
difference is the band pass alone), the share of the FLOP bound the sum
makes (``chipbench/shapes_minicpm_sala.py``, the rule's pairs: the cut
table's row is bounded by the same pairs and is no roofline), and the
wall seconds a call. Agreement: at 2,048 tokens, the kernels in bfloat16
against the masked softmax in float32 on the same (bfloat16-rounded)
inputs and the same table: the largest difference over the largest
entry, for ``o`` and each of ``dq``, ``dk``, ``dv``.

``--file other/sparse_attention.py`` prices that file too (the parent's,
a variant) in the same process, after the repository's. Off the chip
nothing is timed: ``--rehearse`` walks the same code at a toy size in
interpret mode and prints the agreement alone.

    chiprun -- python3 benchmarks/sparse_bench.py [--file PATH ...]
    JAX_PLATFORMS=cpu python3 benchmarks/sparse_bench.py --rehearse
"""
import argparse
import functools
import inspect
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from benchmarks.gdn_bench import _wall, load

CELL = dict(seq=16384, heads=32, groups=2, d=128)
RULE = dict(kernel_size=32, kernel_stride=16, block_size=64, topk=64,
            init_blocks=1, window_size=2048)
TOY = dict(seq=256, heads=4, groups=2, d=128)
TOY_RULE = dict(RULE, topk=4, window_size=128)
NAMES = ("q", "k", "v")


def inputs(seed, seq, heads, groups, d, dtype):
    """q and k with entries of unit variance (the layer norms them a
    head), v alike, and the cotangent of ``o``."""
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (1, seq, heads, d))
    k, v = (jax.random.normal(key, (1, seq, groups, d)) for key in ks[1:3])
    weight = jax.random.normal(ks[3], (1, seq, heads, d))
    return tuple(a.astype(dtype) for a in (q, k, v)), weight.astype(dtype)


def band_of(rule):
    return rule["init_blocks"], rule["window_size"] // rule["block_size"]


def free_columns(table, rule):
    """table [B, G, S, K] -> [B, G, S, K - forced]: each row's ids that
    the rule does not force, ascending, then -1."""
    init, window = band_of(rule)
    own = (jnp.arange(table.shape[2]) // rule["block_size"])[:, None]
    free = (table >= init) & (table <= own - window)
    big = jnp.int32(2 ** 30)
    ids = jnp.sort(jnp.where(free, table, big), axis=-1)
    ids = ids[..., :table.shape[-1] - init - window]
    return jnp.where(ids < big, ids, -1)


def masked_softmax(q, k, v, table, block):
    """Softmax over the keys i <= t in the blocks row t names, float32."""
    s, h, g = q.shape[1], q.shape[2], k.shape[2]
    blocks = s // block
    member = jnp.zeros(table.shape[:3] + (blocks + 1,), bool)
    member = jnp.put_along_axis(
        member, jnp.where(table >= 0, table, blocks), True, axis=-1,
        inplace=False)
    mask = jnp.repeat(member[..., :-1], block, axis=-1)      # [B, G, S, S]
    mask &= jnp.tril(jnp.ones((s, s), bool))
    mask = jnp.repeat(mask, h // g, axis=1)
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    kk, vv = (jnp.repeat(x, h // g, axis=2) for x in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kk,
                        precision="highest") * q.shape[-1] ** -0.5
    p = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vv, precision="highest")


def _both(fn):
    """q, k, v, table, weight -> (loss, its gradient in q, k, v)."""
    loss = lambda q, k, v, table, weight: jnp.sum(
        fn(q, k, v, table).astype(jnp.float32) * weight.astype(jnp.float32))
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))


def ways(module, table, rule):
    """{way: (q, k, v, table -> o, the table it is given)} for the ways a
    kernel file can run the layer."""
    attend = module.block_sparse_attention
    out = {"columns_64": (attend, table),
           "columns_31": (attend, free_columns(table, rule))}
    if "init_blocks" in inspect.signature(attend).parameters:
        init, window = band_of(rule)
        out["band"] = (functools.partial(attend, init_blocks=init,
                                         window_blocks=window), table)
    return out


def agreement(module, shape, rule, seq):
    """{way: {"o": .., "q": .., "k": .., "v": ..}}: bfloat16 kernels
    against the float32 masked softmax of the whole table (the cut
    table's row is a price, not a result: its tokens lack their own
    block)."""
    args, weight = inputs(7, **{**shape, "seq": seq}, dtype=jnp.bfloat16)
    table = module.select_blocks(*args[:2], **rule)
    worst = lambda got, want: float(
        jnp.abs(got.astype(jnp.float32) - want).max() / jnp.abs(want).max())
    plain = functools.partial(masked_softmax, block=rule["block_size"])
    want_o = jax.jit(plain)(*args, table)
    want = _both(plain)(*args, table, weight)[1]
    out = {}
    for way, (fn, given) in ways(module, table, rule).items():
        if way == "columns_31":
            continue
        out[way] = {"o": worst(jax.jit(fn)(*args, given), want_o)}
        out[way].update({n: worst(a, b.astype(jnp.float32))
                         for n, a, b in zip(
                             NAMES, _both(fn)(*args, given, weight)[1], want)})
    return out


def price(module, shape, rule, calls):
    """{way: wall ms a gradient call and, a direction, the device ms an
    event of all its kernels, of the writer alone, and the sum's share of
    the rule's FLOP bound}."""
    from chipbench import peaks, shapes_minicpm_sala, trace as tracelib
    from chipbench.layer_metrics import (sparse_attn_bwd_roofline,
                                         sparse_attn_fwd_roofline)

    args, weight = inputs(11, **shape, dtype=jnp.bfloat16)
    table = jax.block_until_ready(module.select_blocks(*args[:2], **rule))
    z = {"rule": rule, "q_heads": shape["heads"], "head_dim": shape["d"]}
    chip = peaks.peaks_for(jax.devices()[0].device_kind)
    out = {}
    for way, (fn, given) in ways(module, table, rule).items():
        both, given = _both(fn), args + (given, weight)
        row = {"grad_wall_ms": 1e3 * _wall(both, given, calls)}
        with tempfile.TemporaryDirectory() as where:
            with jax.profiler.trace(where):
                for _ in range(3):
                    jax.block_until_ready(both(*given))
            trace = tracelib.load(tracelib.find_xplane(where))
        for name, reader in (("fwd", sparse_attn_fwd_roofline),
                             ("bwd", sparse_attn_bwd_roofline)):
            seconds, _ = tracelib.kernel_seconds(trace, reader.KERNELS)
            alone, passes = tracelib.kernel_seconds(trace, reader.WRITER)
            if not passes:          # a trace without the kernel's events
                continue
            row[f"{name}_ms"] = 1e3 * seconds / passes
            row[f"{name}_writer_ms"] = 1e3 * alone / passes
            row[f"{name}_band_ms"] = 1e3 * (seconds - alone) / passes
            if way != "columns_31":
                bound = getattr(shapes_minicpm_sala,
                                f"sparse_attn_{name}_flops")(
                    shape["seq"], z) / chip.bf16_flops
                row[f"{name}_roofline"] = 100.0 * bound * passes / seconds
        out[way] = row
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--file", action="append", default=[],
                    help="another sparse_attention.py to price as well")
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy size, no times: what a run off the chip may do")
    args = ap.parse_args()

    device = jax.devices()[0]
    on_chip = device.platform == "tpu"
    if not on_chip and not args.rehearse:
        sys.exit("sparse_bench: no chip here (a time comes only from a chip "
                 "run); --rehearse walks the code at a toy size")
    from paddle_tpu.ops import sparse_attention as own

    rows = []
    for label, module in [("repository", own)] + [
            (path, load(path)) for path in args.file]:
        row = {"kernels": label, "device": device.device_kind}
        if on_chip and not args.rehearse:
            row["ms"] = price(module, CELL, RULE, args.calls)
            row["agreement_bf16_2048"] = agreement(module, CELL, RULE, 2048)
        else:
            row["agreement_bf16_toy"] = agreement(module, TOY, TOY_RULE,
                                                  TOY["seq"])
        print(json.dumps(row), flush=True)
        rows.append(row)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "sparse_bench.json"), "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
