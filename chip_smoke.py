#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

Drives the main path once, through the entry points a user calls, at the
full width of GPT-3 13B (``GPTConfig.gpt3_13b()``: h=5120, 40 heads x
128, ff 20480, vocab 50304) cut by depth only, weights random from a
seed, in ONE process on ONE chip:

  kernels  every Pallas kernel the package owns or calls, compiled by
           Mosaic at the shapes in use and checked against its
           ``jax.numpy`` reference: flash fwd/dq/dkv (D = 64/128/256,
           GQA, Sk=77 cross-attention), jax's paged decode kernel
           (ratio 1 with 32 and 64 pages per compute block, ratio 8
           with 8, bf16 and int8 pools); the grouped matmul (forward,
           input and weight gradient, uneven groups with an empty one)
           and one ZAYA1 block at its published widths against its
           float32 reference;
  trainer  ``jit.to_static(step, layers=[model], optimizers=[opt])`` with
           the cells' optimizer settings (bf16 params, masterless
           stochastic rounding, bf16 moments), 2 layers, B x S = 1 x 2048:
           loss finite, first loss ~ ln(vocab), loss falls, flash
           fwd/dq/dkv in the program that ran, 0 compilations in the
           steady window;
  server   ``ContinuousBatchingEngine`` over the paged pool
           (block_size=64, chunked prefill, ragged tables), 4 layers:
           every request returns the tokens asked for, the paged Pallas
           kernel is in the decode program that ran, 0 compilations in
           the steady wave, and logits through the cache agree with the
           plain full forward.

The trainer is freed before the server is built. Any phase that fails
makes the exit code non-zero (later phases still run, so one chip call
reports everything that is broken). Without an accelerator the script
exits non-zero and prints no result. Timings printed here are health
information, not benchmark numbers.

    python chip_smoke.py             # one chip
    python chip_smoke.py --fleet     # the four chips of one host: the same
                                     # train step through fleet.init
                                     # (sharding 2 x mp 2) +
                                     # group_sharded_parallel("p_g_os") at a
                                     # depth one chip cannot hold
    python chip_smoke.py --tiny-cpu  # the same phases at toy size on the
                                     # CPU with kernels interpreted: what
                                     # tier-1 runs, and what to debug with
                                     # before spending chip time

Last line of stdout on success: ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import glob
import importlib.metadata
import json
import math
import os
import shutil
import sys
import time
import traceback

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
IR_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke_ir")

PLATFORM = "?"
FAILED: list = []


def say(msg: str) -> None:
    print(f"[smoke] platform={PLATFORM} {msg}", flush=True)


def run_phase(name: str, fn, *args) -> None:
    """Run one phase; a failure is printed in full, recorded, and makes
    the exit code non-zero — it is never reported as skipped."""
    t0 = time.perf_counter()
    try:
        fn(*args)
        say(f"phase={name} ok wall_s={time.perf_counter() - t0:.1f}")
    except Exception:  # noqa: BLE001 — boundary: record, report, go on
        traceback.print_exc()
        sys.stderr.flush()
        say(f"phase={name} FAILED wall_s={time.perf_counter() - t0:.1f}")
        FAILED.append(name)
    gc.collect()


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# ---------------------------------------------------------------------------
# sizes: the chip runs published widths cut by depth; tiny is the CPU
# debugging size behind --tiny-cpu
# ---------------------------------------------------------------------------

CHIP = dict(
    train_layers=2, train_batch=1, seq=2048, steady_steps=4,
    serve_layers=4, max_batch=8, max_len=2048, block_size=64,
    num_blocks=256, prefill_chunk=256,
    prompts=(23, 64, 200, 331, 513, 40), new_tokens=(16, 8, 12, 8, 8, 24),
    logit_prompt=96, logit_decode=8,
    fleet_layers=6, fleet_shared_layers=2,
)
TINY = dict(
    train_layers=2, train_batch=2, seq=32, steady_steps=3,
    serve_layers=2, max_batch=2, max_len=64, block_size=8,
    num_blocks=16, prefill_chunk=8,
    prompts=(5, 19, 8), new_tokens=(5, 4, 6),
    logit_prompt=12, logit_decode=4,
    fleet_layers=2, fleet_shared_layers=2,
)


def gpt_config(tiny: bool, layers: int):
    from paddle_tpu.models import GPTConfig

    base = GPTConfig.tiny() if tiny else GPTConfig.gpt3_13b()
    return dataclasses.replace(base, num_hidden_layers=layers)


# ---------------------------------------------------------------------------
# instruments
# ---------------------------------------------------------------------------


def cache_verdicts() -> dict:
    """Persistent-compile-cache hits and misses: the program's own compile
    spans (``paddle_tpu/obs/compile.py``, the one ``jax.monitoring``
    listener) counted by their ``cache``.

    ``misses`` is every compile that asked the cache and found nothing
    (not jax's ``cache_misses`` event, which fires only when an entry is
    WRITTEN). jax stores no program that compiled in under
    ``jax_persistent_cache_min_compile_time_secs`` (1 s here; the
    benchmark's harness sets 0), so such a program misses in every run:
    ``unstored`` counts those among the misses, and a warm smoke reads
    ``misses == unstored``, not ``misses=0``. ``dropped`` says how many
    events the bounded ring (8,192 over the whole smoke) has let go of;
    while it is 0 the counts are whole."""
    import jax

    from paddle_tpu import obs

    ring = obs.ring()
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    compiles = [e for e in ring.dump()
                if e["name"] in ("to_static.compile", "xla.compile")]
    hits = sum(e["args"].get("cache") == "hit" for e in compiles)
    missed = [e["dur"] for e in compiles if e["args"].get("cache") == "miss"]
    return {"hits": hits, "misses": len(missed),
            "unstored": sum(d < floor for d in missed),
            "dropped": ring.n_dropped}


def memory_line(tag: str) -> dict:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    if stats:
        say(f"memory {tag} peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
            f"bytes_in_use={stats.get('bytes_in_use')} "
            f"bytes_limit={stats.get('bytes_limit')}")
    else:
        say(f"memory {tag} memory_stats=unavailable")
    return stats


# the Mosaic kernel_name of each of the package's own kernels: the ``name``
# its pl.pallas_call carries (tests/test_pallas_kernel_names.py lowers them
# for the TPU and holds the two together)
# ops/flash_attention.py
FLASH_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
MOE_KERNELS = ("moe_gmm", "moe_tgmm")   # ops/grouped_matmul.py


def kernels_in(text: str) -> list:
    """Names of the Mosaic kernels a lowered module carries."""
    import re

    return sorted(set(re.findall(r'kernel_name\s*=\s*"([^"]+)"', text)))


def program_that_ran(name: str) -> str:
    """StableHLO text of the newest module jax handed to the compiler for
    ``jit(name)`` — jax dumps every lowering to IR_DIR, so this is the
    program that ran, not a re-trace."""
    files = glob.glob(
        os.path.join(IR_DIR, f"jax_ir*_jit_{name}_compile.mlir"))
    check(bool(files), f"no dumped module for jit({name}) under {IR_DIR}")
    newest = max(files, key=lambda f: int(
        os.path.basename(f)[len("jax_ir"):].split("_", 1)[0]))
    with open(newest, encoding="utf-8") as fh:
        return fh.read()


def require_kernels(program, wanted, where: str) -> None:
    """On the chip the named Pallas kernels must be custom calls of the
    program (``program()`` returns its StableHLO text); in the CPU test
    mode there is nothing to prove (kernels are interpreted or a jnp
    path is taken)."""
    if PLATFORM != "tpu":
        say(f"{where} kernels_in_program=n/a (cpu test mode)")
        return
    text = program()
    have = kernels_in(text)
    say(f"{where} tpu_custom_calls={text.count('tpu_custom_call')} "
        f"kernels={have}")
    for k in wanted:
        check(k in have, f"{where}: kernel {k!r} is not in the program "
                         f"(has {have})")


def compiled_with_kernels(fn, args, wanted, where):
    """Compile ``fn`` once, require ``wanted`` among that program's
    custom calls, and hand back the executable to run."""
    import jax

    lowered = jax.jit(fn).lower(*args)
    require_kernels(lowered.as_text, wanted, where)
    return lowered.compile()


# ---------------------------------------------------------------------------
# phase: environment
# ---------------------------------------------------------------------------


def phase_env(cache_dir: str) -> None:
    import jax
    import jaxlib

    import paddle_tpu as paddle
    from paddle_tpu.ops.pallas_common import interpret_default

    dev = jax.devices()[0]
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    say(f"device_kind={dev.device_kind!r} count={len(jax.devices())} "
        f"jax={jax.__version__} jaxlib={jaxlib.__version__} libtpu={libtpu}")
    say(f"compile_cache_dir={cache_dir}")
    say(f"paddle.get_device()={paddle.get_device()}")
    interpret = interpret_default()
    say(f"interpret flash={interpret}")
    if PLATFORM == "tpu":
        from paddle_tpu.device.peaks import chip_peaks

        chip_peaks(dev)  # unknown device_kind = error
        check(paddle.get_device() == "tpu:0",
              f"paddle.get_device() is {paddle.get_device()!r}, not 'tpu:0'")
        check(not interpret,
              "Pallas kernels default to interpret mode on the chip")


# ---------------------------------------------------------------------------
# phase: kernels vs their jax.numpy references
# ---------------------------------------------------------------------------

# bf16 keeps 8 significant bits (2**-8 = 0.4% per rounding). Each flash
# output passes through a handful of bf16 roundings the f32 reference
# does not make (p and ds cast for the MXU, the stored result), and the
# paged kernel adds the bf16 q*scale: agree to 2% of the reference's
# largest magnitude. A kernel computing the wrong thing (mask off by one
# block, a dropped page) is off by tens of percent.
KERNEL_TOL = 2e-2


def _close(got, ref, what: str) -> None:
    import jax.numpy as jnp

    got = np.asarray(jnp.asarray(got, jnp.float32))
    ref = np.asarray(jnp.asarray(ref, jnp.float32))
    check(got.shape == ref.shape, f"{what}: shape {got.shape} != {ref.shape}")
    check(bool(np.isfinite(got).all()), f"{what}: non-finite values")
    scale = float(np.abs(ref).max())
    err = float(np.abs(got - ref).max())
    say(f"{what} max_abs_err={err:.3e} ref_max={scale:.3e} "
        f"err/ref_max={err / scale:.2e}")
    check(err <= KERNEL_TOL * scale,
          f"{what}: err {err:.3e} > {KERNEL_TOL} * ref_max {scale:.3e}")


def kernel_flash(sq, sk, hq, hkv, d, causal):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn.functional.attention import _naive_attention
    from paddle_tpu.ops.flash_attention import flash_attention

    ks = jax.random.split(jax.random.key(sq + sk + d), 4)
    q = jax.random.normal(ks[0], (1, sq, hq, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, sk, hkv, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, sk, hkv, d), jnp.bfloat16)
    do = jax.random.normal(ks[3], (1, sq, hq, d), jnp.bfloat16)

    def kernel(q, k, v, do):
        out, vjp = jax.vjp(lambda *a: flash_attention(*a, causal), q, k, v)
        return (out,) + vjp(do)

    def reference(q, k, v, do):
        f32 = [x.astype(jnp.float32) for x in (q, k, v)]
        with jax.default_matmul_precision("highest"):
            out, vjp = jax.vjp(
                lambda *a: _naive_attention(*a, None, 0.0, causal, None, None),
                *f32)
            return (out,) + vjp(do.astype(jnp.float32))

    tag = f"flash sq={sq} sk={sk} hq={hq} hkv={hkv} d={d} causal={causal}"
    got = compiled_with_kernels(
        kernel, (q, k, v, do),
        FLASH_KERNELS, tag)(q, k, v, do)
    ref = jax.jit(reference)(q, k, v, do)
    for g, r, name in zip(got, ref, ("out", "dq", "dk", "dv")):
        _close(g, r, f"{tag} {name}")


def kernel_paged(h, kvh, pages_per_seq, want_pages, int8, bs=64, d=128):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn.functional.attention import _naive_attention
    from paddle_tpu.ops import paged_attention as pa

    b = 4
    max_len = pages_per_seq * bs
    ratio = h // kvh
    check(pa._ratio_aware_pages_per_block(pages_per_seq, ratio) == want_pages,
          "pages per compute block is not the case this check names")
    num_blocks = b * pages_per_seq
    rng = np.random.RandomState(h * 7 + pages_per_seq)
    # ragged tables: every sequence's pages scattered over the pool
    tables = jnp.asarray(
        rng.permutation(num_blocks).reshape(b, pages_per_seq), jnp.int32)
    ks = jax.random.split(jax.random.key(h + kvh + pages_per_seq), 3)
    kk = jax.random.normal(ks[0], (b, max_len, kvh, d), jnp.bfloat16)
    vv = jax.random.normal(ks[1], (b, max_len, kvh, d), jnp.bfloat16)
    q = jax.random.normal(ks[2], (b, 1, h, d), jnp.bfloat16)
    pool_dt = jnp.int8 if int8 else jnp.bfloat16
    pools = [jnp.zeros((kvh, num_blocks, bs, d), pool_dt) for _ in range(2)]
    scales = ([jnp.zeros((kvh, num_blocks, bs), jnp.float32)
               for _ in range(2)] if int8 else [None, None])
    # fill through the engine's own write path (quantising if int8)
    written = jax.jit(
        lambda kk, vv, kp, vp, ks_, vs_: pa.paged_write_kv(
            kk, vv, kp, vp, tables, jnp.zeros((b,), jnp.int32), max_len,
            k_scale=ks_, v_scale=vs_))(kk, vv, *pools, *scales)
    k_pool, v_pool = written[0], written[1]
    k_scale, v_scale = (written[2], written[3]) if int8 else (None, None)
    cache_len = jnp.asarray([5, max_len // 3, max_len // 2 + 7, max_len - 1],
                            jnp.int32)

    def kernel(q, kp, vp, ks_, vs_):
        return pa.paged_decode_attention(
            q, kp, vp, tables, cache_len, contiguous=False,
            k_scale=ks_, v_scale=vs_)

    def reference(q, kp, vp, ks_, vs_):
        kc, vc = pa.paged_gather_kv(kp, vp, tables, k_scale=ks_, v_scale=vs_,
                                    out_dtype=jnp.float32)
        mask = (jnp.arange(max_len)[None, :]
                <= cache_len[:, None])[:, None, None, :]
        with jax.default_matmul_precision("highest"):
            return _naive_attention(
                q.astype(jnp.float32), kc.astype(jnp.float32),
                vc.astype(jnp.float32), mask, 0.0, False, None, None)

    tag = (f"paged ratio={ratio} pages_per_block={want_pages} "
           f"pool={'int8' if int8 else 'bf16'}")
    args = (q, k_pool, v_pool, k_scale, v_scale)
    got = compiled_with_kernels(
        kernel, args, ("paged_flash_attention_kernel_inline_seq_dim",),
        tag)(*args)
    _close(got, jax.jit(reference)(*args), tag)


def kernel_grouped_matmul(rows, k, n, groups):
    """``ops.grouped_matmul`` forward, input gradient and weight gradient
    against a float32 loop over the groups; the sizes are uneven, one
    group is empty and the edges fall inside tiles."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.grouped_matmul import grouped_matmul

    rng = np.random.RandomState(rows + k + n)
    share = rng.uniform(0.5, 1.5, groups)
    share[groups // 2] = 0.0
    sizes = rng.multinomial(rows, share / share.sum()).astype(np.int32)
    ks = jax.random.split(jax.random.key(rows + k + n), 3)
    x = jax.random.normal(ks[0], (rows, k), jnp.bfloat16)
    w = jax.random.normal(ks[1], (groups, k, n), jnp.bfloat16)
    dy = jax.random.normal(ks[2], (rows, n), jnp.bfloat16)
    gs = jnp.asarray(sizes)

    def kernel(x, w, dy):
        out, vjp = jax.vjp(lambda x, w: grouped_matmul(x, w, gs), x, w)
        return (out,) + vjp(dy)

    def plain(x, w):
        ends = jnp.cumsum(gs)
        row = jnp.arange(rows)[:, None]
        out = jnp.zeros((rows, n), jnp.float32)
        for e in range(groups):
            own = (row >= ends[e] - gs[e]) & (row < ends[e])
            out = out + jnp.where(own, x @ w[e], 0.0)
        return out

    def reference(x, w, dy):
        f32 = [a.astype(jnp.float32) for a in (x, w, dy)]
        with jax.default_matmul_precision("highest"):
            out, vjp = jax.vjp(plain, *f32[:2])
            return (out,) + vjp(f32[2])

    tag = f"grouped_matmul rows={rows} k={k} n={n} groups={groups}"
    say(f"{tag} sizes={sizes.tolist()}")
    got = compiled_with_kernels(kernel, (x, w, dy), MOE_KERNELS,
                                tag)(x, w, dy)
    ref = jax.jit(reference)(x, w, dy)
    for g, r, name in zip(got, ref, ("out", "dx", "dw")):
        _close(g, r, f"{tag} {name}")


def kernel_zaya_block(tiny: bool):
    """One ZAYA1 decoder block (``models/zaya.py``: compressed
    convolutional attention through the flash kernel, the MLP router, the
    dropless experts through the grouped matmul) in bfloat16 against its
    plain float32 reference on the same seeded weights. Top-1 routing is
    discrete, so the reference is told which expert the program gave each
    token and computes the rest itself (PERF.md section 2): every row is
    compared, and the tokens the reference would have routed otherwise
    are counted beside."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from chipbench.families import zaya
    from chipbench.harness import ROOT, load_json
    from paddle_tpu.base.tape import no_grad

    name = ("tests/chipbench/configs/toy-zaya.json" if tiny
            else "chipbench/configs/zaya1-8b-d6.json")
    cfg = load_json(os.path.join(ROOT, name))
    cfg = dict(cfg, held=dict(cfg["held"], layers=1))
    seq, seed = (64 if tiny else 4096), 5
    model, _ = zaya._build_model(cfg, seed)
    layer = model.model.layers[0]
    x = jax.random.normal(jax.random.key(seed), (1, seq, cfg["hidden_size"]),
                          jnp.float32).astype(jnp.bfloat16)

    def block(x):
        chosen = []
        with no_grad():
            out = layer(paddle.to_tensor(x), chosen)
        return out._data, chosen[0]._data[0, :, 0]

    tag = f"zaya block seq={seq}"
    wanted = FLASH_KERNELS[:1] + MOE_KERNELS[:1]
    out, ids = compiled_with_kernels(block, (x,), wanted, tag)(x)
    ref = zaya.reference(cfg, seed)
    with jax.default_matmul_precision("highest"):
        want, (own, _) = ref._block(ref.block_params(ref.get, 0),
                                    ref.fixed(0, 0), x[0].astype(jnp.float32),
                                    ids)
    flips = float((np.asarray(ids) != np.asarray(own)).mean())
    say(f"{tag} routing_flip_share={flips:.4f} "
        f"({int(round(flips * seq))} of {seq} tokens)")
    check(flips <= 0.05, f"{tag}: {flips:.3f} of the tokens went to another "
                         "expert than the float32 reference's")
    _close(out[0].astype(jnp.float32), want,
           f"{tag} out (the reference following the program's routing)")


def phase_kernels(tiny: bool) -> None:
    if tiny:  # interpreted kernels at the smallest legal tiles
        cases = [("flash", kernel_flash, (128, 128, 2, 1, 64, True)),
                 ("moe-gmm", kernel_grouped_matmul, (256, 128, 128, 4)),
                 ("zaya-block", kernel_zaya_block, (True,))]
    else:
        cases = [
            # the train step's shape, then the other head dims
            # _use_pallas admits, GQA, and cross-attention Sk=77 (no
            # block divides it: the tiling takes the whole axis)
            ("flash-d128", kernel_flash, (2048, 2048, 4, 4, 128, True)),
            ("flash-d64-gqa", kernel_flash, (2048, 2048, 4, 2, 64, True)),
            ("flash-d256", kernel_flash, (1024, 1024, 2, 2, 256, True)),
            ("flash-cross77", kernel_flash, (1024, 77, 2, 2, 64, False)),
            # the causal schedule's off > 0 branch (sq < sk), and the
            # benchmark cell's own shape and head count
            ("flash-sq-lt-sk", kernel_flash, (512, 2048, 4, 4, 128, True)),
            ("flash-cell-h16", kernel_flash, (2048, 2048, 16, 16, 128, True)),
            # the routed cell (train-zaya1-6l-4k): GQA 8/2 at 4096 on the
            # looped schedule, both grouped matmuls of a block, one block
            ("flash-zaya-4k", kernel_flash, (4096, 4096, 8, 2, 128, True)),
            ("moe-gmm-gate-up", kernel_grouped_matmul, (4096, 2048, 4096, 16)),
            ("moe-gmm-down", kernel_grouped_matmul, (4096, 2048, 2048, 16)),
            ("zaya-block", kernel_zaya_block, (False,)),
            ("paged-r1-p32", kernel_paged, (8, 8, 32, 32, False)),
            ("paged-r1-p64", kernel_paged, (8, 8, 64, 64, False)),
            ("paged-r8-p8", kernel_paged, (16, 2, 32, 8, False)),
            ("paged-r1-p32-int8", kernel_paged, (8, 8, 32, 32, True)),
            ("paged-r1-p64-int8", kernel_paged, (8, 8, 64, 64, True)),
            ("paged-r8-p8-int8", kernel_paged, (16, 2, 32, 8, True)),
        ]
    for name, fn, args in cases:
        run_phase(f"kernels/{name}", fn, *args)


# ---------------------------------------------------------------------------
# phase: trainer
# ---------------------------------------------------------------------------


def build_train_step(model):
    """The cells' train step and optimizer settings
    (chipbench/configs/*.json ``optimizer``): bf16 params, masterless
    stochastic rounding, bf16 moments."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as popt
    from paddle_tpu.tensor import manipulation as M

    opt = popt.AdamW(
        learning_rate=1e-4, parameters=model.parameters(),
        multi_precision=False, use_stochastic_rounding=True,
        moment_dtype="bfloat16")

    def step(ids, labels):
        logits = model(ids)
        b, s, v = logits.shape
        loss = F.cross_entropy(
            M.reshape(logits, [b * s, v]), M.reshape(labels, [b * s]))
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    return opt, paddle.jit.to_static(step, layers=[model], optimizers=[opt])


def seeded_batch(cfg, batch, seq):
    import paddle_tpu as paddle

    ids = np.random.RandomState(0).randint(0, cfg.vocab_size, (batch, seq))
    labels = np.roll(ids, -1, axis=1)  # next-token targets
    return (paddle.to_tensor(ids.astype("int32")),
            paddle.to_tensor(labels.astype("int32")))


def timed_steps(compiled, ids, labels, n):
    """n steps, each timed around block_until_ready, under a guard that
    counts every compilation in the window."""
    import jax

    from paddle_tpu.analysis import recompile_guard

    losses, times = [], []
    with recompile_guard() as guard:
        for _ in range(n):
            t0 = time.perf_counter()
            loss = compiled(ids, labels)
            jax.block_until_ready(loss._data)
            times.append(time.perf_counter() - t0)
            losses.append(float(loss))
    return losses, times, guard


def check_losses(losses, vocab: int, where: str) -> None:
    say(f"{where} losses={[round(v, 4) for v in losses]} "
        f"ln_vocab={math.log(vocab):.4f}")
    check(all(math.isfinite(v) for v in losses), f"{where}: loss not finite")
    check(abs(losses[0] - math.log(vocab)) < 0.5,
          f"{where}: first loss {losses[0]:.3f} is not ~ ln(vocab)")
    check(losses[-1] < losses[0], f"{where}: loss did not fall")


def phase_trainer(tiny: bool, size: dict) -> None:
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM

    cfg = gpt_config(tiny, size["train_layers"])
    t0 = time.perf_counter()
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    model.bfloat16()
    opt, compiled = build_train_step(model)
    ids, labels = seeded_batch(cfg, size["train_batch"], size["seq"])
    say(f"trainer config h={cfg.hidden_size} heads={cfg.num_attention_heads} "
        f"ff={cfg.intermediate_size} vocab={cfg.vocab_size} "
        f"layers={cfg.num_hidden_layers} params={model.num_params()} "
        f"batch={size['train_batch']} seq={size['seq']}")
    if PLATFORM == "tpu":
        check(all(d.platform == "tpu" for p in model.parameters()
                  for d in p._data.devices()), "a parameter is not on the TPU")
    # set-up: the first call traces without optimizer state, the second
    # retraces with it; both compile
    warm = []
    for _ in range(2):
        loss = compiled(ids, labels)
        jax.block_until_ready(loss._data)
        warm.append(float(loss))
    setup_s = time.perf_counter() - t0
    losses, times, guard = timed_steps(compiled, ids, labels,
                                       size["steady_steps"])
    say(f"trainer setup_s={setup_s:.1f} (build + 2 compiling calls)")
    say(f"trainer steady compiles_in_window={guard.count()} "
        f"steps={len(times)}"
        + (f" step_ms_median={1e3 * sorted(times)[len(times) // 2]:.1f}"
           if PLATFORM == "tpu" else ""))
    check(guard.count() == 0,
          f"trainer: compiled in the steady window: {guard.names()}")
    check_losses(warm + losses, cfg.vocab_size, "trainer")
    require_kernels(
        lambda: program_that_ran("pure"), FLASH_KERNELS, "trainer step")
    memory_line("after trainer")


def phase_free() -> None:
    """The first half is gone from the chip before the second is built."""
    import jax

    jax.clear_caches()
    gc.collect()
    stats = memory_line("after freeing the trainer")
    if stats:
        check(stats["bytes_in_use"] < 1 << 30,
              "the trainer was not freed before the server")


# ---------------------------------------------------------------------------
# phase: server
# ---------------------------------------------------------------------------

# Logits through the cache vs the plain forward: both are bf16 programs
# over the same weights whose attention differs only in summation order
# and in the decode kernel's bf16 q*scale. The logits are O(1) bf16
# values (ulp 2**-8 relative), each the end of ~10 bf16 roundings per
# layer: agree to 2% of the reference's largest |logit| (measured on
# the chip, PR 21: 0.5%). A cache bug (a wrong page, an off-by-one
# length) changes logits by their own size.
LOGIT_TOL = 2e-2


def ragged_tables(batch, max_len, block_size, seed=0):
    per_seq = -(-max_len // block_size)
    perm = np.random.RandomState(seed).permutation(batch * per_seq)
    return perm.reshape(batch, per_seq).astype(np.int32), batch * per_seq


def cache_vs_full_forward(model, cfg, size: dict) -> None:
    """Prefill then token-by-token decode through a ragged paged cache —
    the calls the engine's programs make — against one plain forward."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.base.tape import no_grad

    p_len, n_dec = size["logit_prompt"], size["logit_decode"]
    ids = np.random.RandomState(5).randint(
        0, cfg.vocab_size, (1, p_len + n_dec)).astype("int32")
    tables, nblocks = ragged_tables(1, size["max_len"], size["block_size"])
    with no_grad():
        full = model(paddle.to_tensor(ids))._data.astype(jnp.float32)
        caches = model.init_cache(
            1, size["max_len"], block_size=size["block_size"],
            num_blocks=nblocks, tables=tables)
        if PLATFORM == "tpu":
            check(all(d.platform == "tpu"
                      for d in caches[0].k_pool._data.devices()),
                  "the KV pool is not on the TPU")
        logits, caches = model.forward_with_cache(
            paddle.to_tensor(ids[:, :p_len]), caches,
            paddle.to_tensor(np.asarray(0, np.int32)))
        got = [logits._data.astype(jnp.float32)]
        for i in range(n_dec):
            logits, caches = model.forward_with_cache(
                paddle.to_tensor(ids[:, p_len + i:p_len + i + 1]), caches,
                paddle.to_tensor(np.asarray(p_len + i, np.int32)))
            got.append(logits._data.astype(jnp.float32))
    got = np.asarray(jnp.concatenate(got, axis=1))
    full = np.asarray(full)
    scale = float(np.abs(full).max())
    err_prefill = float(np.abs(got[:, :p_len] - full[:, :p_len]).max())
    err_decode = float(np.abs(got[:, p_len:] - full[:, p_len:]).max())
    say(f"server logits cache-vs-full: prefill_err={err_prefill:.3e} "
        f"decode_err={err_decode:.3e} ref_max={scale:.3e} "
        f"ref_std={float(full.std()):.3e} tol={LOGIT_TOL}*ref_max")
    check(bool(np.isfinite(got).all()), "cache logits not finite")
    check(max(err_prefill, err_decode) <= LOGIT_TOL * scale,
          "logits through the cache disagree with the plain forward")
    return full


def engine_tokens_vs_forward(model, cfg, req) -> None:
    """The engine's tokens for one short request, judged on LOGITS: under
    the plain forward over prompt + its own output, each emitted token's
    logit is within tolerance of that position's maximum (random weights
    flip near-ties, so equality of argmax is not the test)."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.base.tape import no_grad

    p_len = len(req.prompt)
    seq = np.concatenate([req.prompt, np.asarray(req.out[:-1], np.int32)])
    with no_grad():
        logits = np.asarray(model(paddle.to_tensor(
            seq[None].astype("int32")))._data.astype(jnp.float32))[0]
    rows = logits[p_len - 1:]
    gap = rows.max(axis=-1) - rows[np.arange(len(req.out)), req.out]
    scale = float(np.abs(rows).max())
    say(f"server engine-vs-forward req={req.req_id!r} "
        f"max_logit_gap={float(gap.max()):.3e} "
        f"exact_argmax={int((gap == 0).sum())}/{len(req.out)} "
        f"tol={LOGIT_TOL}*ref_max={LOGIT_TOL * scale:.3e}")
    check(float(gap.max()) <= LOGIT_TOL * scale,
          "an engine token is not a near-argmax of the plain forward")


def phase_server(tiny: bool, size: dict) -> None:
    import paddle_tpu as paddle
    from paddle_tpu.analysis import recompile_guard
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.models import GPTForCausalLM

    cfg = gpt_config(tiny, size["serve_layers"])
    t0 = time.perf_counter()
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    model.bfloat16()
    model.eval()
    say(f"server config h={cfg.hidden_size} heads={cfg.num_attention_heads} "
        f"layers={cfg.num_hidden_layers} params={model.num_params()} "
        f"max_batch={size['max_batch']} max_len={size['max_len']} "
        f"block_size={size['block_size']} num_blocks={size['num_blocks']} "
        f"prefill_chunk={size['prefill_chunk']}")
    eng = ContinuousBatchingEngine(
        model, max_batch=size["max_batch"], max_len=size["max_len"],
        block_size=size["block_size"], num_blocks=size["num_blocks"],
        prefill_chunk=size["prefill_chunk"])

    def wave(tag, seed):
        rng = np.random.RandomState(seed)
        for i, (n, new) in enumerate(zip(size["prompts"], size["new_tokens"])):
            eng.add_request(f"{tag}{i}", rng.randint(0, cfg.vocab_size, (n,)),
                            max_new_tokens=new)
        done = eng.run()
        for i, new in enumerate(size["new_tokens"]):
            req = done[f"{tag}{i}"]
            check(req.status == "ok" and len(req.out) == new,
                  f"request {tag}{i}: status={req.status} "
                  f"tokens={len(req.out)}/{new}")
            check(all(0 <= t < cfg.vocab_size for t in req.out),
                  f"request {tag}{i}: token out of range")
        return done

    done = wave("warm", 1)  # compiles prefill + decode
    setup_s = time.perf_counter() - t0
    steps0, tokens0 = eng.steps, eng.decode_tokens
    t1 = time.perf_counter()
    with recompile_guard() as guard:
        done = wave("steady", 2)
    steady_s = time.perf_counter() - t1
    say(f"server setup_s={setup_s:.1f} (build + first wave, compiling)")
    say(f"server steady compiles_in_window={guard.count()} "
        f"requests={len(size['prompts'])} steps={eng.steps - steps0} "
        f"decode_tokens={eng.decode_tokens - tokens0}"
        + (f" wall_s={steady_s:.2f}" if PLATFORM == "tpu" else ""))
    check(guard.count() == 0,
          f"server: compiled in the steady wave: {guard.names()}")
    require_kernels(lambda: program_that_ran("decode"),
                    ("paged_flash_attention_kernel_inline_seq_dim",),
                    "server decode program")
    if PLATFORM == "tpu":
        check(all(d.platform == "tpu" for pool in eng._pools
                  for d in pool[0].devices()),
              "an engine KV pool is not on the TPU")
    engine_tokens_vs_forward(model, cfg, done["steady0"])
    cache_vs_full_forward(model, cfg, size)
    memory_line("after server")


# ---------------------------------------------------------------------------
# --fleet: the train step on the four chips of the host
# ---------------------------------------------------------------------------


def fleet_train(cfg, size, steps, sharded: bool):
    """(losses, model, opt) for ``steps`` train steps: on the default
    device alone, or through fleet.distributed_model (mp) +
    group_sharded_parallel("p_g_os") over the mesh fleet.init built."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.sharding import group_sharded_parallel
    from paddle_tpu.models import GPTForCausalLM

    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    model.bfloat16()
    if sharded:
        fleet.distributed_model(model)  # places every parameter over mp
    opt, compiled = build_train_step(model)
    if sharded:
        group_sharded_parallel(model, opt, "p_g_os")
    ids, labels = seeded_batch(cfg, size["train_batch"], size["seq"])
    losses = []
    for _ in range(steps):
        loss = compiled(ids, labels)
        jax.block_until_ready(loss._data)
        losses.append(float(loss))
    return losses, model, opt


def phase_fleet(tiny: bool, size: dict) -> None:
    import jax

    from paddle_tpu.distributed import fleet

    devices = jax.devices()
    check(len(devices) >= 4, f"--fleet needs 4 devices, found {len(devices)}")
    steps = 4

    def release():
        jax.clear_caches()
        gc.collect()
        if PLATFORM == "tpu":
            say("fleet bytes_in_use_per_device="
                f"{[d.memory_stats()['bytes_in_use'] for d in devices[:4]]}")

    shared = gpt_config(tiny, size["fleet_shared_layers"])

    # 1. a depth one chip holds, on one chip (before any mesh exists)
    t0 = time.perf_counter()
    one, model, opt = fleet_train(shared, size, steps, sharded=False)
    say(f"fleet one-chip layers={shared.num_hidden_layers} "
        f"losses={[round(v, 4) for v in one]} "
        f"wall_s={time.perf_counter() - t0:.1f}")
    del model, opt
    release()

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"sharding_degree": 2, "mp_degree": 2}
    hcg = fleet.init(strategy=strategy)
    say(f"fleet mesh={dict(hcg.mesh.shape)}")

    # 2. the same depth, seed and batch over the four chips
    t0 = time.perf_counter()
    four, model, opt = fleet_train(shared, size, steps, sharded=True)
    say(f"fleet four-chip layers={shared.num_hidden_layers} "
        f"losses={[round(v, 4) for v in four]} "
        f"wall_s={time.perf_counter() - t0:.1f}")
    # the same bf16 program up to the order of cross-chip reductions
    # (partial matmul sums meet in bf16), on a trajectory that drops by
    # ~1.5 a step: agree to 0.5% of each loss. A wrong partition spec
    # or a misplaced shard is off by O(1).
    check(np.allclose(four, one, rtol=5e-3, atol=0),
          f"four-chip losses {four} != one-chip losses {one}")
    del model, opt
    release()

    # 3. a depth one chip cannot hold
    deep = gpt_config(tiny, size["fleet_layers"])
    t0 = time.perf_counter()
    losses, model, opt = fleet_train(deep, size, steps, sharded=True)
    check_losses(losses, deep.vocab_size, "fleet deep")
    params = list(model.parameters())
    n_params = sum(p.size for p in params)
    param_bytes = sum(p._data.nbytes for p in params)
    state_bytes = param_bytes + sum(
        a.nbytes for store in opt._accumulators.values()
        for a in store.values())
    say(f"fleet deep layers={deep.num_hidden_layers} params={n_params} "
        f"state_bytes={state_bytes} (+{param_bytes} of gradients inside a "
        f"step) wall_s={time.perf_counter() - t0:.1f}")
    mesh_devices = set(hcg.mesh.devices.flat)
    quarter = 0
    for p in params:
        a = p._data
        check(set(a.sharding.device_set) == mesh_devices,
              f"{p.name}: not placed on the four mesh devices")
        shard = a.addressable_shards[0].data
        if shard.size * 4 == a.size:
            quarter += a.size
    say(f"fleet deep params_on_4_devices=all "
        f"quarter_sharded_fraction={quarter / n_params:.4f}")
    # the position table and the 1-D parameters carry no tp_axis and so
    # split over the sharding axis only (two ways); every matrix — over
    # 99% of the elements at 13B widths — must be a quarter per chip
    check(quarter / n_params > 0.9,
          "under 90% of the parameter elements are split four ways")
    if PLATFORM == "tpu":
        check(all(d.platform == "tpu" for d in mesh_devices),
              "the mesh is not four TPU devices")
        in_use = [d.memory_stats()["bytes_in_use"] for d in devices[:4]]
        say(f"fleet deep bytes_in_use_per_device={in_use} "
            f"state_bytes/4={state_bytes // 4}")
        for b in in_use:
            check(0.8 * state_bytes / 4 <= b <= 1.5 * state_bytes / 4,
                  "per-device memory is not near a quarter of the state")
        # parameters + moments + the gradients a step materialises (one
        # per parameter): what "everything on the first chip" must hold
        limit = devices[0].memory_stats()["bytes_limit"]
        say(f"fleet deep one_chip_need={state_bytes + param_bytes} "
            f"bytes_limit={limit}")
        check(state_bytes + param_bytes > limit,
              "the deep model would fit one chip: no proof of sharding")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    global PLATFORM
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny-cpu", action="store_true",
                    help="toy sizes on the CPU, kernels interpreted")
    ap.add_argument("--fleet", action="store_true",
                    help="the four-chip sharded train step instead of the "
                         "one-chip phases")
    args = ap.parse_args(argv)

    import jax

    if args.tiny_cpu:
        jax.config.update("jax_platforms", "cpu")
        if args.fleet and "xla_force_host_platform_device_count" \
                not in os.environ.get("XLA_FLAGS", ""):
            jax.config.update("jax_num_cpu_devices", 4)
    dev = jax.devices()[0]
    PLATFORM = dev.platform
    if not args.tiny_cpu and PLATFORM != "tpu":
        print(f"chip_smoke.py: no accelerator (jax platform is "
              f"{PLATFORM!r}); pass --tiny-cpu for the CPU test mode",
              file=sys.stderr)
        return 1

    from paddle_tpu.utils.compile_cache import enable_compile_cache

    if args.tiny_cpu:
        cache_dir = "off (cpu test mode)"
    else:
        cache_dir = enable_compile_cache()
        shutil.rmtree(IR_DIR, ignore_errors=True)
        jax.config.update("jax_dump_ir_to", IR_DIR)
    size = TINY if args.tiny_cpu else CHIP

    t0 = time.perf_counter()
    run_phase("env", phase_env, cache_dir)
    if args.fleet:
        run_phase("fleet", phase_fleet, args.tiny_cpu, size)
    else:
        phase_kernels(args.tiny_cpu)
        run_phase("trainer", phase_trainer, args.tiny_cpu, size)
        run_phase("free-trainer", phase_free)
        run_phase("server", phase_server, args.tiny_cpu, size)
    memory_line("end")
    cache = cache_verdicts()
    say(f"compile_cache hits={cache['hits']} misses={cache['misses']} "
        f"unstored={cache['unstored']} ring_dropped={cache['dropped']} "
        f"total_wall_s={time.perf_counter() - t0:.1f}")
    if FAILED:
        say(f"FAILED phases: {FAILED}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
