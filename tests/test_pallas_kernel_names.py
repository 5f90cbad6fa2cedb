"""The Mosaic ``kernel_name`` of each kernel the package owns, as the
TPU lowering writes it, against the names ``chip_smoke.py`` requires in
the programs that ran and the patterns the benchmark's flash readers
match in a device trace. Lowered for the TPU from the CPU, so a rename of
a ``pl.pallas_call`` cannot pass tier-1 and break only on the chip."""
import re

import jax
import jax.numpy as jnp
import pytest

import chip_smoke
from chipbench.layer_metrics import (flash_bwd_roofline, flash_fwd_roofline,
                                     moe_gmm_roofline)
from paddle_tpu.ops import flash_attention as flash
from paddle_tpu.ops import (conv_silu, gated_delta_rule, gated_norm,
                            gdn_inputs, grouped_matmul, lightning_attention,
                            mamba2_ssd, sparse_attention)


def _lowered_for_tpu(fn, *args):
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()


def _flash_program():
    def loss(q, k, v):
        return flash.flash_attention(
            q, k, v, True, None, False).astype(jnp.float32).sum()

    q = jax.ShapeDtypeStruct((1, 256, 4, 128), jnp.bfloat16)
    return _lowered_for_tpu(jax.grad(loss, (0, 1, 2)), q, q, q)


def _window_program():
    def loss(q, k, v):
        return flash.flash_attention(
            q, k, v, True, None, False, 100).astype(jnp.float32).sum()

    q = jax.ShapeDtypeStruct((1, 256, 4, 128), jnp.bfloat16)
    return _lowered_for_tpu(jax.grad(loss, (0, 1, 2)), q, q, q)


def _grouped_matmul_program():
    def loss(x, w, sizes):
        out = grouped_matmul.grouped_matmul(x, w, sizes, False)
        return jnp.sum(jnp.square(out.astype(jnp.float32)))

    x = jax.ShapeDtypeStruct((256, 128), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((4, 128, 128), jnp.bfloat16)
    sizes = jax.ShapeDtypeStruct((4,), jnp.int32)
    return _lowered_for_tpu(jax.grad(loss, (0, 1)), x, w, sizes)


def _gated_delta_rule_program():
    def loss(q, k, v, g, beta):
        return gated_delta_rule.gated_delta_rule(
            q, k, v, g, beta, False).astype(jnp.float32).sum()

    q = jax.ShapeDtypeStruct((1, 256, 2, 128), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 256, 4, 128), jnp.bfloat16)
    g = jax.ShapeDtypeStruct((1, 256, 4), jnp.float32)
    return _lowered_for_tpu(jax.grad(loss, (0, 1, 2, 3, 4)), q, q, v, g, g)


def _gdn_inputs_program():
    def loss(qkv, w):
        return sum(o.astype(jnp.float32).sum() for o in
                   gdn_inputs.conv_silu_l2norm(qkv, w, 2, 4, 128, 128, False))

    qkv = jax.ShapeDtypeStruct((1, 256, 1024), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((4, 1024), jnp.bfloat16)
    # the backward needs no result of the forward: the value keeps it
    return _lowered_for_tpu(jax.value_and_grad(loss, (0, 1)), qkv, w)


def _gated_norm_program():
    def run(o, z, w, dy):
        out, back = jax.vjp(
            lambda *a: gated_norm.gated_rms_norm(*a, 1e-6, False), o, z, w)
        return out, back(dy)

    o = jax.ShapeDtypeStruct((1, 256, 512), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((128,), jnp.bfloat16)
    return _lowered_for_tpu(run, o, o, w, o)


def _lightning_program():
    def loss(q, k, v, slopes):
        return lightning_attention.lightning_attention(
            q, k, v, slopes, None, False).astype(jnp.float32).sum()

    q = jax.ShapeDtypeStruct((1, 256, 4, 128), jnp.bfloat16)
    slopes = jax.ShapeDtypeStruct((4,), jnp.float32)
    return _lowered_for_tpu(jax.grad(loss, (0, 1, 2)), q, q, q, slopes)


def _sparse_attention_program():
    def loss(q, k, v, table):
        return sparse_attention.block_sparse_attention(
            q, k, v, table, False).astype(jnp.float32).sum()

    q = jax.ShapeDtypeStruct((1, 256, 32, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 256, 2, 128), jnp.bfloat16)
    table = jax.ShapeDtypeStruct((1, 2, 256, 4), jnp.int32)
    return _lowered_for_tpu(jax.grad(loss, (0, 1, 2)), q, k, k, table)


def _sparse_band_program():
    """The same with the rule that made the table: 4 blocks of keys, 1
    first block and a window of 2 forced, one free column."""
    def loss(q, k, v, table):
        return sparse_attention.block_sparse_attention(
            q, k, v, table, False, 1, 2).astype(jnp.float32).sum()

    q = jax.ShapeDtypeStruct((1, 256, 32, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 256, 2, 128), jnp.bfloat16)
    table = jax.ShapeDtypeStruct((1, 2, 256, 4), jnp.int32)
    return _lowered_for_tpu(jax.grad(loss, (0, 1, 2)), q, k, k, table)


def _ssd_program():
    def loss(x, dt, a, b, c, d):
        return mamba2_ssd.ssd(x, dt, a, b, c, d,
                              False).astype(jnp.float32).sum()

    x = jax.ShapeDtypeStruct((1, 256, 4 * 64), jnp.bfloat16)
    dt = jax.ShapeDtypeStruct((1, 256, 4), jnp.float32)
    a = jax.ShapeDtypeStruct((4,), jnp.float32)
    b = jax.ShapeDtypeStruct((1, 256, 128), jnp.bfloat16)
    return _lowered_for_tpu(jax.grad(loss, (0, 1, 2, 3, 4, 5)),
                            x, dt, a, b, b, a)


def _conv_silu_program():
    def loss(x, w, b):
        return conv_silu.conv_silu(x, w, b, False).astype(jnp.float32).sum()

    x = jax.ShapeDtypeStruct((1, 256, 512), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((4, 512), jnp.bfloat16)
    b = jax.ShapeDtypeStruct((512,), jnp.bfloat16)
    # the backward needs no result of the forward: the value keeps it
    return _lowered_for_tpu(jax.value_and_grad(loss, (0, 1, 2)), x, w, b)


@pytest.mark.parametrize("program, wanted", [
    (_flash_program, chip_smoke.FLASH_KERNELS),
    (_grouped_matmul_program, chip_smoke.MOE_KERNELS),
    (_window_program, flash.kernel_names(100)),
    (_gated_delta_rule_program, gated_delta_rule.KERNELS),
    (_gdn_inputs_program, gdn_inputs.KERNELS),
    (_gated_norm_program, gated_norm.KERNELS),
    (_lightning_program, lightning_attention.KERNELS),
    (_sparse_attention_program, sparse_attention.KERNELS),
    (_sparse_band_program,
     sparse_attention.KERNELS + sparse_attention.BAND_KERNELS),
    (_ssd_program, mamba2_ssd.KERNELS),
    (_conv_silu_program, conv_silu.KERNELS),
], ids=["flash", "grouped_matmul", "flash_window", "gated_delta_rule",
        "gdn_inputs", "gated_norm", "lightning_attention", "sparse_attention",
        "sparse_attention_band", "mamba2_ssd", "conv_silu"])
def test_kernel_names_are_the_ones_chip_smoke_requires(program, wanted):
    have = chip_smoke.kernels_in(program())
    assert have == sorted(wanted)


def test_flash_readers_patterns_match_the_kernel_names():
    # the trace shows a kernel as its HLO instruction, named after the
    # pallas_call inside jax's transforms (PERF.md §3, chip runs of PR 24)
    shown = {
        "flash_fwd": "%jvp_flash_fwd_.12 = ",
        "flash_bwd_dq": "%transpose_jvp_flash_bwd_dq__.3 = ",
        "flash_bwd_dkv": "%transpose_jvp_flash_bwd_dkv__.4 = ",
    }
    assert sorted(shown) == sorted(chip_smoke.FLASH_KERNELS)
    patterns = {"flash_fwd": flash_fwd_roofline.KERNEL,
                "flash_bwd_dq": flash_bwd_roofline.DQ,
                "flash_bwd_dkv": flash_bwd_roofline.DKV}
    for kernel, pattern in patterns.items():
        hits = [k for k, text in shown.items() if re.search(pattern, text)]
        assert hits == [kernel]


def _load_reader(name):
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(moe_gmm_roofline.__file__),
                        name + ".py")
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_window_calls_have_names_the_causal_readers_do_not_match():
    """A call with a window runs under kernel names of its own: the three
    causal patterns match none of them (so ``flash_*_roofline.gqa`` keeps
    reading causal work for causal events), and the window readers'
    patterns match each its own and no causal kernel."""
    assert flash.kernel_names() == chip_smoke.FLASH_KERNELS
    window = flash.kernel_names(4096)
    assert window == ("flash_window_fwd", "flash_window_bwd_dq",
                      "flash_window_bwd_dkv")
    shown = {name: f"%{name}.{i} = bf16[1,48,8192,128]{{3,2,1,0}} custom-call("
             for i, name in enumerate(chip_smoke.FLASH_KERNELS + window)}
    fwd = _load_reader("flash_fwd_roofline.window")
    bwd = _load_reader("flash_bwd_roofline.window")
    patterns = {"flash_fwd": flash_fwd_roofline.KERNEL,
                "flash_bwd_dq": flash_bwd_roofline.DQ,
                "flash_bwd_dkv": flash_bwd_roofline.DKV,
                "flash_window_fwd": fwd.KERNEL,
                "flash_window_bwd_dq": bwd.DQ,
                "flash_window_bwd_dkv": bwd.DKV}
    for kernel, pattern in patterns.items():
        hits = [k for k, text in shown.items() if re.search(pattern, text)]
        assert hits == [kernel], (kernel, hits)


def test_grouped_matmul_readers_patterns_match_the_kernel_names():
    # the launchers are jitted, so the trace shows the pallas_call's own
    # name: %moe_gmm.<n> for the forward AND the input gradient (the same
    # kernel), %moe_tgmm.<n> for the weight gradient
    shown = {"moe_gmm": "%moe_gmm.7 = bf16[4096,4096]{1,0} custom-call(",
             "moe_tgmm": "%moe_tgmm.2 = bf16[16,2048,4096]{2,1,0} custom-call("}
    assert sorted(shown) == sorted(chip_smoke.MOE_KERNELS)
    patterns = {"moe_gmm": moe_gmm_roofline.GMM,
                "moe_tgmm": moe_gmm_roofline.TGMM}
    for kernel, pattern in patterns.items():
        hits = [k for k, text in shown.items() if re.search(pattern, text)]
        assert hits == [kernel]
    # a fusion that only USES a kernel's result is not the kernel
    user = "%fusion.9 = bf16[4096,2048]{1,0} fusion(bf16[4096,4096] %moe_gmm.7)"
    assert not any(re.search(p, user) for p in patterns.values())


def test_gated_delta_rule_readers_patterns_match_the_kernel_names():
    """Every forward kernel's name starts ``gdn_fwd``, every backward
    one's ``gdn_bwd`` (the launchers are jitted: the trace shows the
    pallas_call's own name), and each reader finds its direction's
    kernels, and as passes the one that writes the result."""
    fwd = _load_reader("gdn_fwd_roofline")
    bwd = _load_reader("gdn_bwd_roofline")
    assert gated_delta_rule.KERNELS == ("gdn_fwd", "gdn_bwd")
    shown = {name: f"%{name}.{i} = (bf16[1,16384,4096]{{2,1,0}}) custom-call("
             for i, name in enumerate(gated_delta_rule.KERNELS)}
    for reader, kernel in ((fwd, "gdn_fwd"), (bwd, "gdn_bwd")):
        for pattern in (reader.KERNELS, reader.WRITER):
            hits = [k for k, text in shown.items()
                    if re.search(pattern, text)]
            assert hits == [kernel], (kernel, pattern)
        # a later helper kernel of the same direction counts in the time
        # and is no pass
        helper = f"%{kernel}_states.4 = f32[32,256,128,128]{{3,2,1,0}} "\
            "custom-call("
        assert re.search(reader.KERNELS, helper)
        assert not re.search(reader.WRITER, helper)
    other = chip_smoke.FLASH_KERNELS + chip_smoke.MOE_KERNELS
    for name in other:
        text = f"%{name}.1 = bf16[8]{{0}} custom-call("
        assert not any(re.search(p, text) for r in (fwd, bwd)
                       for p in (r.KERNELS, r.WRITER))


@pytest.mark.parametrize("module, stem", [
    (lightning_attention, "lightning"), (sparse_attention, "sparse_attn"),
    (mamba2_ssd, "ssd"), (conv_silu, "conv_silu")],
    ids=["lightning", "sparse_attn", "ssd", "conv_silu"])
def test_minicpm_salas_readers_patterns_match_the_kernel_names(module, stem):
    """Every forward kernel's name starts ``<stem>_fwd``, every backward
    one's ``<stem>_bwd``, and each reader finds its direction's kernels,
    and as passes the one that writes the result."""
    assert module.KERNELS == (f"{stem}_fwd", f"{stem}_bwd")
    readers = {k: _load_reader(f"{k}_roofline") for k in module.KERNELS}
    shown = {name: f"%{name}.{i} = (bf16[1,16384,4096]{{2,1,0}}) custom-call("
             for i, name in enumerate(module.KERNELS)}
    for kernel, reader in readers.items():
        for pattern in (reader.KERNELS, reader.WRITER):
            hits = [k for k, text in shown.items()
                    if re.search(pattern, text)]
            assert hits == [kernel], (kernel, pattern)
        helper = f"%{kernel}_dkv.4 = f32[2,16384,128]{{2,1,0}} custom-call("
        assert re.search(reader.KERNELS, helper)
        assert not re.search(reader.WRITER, helper)
    other = (chip_smoke.FLASH_KERNELS + chip_smoke.MOE_KERNELS
             + gated_delta_rule.KERNELS + gdn_inputs.KERNELS
             + gated_norm.KERNELS
             + tuple(k for m in (lightning_attention, sparse_attention,
                                 mamba2_ssd, conv_silu) if m is not module
                     for k in m.KERNELS))
    for name in other:
        text = f"%{name}.1 = bf16[8]{{0}} custom-call("
        assert not any(re.search(p, text) for r in readers.values()
                       for p in (r.KERNELS, r.WRITER))


@pytest.mark.parametrize("way", range(2), ids=["fwd", "bwd"])
def test_the_band_kernels_count_in_their_readers_time_and_are_no_pass(way):
    """The band program shows two events a direction: the reader's
    ``KERNELS`` pattern finds both (their time is the layer's), its
    ``WRITER`` the one that writes ``o`` / ``dq`` (a pass), and neither
    the other direction's."""
    assert sparse_attention.KERNELS == ("sparse_attn_fwd", "sparse_attn_bwd")
    assert sparse_attention.BAND_KERNELS == ("sparse_attn_fwd_band",
                                             "sparse_attn_bwd_band")
    reader = _load_reader(sparse_attention.KERNELS[way] + "_roofline")
    shown = {name: f"%{name}.{i} = (f32[1,2,262144,128]{{3,2,1,0}}) "
             "custom-call("
             for i, name in enumerate(sparse_attention.KERNELS
                                      + sparse_attention.BAND_KERNELS)}
    hits = lambda pattern: [k for k, text in shown.items()
                            if re.search(pattern, text)]
    assert hits(reader.KERNELS) == [sparse_attention.KERNELS[way],
                                    sparse_attention.BAND_KERNELS[way]]
    assert hits(reader.WRITER) == [sparse_attention.KERNELS[way]]
