"""The flash-attention and grouped-matmul kernels compile for the chip the
benchmark runs on.

Interpret mode proves the arithmetic; it cannot see what Mosaic and the
v5e refuse: a slice off the (8, 128) tiling, a block VMEM cannot hold, a
loop form the lowering does not know. The TPU compiler is installed here
and compiles for a chip that is described, not attached
(/opt/skills/guides/on-chip-measurement §2), so every later change of
tile sizes is held to the chip's rules at no chip time. Nothing runs:
these say nothing about results or times.

The topology is described inside a module-scoped fixture (only the
worker that is given this file loads the TPU's library) and everything
that needs it is in this one file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from chipbench.layer_metrics import (flash_bwd_roofline, flash_fwd_roofline,
                                     moe_gmm_roofline)
from paddle_tpu.ops.flash_attention import flash_attention, kernel_names
from paddle_tpu.ops.gated_delta_rule import gated_delta_rule
from paddle_tpu.ops.gated_norm import KERNELS as GATE_KERNELS
from paddle_tpu.ops.gated_norm import gated_rms_norm
from paddle_tpu.ops.gdn_inputs import KERNELS as INPUTS_KERNELS
from paddle_tpu.ops.gdn_inputs import conv_silu_l2norm
from paddle_tpu.ops.grouped_matmul import grouped_matmul

# the patterns the benchmark's flash readers find the kernels by in a
# device trace, whose events are named by the compiled HLO instruction
READERS = (flash_fwd_roofline.KERNEL, flash_bwd_roofline.DQ,
           flash_bwd_roofline.DKV)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # whatever keeps the compiler from a topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep it out of there
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# (sq, sk, q heads, kv heads, d_head, causal, dtype)
SHAPES = {
    # the benchmark's cell, train-1p3b-2k: held whole, 10 tiles unrolled
    "cell-1p3b": (2048, 2048, 16, 16, 128, True, jnp.bfloat16),
    # train-zaya1-6l-4k: GQA 8/2 at 4096, 36 live tiles a head: the loop
    "cell-zaya-4k": (4096, 4096, 8, 2, 128, True, jnp.bfloat16),
    # a 13B shard of train-13b-fleet4 (40 heads over mp 2)
    "fleet-13b-shard": (2048, 2048, 20, 20, 128, True, jnp.bfloat16),
    # chip_smoke.py's kernel cases
    "flash-d128": (2048, 2048, 4, 4, 128, True, jnp.bfloat16),
    "flash-d64-gqa": (2048, 2048, 4, 2, 64, True, jnp.bfloat16),
    "flash-d256": (1024, 1024, 2, 2, 256, True, jnp.bfloat16),
    "flash-cross77": (1024, 77, 2, 2, 64, False, jnp.bfloat16),
    "flash-sq-lt-sk": (512, 2048, 4, 4, 128, True, jnp.bfloat16),
    # the looped schedule at real sizes: a sequence too long to hold, and
    # float32 operands, whose blocks hold half the rows
    "long-8k": (8192, 8192, 2, 2, 128, True, jnp.bfloat16),
    "noncausal-4k": (4096, 4096, 2, 2, 64, False, jnp.bfloat16),
    "float32": (2048, 2048, 2, 2, 128, True, jnp.float32),
    # train-trinity-5l-8k's one full layer: GQA 48/8 at 8192, the loop
    "cell-trinity-full": (8192, 8192, 48, 8, 128, True, jnp.bfloat16),
    # train-qwen3next-4l-16k's one full layer: GQA 16/2 at d 256 and
    # 16,384 keys: the loop, on tiles half as long as d 128's
    "cell-qwen3next-full": (16384, 16384, 16, 2, 256, True, jnp.bfloat16),
    # train-granite4h-10l-16k's one attention layer: GQA 32/8 at d 64 (half
    # the MXU's depth a contraction) and 16,384 keys, the loop
    "cell-granite4h-full": (16384, 16384, 32, 8, 64, True, jnp.bfloat16),
    # train-smallthinker-4l-16k's one full layer: GQA 28/4 (seven query
    # heads a kv head: the backward repeats k and v seven-fold) at 16,384
    "cell-smallthinker-full": (16384, 16384, 28, 4, 128, True, jnp.bfloat16),
}

# (sq, sk, q heads, kv heads, d_head, window, dtype): a sliding window
WINDOWS = {
    # train-trinity-5l-8k's four window layers: 4096 of 8192, the loop
    "cell-trinity-window": (8192, 8192, 48, 8, 128, 4096, jnp.bfloat16),
    # train-smallthinker-4l-16k's three window layers: 4096 of 16,384 at
    # GQA 28/4, the loop
    "cell-smallthinker-window": (16384, 16384, 28, 4, 128, 4096,
                                 jnp.bfloat16),
    # held whole: the window's edge settled when the kernel is traced
    "whole-2k": (2048, 2048, 4, 2, 128, 600, jnp.bfloat16),
    "float32-loop": (4096, 4096, 2, 2, 128, 1000, jnp.float32),
}


@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES.keys())
def test_grad_of_the_kernel_compiles_for_a_v5e(one_chip, shape):
    sq, sk, hq, hkv, d, causal, dtype = shape

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal, None, False)
        return out.astype(jnp.float32).sum()

    q = jax.ShapeDtypeStruct((1, sq, hq, d), dtype, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, sk, hkv, d), dtype, sharding=one_chip)
    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, kv, kv).compile()
    calls = [line.strip().removeprefix("ROOT ")
             for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 3  # forward, dq, dk/dv: one kernel each
    for pattern in READERS:
        assert sum(bool(re.search(pattern, c)) for c in calls) == 1, pattern


@pytest.mark.parametrize("shape", WINDOWS.values(), ids=WINDOWS.keys())
def test_grad_of_the_window_kernels_compiles_for_a_v5e(one_chip, shape):
    sq, sk, hq, hkv, d, window, dtype = shape

    def loss(q, k, v):
        out = flash_attention(q, k, v, True, None, False, window)
        return out.astype(jnp.float32).sum()

    q = jax.ShapeDtypeStruct((1, sq, hq, d), dtype, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, sk, hkv, d), dtype, sharding=one_chip)
    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, kv, kv).compile()
    calls = [line.strip().removeprefix("ROOT ")
             for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 3
    # under names of their own, which the causal readers do not match
    for name in kernel_names(window):
        assert sum(bool(re.search(rf"^%[\w.\-]*{name}[\w.\-]* = ", c))
                   for c in calls) == 1, name
    for pattern in READERS:
        assert not any(re.search(pattern, c) for c in calls), pattern


# (rows, k, n, groups, dtype): both grouped matmuls of a block of
# train-zaya1-6l-4k, chip_smoke.py's cases, and float32 operands
GROUPED = {
    "cell-zaya-gate-up": (4096, 2048, 4096, 16, jnp.bfloat16),
    "cell-zaya-down": (4096, 2048, 2048, 16, jnp.bfloat16),
    "float32": (1024, 512, 1024, 8, jnp.float32),
    # train-trinity-5l-8k: 8 held experts on 32,768 (token, choice) rows,
    # matrices over the 16 MB slot: fetched in column blocks
    "cell-trinity-gate-up": (32768, 3072, 6144, 8, jnp.bfloat16),
    "cell-trinity-down": (32768, 3072, 3072, 8, jnp.bfloat16),
    # ... and on the 4,096 rows a share's bound gives them (PR 33:
    # ``nn.layer.moe.row_bound``), the step's usual path
    "cell-trinity-gate-up-bounded": (4096, 3072, 6144, 8, jnp.bfloat16),
    "cell-trinity-down-bounded": (4096, 3072, 3072, 8, jnp.bfloat16),
    # train-qwen3next-4l-16k: 64 held experts of width 512 on 81,920 rows
    # (PR 34's window, four even shares of 163,840 pairs), ~320 live a
    # group: kept as a length a share's rows may have
    "cell-qwen3next-gate-up": (81920, 2048, 1024, 64, jnp.bfloat16),
    "cell-qwen3next-down": (81920, 512, 2048, 64, jnp.bfloat16),
    # ... and on the 42,240 rows ``row_bound`` gives that cell since PR 35
    # (2.06 even shares: 165 row tiles), the step's usual path; a call
    # past the bound runs the same shapes once more
    "cell-qwen3next-gate-up-window": (42240, 2048, 1024, 64, jnp.bfloat16),
    "cell-qwen3next-down-window": (42240, 512, 2048, 64, jnp.bfloat16),
    # train-smallthinker-4l-16k: 16 held experts of width 768 on a row for
    # every one of 98,304 (token, choice) pairs (a quarter share has no
    # bound), ~1,536 live a group
    "cell-smallthinker-gate-up": (98304, 2560, 1536, 16, jnp.bfloat16),
    "cell-smallthinker-down": (98304, 768, 2560, 16, jnp.bfloat16),
}


@pytest.mark.parametrize("shape", GROUPED.values(), ids=GROUPED.keys())
def test_grad_of_the_grouped_matmul_compiles_for_a_v5e(one_chip, shape):
    rows, k, n, groups, dtype = shape

    def loss(x, w, sizes):
        out = grouped_matmul(x, w, sizes, False)
        return jnp.sum(jnp.square(out.astype(jnp.float32)))

    x = jax.ShapeDtypeStruct((rows, k), dtype, sharding=one_chip)
    w = jax.ShapeDtypeStruct((groups, k, n), dtype, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((groups,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(jax.grad(loss, (0, 1))).lower(x, w, sizes).compile()
    calls = [line.strip().removeprefix("ROOT ")
             for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    # forward and input gradient are the same kernel, the weight
    # gradient the other
    assert sum(bool(re.search(moe_gmm_roofline.GMM, c)) for c in calls) == 2
    assert sum(bool(re.search(moe_gmm_roofline.TGMM, c)) for c in calls) == 1
    assert len(calls) == 3


# (sequence, key heads, value heads, dtype): the gated delta rule
RECURRENCES = {
    # train-qwen3next-4l-16k's three linear layers: 16 key / 32 value
    # heads of 128 over 16,384 tokens, 128 grid steps of two chunks a key
    # head, its two value heads in one step
    "cell-qwen3next": (16384, 16, 32, jnp.bfloat16),
    "float32": (1024, 2, 4, jnp.float32),
    # one value head a key head: the same body with a group of one
    "group1": (1024, 4, 4, jnp.bfloat16),
}


@pytest.mark.parametrize("shape", RECURRENCES.values(),
                         ids=RECURRENCES.keys())
def test_grad_of_the_gated_delta_rule_compiles_for_a_v5e(one_chip, shape):
    from chipbench.layer_metrics import gdn_bwd_roofline, gdn_fwd_roofline

    s, hk, hv, dtype = shape

    def loss(q, k, v, g, beta):
        out = gated_delta_rule(q, k, v, g, beta, False)
        return out.astype(jnp.float32).sum()

    q = jax.ShapeDtypeStruct((1, s, hk, 128), dtype, sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, s, hv, 128), dtype, sharding=one_chip)
    g = jax.ShapeDtypeStruct((1, s, hv), jnp.float32, sharding=one_chip)
    compiled = jax.jit(jax.grad(loss, (0, 1, 2, 3, 4))).lower(
        q, q, v, g, g).compile()
    calls = [line.strip().removeprefix("ROOT ")
             for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2  # one forward, one backward kernel
    for reader in (gdn_fwd_roofline, gdn_bwd_roofline):
        for pattern in (reader.KERNELS, reader.WRITER):
            assert sum(bool(re.search(pattern, c)) for c in calls) == 1


@pytest.mark.parametrize("shape", RECURRENCES.values(),
                         ids=RECURRENCES.keys())
def test_grad_of_the_recurrences_inputs_compiles_for_a_v5e(one_chip, shape):
    """``ops/gdn_inputs.py`` at the same shapes: the convolution, SiLU
    and norms before the recurrence."""
    s, hk, hv, dtype = shape
    c = (2 * hk + hv) * 128

    def loss(qkv, w):
        return sum(o.astype(jnp.float32).sum()
                   for o in conv_silu_l2norm(qkv, w, hk, hv, 128, 128, False))

    qkv = jax.ShapeDtypeStruct((1, s, c), dtype, sharding=one_chip)
    w = jax.ShapeDtypeStruct((4, c), dtype, sharding=one_chip)
    compiled = jax.jit(jax.value_and_grad(loss, (0, 1))).lower(
        qkv, w).compile()
    calls = [line.strip().removeprefix("ROOT ")
             for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert sorted(re.match(r"%(\w+?)\.\d+ = ", c).group(1) for c in calls) \
        == sorted(INPUTS_KERNELS)


@pytest.mark.parametrize("shape", RECURRENCES.values(),
                         ids=RECURRENCES.keys())
def test_grad_of_the_recurrences_gate_compiles_for_a_v5e(one_chip, shape):
    """``ops/gated_norm.py`` at the same shapes: the norm over each value
    head, its gain and the SiLU gate after the recurrence."""
    s, _, hv, dtype = shape

    def run(o, z, w, dy):
        out, back = jax.vjp(
            lambda *a: gated_rms_norm(*a, 1e-6, False), o, z, w)
        return out, back(dy)

    o = jax.ShapeDtypeStruct((1, s, hv * 128), dtype, sharding=one_chip)
    w = jax.ShapeDtypeStruct((128,), dtype, sharding=one_chip)
    compiled = jax.jit(run).lower(o, o, w, o).compile()
    calls = [line.strip().removeprefix("ROOT ")
             for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert sorted(re.match(r"%(\w+?)\.\d+ = ", c).group(1) for c in calls) \
        == sorted(GATE_KERNELS)


# (sequence, heads, dtype): lightning attention
LIGHTNING = {
    # train-minicpmsala-4l-16k's three lightning layers: 32 heads of 128
    # over 16,384 tokens, 32 grid steps of four chunks a head
    "cell-minicpmsala": (16384, 32, jnp.bfloat16),
    "float32": (1024, 4, jnp.float32),
}


@pytest.mark.parametrize("shape", LIGHTNING.values(), ids=LIGHTNING.keys())
def test_grad_of_lightning_attention_compiles_for_a_v5e(one_chip, shape):
    from chipbench.layer_metrics import (lightning_bwd_roofline,
                                         lightning_fwd_roofline)
    from paddle_tpu.ops.lightning_attention import lightning_attention

    s, h, dtype = shape

    def loss(q, k, v, slopes):
        out = lightning_attention(q, k, v, slopes, None, False)
        return out.astype(jnp.float32).sum()

    q = jax.ShapeDtypeStruct((1, s, h, 128), dtype, sharding=one_chip)
    slopes = jax.ShapeDtypeStruct((h,), jnp.float32, sharding=one_chip)
    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        q, q, q, slopes).compile()
    calls = [line.strip().removeprefix("ROOT ")
             for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2  # one forward, one backward kernel
    for reader in (lightning_fwd_roofline, lightning_bwd_roofline):
        for pattern in (reader.KERNELS, reader.WRITER):
            assert sum(bool(re.search(pattern, c)) for c in calls) == 1


# (sequence, heads, state, dtype): the state-space scan, heads of 64
SCANS = {
    # train-granite4h-10l-16k's nine Mamba layers: 64 heads of 64 over
    # 16,384 tokens, state 128; 32 x 8 grid steps of four chunks
    "cell-granite4h": (16384, 64, 128, jnp.bfloat16),
    "float32": (1024, 4, 128, jnp.float32),
}


@pytest.mark.parametrize("shape", SCANS.values(), ids=SCANS.keys())
def test_grad_of_the_state_space_scan_compiles_for_a_v5e(one_chip, shape):
    from chipbench.layer_metrics import ssd_bwd_roofline, ssd_fwd_roofline
    from paddle_tpu.ops.mamba2_ssd import ssd

    s, h, n, dtype = shape

    def loss(x, dt, a, b, c, d):
        return ssd(x, dt, a, b, c, d, False).astype(jnp.float32).sum()

    def of(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(jax.grad(loss, tuple(range(6)))).lower(
        of((1, s, h * 64), dtype), of((1, s, h)), of((h,)),
        of((1, s, n), dtype), of((1, s, n), dtype), of((h,))).compile()
    calls = [line.strip().removeprefix("ROOT ")
             for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2  # one forward, one backward kernel
    for reader in (ssd_fwd_roofline, ssd_bwd_roofline):
        for pattern in (reader.KERNELS, reader.WRITER):
            assert sum(bool(re.search(pattern, c)) for c in calls) == 1


# (sequence, channels, dtype): the Mamba layers' convolution
CONVS = {
    # train-granite4h-10l-16k: x, B and C side by side, 4096 + 2 x 128
    # channels in 17 column blocks of 256
    "cell-granite4h": (16384, 4352, jnp.bfloat16),
    "float32": (1024, 512, jnp.float32),
}


@pytest.mark.parametrize("shape", CONVS.values(), ids=CONVS.keys())
def test_grad_of_the_convolution_compiles_for_a_v5e(one_chip, shape):
    from chipbench.layer_metrics import (conv_silu_bwd_roofline,
                                         conv_silu_fwd_roofline)
    from paddle_tpu.ops.conv_silu import conv_silu

    s, c, dtype = shape

    def loss(x, w, b):
        return conv_silu(x, w, b, False).astype(jnp.float32).sum()

    def of(shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    # the backward needs no result of the forward: the value keeps it
    compiled = jax.jit(jax.value_and_grad(loss, (0, 1, 2))).lower(
        of((1, s, c)), of((4, c)), of((c,))).compile()
    calls = [line.strip().removeprefix("ROOT ")
             for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2  # one forward, one backward kernel
    for reader in (conv_silu_fwd_roofline, conv_silu_bwd_roofline):
        for pattern in (reader.KERNELS, reader.WRITER):
            assert sum(bool(re.search(pattern, c)) for c in calls) == 1


# (sequence, query heads, kv groups, blocks a token, dtype, the rule's
# band: first blocks and window blocks forced, or none): block-sparse
# attention
SPARSE = {
    # train-minicpmsala-4l-16k's one sparse layer: 32 / 2 heads of 128,
    # 64 blocks a token of the 256; a group's K, V (4 MB each) and their
    # float32 gradients (8 MB each) resident in VMEM — as the cell runs
    # it, with the published rule's band (1 first block, a window of 32),
    # and on any table
    "cell-minicpmsala": (16384, 32, 2, 64, jnp.bfloat16, (1, 32)),
    "cell-minicpmsala-any-table": (16384, 32, 2, 64, jnp.bfloat16, None),
    "short": (2048, 32, 2, 8, jnp.bfloat16, None),
}


@pytest.mark.parametrize("shape", SPARSE.values(), ids=SPARSE.keys())
def test_grad_of_block_sparse_attention_compiles_for_a_v5e(one_chip, shape):
    from chipbench.layer_metrics import (sparse_attn_bwd_roofline,
                                         sparse_attn_fwd_roofline)
    from paddle_tpu.ops.sparse_attention import block_sparse_attention

    s, h, g, picks, dtype, band = shape

    def loss(q, k, v, table):
        out = block_sparse_attention(q, k, v, table, False, *(band or ()))
        return out.astype(jnp.float32).sum()

    q = jax.ShapeDtypeStruct((1, s, h, 128), dtype, sharding=one_chip)
    k = jax.ShapeDtypeStruct((1, s, g, 128), dtype, sharding=one_chip)
    table = jax.ShapeDtypeStruct((1, g, s, picks), jnp.int32,
                                 sharding=one_chip)
    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        q, k, k, table).compile()
    text = compiled.as_text()
    calls = [line.strip().removeprefix("ROOT ")
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    # one forward, one backward kernel, and with a band its pass each way
    assert len(calls) == (4 if band else 2)
    for reader in (sparse_attn_fwd_roofline, sparse_attn_bwd_roofline):
        found = {pattern: sum(bool(re.search(pattern, c)) for c in calls)
                 for pattern in (reader.KERNELS, reader.WRITER)}
        assert found == {reader.KERNELS: 2 if band else 1, reader.WRITER: 1}
    # work proportional to the table: no [S, S] array anywhere
    assert not re.search(rf"\[(\d+,)*{s},{s}\]", text)


def test_the_selection_compiles_for_a_v5e_without_its_scores_in_hbm(one_chip):
    """``select_blocks`` at the cell's shapes: no [S, 32, S / 16] array
    (2.1 GB in float32) in the compiled program, whose temporaries stay
    under a chunk of queries' worth."""
    from paddle_tpu.ops.sparse_attention import select_blocks

    s = 16384
    q = jax.ShapeDtypeStruct((1, s, 32, 128), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((1, s, 2, 128), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(lambda q, k: select_blocks(q, k)).lower(q, k).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 256 * 2 ** 20
    assert not re.search(rf"\[(\d+,)*{s},(\d+,)*1023\]", compiled.as_text())


def test_the_expected_change_keeps_its_rounding_on_a_v5e(one_chip):
    """``families/smallthinker.py::_rounded_change_norms`` at the cell's
    embedding: the first write's stored value is a ``reduce-precision`` in
    the program the TPU's compiler leaves (a pair of converts it elides
    inside the fusion, which read ``embed/wte`` 16% off on the chip)."""
    from chipbench.families import smallthinker

    leaf = {"wte": jax.ShapeDtypeStruct((37984, 2560), jnp.float32,
                                        sharding=one_chip)}
    text = smallthinker._rounded_change_norms.lower(
        leaf, leaf, leaf).compile().as_text()
    assert "reduce-precision(" in text
