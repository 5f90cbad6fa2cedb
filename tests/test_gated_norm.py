"""``ops/gated_norm.py`` (interpret mode, CPU) against the plain form of
the same equations, ``models/qwen3_next.py::gdn_gate``: ``out`` and the
gradients to ``o``, ``z`` and the gain, over two blocks of 256 rows and
five of 64 (two column blocks of three heads), bfloat16 and float32.

Tolerances: kernel and plain form compute in float32 and differ in the
order of sums alone. With bfloat16 inputs both round their results to
bfloat16, so ``out`` may land the other side of a rounding boundary: one
unit in the last place of eight bits, 2^-7 of the entry. A gradient sums
over a head (``d o``) or over every row and head (``d w``): ``d o``,
``d z`` to 1e-2 of the array's largest entry, ``d w`` to 2e-3 of its
norm. With float32 inputs: 1e-5 of the largest entry everywhere."""
import importlib.util
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.layer_metrics import gdn_fwd_roofline
from paddle_tpu.models.qwen3_next import gdn_gate
from paddle_tpu.ops import gated_norm
from paddle_tpu.ops.gated_norm import gated_rms_norm

NAMES = ("out", "d_o", "d_z", "d_w")
D = 128
EPS = 1e-6


def plain(o, z, w):
    """The layer's formula before the kernels: heads apart, float32."""
    b, s, c = o.shape
    return gdn_gate(o.reshape(b, s, c // D, D), z, w, EPS)


def kernels(o, z, w):
    return gated_rms_norm(o, z, w, EPS)


def _make(b, s, heads, dtype, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    o, z, dy = (jax.random.normal(k, (b, s, heads * D)).astype(dtype)
                for k in ks[:3])
    w = (1.0 + 0.1 * jax.random.normal(ks[3], (D,))).astype(dtype)
    return o, z, w, dy


def _results(fn, o, z, w, dy):
    """-> (out, d o, d z, d w) of ``fn``, float32, for the cotangent
    ``dy`` in the inputs' type, as the kernels meet it."""
    out, back = jax.vjp(fn, o, z, w)
    return [np.asarray(a.astype(jnp.float32)) for a in (out, *back(dy))]


# (batch, sequence, heads, type): 512 is two blocks of 256 rows and one of
# four heads; 320 x 6 five blocks of 64 rows by two of three heads
CASES = {
    "two-blocks-bf16": (2, 512, 4, "bfloat16"),
    "five-by-two-blocks-bf16": (1, 320, 6, "bfloat16"),
    "two-blocks": (2, 512, 4, "float32"),
    "five-by-two-blocks": (1, 320, 6, "float32"),
}


@pytest.fixture(scope="module", params=CASES.values(), ids=CASES.keys())
def case(request):
    b, s, heads, dtype = request.param
    args = _make(b, s, heads, jnp.dtype(dtype))
    assert gated_norm._blocks(s, heads * D, D) == {
        512: (256, 512), 320: (64, 384)}[s]
    return {"dtype": dtype, "want": _results(plain, *args),
            "got": _results(kernels, *args)}


@pytest.mark.parametrize("leaf", range(4), ids=NAMES)
def test_the_kernels_are_the_plain_form(case, leaf):
    got, want = case["got"][leaf], case["want"][leaf]
    assert got.shape == want.shape
    gap = np.abs(got - want)
    if case["dtype"] == "float32":
        assert gap.max() <= 1e-5 * np.abs(want).max(), NAMES[leaf]
    elif NAMES[leaf] == "out":
        assert (gap <= 2.0 ** -7 * np.abs(want)).all()
    elif NAMES[leaf] == "d_w":
        assert np.linalg.norm(gap) <= 2e-3 * np.linalg.norm(want)
    else:
        assert gap.max() <= 1e-2 * np.abs(want).max(), NAMES[leaf]


def test_results_come_in_the_inputs_type_and_shapes():
    o, z, w, dy = _make(1, 64, 2, jnp.bfloat16)
    out, back = jax.vjp(kernels, o, z, w)
    assert (out.shape, out.dtype) == (o.shape, o.dtype)
    for grad, of in zip(back(dy), (o, z, w)):
        assert (grad.shape, grad.dtype) == (of.shape, of.dtype)
    # a head of the normed o has a mean square of one: w = 1, z large
    # (silu(z) = z) gives it back
    ones = jnp.ones_like(w)
    n = kernels(o, jnp.full_like(z, 64.0), ones).astype(jnp.float32) / 64.0
    mean_square = np.asarray(jnp.mean(jnp.square(n.reshape(1, 64, 2, D)), -1))
    assert np.abs(mean_square - 1).max() < 2e-2


def test_the_backward_pass_keeps_o_z_and_the_gain_alone():
    """What ``jax.checkpoint`` kept of the plain form: the three inputs
    in their own type, and no float32 of [S, H d]."""
    o, z, w, _ = _make(1, 128, 2, jnp.bfloat16)
    kept = jax.tree_util.tree_leaves(jax.vjp(kernels, o, z, w)[1])
    assert sorted((a.shape, str(a.dtype)) for a in kept) == sorted(
        (a.shape, "bfloat16") for a in (o, z, w))
    assert len(jax.tree_util.tree_leaves(jax.vjp(plain, o, z, w)[1])) > 3


def test_shapes_the_kernels_cannot_take_are_errors():
    o, z, w, _ = _make(1, 128, 2, jnp.float32)
    with pytest.raises(ValueError, match="head of 64"):
        gated_rms_norm(o, z, w[:64], EPS)
    with pytest.raises(ValueError, match="sequence 100"):
        gated_rms_norm(o[:, :100], z[:, :100], w, EPS)
    with pytest.raises(ValueError, match="whole heads"):
        gated_rms_norm(o[..., :192], z[..., :192], w, EPS)
    with pytest.raises(ValueError, match="a gain"):
        gated_rms_norm(o, z[..., :128], w, EPS)
    with pytest.raises(ValueError, match="sequence 100"):
        jax.grad(lambda x: kernels(x, z[:, :100], w).sum())(o[:, :100])


@pytest.mark.parametrize("name", gated_norm.KERNELS)
@pytest.mark.parametrize("way", ["fwd", "bwd"])
def test_the_recurrences_readers_do_not_time_these_kernels(name, way):
    """``gdn_fwd_roofline`` / ``gdn_bwd_roofline`` sum the device time of
    every instruction whose name holds ``gdn_fwd`` / ``gdn_bwd``."""
    assert gated_norm.KERNELS == ("gated_norm_fwd", "gated_norm_bwd")
    assert "gdn_fwd" not in name and "gdn_bwd" not in name
    shown = f"%{name}.1 = (bf16[1,16384,4096]{{2,1,0}}) custom-call("
    for pattern in gdn_fwd_roofline.patterns(way):
        assert not re.search(pattern, shown)


def test_the_bench_script_rehearses_off_the_chip(monkeypatch, tmp_path):
    """``benchmarks/gated_norm_bench.py``: without a chip it times nothing
    — it refuses, or with ``--rehearse`` walks the kernels at a toy size
    and writes their agreement with XLA's form of the same formula."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "gated_norm_bench.py")
    spec = importlib.util.spec_from_file_location("gated_norm_bench", path)
    bench = importlib.util.module_from_spec(spec)
    monkeypatch.setattr(sys, "path", list(sys.path))
    spec.loader.exec_module(bench)
    monkeypatch.chdir(tmp_path)
    monkeypatch.setattr(sys, "argv", ["gated_norm_bench.py"])
    with pytest.raises(SystemExit, match="no chip here"):
        bench.main()
    monkeypatch.setattr(sys, "argv", ["gated_norm_bench.py", "--rehearse"])
    bench.main()
    with open(tmp_path / "chiprun_out" / "gated_norm_bench.json") as fh:
        (row,) = json.load(fh)
    assert row["gate"] == "qwen3next.kernels" and "fwd_wall_ms" not in row
    for dtype, tol in (("bfloat16", 2.0 ** -7), ("float32", 1e-5)):
        gaps = row[f"agreement_{dtype}"]
        assert sorted(gaps) == sorted(NAMES)
        assert all(0 <= gap <= tol for gap in gaps.values())
    # the other two decoders' gates are priced as their layers call them
    assert sorted(bench.xla_forms(2, D)) == [
        "granite.xla", "minicpm_sala.xla", "qwen3next.xla"]
