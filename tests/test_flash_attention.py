"""Pallas flash-attention kernel tests (interpret mode on CPU).

Reference pattern: test/legacy_test/test_flash_attention.py — parity
against the naive math implementation across causal/GQA/dtype, forward
and backward, plus the functional dispatch path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.ops import flash_attention as kernels
from paddle_tpu.ops.flash_attention import (
    Sweep, TileCounts, flash_attention, tile_plan)


def _naive(q, k, v, causal, window=None):
    hq, hkv = q.shape[2], k.shape[2]
    qh, kh, vh = [jnp.swapaxes(x, 1, 2) for x in (q, k, v)]
    if hq != hkv:
        kh = jnp.repeat(kh, hq // hkv, axis=1)
        vh = jnp.repeat(vh, hq // hkv, axis=1)
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh).astype(jnp.float32) / np.sqrt(d)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq - window)
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", p, vh), 1, 2)


def _rand(shape, dtype=jnp.float32, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), dtype)


class TestFlashKernel:
    @pytest.mark.parametrize("causal", [False, True])
    def test_fwd_matches_naive(self, causal):
        q = _rand((2, 256, 4, 64), seed=0)
        k = _rand((2, 256, 4, 64), seed=1)
        v = _rand((2, 256, 4, 64), seed=2)
        out = flash_attention(q, k, v, causal, None, True)
        ref = _naive(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_grads_match_naive(self):
        q = _rand((1, 128, 2, 64), seed=0)
        k = _rand((1, 128, 2, 64), seed=1)
        v = _rand((1, 128, 2, 64), seed=2)
        g1 = jax.grad(
            lambda *a: (flash_attention(*a, True, None, True) ** 2).sum(), (0, 1, 2)
        )(q, k, v)
        g2 = jax.grad(lambda *a: (_naive(*a, True) ** 2).sum(), (0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)

    def test_gqa(self):
        q = _rand((2, 128, 8, 64), seed=0)
        k = _rand((2, 128, 2, 64), seed=1)
        v = _rand((2, 128, 2, 64), seed=2)
        out = flash_attention(q, k, v, True, None, True)
        ref = _naive(q, k, v, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
        g1 = jax.grad(
            lambda *a: (flash_attention(*a, True, None, True) ** 2).sum(), (1, 2)
        )(q, k, v)
        g2 = jax.grad(lambda *a: (_naive(*a, True) ** 2).sum(), (1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            assert a.shape == b.shape  # kv-head shaped, reduced over group
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)

    def test_bf16(self):
        q = _rand((1, 128, 2, 64), jnp.bfloat16, seed=0)
        k = _rand((1, 128, 2, 64), jnp.bfloat16, seed=1)
        v = _rand((1, 128, 2, 64), jnp.bfloat16, seed=2)
        out = flash_attention(q, k, v, True, None, True)
        ref = _naive(
            q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32), True
        )
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref), atol=5e-2
        )

    @pytest.mark.parametrize("causal", [False, True])
    def test_cross_attention_lengths(self, causal):
        """sq != sk: causal must use bottom-right alignment (query i sees
        keys <= i + sk - sq), matching the jnp fallback and FA2 — the
        KV-cache decode case."""
        q = _rand((1, 128, 2, 64), seed=0)
        k = _rand((1, 256, 2, 64), seed=1)
        v = _rand((1, 256, 2, 64), seed=2)
        out = flash_attention(q, k, v, causal, None, True)
        ref = _naive(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_under_jit(self):
        q = _rand((1, 128, 2, 64), seed=0)
        f = jax.jit(lambda q: flash_attention(q, q, q, True, None, True))
        out = f(q)
        ref = _naive(q, q, q, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.quick
class TestTileSchedule:
    """The causal tile schedule (PR 25): which tiles run bare, which
    masked, which never — counted per head, and proven against the
    naive path on every branch the schedule has."""

    def test_plan_of_the_benchmark_cell(self):
        # (1, 2048, 16, 128) bf16 causal: the sequence is held whole and
        # its 16 tiles of 512 x 512 are classified when the kernel is
        # traced: 6 below the diagonal, 4 on it, 6 never visited
        whole = Sweep(held=2048, rows=512, fetch=2048, sub=512)
        assert tile_plan(2048, 2048, 128, True) == {
            name: (whole, TileCounts(full=6, masked=4, dead=6))
            for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")}
        assert tile_plan(2048, 2048, 128, False)["flash_fwd"][1] == \
            TileCounts(full=16, masked=0, dead=0)

    def test_plan_with_an_offset(self):
        # sq < sk: query i sees keys <= i + 1536; only the last k tile
        # straddles the diagonal
        plan = tile_plan(512, 2048, 128, True)
        assert plan["flash_fwd"] == (
            Sweep(held=512, rows=512, fetch=2048, sub=512),
            TileCounts(full=3, masked=1, dead=0))
        assert plan["flash_bwd_dkv"] == (
            Sweep(held=2048, rows=512, fetch=512, sub=512),
            TileCounts(full=3, masked=1, dead=0))
        # rectangular tiles, 128 x 512, whose diagonal tiles are partly
        # dead: rows 0..127 see keys <= 767, rows 256..383 keys <= 1023
        assert tile_plan(384, 1024, 64, True)["flash_fwd"] == (
            Sweep(held=384, rows=128, fetch=1024, sub=512),
            TileCounts(full=3, masked=3, dead=0))
        # float32 operands halve the rows a fetched block holds: the
        # sequence is no longer held whole, the counts stay
        assert tile_plan(2048, 2048, 128, True, itemsize=4)["flash_fwd"] == (
            Sweep(held=512, rows=512, fetch=1024, sub=512),
            TileCounts(full=6, masked=4, dead=6))

    @pytest.mark.parametrize("window", [None, 1, 100, 128, 300, 5000])
    @pytest.mark.parametrize("off", [0, 256, -256, 100])
    @pytest.mark.parametrize("over_k", [True, False])
    def test_spans_against_the_rule_itself(self, off, over_k, window):
        """``_spans`` against a brute-force count of visible pairs, on
        ints and on traced scalars alike, with and without a window."""
        rows, sub, n = 256, 128, 8
        traced = jax.jit(lambda first: kernels._spans(
            first, rows, off, sub, n, over_k, window))
        for first in range(0, 1024, rows):
            m0, f0, f1, m1 = kernels._spans(first, rows, off, sub, n, over_k,
                                            window)
            assert tuple(int(x) for x in traced(first)) == (m0, f0, f1, m1)
            assert 0 <= m0 <= f0 <= f1 <= m1 <= n
            for j in range(n):
                held = np.arange(first, first + rows)[:, None]
                swept = np.arange(j * sub, (j + 1) * sub)[None, :]
                q, k = (held, swept) if over_k else (swept, held)
                seen = k <= q + off
                if window is not None:
                    seen &= q + off - k < window
                seen = int(seen.sum())
                kind = ("full" if f0 <= j < f1 else
                        "masked" if m0 <= j < m1 else "dead")
                assert kind == ("full" if seen == rows * sub else
                                "masked" if seen else "dead"), (first, j)

    @pytest.fixture
    def looped(self, monkeypatch):
        """Fetched blocks of 128 rows and nothing unrolled: the loops
        with traced bounds and the clamped index maps, which at these
        small shapes the whole-sequence schedule would bypass."""
        monkeypatch.setattr(kernels, "_FETCH_BYTES", 128 * 64 * 4)
        monkeypatch.setattr(kernels, "_UNROLL", 0)
        self._forget_traces()
        yield
        self._forget_traces()

    @staticmethod
    def _forget_traces():
        # the launchers are jitted and the constants patched above are
        # no part of a trace's key
        kernels._flash_fwd.clear_cache()
        kernels._flash_bwd.clear_cache()

    @staticmethod
    def _parity(sq, sk, hq, hkv, d, causal, window=None):
        q = _rand((1, sq, hq, d), seed=0)
        k = _rand((1, sk, hkv, d), seed=1)
        v = _rand((1, sk, hkv, d), seed=2)
        out = flash_attention(q, k, v, causal, None, True, window)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(_naive(q, k, v, causal, window)),
            atol=2e-5)
        g1 = jax.grad(
            lambda *a: (flash_attention(*a, causal, None, True, window)
                        ** 2).sum(), (0, 1, 2))(q, k, v)
        g2 = jax.grad(
            lambda *a: (_naive(*a, causal, window) ** 2).sum(),
            (0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            assert a.shape == b.shape
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)

    @pytest.mark.parametrize("sq, sk, hq, hkv, d, causal", [
        (256, 512, 2, 2, 64, True),     # sq < sk: off > 0
        (384, 1024, 1, 1, 64, True),    # 128 x 512 tiles, diagonal partly dead
        (1024, 1024, 1, 1, 64, True),   # a dead tile, skipped when traced
        (256, 256, 4, 2, 64, True),     # GQA under the causal mask
        (256, 384, 2, 2, 64, False),    # non-causal, three fetched blocks
        (128, 77, 2, 1, 64, False),     # cross-attention, sk whole
        (256, 256, 1, 1, 256, True),    # D = 256
        (256, 256, 1, 1, 128, True),    # D = 128
        (256, 256, 16, 2, 256, True),   # D = 256 under GQA 16 : 2
    ], ids=["sq<sk", "rect-partly-dead", "dead-tile", "gqa", "noncausal",
            "cross77", "d256", "d128", "d256-gqa16:2"])
    def test_unrolled_schedule_matches_naive(self, sq, sk, hq, hkv, d, causal):
        self._parity(sq, sk, hq, hkv, d, causal)

    @pytest.mark.parametrize("sq, sk, hq, hkv, d, causal", [
        (512, 512, 1, 1, 64, True),     # 512 x 128 tiles: bq != bk
        (256, 512, 2, 1, 64, True),     # off > 0, GQA
        (384, 384, 1, 1, 64, True),     # 128 x 128, dead blocks clamped
        (256, 384, 1, 1, 64, False),    # non-causal through the loop
        (256, 256, 8, 1, 256, True),    # D = 256, GQA 8 : 1, the loop
    ], ids=["rect", "sq<sk-gqa", "dead-blocks", "noncausal", "d256-gqa8"])
    def test_looped_schedule_matches_naive(self, looped, sq, sk, hq, hkv, d,
                                           causal):
        plan = tile_plan(sq, sk, d, causal, itemsize=4)
        assert all(t.held == t.rows and t.fetch == 128
                   for t, _ in plan.values())
        self._parity(sq, sk, hq, hkv, d, causal)

    @pytest.mark.parametrize("sq, sk, hq, hkv, d, window", [
        (512, 512, 2, 2, 64, 128),      # a window of one tile
        (512, 512, 1, 1, 64, 200),      # no multiple of a tile
        (384, 384, 6, 1, 64, 100),      # GQA 6:1, shorter than a tile
        (256, 512, 2, 1, 64, 300),      # off > 0
        (256, 256, 1, 1, 128, 1),       # every query sees itself alone
    ], ids=["tile", "off-tile", "gqa6", "sq<sk", "one"])
    @pytest.mark.parametrize("loop", [False, True], ids=["unrolled", "looped"])
    def test_window_matches_naive(self, request, loop, sq, sk, hq, hkv, d,
                                  window):
        """Forward, dq and dk/dv under a sliding window against plain
        masked attention, held whole and on the loop path."""
        if loop:
            request.getfixturevalue("looped")
        self._parity(sq, sk, hq, hkv, d, True, window)

    def test_a_window_as_long_as_the_sequence_is_causal(self, looped):
        q, k, v = (_rand((1, 384, 2, 64), seed=i) for i in range(3))
        for window in (384, 10_000):
            np.testing.assert_array_equal(
                np.asarray(flash_attention(q, k, v, True, None, True, window)),
                np.asarray(flash_attention(q, k, v, True, None, True)))
        assert tile_plan(384, 384, 64, True, window=384)[
            "flash_window_fwd"][1] == tile_plan(384, 384, 64, True)[
            "flash_fwd"][1]

    def test_window_plan_at_the_8k_cell(self):
        # S = 8192, W = 4096, 512 x 512 tiles on the loop path: a row of
        # tiles has the diagonal tile, 7 bare ones and the window's edge
        plan = tile_plan(8192, 8192, 128, True, window=4096)
        assert set(plan) == {"flash_window_fwd", "flash_window_bwd_dq",
                             "flash_window_bwd_dkv"}
        sweep, counts = plan["flash_window_fwd"]
        assert sweep == Sweep(held=512, rows=512, fetch=2048, sub=512)
        # rows 0-7 of tiles: 1 masked + r bare; rows 8-15: 2 masked + 7
        assert counts == TileCounts(full=sum(range(8)) + 8 * 7,
                                    masked=8 + 16, dead=256 - 84 - 24)
        assert plan["flash_window_bwd_dkv"][1] == counts
        with pytest.raises(ValueError, match="causal"):
            flash_attention(*(jnp.zeros((1, 128, 1, 64)),) * 3, False, None,
                            True, 64)

    @pytest.mark.parametrize("unrolled", [True, False])
    def test_more_queries_than_keys(self, unrolled, monkeypatch):
        """sq > sk under the causal mask (off < 0): the first sq - sk
        queries see no key and emit zeros; the rest are the square case."""
        if not unrolled:
            monkeypatch.setattr(kernels, "_UNROLL", 0)
        self._forget_traces()
        q = _rand((1, 256, 2, 64), seed=0)
        k = _rand((1, 128, 2, 64), seed=1)
        v = _rand((1, 128, 2, 64), seed=2)

        def loss(fn):
            return lambda q, k, v: (fn(q, k, v) ** 2).sum()

        flash = lambda q, k, v: flash_attention(q, k, v, True, None, True)
        seen = lambda q, k, v: _naive(q[:, 128:], k, v, True)
        out = flash(q, k, v)
        assert not np.asarray(out[:, :128]).any()
        np.testing.assert_allclose(
            np.asarray(out[:, 128:]), np.asarray(seen(q, k, v)), atol=2e-5)
        g1 = jax.grad(loss(flash), (0, 1, 2))(q, k, v)
        g2 = jax.grad(loss(seen), (0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)
        self._forget_traces()


class TestFunctionalDispatch:
    def test_sdpa_tensor_api_grads(self):
        qn = np.random.RandomState(0).randn(2, 64, 2, 32).astype(np.float32)
        q = paddle.to_tensor(qn)
        q.stop_gradient = False
        out = F.scaled_dot_product_attention(q, q, q, is_causal=True)
        assert out.shape == [2, 64, 2, 32]
        out.sum().backward()
        assert q.grad is not None
        ref = _naive(jnp.asarray(qn), jnp.asarray(qn), jnp.asarray(qn), True)
        np.testing.assert_allclose(np.asarray(out.numpy()), np.asarray(ref), atol=2e-5)


@pytest.mark.quick
class TestKernelOverFleetMesh:
    """GSPMD cannot partition a Mosaic kernel: inside a multi-device jit
    jax refuses to lower one ("wrap the call in a shard_map"). Found by
    the four-chip bring-up; both halves reproduce on the CPU — the
    numerics in interpret mode on virtual devices, the refusal by
    lowering for the TPU platform from here."""

    @pytest.fixture
    def mesh(self, monkeypatch):
        import paddle_tpu.distributed as dist
        from paddle_tpu.distributed import fleet
        from paddle_tpu.nn.functional import attention

        monkeypatch.setattr(attention, "_use_pallas", lambda *a: True)
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"sharding_degree": 2, "mp_degree": 2}
        yield fleet.init(strategy=strategy).mesh
        dist.destroy_process_group()
        fleet.set_hybrid_communicate_group(None)

    @staticmethod
    def _loss(q, k, v):
        from paddle_tpu.base.tensor import Tensor

        out = F.scaled_dot_product_attention(
            *(Tensor(x, _internal=True) for x in (q, k, v)), is_causal=True)
        return out._data.astype(jnp.float32).sum()

    def test_mapped_kernel_matches_naive(self, mesh):
        # batch 2 splits over sharding, 4 q / 2 kv heads over mp
        q, k = _rand((2, 128, 4, 64), seed=1), _rand((2, 128, 2, 64), seed=2)
        got = jax.jit(jax.value_and_grad(self._loss, (0, 1, 2)))(q, k, k)
        ref = jax.value_and_grad(
            lambda *a: _naive(*a, True).sum(), (0, 1, 2))(q, k, k)
        for g, r in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(ref)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       rtol=1e-4, atol=1e-4)

    def test_lowers_for_tpu_inside_a_multi_device_jit(self, mesh, monkeypatch):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from paddle_tpu.ops import flash_attention as kernel_module

        monkeypatch.setattr(kernel_module, "interpret_default",
                            lambda: False)
        q = jax.ShapeDtypeStruct(
            (1, 256, 4, 128), jnp.bfloat16,
            sharding=NamedSharding(mesh, P(None, None, "mp", None)))
        text = jax.jit(jax.grad(self._loss, (0, 1, 2))).trace(q, q, q).lower(
            lowering_platforms=("tpu",)).as_text()
        assert text.count("tpu_custom_call") == 3  # fwd, dq, dkv
