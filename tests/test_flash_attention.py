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
from paddle_tpu.ops.flash_attention import flash_attention


def _naive(q, k, v, causal):
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

        monkeypatch.setattr(kernel_module, "_interpret_default",
                            lambda: False)
        q = jax.ShapeDtypeStruct(
            (1, 256, 4, 128), jnp.bfloat16,
            sharding=NamedSharding(mesh, P(None, None, "mp", None)))
        text = jax.jit(jax.grad(self._loss, (0, 1, 2))).trace(q, q, q).lower(
            lowering_platforms=("tpu",)).as_text()
        assert text.count("tpu_custom_call") == 3  # fwd, dq, dkv
