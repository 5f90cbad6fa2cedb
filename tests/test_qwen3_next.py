"""``models/qwen3_next.py`` (Qwen3NextForCausalLM, training path) against
its plain reference ``chipbench/families/qwen3next_reference.py`` on
seeded weights, at a toy size on the CPU (the gated-delta-rule, flash and
grouped-matmul kernels interpreted): both kinds of mixer, a held share of
the experts, two chunks of the recurrence, and piece by piece what each
part must do — among them that the shares ADD UP.

Tolerances: program and reference both run in float32 here (the
program's model is widened with ``model.float()``), so only the order of
sums differs: logits and loss agree to 1e-5 of their scale, a gradient
leaf to 1e-4 of its largest entry.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from chipbench.families import qwen3next
from chipbench.families import qwen3next_reference as qr
from paddle_tpu import nn
from paddle_tpu.base.tape import no_grad
from paddle_tpu.models import Qwen3NextConfig, Qwen3NextForCausalLM
from paddle_tpu.models import qwen3_next as qmodel

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2**31 + 11


@pytest.fixture(scope="module")
def cfg():
    """The toy configuration (every leaf trained, as the cell's is)."""
    with open(os.path.join(ROOT, "tests", "chipbench", "configs",
                           "toy-qwen3next.json"), encoding="utf-8") as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def ids(cfg):
    rng = np.random.default_rng(0)
    tok = rng.integers(0, cfg["held"]["vocab_rows"], (2, 129)).astype(np.int32)
    return tok[:, :-1], tok[:, 1:]


def _program(cfg):
    model, params = qwen3next._build_model(cfg, SEED)
    model.float()          # the program in float32: only the code differs
    return model, params


@pytest.fixture(scope="module")
def program(cfg):
    return _program(cfg)


@pytest.fixture(scope="module")
def wanted(cfg, ids):
    """The reference's loss and every leaf's gradient."""
    reference = qwen3next.reference(cfg, SEED)
    want = {}
    for group, grads in reference.loss_and_grads(reference.get, *ids):
        want.update({f"{group}/{k}": np.asarray(v) for k, v in grads.items()})
    return reference.loss, want


def _held_to(cfg, params, want):
    names = [f"{leaf[0]}/{leaf[1]}" for leaf in qwen3next.leaves(cfg)]
    assert sorted(names) == sorted(want) and len(names) == len(params)
    for name, p in zip(names, params):
        scale = np.abs(want[name]).max()
        assert scale > 0, name               # every leaf is reached
        assert np.abs(np.asarray(p.grad._data) - want[name]).max() \
            <= 1e-4 * scale, name
    return names


def test_the_toy_holds_both_kinds_of_mixer_and_a_share(cfg):
    z = qwen3next.sizes(cfg)
    # published layers 1-4 of 8: linear, linear, full ((3 + 1) % 4 == 0),
    # linear; experts 2-5 of 8; the decays the seed gives leave between a
    # hundredth and nine tenths of a state after a chunk
    assert z["layer_kinds"] == ["linear_attention", "linear_attention",
                                "full_attention", "linear_attention"]
    assert (z["first_expert"], z["experts"], z["published_experts"]) == \
        (2, 4, 8)
    spec = qwen3next._all_leaves(cfg)
    a_log = [i for i, l in enumerate(spec) if l[1] == "gdn.a_log"]
    rates = np.exp(np.asarray(qwen3next.make_leaf(cfg, SEED, a_log[0]),
                              np.float32))
    assert len(a_log) == 3 and rates.min() < 0.004 and rates.max() > 0.03


def test_logits_agree_with_the_reference(cfg, program, ids):
    model, _ = program
    reference = qwen3next.reference(cfg, SEED)
    with no_grad():
        got = np.asarray(model(paddle.to_tensor(ids[0]))._data)
    want = reference.logits(ids[0])
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
    # ... and the held experts got the rows the reference's routing sends
    # them, the absent experts' pairs none
    counts = np.asarray(model.tokens_per_expert())    # the first forward
    for blk, (chosen, _) in enumerate(reference.routing):
        every = np.bincount(chosen.reshape(-1), minlength=8)
        assert (every[2:6] == counts[blk]).all()
        assert every.sum() == chosen.size == 2 * 128 * 3
        assert 0 < counts[blk].sum() < every.sum()


@pytest.mark.parametrize("recompute", ["none", "mlp", "layer"])
def test_loss_and_every_leafs_gradient_agree(cfg, program, wanted, ids,
                                             recompute):
    """Nothing recomputed, each block's MoE half, each whole block
    (``fleet.utils.recompute``): the reference's loss, its gradient in
    every leaf, and the counters still count (they are written outside
    the recomputed function)."""
    model, params = program if recompute == "none" else _program(
        dict(cfg, training={"recompute": recompute}))
    before = np.asarray(model.pairs_routed())
    loss = model.loss(paddle.to_tensor(ids[0]), paddle.to_tensor(ids[1]))
    loss.backward()
    ref_loss, want = wanted
    assert abs(float(loss) - ref_loss) <= 1e-5 * ref_loss
    names = _held_to(cfg, params, want)
    assert {"h.0/gdn.w_qkvz", "h.0/gdn.conv", "h.0/gdn.a_log",
            "h.0/gdn.dt_bias", "h.0/gdn.norm.g", "h.1/router.w",
            "h.2/wq", "h.2/q_norm.g", "h.2.gu/w", "h.3/shared.gate",
            "head/norm_f.g"} <= set(names)
    assert (np.asarray(model.pairs_routed()) - before == 2 * 128 * 3).all()
    assert np.asarray(model.tokens_per_expert()).sum() > 0


def test_the_gates_kernels_are_the_plain_form_inside_the_model(
        cfg, ids, monkeypatch):
    """The layer has ONE path, ``ops/gated_norm.py``'s kernels; with the
    op swapped for the plain formula (``gdn_gate``, float32 under
    autodiff) the same model gives the same loss and the same gradient in
    every leaf."""
    model, params = _program(cfg)

    def run():
        loss = model.loss(paddle.to_tensor(ids[0]), paddle.to_tensor(ids[1]))
        loss.backward()
        grads = [np.asarray(p.grad._data) for p in params]
        model.clear_gradients()
        return float(loss), grads

    calls = []

    def plain(o, z, w, eps):
        calls.append(o.shape)
        heads = o.shape[-1] // w.shape[0]
        return qmodel.gdn_gate(o.reshape(*o.shape[:2], heads, w.shape[0]),
                               z, w, eps)

    loss, got = run()
    monkeypatch.setattr("paddle_tpu.ops.gated_norm.gated_rms_norm", plain)
    plain_loss, want = run()
    assert len(calls) == 3 and not np.isnan(loss)    # one a linear layer
    assert abs(loss - plain_loss) <= 1e-5 * plain_loss
    for leaf, a, b in zip(qwen3next.leaves(cfg), got, want):
        assert np.abs(a - b).max() <= 1e-4 * np.abs(b).max(), leaf[:2]


def test_two_adamw_steps_through_the_compiled_step(cfg, ids):
    """``jit.to_static`` over model and AdamW (the family's trainer, whole
    blocks recomputed as the cell runs them) in float32: both steps'
    losses, the first gradient's norm in every leaf, both moments and
    every leaf's change after two steps are the reference's, which
    follows the sets the compiled step returned."""
    o = dict(cfg["optimizer"], stochastic_rounding=False,
             moment_dtype="float32")
    toy = dict(cfg, optimizer=o, dtype="float32",
               training={"recompute": "layer"})
    trainer = qwen3next.Trainer(toy, SEED)
    rng = np.random.default_rng(1)
    tok = rng.integers(0, 96, (2, 129)).astype(np.int32)
    batches = [ids, (tok[:, :-1], tok[:, 1:])]
    losses, follow = [], []
    for n, batch in enumerate(batches):
        losses.append(trainer.step(*batch))
        follow.append(trainer.routing())
        if n == 0:
            grads = trainer.grad_norms()
    assert follow[0].shape == (4, 2, 128)
    sets = qwen3next.unpack(follow[1])
    assert sets.shape == (4, 2, 128, 3) and sets.max() < 8
    assert (sets[..., :-1] < sets[..., 1:]).all()
    want = qwen3next.reference_training(toy, SEED, batches, follow=follow)
    for got, ref in zip(losses, want["loss"]):
        assert abs(got - ref) <= 1e-5 * ref
    for name, norms in (("grad_norm", grads),
                        ("moment_norm", trainer.moment_norms()),
                        ("delta_norm", trainer.delta_norms())):
        assert sorted(norms) == sorted(want[name])
        for leaf, got in norms.items():
            # (a handful of numbers whose AdamW step is lr times a ratio
            # of two sums: the decays' rates read 1e-3 to 2e-3)
            assert abs(got - want[name][leaf]) <= 5e-3 * want[name][leaf], \
                (name, leaf)
    counts = np.asarray(trainer.model.tokens_per_expert())
    assert (np.asarray(trainer.model.pairs_routed()) == 2 * 768).all()
    assert counts.shape == (4, 4) and counts.sum() > 0
    assert (np.asarray(trainer.model.calls_in_full()) == 2).all()   # no bound


# -- piece by piece ----------------------------------------------------------


def _moe_weights(seed, h=32, f=16, e=16, t=48):
    ks = jax.random.split(jax.random.key(seed), 9)
    n = lambda k, *s: 0.3 * jax.random.normal(k, s, jnp.float32)
    return {"router.w": n(ks[0], h, e),
            "shared.w1": n(ks[2], h, f), "shared.w3": n(ks[3], h, f),
            "shared.w2": n(ks[4], f, h), "shared.gate": n(ks[8], h, 1),
            "experts.w_gu": n(ks[5], e, h, 2 * f),
            "experts.w_dn": n(ks[6], e, f, h)}, n(ks[7], t, h)


def _share(p, first, held, h=32, f=16, e=16, k=3):
    """The program's routed MLP holding experts first .. first + held,
    given the weights ``p`` names."""
    c = Qwen3NextConfig.tiny(
        hidden_size=h, moe_intermediate_size=f,
        shared_expert_intermediate_size=f, num_experts=e,
        num_experts_per_tok=k, first_expert=first, held_experts=held)
    moe = qmodel.Qwen3NextMoE(c)
    give = {moe.router.weight: p["router.w"],
            moe.shared_expert.gate_proj.weight: p["shared.w1"],
            moe.shared_expert.up_proj.weight: p["shared.w3"],
            moe.shared_expert.down_proj.weight: p["shared.w2"],
            moe.shared_expert_gate.weight: p["shared.gate"],
            moe.experts.w_gu: p["experts.w_gu"][first:first + held],
            moe.experts.w_dn: p["experts.w_dn"][first:first + held]}
    for t, a in give.items():
        t._data = a
    return moe


def _shared(p, m):
    return jax.nn.sigmoid(m @ p["shared.gate"]) * qr.swiglu(
        m, p["shared.w1"], p["shared.w3"], p["shared.w2"], "f32")


@pytest.mark.parametrize("held, e, t", [(2, 16, 48), (16, 16, 48),
                                        (4, 64, 512)],
                         ids=["8-shares", "whole", "16-shares-bounded"])
def test_the_shares_add_up(held, e, t):
    """The routed parts that all shares give, with the gated shared
    expert counted once, are the uncut reference's layer: 8 shares of 2
    experts of 16, three a token (and the one that holds all); 16 shares
    of 4 of 64 at 512 tokens have a bound of 512 rows each on the 1,536
    pairs (5.24 even shares of 96, rounded up to the row tile). The
    cell's bound is 42,240 rows of its 163,840 pairs (2.06 even shares
    of 20,480): no longer at ``row_bound``'s edge (2 x cap <= pairs)."""
    from paddle_tpu.nn.layer.moe import row_bound

    p, m = _moe_weights(5, e=e, t=t)
    assert row_bound(t * 3, held, e) == (512 if t == 512 else None)
    assert row_bound(16384 * 10, 64, 512) == 42240
    with jax.default_matmul_precision("highest"):
        whole, _ = qr.moe(p, m, top_k=3, first=0)
        shared = _shared(p, m)
        total, rows = 0.0, 0
        with no_grad():
            for first in range(0, e, held):
                f, ids, sizes = _share(p, first, held, e=e).compute(
                    paddle.to_tensor(m))
                total = total + (f._data - shared)
                rows += int(sizes._data.sum())
                assert sizes.shape == [held] and ids.shape == [t, 3]
    assert rows == t * 3                  # every pair met exactly one share
    assert float(jnp.abs(total + shared - whole).max()) \
        <= 1e-5 * float(jnp.abs(whole).max())


def test_the_router_is_softmax_then_top_k_and_its_gradient_the_references():
    router = nn.SoftmaxTopKRouter(16, 8, 3)
    m = jax.random.normal(jax.random.key(1), (40, 16))
    w = jax.random.normal(jax.random.key(2), (16, 8))
    weight = jax.random.normal(jax.random.key(3), (40, 3))
    router.weight._data = w
    assert [n for n, _ in router.named_buffers()] == []   # no bias
    x = paddle.to_tensor(m, stop_gradient=False)
    router.weight.stop_gradient = False
    ids0, gates0 = router(x)
    (gates0 * paddle.to_tensor(weight)).sum().backward()
    probs = jax.nn.softmax(m @ w, -1)
    assert ids0.shape == [40, 3] and gates0.dtype == jnp.float32
    best = jax.lax.top_k(probs, 3)[1]
    assert (ids0._data == best).all()                 # best first
    np.testing.assert_allclose(np.asarray(gates0._data.sum(-1)), 1.0,
                               rtol=1e-6)

    def of_reference(m, w):
        _, gates, _, _ = qr.route({"router.w": w}, m, top_k=3,
                                  forced=ids0._data)
        return jnp.sum(gates * weight)

    for got, want in zip((x.grad, router.weight.grad),
                         jax.grad(of_reference, (0, 1))(m, w)):
        assert float(jnp.abs(got._data - want).max()) \
            <= 1e-5 * float(jnp.abs(want).max())
    picked = jnp.take_along_axis(probs, best, -1)
    np.testing.assert_allclose(np.asarray(gates0._data), np.asarray(
        picked / picked.sum(-1, keepdims=True)), rtol=1e-5)
    unnormed = nn.SoftmaxTopKRouter(16, 8, 3, norm_topk_prob=False)
    unnormed.weight._data = w
    with no_grad():
        _, raw = unnormed(paddle.to_tensor(m))
    np.testing.assert_allclose(
        np.asarray(raw._data),
        np.asarray(jnp.take_along_axis(probs, best, -1)), rtol=1e-5)


def test_norms_are_zero_centred():
    norm = qmodel.Qwen3NextRMSNorm(32, 1e-6)
    x = jax.random.normal(jax.random.key(4), (5, 32)) * 3.0
    assert not np.asarray(norm.weight._data).any()    # g starts at 0
    with no_grad():
        unit = norm(paddle.to_tensor(x))._data
        norm.weight._data = jnp.full((32,), -0.5)
        half = norm(paddle.to_tensor(x))._data
    np.testing.assert_allclose(
        np.asarray(jnp.mean(unit * unit, -1)), 1.0, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(half), np.asarray(0.5 * unit),
                               rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(half), np.asarray(qr._norm(x, jnp.full((32,), -0.5), 1e-6)),
        rtol=1e-6)


def test_positions_turn_a_quarter_of_the_head():
    c = Qwen3NextConfig.tiny()
    attn = qmodel.Qwen3NextAttention(c)
    assert (attn.d, attn.rot) == (32, 8)
    q = jax.random.normal(jax.random.key(4), (1, 8, 2, 32))
    turned = qmodel.partial_rope(q, 1e7, 8)
    assert float(jnp.abs(turned[..., 8:] - q[..., 8:]).max()) == 0.0
    assert float(jnp.abs(turned[:, 0] - q[:, 0]).max()) == 0.0   # angle 0
    assert float(jnp.abs(turned[:, 1:, :, :8] - q[:, 1:, :, :8]).max()) > 0.1
    np.testing.assert_allclose(
        np.asarray(jnp.sum(turned * turned, -1)),
        np.asarray(jnp.sum(q * q, -1)), rtol=1e-5)               # a rotation
    np.testing.assert_allclose(np.asarray(turned[0]),
                               np.asarray(qr.rope(q[0], 1e7, 8)), atol=1e-6)


def test_the_stack_is_declared_by_the_published_keys():
    c = Qwen3NextConfig()          # Qwen3-Next-80B-A3B-Instruct's
    assert c.layer_types.count("full_attention") == 12
    assert [i for i, t in enumerate(c.layer_types)
            if t == "full_attention"][:3] == [3, 7, 11]
    with paddle.LazyGuard():
        model = Qwen3NextForCausalLM(Qwen3NextConfig(
            vocab_size=18992, held_layers=4, held_experts=64))
    assert model.model.layer_ids == [0, 1, 2, 3]
    assert [l.layer_type for l in model.model.layers] == \
        ["linear_attention"] * 3 + ["full_attention"]
    shapes = {n: tuple(p._lazy_init[1]) for n, p in model.named_parameters()}
    linear, full = "model.layers.0.linear_attn.", "model.layers.3.self_attn."
    assert shapes[linear + "in_proj_qkvz.weight"] == (2048, 12288)
    assert shapes[linear + "in_proj_ba.weight"] == (2048, 64)
    assert shapes[linear + "conv1d_weight"] == (4, 8192)
    assert shapes[linear + "A_log"] == shapes[linear + "dt_bias"] == (32,)
    assert shapes[full + "q_proj.weight"] == (2048, 8192)
    assert shapes[full + "k_proj.weight"] == (2048, 512)
    assert shapes["model.layers.1.mlp.experts.w_gu"] == (64, 2048, 1024)
    assert shapes["model.layers.1.mlp.router.weight"] == (2048, 512)
    assert shapes["model.layers.1.mlp.shared_expert_gate.weight"] == (2048, 1)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 1_028_320_320
    with pytest.raises(ValueError):
        Qwen3NextConfig(recompute="some")
    with pytest.raises(ValueError):
        Qwen3NextConfig(layer_types=["full_attention"])
