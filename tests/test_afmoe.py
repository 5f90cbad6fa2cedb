"""``models/afmoe.py`` (AfmoeForCausalLM, training path) against its plain
reference ``chipbench/families/afmoe_reference.py`` on seeded weights, at
a toy size on the CPU (the flash and grouped-matmul kernels interpreted):
a window shorter than the sequence, both kinds of attention, both kinds
of MLP, a held share of the experts, and piece by piece what each part
must do — among them that the shares ADD UP.

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
from chipbench.families import afmoe, afmoe_reference as ar
from paddle_tpu import nn
from paddle_tpu.base.tape import no_grad
from paddle_tpu.models import AfmoeConfig, AfmoeForCausalLM
from paddle_tpu.models import afmoe as amodel

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2**31 + 11


@pytest.fixture(scope="module")
def cfg():
    """The toy configuration with nothing frozen (every leaf has a
    gradient to hold to the reference's) and nothing recomputed."""
    with open(os.path.join(ROOT, "tests", "chipbench", "configs",
                           "toy-afmoe.json"), encoding="utf-8") as fh:
        toy = json.load(fh)
    return dict(toy, frozen=[], training={"recompute": "none"})


@pytest.fixture(scope="module")
def ids(cfg):
    rng = np.random.default_rng(0)
    tok = rng.integers(0, cfg["held"]["vocab_rows"], (2, 65)).astype(np.int32)
    return tok[:, :-1], tok[:, 1:]


def _program(cfg):
    model, params = afmoe._build_model(cfg, SEED)
    model.float()          # the program in float32: only the code differs
    return model, params


@pytest.fixture(scope="module")
def program(cfg):
    return _program(cfg)


@pytest.fixture(scope="module")
def reference(cfg):
    return afmoe.reference(cfg, SEED)


@pytest.fixture(scope="module")
def wanted(reference, ids):
    """The reference's loss and every leaf's gradient."""
    want = {}
    for group, grads in reference.loss_and_grads(reference.get, *ids):
        want.update({f"{group}/{k}": np.asarray(v) for k, v in grads.items()})
    return reference.loss, want


def test_the_toy_holds_both_kinds_of_attention_and_of_mlp(cfg):
    z = afmoe.sizes(cfg)
    # published layers 1-4 of 8: dense + window, routed + window, routed +
    # full, routed + window; the window (32) is shorter than the tests'
    # sequence (64); experts 2-5 of 8
    assert z["layer_kinds"] == [(32, False), (32, True), (None, True),
                                (32, True)]
    assert (z["first_expert"], z["experts"], z["published_experts"]) == \
        (2, 4, 8)


def test_logits_agree_with_the_reference(program, reference, ids):
    model, _ = program
    before = np.asarray(model.tokens_per_expert())
    with no_grad():
        got = np.asarray(model(paddle.to_tensor(ids[0]))._data)
    want = reference.logits(ids[0])
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
    # ... and the held experts got the rows the reference's routing sends
    # them, the absent experts' pairs none
    counts = np.asarray(model.tokens_per_expert()) - before
    for blk, (chosen, _) in enumerate(reference.routing):
        every = np.bincount(chosen.reshape(-1), minlength=8)
        assert (every[2:6] == counts[blk]).all()
        assert every.sum() == chosen.size == 2 * 64 * 2
        assert 0 < counts[blk].sum() < every.sum()


def test_loss_and_every_leafs_gradient_agree(cfg, program, wanted, ids):
    model, params = program
    for p in params:
        p.clear_grad()
    loss = model.loss(paddle.to_tensor(ids[0]), paddle.to_tensor(ids[1]))
    loss.backward()
    ref_loss, want = wanted
    assert abs(float(loss) - ref_loss) <= 1e-5 * ref_loss
    names = [f"{leaf[0]}/{leaf[1]}" for leaf in afmoe.leaves(cfg)]
    assert sorted(names) == sorted(want) and len(names) == len(params)
    assert {"h.0/w1", "h.1/router.w", "h.2.gu/w", "h.2/q_norm.g",
            "head/lm_head"} <= set(names)
    for name, p in zip(names, params):
        got = np.asarray(p.grad._data)
        scale = np.abs(want[name]).max()
        assert scale > 0, name               # every leaf is reached
        assert np.abs(got - want[name]).max() <= 1e-4 * scale, name


def test_a_recomputed_mlp_half_gives_the_same_gradients(cfg, wanted, ids):
    """``fleet.utils.recompute`` around each block's MLP half: same loss,
    same gradient in every leaf, and the counters still count (they are
    written outside the recomputed function)."""
    model, params = _program(dict(cfg, training={"recompute": "mlp"}))
    loss = model.loss(paddle.to_tensor(ids[0]), paddle.to_tensor(ids[1]))
    loss.backward()
    ref_loss, want = wanted
    assert abs(float(loss) - ref_loss) <= 1e-5 * ref_loss
    names = [f"{leaf[0]}/{leaf[1]}" for leaf in afmoe.leaves(cfg)]
    for name, p in zip(names, params):
        scale = np.abs(want[name]).max()
        assert np.abs(np.asarray(p.grad._data) - want[name]).max() \
            <= 1e-4 * scale, name
    assert (np.asarray(model.pairs_routed()) == 2 * 64 * 2).all()
    assert np.asarray(model.tokens_per_expert()).sum() > 0


def test_the_train_step_compiles_with_recomputed_mlp_halves(cfg, ids):
    """``jit.to_static`` over model and AdamW with the MLP halves
    recomputed: the family's trainer, two steps, a loss that is a
    number, the step's routing the sets the model itself would choose."""
    toy = dict(cfg, frozen=["router."], training={"recompute": "mlp"})
    trainer = afmoe.Trainer(toy, SEED)
    with no_grad():
        chosen = []
        trainer.model(paddle.to_tensor(ids[0]), chosen)
    losses = [trainer.step(*ids) for _ in range(2)]
    assert all(np.isfinite(l) for l in losses)
    routing = trainer.routing()                   # of step two
    assert routing.shape == (3, 2, 64)
    sets = afmoe.unpack(routing, 8, 2)
    assert (sets[..., 0] < sets[..., 1]).all() and sets.max() < 8
    grads = trainer.moment_norms()                # AdamW reached every leaf
    assert len(grads) == 2 * len(trainer.params) and min(grads.values()) > 0
    counts = np.asarray(trainer.model.tokens_per_expert())
    assert (np.asarray(trainer.model.pairs_routed()) == 3 * 256).all()
    assert counts.shape == (3, 4) and counts.sum() > 0
    # the first forward above routed on the seeded weights: so did step 1
    first = afmoe.pack(np.stack([c.numpy() for c in chosen]), 8)
    assert first.shape == (3, 2, 64)


# -- a share small enough to have a bound on its rows --------------------------


@pytest.fixture(scope="module")
def bounded_cfg(cfg):
    """The toy with 64 experts, four a token, of which experts 9-11 are
    held: 2 x 64 tokens make 512 pairs a routed block, an even share of
    24, a bound of 256 rows (``nn.layer.moe.row_bound``), so each block's
    routed half is the loop over windows of 256 of its 512 pairs. The
    seeded routing gives the three 10 to 70 pairs a block; a bias can
    give them up to 384."""
    return dict(cfg, num_experts=64, num_experts_per_tok=4,
                held=dict(cfg["held"], experts=3, first_expert=9))


@pytest.fixture(scope="module")
def bounded_wanted(bounded_cfg, ids):
    reference = afmoe.reference(bounded_cfg, SEED)
    want = {}
    for group, grads in reference.loss_and_grads(reference.get, *ids):
        want.update({f"{group}/{k}": np.asarray(v) for k, v in grads.items()})
    return reference.loss, want


@pytest.mark.parametrize("recompute", ["none", "mlp"])
def test_a_bounded_share_gives_the_references_loss_and_gradients(
        bounded_cfg, bounded_wanted, ids, recompute):
    """One window (256 rows for 512 pairs) in every routed block,
    recomputed or not: the uncut float32 reference's held part, loss and
    every leaf's gradient; no call past the bound, the counters as ever."""
    from paddle_tpu.nn.layer.moe import row_bound

    assert row_bound(2 * 64 * 4, 3, 64) == 256
    model, params = _program(dict(bounded_cfg,
                                  training={"recompute": recompute}))
    loss = model.loss(paddle.to_tensor(ids[0]), paddle.to_tensor(ids[1]))
    loss.backward()
    ref_loss, want = bounded_wanted
    assert abs(float(loss) - ref_loss) <= 1e-5 * ref_loss
    names = [f"{leaf[0]}/{leaf[1]}" for leaf in afmoe.leaves(bounded_cfg)]
    assert sorted(names) == sorted(want)
    for name, p in zip(names, params):
        scale = np.abs(want[name]).max()
        assert scale > 0, name
        assert np.abs(np.asarray(p.grad._data) - want[name]).max() \
            <= 1e-4 * scale, name
    assert (np.asarray(model.calls_in_full()) == 0).all()
    assert (np.asarray(model.pairs_routed()) == 512).all()
    rows = np.asarray(model.tokens_per_expert()).sum(-1)
    assert ((0 < rows) & (rows < 256)).all()


def test_the_compiled_step_takes_the_bounded_path_and_the_full_one(
        bounded_cfg, bounded_wanted, ids):
    """``jit.to_static`` over model and AdamW in float32: step one's loss
    and every leaf's gradient norm (from the first moment) are the
    reference's through the compiled loop. Then a bias sends every token
    to all three held experts (384 pairs, over the bound: a second window,
    every one of them counted), then to two (256: at the bound)."""
    o = dict(bounded_cfg["optimizer"], stochastic_rounding=False,
             moment_dtype="float32")
    trainer = afmoe.Trainer(
        dict(bounded_cfg, optimizer=o, training={"recompute": "mlp"},
             router_balancing={"rate": 0}), SEED)
    trainer.model.float()     # the seeded bfloat16 weights, widened
    ref_loss, want = bounded_wanted
    assert abs(trainer.step(*ids) - ref_loss) <= 1e-5 * ref_loss
    for name, got in trainer.grad_norms().items():
        norm = float(np.linalg.norm(want[name]))
        assert abs(got - norm) <= 1e-5 * norm, name
    model = trainer.model
    assert (np.asarray(model.calls_in_full()) == 0).all()
    for bias_of_third, rows, in_full in ((10.0, 384, 1), (-10.0, 256, 1)):
        for layer in model.routed_layers():
            bias = layer.mlp.router.bias
            bias._data = bias._data.at[9:11].set(10.0).at[11].set(
                bias_of_third)
        before = np.asarray(model.tokens_per_expert()).sum(-1)
        assert np.isfinite(trainer.step(*ids))
        assert (np.asarray(model.tokens_per_expert()).sum(-1) - before
                == rows).all()
        assert (np.asarray(model.calls_in_full()) == in_full).all()
    assert (np.asarray(model.pairs_routed()) == 3 * 512).all()


# -- piece by piece ----------------------------------------------------------


def _moe_weights(seed, h=32, f=16, e=8, t=48):
    ks = jax.random.split(jax.random.key(seed), 8)
    n = lambda k, *s: 0.3 * jax.random.normal(k, s, jnp.float32)
    return {"router.w": n(ks[0], h, e), "router.bias": 0.1 * n(ks[1], e),
            "shared.w1": n(ks[2], h, f), "shared.w3": n(ks[3], h, f),
            "shared.w2": n(ks[4], f, h), "experts.w_gu": n(ks[5], e, h, 2 * f),
            "experts.w_dn": n(ks[6], e, f, h)}, n(ks[7], t, h)


def _share(p, first, held, h=32, f=16, e=8, k=3):
    """The program's routed MLP holding experts first .. first + held,
    given the weights ``p`` names."""
    c = AfmoeConfig.tiny(hidden_size=h, moe_intermediate_size=f,
                         num_experts=e, num_experts_per_tok=k,
                         first_expert=first, held_experts=held)
    moe = amodel.AfmoeMoE(c)
    give = {moe.router.weight: p["router.w"], moe.router.bias: p["router.bias"],
            moe.shared_experts.gate_proj.weight: p["shared.w1"],
            moe.shared_experts.up_proj.weight: p["shared.w3"],
            moe.shared_experts.down_proj.weight: p["shared.w2"],
            moe.experts.w_gu: p["experts.w_gu"][first:first + held],
            moe.experts.w_dn: p["experts.w_dn"][first:first + held]}
    for t, a in give.items():
        t._data = a
    return moe


@pytest.mark.parametrize("held, e, k, t", [
    (2, 8, 3, 48), (4, 8, 3, 48), (8, 8, 3, 48), (4, 64, 4, 256)],
    ids=["2-of-8", "4-of-8", "8-of-8", "4-of-64-bounded"])
def test_the_shares_add_up(held, e, k, t):
    """The routed parts that all shares give, with the shared expert
    counted once, are the uncut reference's layer: 4 shares of 2 experts
    of 8 (or 2 of 4, or the one that holds all); and 16 shares of 4 of
    64, each with a bound of 512 rows on its 1,024 pairs, where a bias
    towards experts 0-3 sends the first share past its bound."""
    p, m = _moe_weights(5, e=e, t=t)
    if e == 64:
        p["router.bias"] = p["router.bias"].at[:4].add(0.5)
    with jax.default_matmul_precision("highest"):
        whole, _ = ar.moe(p, m, top_k=k, scale=2.448, first=0)
        shared = ar.swiglu(m, p["shared.w1"], p["shared.w3"], p["shared.w2"],
                           "f32")
        total, rows, in_full = 0.0, 0, []
        with no_grad():
            for first in range(0, e, held):
                moe = _share(p, first, held, e=e, k=k)
                f, ids, sizes = moe.compute(paddle.to_tensor(m))
                total = total + (f._data - shared)
                rows += int(sizes._data.sum())
                assert sizes.shape == [held]
                if held < e:
                    moe.experts.count(sizes, t * k)
                    in_full.append(int(moe.experts.calls_in_full._data))
    assert rows == t * k                  # every pair met exactly one share
    assert float(jnp.abs(total + shared - whole).max()) \
        <= 1e-5 * float(jnp.abs(whole).max())
    # a share of the 8 has no bound (it would not halve its rows); of the
    # 64, the first alone passed its bound and took a second window
    assert in_full == ([1] + [0] * 15 if e == 64 else [1] * len(in_full))


def test_a_share_with_none_of_a_batchs_experts_adds_the_shared_expert_alone():
    p, m = _moe_weights(6)
    p["router.bias"] = p["router.bias"].at[6:].set(-10.0)   # never chosen
    moe = _share(p, 6, 2)
    with no_grad():
        f, ids, sizes = moe.compute(paddle.to_tensor(m))
    shared = ar.swiglu(m, p["shared.w1"], p["shared.w3"], p["shared.w2"],
                       "f32")
    assert not np.asarray(sizes._data).any() and int(ids._data.max()) < 6
    assert bool(jnp.isfinite(f._data).all())
    assert float(jnp.abs(f._data - shared).max()) <= 1e-5


def test_the_gradient_of_a_share_is_the_held_experts_part():
    """Rows past the last group carry no gradient anywhere: the input's
    and the held experts' gradients are the reference's, finite."""
    p, m = _moe_weights(7)
    moe = _share(p, 2, 3)
    x = paddle.to_tensor(m, stop_gradient=False)
    for w in (moe.experts.w_gu, moe.experts.w_dn):
        w.stop_gradient = False
    f, _, _ = moe.compute(x)
    (f * f).sum().backward()
    held = dict(p, **{"experts.w_gu": p["experts.w_gu"][2:5],
                      "experts.w_dn": p["experts.w_dn"][2:5]})

    def loss(m, w_gu, w_dn):
        q = dict(held, **{"experts.w_gu": w_gu, "experts.w_dn": w_dn})
        return jnp.sum(jnp.square(ar.moe(q, m, top_k=3, scale=2.448,
                                         first=2)[0]))

    with jax.default_matmul_precision("highest"):
        want = jax.grad(loss, (0, 1, 2))(m, held["experts.w_gu"],
                                         held["experts.w_dn"])
    for got, w in zip((x.grad, moe.experts.w_gu.grad, moe.experts.w_dn.grad),
                      want):
        assert float(jnp.abs(got._data - w).max()) \
            <= 1e-4 * float(jnp.abs(w).max())


def test_the_router_chooses_by_score_plus_bias_and_gates_by_score():
    router = nn.SigmoidTopKRouter(16, 8, 3, route_scale=2.448)
    m = jax.random.normal(jax.random.key(1), (40, 16))
    router.weight._data = jax.random.normal(jax.random.key(2), (16, 8))
    with no_grad():
        ids0, gates0 = router(paddle.to_tensor(m))
        bias = jnp.zeros((8,)).at[5].set(10.0)      # 5 is always chosen ...
        router.bias._data = bias
        ids1, gates1 = router(paddle.to_tensor(m))
    scores = jax.nn.sigmoid(m @ router.weight._data)
    assert ids0.shape == [40, 3] and gates0.dtype == jnp.float32
    top = jnp.sort(jax.lax.top_k(scores, 3)[1], -1)
    assert (jnp.sort(ids0._data, -1) == top).all()
    assert (ids1._data == 5).any(axis=-1).all()
    # ... and the gates are the chosen scores over their sum, scaled: the
    # bias is in none of them
    picked = jnp.take_along_axis(scores, ids1._data, -1)
    want = 2.448 * picked / picked.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(gates1._data), np.asarray(want),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gates0._data.sum(-1)), 2.448,
                               rtol=1e-5)


def test_window_layers_turn_positions_full_layers_have_none():
    """Moving the whole sequence one place later leaves a full layer's
    output where it was (no positions at all); a window layer keeps its
    output too (RoPE is relative) but only as far as its window reaches."""
    c = AfmoeConfig.tiny()
    a = jax.random.normal(jax.random.key(3), (1, 48, 64))
    for window in (None, 16):
        attn = amodel.AfmoeAttention(c, window)
        with no_grad():
            out = attn(paddle.to_tensor(a))._data
            # the same keys seen from further away: only a window cuts them
            cut = attn(paddle.to_tensor(a[:, 16:]))._data
        far = float(jnp.abs(out[:, 40:] - cut[:, 24:]).max())
        assert (far <= 1e-4) == (window is not None), (window, far)
    q = jax.random.normal(jax.random.key(4), (1, 8, 2, 16))
    turned = amodel.rope(q, 10000.0)
    assert float(jnp.abs(turned[:, 0] - q[:, 0]).max()) == 0.0   # angle 0
    np.testing.assert_allclose(
        np.asarray(jnp.sum(turned * turned, -1)),
        np.asarray(jnp.sum(q * q, -1)), rtol=1e-5)               # a rotation
    np.testing.assert_allclose(np.asarray(turned[0]),
                               np.asarray(ar.rope(q[0], 10000.0)), atol=1e-6)


def test_the_stack_is_declared_by_the_published_keys():
    c = AfmoeConfig()          # Trinity-Large-Preview's
    assert c.layer_types.count("full_attention") == 15
    assert [i for i, t in enumerate(c.layer_types)
            if t == "full_attention"][:3] == [3, 7, 11]
    with paddle.LazyGuard():
        model = AfmoeForCausalLM(AfmoeConfig(
            vocab_size=25024, first_layer=5, held_layers=5, held_experts=8))
    kinds = [(l.self_attn.window, l.routed) for l in model.model.layers]
    assert model.model.layer_ids == [5, 6, 7, 8, 9]
    assert kinds == [(4096, False), (4096, True), (None, True), (4096, True),
                     (4096, True)]
    shapes = {n: tuple(p._lazy_init[1]) for n, p in model.named_parameters()}
    assert shapes["model.layers.1.mlp.experts.w_gu"] == (8, 3072, 6144)
    assert shapes["model.layers.1.mlp.router.weight"] == (3072, 256)
    assert shapes["model.layers.0.mlp.gate_proj.weight"] == (3072, 12288)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 1_603_993_856
    with pytest.raises(ValueError):
        AfmoeConfig(recompute="some")
    with pytest.raises(ValueError):
        nn.RoutedExperts(8, 8, 8, held=4, first=6)
