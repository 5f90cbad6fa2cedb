"""``models/smallthinker.py`` (SmallThinkerForCausalLM, training path)
against its plain reference ``chipbench/families/smallthinker_reference.py``
on seeded weights, at a toy size on the CPU (the flash and grouped-matmul
kernels interpreted): seven query heads to one kv head, a window shorter
than the sequence, the position-free full layer first, a quarter of the
experts held under top-3 — and piece by piece what each part must do:
the router reads the block's INPUT, the experts are ReGLU on every path
of ``nn.RoutedExperts``, the shares ADD UP.

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
from chipbench.families import afmoe_reference as ar
from chipbench.families import smallthinker, smallthinker_reference as sr
from paddle_tpu import nn
from paddle_tpu.base.tape import no_grad
from paddle_tpu.models import SmallThinkerConfig, SmallThinkerForCausalLM
from paddle_tpu.models import smallthinker as smodel
from paddle_tpu.nn.layer import moe as moe_layer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2**31 + 11


@pytest.fixture(scope="module")
def cfg():
    """The toy configuration with nothing recomputed."""
    with open(os.path.join(ROOT, "tests", "chipbench", "configs",
                           "toy-smallthinker.json"), encoding="utf-8") as fh:
        toy = json.load(fh)
    return dict(toy, training={"recompute": "none"})


@pytest.fixture(scope="module")
def ids(cfg):
    rng = np.random.default_rng(0)
    tok = rng.integers(0, cfg["held"]["vocab_rows"], (2, 65)).astype(np.int32)
    return tok[:, :-1], tok[:, 1:]


def _program(cfg):
    model, params = smallthinker._build_model(cfg, SEED)
    model.float()          # the program in float32: only the code differs
    return model, params


@pytest.fixture(scope="module")
def program(cfg):
    return _program(cfg)


@pytest.fixture(scope="module")
def reference(cfg):
    return smallthinker.reference(cfg, SEED)


@pytest.fixture(scope="module")
def wanted(reference, ids):
    """The reference's loss and every leaf's gradient."""
    want = {}
    for group, grads in reference.loss_and_grads(reference.get, *ids):
        want.update({f"{group}/{k}": np.asarray(v) for k, v in grads.items()})
    return reference.loss, want


def _grads_agree(cfg, params, want):
    names = [f"{leaf[0]}/{leaf[1]}" for leaf in smallthinker.leaves(cfg)]
    assert sorted(names) == sorted(want) and len(names) == len(params)
    for name, p in zip(names, params):
        scale = np.abs(want[name]).max()
        assert scale > 0, name               # every leaf is reached
        assert np.abs(np.asarray(p.grad._data) - want[name]).max() \
            <= 1e-4 * scale, name
    return names


def test_the_toy_holds_both_kinds_of_attention_and_a_quarter_share(cfg):
    z = smallthinker.sizes(cfg)
    # published layers 4-7 of 8: the position-free full layer FIRST, then
    # three window layers with RoPE; the window (32) is shorter than the
    # tests' sequence (64); seven query heads to one kv head; experts 2-3
    # of 8, three a token: a quarter, on the every-pair path
    assert z["layer_kinds"] == [(None, False), (32, True), (32, True),
                                (32, True)]
    assert (z["q_heads"], z["kv_heads"]) == (7, 1)
    assert (z["first_expert"], z["experts"], z["published_experts"],
            z["top_k"]) == (2, 2, 8, 3)
    assert moe_layer.row_bound(2 * 64 * 3, 2, 8) is None


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
        assert (every[2:4] == counts[blk]).all()
        assert every.sum() == chosen.size == 2 * 64 * 3
        assert 0 < counts[blk].sum() < every.sum()


def test_loss_and_every_leafs_gradient_agree(cfg, program, wanted, ids):
    model, params = program
    for p in params:
        p.clear_grad()
    loss = model.loss(paddle.to_tensor(ids[0]), paddle.to_tensor(ids[1]))
    loss.backward()
    ref_loss, want = wanted
    assert abs(float(loss) - ref_loss) <= 1e-5 * ref_loss
    names = _grads_agree(cfg, params, want)
    # the router's gradient among them, in the full layer and a window one
    assert {"h.0/router.w", "h.1/router.w", "h.2.gu/w", "h.3.dn/w", "h.0/wq",
            "h.1/wk", "head/lm_head", "embed/wte"} <= set(names)


def test_a_recomputed_mlp_half_routes_alike_and_gives_the_same_gradients(
        cfg, program, wanted, ids):
    """``fleet.utils.recompute`` around each block's MLP half, the router
    OUTSIDE it: the choice is the unrecomputed model's bit for bit, loss
    and every leaf's gradient the reference's, and the counters still
    count (they are written outside the recomputed function)."""
    model, params = _program(dict(cfg, training={"recompute": "mlp"}))
    chosen, plain = [], []
    with no_grad():
        model(paddle.to_tensor(ids[0]), chosen)
        program[0](paddle.to_tensor(ids[0]), plain)
    assert len(chosen) == 4 and chosen[0].shape == [2, 64, 3]
    for a, b in zip(chosen, plain):
        assert (np.asarray(a._data) == np.asarray(b._data)).all()
    before = np.asarray(model.pairs_routed())
    loss = model.loss(paddle.to_tensor(ids[0]), paddle.to_tensor(ids[1]))
    loss.backward()
    ref_loss, want = wanted
    assert abs(float(loss) - ref_loss) <= 1e-5 * ref_loss
    _grads_agree(cfg, params, want)
    assert (np.asarray(model.pairs_routed()) - before == 2 * 64 * 3).all()
    assert (np.asarray(model.calls_in_full()) == 2).all()   # no bound
    # what a pass ran over, by the layer's own record: a row for every pair
    assert [l.mlp.experts.rows_a_window for l in model.model.layers] \
        == [2 * 64 * 3] * 4


def test_the_train_step_compiles_with_recomputed_mlp_halves(cfg, ids):
    """``jit.to_static`` over model and AdamW with the MLP halves
    recomputed: the family's trainer, two steps, a loss that is a
    number, the step's routing the sets the model itself would choose."""
    trainer = smallthinker.Trainer(
        dict(cfg, training={"recompute": "mlp"}), SEED)
    with no_grad():
        chosen = []
        trainer.model(paddle.to_tensor(ids[0]), chosen)
    first = smallthinker.pack(np.stack([c.numpy() for c in chosen]), 8)
    losses = [trainer.step(*ids)]
    assert (trainer.routing() == first).all()     # seeded weights: step 1's
    losses.append(trainer.step(*ids))
    assert all(np.isfinite(l) for l in losses)
    routing = trainer.routing()                   # of step two
    assert routing.shape == (4, 2, 64)
    sets = smallthinker.unpack(routing, 8, 3)
    assert (sets[..., 0] < sets[..., 1]).all() and sets.max() < 8
    grads = trainer.moment_norms()                # AdamW reached every leaf
    assert len(grads) == 2 * len(trainer.params) and min(grads.values()) > 0
    assert any("router.w" in name for name in grads)
    counts = np.asarray(trainer.model.tokens_per_expert())
    assert (np.asarray(trainer.model.pairs_routed()) == 3 * 384).all()
    assert counts.shape == (4, 2) and counts.sum() > 0


# -- the router reads the block's input --------------------------------------


def test_the_router_reads_the_blocks_input_not_the_stream_after_attention(
        cfg, program, reference, ids):
    """Each block's choice is the top-k of ``softmax(x Wr)`` on the
    stream AS IT ENTERS the block; a model that routed where the other
    decoders do — on the normed stream after attention — is another
    model: the reference made to do so (``precision="late"``) is far
    from the program."""
    model, _ = program
    entered, chosen = [], []
    hooks = [layer.register_forward_pre_hook(
        lambda _, args: entered.append(args[0]._data))
        for layer in model.model.layers]
    with no_grad():
        got = np.asarray(model(paddle.to_tensor(ids[0]), chosen)._data)
    for h in hooks:
        h.remove()
    assert len(entered) == len(chosen) == 4
    flips_late = []
    for layer, x, picked in zip(model.model.layers, entered, chosen):
        w = layer.mlp.router.weight._data
        with jax.default_matmul_precision("highest"):
            own = jax.lax.top_k(jax.nn.softmax(x @ w, -1), 3)[1]
            a = layer.post_attention_layernorm(
                paddle.to_tensor(layer._attn_half(paddle.to_tensor(x))._data))
            late = jax.lax.top_k(jax.nn.softmax(a._data @ w, -1), 3)[1]
        assert (np.asarray(own) == np.asarray(picked._data)).all()
        flips_late.append(float(np.mean(
            np.sort(late, -1) != np.sort(np.asarray(picked._data), -1))))
    assert min(flips_late) > 0.2, flips_late
    late_ref = smallthinker.reference(cfg, SEED, precision="late")
    want = reference.logits(ids[0])
    wrong = late_ref.logits(ids[0])
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
    assert np.abs(got - wrong).max() > 1e-2 * np.abs(want).max()


# -- ReGLU on every path of the routed experts -------------------------------


def _expert_weights(seed, h=32, f=16, e=8, t=48, k=3):
    ks = jax.random.split(jax.random.key(seed), 5)
    n = lambda key, *s: 0.3 * jax.random.normal(key, s, jnp.float32)
    probs = jax.nn.softmax(4.0 * n(ks[0], t, e), -1)
    gates, ids = jax.lax.top_k(probs, k)
    return (n(ks[1], t, h), n(ks[2], e, h, 2 * f), n(ks[3], e, f, h),
            ids.astype(jnp.int32), gates / gates.sum(-1, keepdims=True))


def _loop(x, w_gu, w_dn, ids, gates, first, held, act):
    """The held experts one by one on every token, each weighted by the
    gate of the tokens that chose it."""
    f = w_dn.shape[1]
    out = jnp.zeros_like(x)
    for e in range(first, first + held):
        gu = x @ w_gu[e]
        y = (act(gu[:, :f]) * gu[:, f:]) @ w_dn[e]
        out = out + jnp.sum(jnp.where(ids == e, gates, 0.0), -1)[:, None] * y
    return out


@pytest.mark.parametrize("first, held, e, k, t, path", [
    (0, 8, 8, 3, 48, "every pair, whole"),
    (16, 16, 64, 6, 64, "every pair, a quarter under top-6"),
    (8, 4, 64, 4, 256, "bounded windows")],
    ids=["whole", "quarter-top6", "bounded"])
@pytest.mark.parametrize("name, act", [("reglu", jax.nn.relu),
                                       ("swiglu", jax.nn.silu)])
def test_the_declared_activation_runs_on_every_path(first, held, e, k, t,
                                                    path, name, act):
    """``nn.RoutedExperts`` told its activation against a loop over the
    held experts, forward and every gradient, on the three paths the
    layer has; a share records the rows one pass of the call ran over."""
    x, w_gu, w_dn, ids, gates = _expert_weights(9, e=e, t=t, k=k)
    bounded = moe_layer.row_bound(t * k, held, e)
    assert (bounded is not None) == (path == "bounded windows")
    layer = nn.RoutedExperts(32, 16, e, held, first, activation=name)
    layer.w_gu._data, layer.w_dn._data = (w_gu[first:first + held],
                                          w_dn[first:first + held])
    xt = paddle.to_tensor(x, stop_gradient=False)
    gt = paddle.to_tensor(gates, stop_gradient=False)
    for w in (layer.w_gu, layer.w_dn):
        w.stop_gradient = False
    out = layer(xt, paddle.to_tensor(ids), gt)
    (out * out).sum().backward()

    def loss(x, w_gu, w_dn, gates):
        return jnp.sum(jnp.square(_loop(x, w_gu, w_dn, ids, gates, first,
                                        held, act)))

    with jax.default_matmul_precision("highest"):
        want = _loop(x, w_gu, w_dn, ids, gates, first, held, act)
        grads = jax.grad(loss, (0, 1, 2, 3))(x, w_gu, w_dn, gates)
    assert float(jnp.abs(out._data - want).max()) \
        <= 1e-5 * float(jnp.abs(want).max())
    got = (xt.grad._data, layer.w_gu.grad._data, layer.w_dn.grad._data,
           gt.grad._data)
    for g, w in zip(got, (grads[0], grads[1][first:first + held],
                          grads[2][first:first + held], grads[3])):
        assert bool(jnp.isfinite(g).all())
        assert float(jnp.abs(g - w).max()) <= 1e-4 * float(jnp.abs(w).max())
    # the counters: the rows of the held experts; a share's record of the
    # rows ONE pass ran over (its window, or a row for every pair)
    inside = (ids >= first) & (ids < first + held)
    assert int(layer.tokens_per_expert._data.sum()) == int(inside.sum())
    if held < e:
        assert layer.rows_a_window == (bounded or t * k)
    else:
        assert not hasattr(layer, "rows_a_window")


def test_an_activation_is_a_name_or_a_function_and_swiglu_the_default():
    x, w_gu, w_dn, ids, gates = _expert_weights(3)

    def run(**kw):
        layer = nn.RoutedExperts(32, 16, 8, **kw)
        layer.w_gu._data, layer.w_dn._data = w_gu, w_dn
        with no_grad():
            return layer(paddle.to_tensor(x), paddle.to_tensor(ids),
                         paddle.to_tensor(gates))._data

    default, swiglu, reglu = run(), run(activation="swiglu"), run(
        activation="reglu")
    own = run(activation=lambda gu, f: jax.nn.relu(gu[:, :f]) * gu[:, f:])
    assert (np.asarray(default) == np.asarray(swiglu)).all()
    assert float(jnp.abs(own - reglu).max()) <= 1e-6
    assert float(jnp.abs(default - reglu).max()) > 1e-3
    assert set(moe_layer.ACTIVATIONS) == {"swiglu", "reglu"}
    with pytest.raises(KeyError):
        nn.RoutedExperts(32, 16, 8, activation="geglu")


# -- the shares add up --------------------------------------------------------


def _block_weights(seed, h=32, f=16, e=8):
    ks = jax.random.split(jax.random.key(seed), 4)
    n = lambda key, *s: 0.3 * jax.random.normal(key, s, jnp.float32)
    return {"router.w": n(ks[0], h, e), "experts.w_gu": n(ks[1], e, h, 2 * f),
            "experts.w_dn": n(ks[2], e, f, h)}, n(ks[3], 48, h)


@pytest.mark.parametrize("held, e, k", [(2, 8, 3), (4, 8, 3), (8, 8, 3),
                                        (16, 64, 6)],
                         ids=["2-of-8", "4-of-8", "8-of-8", "16-of-64-top6"])
def test_the_shares_add_up(held, e, k):
    """The routed parts that all the shares give (there is no shared
    expert, nothing every chip computes alike) are the uncut reference's
    whole layer: four quarter shares of 8 under top-3, and four of 64
    under top-6, the cell's own shape; every share is told the SAME
    choice, made once from the block's input."""
    p, x = _block_weights(5, e=e)
    m = 0.5 * jnp.roll(x, 1, axis=0) + 0.1      # what the experts are given
    with jax.default_matmul_precision("highest"):
        chosen, gates, own, _ = sr.route(p, x, top_k=k)
        whole = sr.experts(p, m, chosen, gates, first=0)
        total, rows, passed = 0.0, 0, 0
        with no_grad():
            for first in range(0, e, held):
                c = SmallThinkerConfig.tiny(
                    hidden_size=32, moe_ffn_hidden_size=16,
                    moe_num_primary_experts=e,
                    moe_num_active_primary_experts=k, first_expert=first,
                    held_experts=held)
                moe = smodel.SmallThinkerMoE(c)
                moe.router.weight._data = p["router.w"]
                moe.experts.w_gu._data = p["experts.w_gu"][first:first + held]
                moe.experts.w_dn._data = p["experts.w_dn"][first:first + held]
                ids, g = moe.router(paddle.to_tensor(x))
                assert (np.sort(np.asarray(ids._data), -1)
                        == np.asarray(own)).all()
                f, sizes = moe.experts.compute(paddle.to_tensor(m), ids, g)
                total = total + f._data
                rows += int(sizes._data.sum())
                passed += 48 * k
    assert rows == 48 * k                 # every pair met exactly one share
    assert float(jnp.abs(total - whole).max()) \
        <= 1e-5 * float(jnp.abs(whole).max())
    # a quarter share passes over a row for every pair: four times its own
    assert passed == (e // held) * rows


def test_a_share_with_none_of_a_batchs_experts_adds_nothing():
    p, x = _block_weights(6)
    c = SmallThinkerConfig.tiny(hidden_size=32, moe_ffn_hidden_size=16,
                                first_expert=6, held_experts=2)
    moe = smodel.SmallThinkerMoE(c)
    moe.experts.w_gu._data = p["experts.w_gu"][6:]
    moe.experts.w_dn._data = p["experts.w_dn"][6:]
    ids = jnp.tile(jnp.arange(3, dtype=jnp.int32), (48, 1))   # experts 0-2
    with no_grad():
        f, sizes = moe.experts.compute(
            paddle.to_tensor(x), paddle.to_tensor(ids),
            paddle.to_tensor(jnp.full((48, 3), 1 / 3)))
    assert not np.asarray(sizes._data).any()
    assert float(jnp.abs(f._data).max()) == 0.0


# -- attention ----------------------------------------------------------------


@pytest.mark.parametrize("window", [None, 16], ids=["full", "window"])
def test_flash_at_seven_query_heads_a_kv_head(window):
    """The flash kernels at group 7 (28 q / 4 kv heads in the cell; here
    14 / 2 of 16), with and without the window, against the reference's
    dense masked softmax: output and the gradients of q, k, v — the
    backward repeats k and v seven-fold and sums dk / dv over the group."""
    from paddle_tpu.ops.flash_attention import flash_attention

    ks = jax.random.split(jax.random.key(7), 4)
    q = jax.random.normal(ks[0], (1, 48, 14, 16), jnp.float32)
    k = jax.random.normal(ks[1], (1, 48, 2, 16), jnp.float32)
    v = jax.random.normal(ks[2], (1, 48, 2, 16), jnp.float32)
    do = jax.random.normal(ks[3], (1, 48, 14, 16), jnp.float32)

    def ours(q, k, v):
        return flash_attention(q, k, v, True, None, None, window)

    def dense(q, k, v):
        return ar.softmax_attention(q[0], k[0], v[0], window, "f32")[None]

    with jax.default_matmul_precision("highest"):
        got, pull = jax.vjp(ours, q, k, v)
        want, pull_ref = jax.vjp(dense, q, k, v)
        grads, wanted = pull(do), pull_ref(do)
    assert float(jnp.abs(got - want).max()) <= 1e-5
    for g, w in zip(grads, wanted):
        assert float(jnp.abs(g - w).max()) <= 1e-4 * float(jnp.abs(w).max())


def test_window_layers_turn_positions_the_full_layer_has_none():
    """Moving the whole sequence sixteen places later leaves a full
    layer's output where it was only if it has no positions; a window
    layer keeps its output (RoPE is relative) as far as its window
    reaches."""
    c = SmallThinkerConfig.tiny()
    a = jax.random.normal(jax.random.key(3), (1, 48, 64))
    for window, positions, same in ((None, False, False), (16, True, True)):
        attn = smodel.SmallThinkerAttention(c, window, positions)
        with no_grad():
            out = attn(paddle.to_tensor(a))._data
            # the same keys seen from further away: only a window cuts them
            cut = attn(paddle.to_tensor(a[:, 16:]))._data
        far = float(jnp.abs(out[:, 40:] - cut[:, 24:]).max())
        assert (far <= 1e-4) == same, (window, far)
        assert not hasattr(attn, "q_norm") and not hasattr(attn, "gate_proj")
    # no positions at all: a full layer sees a permutation of its PAST as
    # the same past
    attn = smodel.SmallThinkerAttention(c, None, False)
    with no_grad():
        out = attn(paddle.to_tensor(a))._data
        swapped = attn(paddle.to_tensor(
            a.at[:, 3].set(a[:, 9]).at[:, 9].set(a[:, 3])))._data
    assert float(jnp.abs(out[:, 20:] - swapped[:, 20:]).max()) <= 1e-4


def test_the_stack_is_declared_by_the_published_keys():
    c = SmallThinkerConfig()          # SmallThinker-21BA3B-Instruct's
    assert list(c.rope_layout) == list(c.sliding_window_layout) \
        == [0, 1, 1, 1] * 13
    assert (c.num_experts, c.held_experts, c.held_layers) == (64, 64, 52)
    with paddle.LazyGuard():
        model = SmallThinkerForCausalLM(SmallThinkerConfig(
            vocab_size=37984, held_layers=4, held_experts=16))
    kinds = [(l.self_attn.window, l.self_attn.positions, l.routed)
             for l in model.model.layers]
    assert model.model.layer_ids == [0, 1, 2, 3]
    assert kinds == [(None, False, True)] + [(4096, True, True)] * 3
    shapes = {n: tuple(p._lazy_init[1]) for n, p in model.named_parameters()}
    assert shapes["model.layers.1.mlp.experts.w_gu"] == (16, 2560, 1536)
    assert shapes["model.layers.1.mlp.experts.w_dn"] == (16, 768, 2560)
    assert shapes["model.layers.1.mlp.router.weight"] == (2560, 64)
    assert shapes["model.layers.0.self_attn.q_proj.weight"] == (2560, 3584)
    assert shapes["model.layers.0.self_attn.k_proj.weight"] == (2560, 512)
    assert len([n for n in shapes if ".layers.0." in n]) == 9
    assert sum(int(np.prod(s)) for s in shapes.values()) == 656_529_920
    with pytest.raises(ValueError):
        SmallThinkerConfig(recompute="layer")
    with pytest.raises(ValueError):
        SmallThinkerConfig(rope_layout=[0, 1])
    with pytest.raises(NotImplementedError):
        SmallThinkerConfig(moe_primary_router_apply_softmax=False)
