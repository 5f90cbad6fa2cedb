"""``models/zaya.py`` (ZayaForCausalLM, training path) against its plain
reference ``chipbench/families/zaya_reference.py`` on seeded weights, at
a toy size on the CPU (the flash and grouped-matmul kernels interpreted),
and piece by piece: what each unusual part of the block must do.

Tolerances: program and reference both run in float32 here (the program's
model is widened with ``model.float()``), so only the order of sums
differs: logits and loss agree to 1e-5 of their scale; a gradient leaf to
1e-4 of its largest entry (sums over 128 tokens through eight layers of
float32 rounding). A wrong tap, shift, head or expert is off by tens of
percent.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from chipbench.families import zaya, zaya_reference as zr
from paddle_tpu import nn
from paddle_tpu.base.tape import no_grad
from paddle_tpu.models import ZayaConfig, ZayaForCausalLM
from paddle_tpu.models import zaya as zmodel

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2**31 + 11


@pytest.fixture(scope="module")
def toy():
    """The toy configuration as its cell runs it: the router's leaves
    are ``frozen`` (no optimizer holds them)."""
    with open(os.path.join(ROOT, "tests", "chipbench", "configs",
                           "toy-zaya.json"), encoding="utf-8") as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def cfg(toy):
    """The same with nothing frozen: every leaf has a gradient to hold to
    the reference's."""
    return dict(toy, frozen=[])


@pytest.fixture(scope="module")
def ids(cfg):
    rng = np.random.default_rng(0)
    tok = rng.integers(0, cfg["held"]["vocab_rows"], (2, 65)).astype(np.int32)
    return tok[:, :-1], tok[:, 1:]


@pytest.fixture(scope="module")
def program(cfg):
    model, params = zaya._build_model(cfg, SEED)
    model.float()          # the program in float32: only the code differs
    return model, params


@pytest.fixture(scope="module")
def reference(cfg):
    return zaya.reference(cfg, SEED)


def test_logits_agree_with_the_reference(program, reference, ids):
    model, _ = program
    with no_grad():
        got = np.asarray(model(paddle.to_tensor(ids[0]))._data)
    want = reference.logits(ids[0])
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
    # ... and the routing is the reference's, token for token
    counts = np.asarray(model.tokens_per_expert())
    for block, (expert, _) in enumerate(reference.routing):
        assert (np.bincount(expert.reshape(-1), minlength=counts.shape[1])
                == counts[block]).all()


def test_loss_and_every_leafs_gradient_agree(cfg, program, reference, ids):
    model, params = program
    for p in params:
        p.clear_grad()
    loss = model.loss(paddle.to_tensor(ids[0]), paddle.to_tensor(ids[1]))
    loss.backward()
    want = {}
    for group, grads in reference.loss_and_grads(reference.get, *ids):
        want.update({f"{group}/{k}": np.asarray(v) for k, v in grads.items()})
    assert abs(float(loss) - reference.loss) <= 1e-5 * reference.loss
    names = [f"{leaf[0]}/{leaf[1]}" for leaf in zaya.leaves(cfg)]
    assert sorted(names) == sorted(want) and len(names) == len(params)
    assert "h.0/router.b2" in names and "h.1/tau" in names
    for name, p in zip(names, params):
        got = np.asarray(p.grad._data)
        scale = np.abs(want[name]).max()
        assert scale > 0, name               # every leaf is reached
        assert np.abs(got - want[name]).max() <= 1e-4 * scale, name


def test_the_tied_heads_gradient_is_the_sum_of_both_uses(program, ids):
    """d loss / d embed_tokens = the lookup's rows + the head's matmul."""
    model, _ = program
    w = model.model.embed_tokens.weight
    tok, labels = paddle.to_tensor(ids[0]), paddle.to_tensor(ids[1])

    def grad_of(lookup_w, head_w):
        def loss(lw, hw):
            x = lw[tok._data]
            for layer in model.model.layers:
                x = layer(paddle.to_tensor(x))._data
            h = model.model.norm(paddle.to_tensor(x))._data
            logp = jax.nn.log_softmax(h @ hw.T)
            return -jnp.mean(jnp.take_along_axis(
                logp, labels._data[..., None], axis=-1))
        return jax.grad(loss, (0, 1))(lookup_w, head_w)

    with no_grad():
        g_lookup, g_head = grad_of(w._data, w._data)
    w.clear_grad()
    model.loss(tok, labels).backward()
    both = np.asarray(g_lookup + g_head)
    assert np.abs(np.asarray(g_lookup)).max() > 0
    assert np.abs(np.asarray(g_head)).max() > 0
    assert np.abs(np.asarray(w.grad._data) - both).max() \
        <= 1e-4 * np.abs(both).max()


# -- piece by piece ----------------------------------------------------------


def _conv_args(heads=6, d=16, taps=(2, 2), seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    c = heads * d
    return (jax.random.normal(ks[0], (1, 24, c)),
            jax.random.normal(ks[1], (taps[0], c)),
            jax.random.normal(ks[2], (c,)),
            jax.random.normal(ks[3], (taps[1], heads, d, d)),
            jax.random.normal(ks[4], (c,)))


def test_the_convolutions_leak_no_future_position():
    c, w0, b0, w1, b1 = _conv_args()
    base = zmodel.cca_conv(c, w0, b0, w1, b1)
    later = zmodel.cca_conv(c.at[:, 10:].add(1.0), w0, b0, w1, b1)
    assert np.abs(np.asarray(later - base))[:, :10].max() == 0.0
    assert np.abs(np.asarray(later - base))[:, 10].max() > 0.0
    # two taps then two taps: position t sees t-2 .. t and nothing older
    older = zmodel.cca_conv(c.at[:, :8].add(1.0), w0, b0, w1, b1)
    assert np.abs(np.asarray(older - base))[:, 10:].max() == 0.0
    assert np.abs(np.asarray(older - base))[:, 9].max() > 0.0


def test_the_grouped_convolution_mixes_a_heads_channels_only():
    c, w0, b0, w1, b1 = _conv_args()
    base = zmodel.cca_conv(c, w0, b0, w1, b1)
    moved = zmodel.cca_conv(c.at[:, :, 16:32].add(1.0), w0, b0, w1, b1)
    diff = np.abs(np.asarray(moved - base)).max(axis=(0, 1))
    assert diff[16:32].min() > 0.0           # head 1's channels all move
    assert diff[:16].max() == 0.0 and diff[32:].max() == 0.0


def test_the_convolutions_are_the_references():
    c, w0, b0, w1, b1 = _conv_args()
    p = {"conv0.w": w0, "conv0.b": b0, "conv1.w": w1, "conv1.b": b1}
    with jax.default_matmul_precision("highest"):
        want = zr.conv1(p, zr.conv0(p, c[0]), "f32")
        got = zmodel.cca_conv(c, w0, b0, w1, b1)[0]
    assert jnp.allclose(got, want, rtol=1e-5, atol=1e-5)


def test_the_value_shift_kv_head_one_reads_the_token_before(program):
    model, _ = program
    attn = model.model.layers[0].self_attn
    u = paddle.to_tensor(np.random.default_rng(1).standard_normal(
        (1, 12, 64)).astype(np.float32))
    with no_grad():
        v1 = np.asarray(attn.v1_proj(u)._data)[0]
        before = np.asarray(zmodel._shift(u._data, 1))[0]
        v2 = before @ np.asarray(attn.v2_proj.weight._data)
    assert np.abs(before[0]).max() == 0.0            # u_{-1} = 0
    assert np.allclose(before[1:], np.asarray(u._data)[0, :-1])
    # the reference builds the same values: head 0 current, head 1 before
    p = {"wv1": attn.v1_proj.weight._data, "wv2": attn.v2_proj.weight._data}
    with jax.default_matmul_precision("highest"):
        want = jnp.concatenate(
            [u._data[0] @ p["wv1"], zr._shift(u._data[0], 1) @ p["wv2"]], -1)
    assert np.allclose(np.concatenate([v1, v2], -1), np.asarray(want),
                       rtol=1e-5, atol=1e-6)


def test_the_qk_mean_under_four_query_heads_a_kv_head():
    s, nq, nkv, d = 5, 8, 2, 16
    ks = jax.random.split(jax.random.key(2), 2)
    qt = jax.random.normal(ks[0], (s, nq, d))
    kt = jax.random.normal(ks[1], (s, nkv, d))
    m_q, m_k = zr.qk_mean(qt, kt, nq // nkv)
    for h in range(nq):
        assert jnp.allclose(m_q[:, h], 0.5 * (qt[:, h] + kt[:, h // 4]))
    for j in range(nkv):
        assert jnp.allclose(
            m_k[:, j], 0.5 * (qt[:, 4 * j:4 * j + 4].mean(axis=1) + kt[:, j]),
            atol=1e-6)
    # the program's: zero convolution output, unit temperature, no
    # rotation at position 0 -> the normalised means themselves
    q, k = zmodel.cca_qk(
        qt.reshape(1, s, nq * d), kt.reshape(1, s, nkv * d),
        jnp.zeros((1, s, (nq + nkv) * d)), jnp.ones((nkv,)),
        nq=nq, nkv=nkv, d=d, theta=5e6, rot=8)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    assert jnp.allclose(q[0, 0], np.sqrt(d) * unit(m_q[0]), atol=1e-5)
    assert jnp.allclose(k[0, 0], np.sqrt(d) * unit(m_k[0]), atol=1e-5)


def test_rope_turns_the_first_half_of_a_head_and_nothing_else():
    x = jax.random.normal(jax.random.key(3), (1, 9, 2, 128))
    out = zmodel.partial_rope(x, 5e6, 64)
    assert float(jnp.abs(out[..., 64:] - x[..., 64:]).max()) == 0.0
    assert float(jnp.abs(out[:, 1:, :, :64] - x[:, 1:, :, :64]).max()) > 0.1
    assert float(jnp.abs(out[:, 0] - x[:, 0]).max()) == 0.0   # angle 0
    # a rotation: pairs (i, i + 32) keep their length
    a, b = out[..., :32], out[..., 32:64]
    assert jnp.allclose(a * a + b * b,
                        x[..., :32] ** 2 + x[..., 32:64] ** 2, atol=1e-4)
    assert jnp.allclose(out[0], zr.rope(x[0], 5e6, 64), atol=1e-6)


def test_the_temperature_scales_the_keys_of_its_kv_head_only():
    s, nq, nkv, d = 4, 4, 2, 16
    ks = jax.random.split(jax.random.key(4), 3)
    args = (jax.random.normal(ks[0], (1, s, nq * d)),
            jax.random.normal(ks[1], (1, s, nkv * d)),
            jax.random.normal(ks[2], (1, s, (nq + nkv) * d)))
    kw = dict(nq=nq, nkv=nkv, d=d, theta=5e6, rot=8)
    q1, k1 = zmodel.cca_qk(*args, jnp.asarray([1.0, 1.0]), **kw)
    q2, k2 = zmodel.cca_qk(*args, jnp.asarray([1.0, 3.0]), **kw)
    assert jnp.allclose(q1, q2) and jnp.allclose(k1[:, :, 0], k2[:, :, 0])
    assert jnp.allclose(3.0 * k1[:, :, 1], k2[:, :, 1], rtol=1e-5)
    assert jnp.allclose(jnp.linalg.norm(q1, axis=-1), np.sqrt(d), rtol=1e-5)


def _router(e=4, h=32, r=8, seed=5):
    paddle.seed(seed)
    router = nn.MLPRouter(h, r, e)
    for name in ("w1", "w2", "w3"):        # logits worth choosing between
        p = getattr(router, name)
        p._data = p._data * 40.0
    w = paddle.to_tensor(np.random.default_rng(seed).standard_normal(
        (64, h)).astype(np.float32))
    return router, w


def test_beta_moves_the_choice_and_not_the_gate_value():
    router, w = _router()
    with no_grad():
        ids0, g0 = (np.asarray(t._data) for t in router(w))
        router.beta._data = jnp.asarray([0.0, 0.0, 0.0, 10.0])
        ids1, g1 = (np.asarray(t._data) for t in router(w))
    assert len(set(ids0.reshape(-1).tolist())) > 1
    assert (ids1 == 3).all()                  # the bias decides the choice
    # ... and the gate is still the chosen expert's probability, below 1
    moved = ids0[:, 0] != 3
    assert moved.any() and (g1[moved] < g0[moved]).all()
    assert (g1 > 0).all() and (g1 < 1).all()
    # where the choice did not move, neither did the gate value
    assert np.allclose(g1[~moved], g0[~moved])


def test_a_reference_that_follows_a_routing_meets_the_given_experts():
    """``route(..., forced)``: the expert is the given one, the gate the
    reference's own probability OF it, the own choice still reported."""
    ks = jax.random.split(jax.random.key(6), 5)
    p = {"router.wd": jax.random.normal(ks[0], (32, 8)),
         "router.w1": jax.random.normal(ks[1], (8, 8)),
         "router.w2": jax.random.normal(ks[2], (8, 8)),
         "router.w3": jax.random.normal(ks[3], (8, 4)),
         "beta": jnp.asarray([0.0, 0.3, 0.0, 0.0])}
    p.update({f"router.b{n}": jnp.zeros((4 if n == "3" else 8,))
              for n in "d123"})
    w = jax.random.normal(ks[4], (40, 32))
    expert, gate, own, probs = zr.route(p, w)
    assert (expert == own).all()
    assert (own == jnp.argmax(probs + p["beta"], -1)).all()
    forced = (own + 1) % 4
    expert2, gate2, own2, _ = zr.route(p, w, forced)
    assert (expert2 == forced).all() and (own2 == own).all()
    rows = np.arange(40)
    assert np.allclose(gate2, np.asarray(probs)[rows, np.asarray(forced)])
    assert not np.allclose(gate2, gate)


def test_a_followed_routing_decides_which_expert_computes_a_token(cfg, ids):
    free = zaya.reference(cfg, SEED)
    free.logits(ids[0])
    own = np.stack([e for e, _ in free.routing])          # [blocks, B, S]
    # told its own choice, the reference is the free-running one ...
    same = zaya.reference(cfg, SEED, follow=[own, own])
    other = zaya.reference(cfg, SEED, follow=[(own + 1) % 4])
    grads = {}
    for ref in (free, same, other):
        for group, g in ref.loss_and_grads(ref.get, *ids):
            if group == "h.1.dn":
                grads[ref] = np.asarray(g["w"])
    assert same.loss == pytest.approx(free.loss, rel=1e-6)
    assert np.allclose(grads[same], grads[free], rtol=1e-5, atol=1e-9)
    # ... told another, it computes another model and still says what it
    # would have chosen itself on those rows
    assert abs(other.loss - free.loss) > 1e-6
    assert not np.allclose(grads[other], grads[free], rtol=1e-3, atol=1e-9)
    assert (other.first_routing[0][0] == own[0]).all()    # block 0's input
    with pytest.raises(ValueError):                       # one forward given
        next(other.loss_and_grads(other.get, *ids))


def test_the_benchmarks_balancing_rule_moves_beta_against_the_load(toy):
    beta = jnp.asarray([0.1, -0.1, 0.0, 0.0], jnp.float32)
    ids = jnp.asarray([0] * 12 + [1] * 4 + [2] * 0 + [3] * 0).reshape(4, 4)
    got = np.asarray(zaya.rebalanced(beta, ids, 0.01))
    # shares 3, 1, 0, 0 of the even 4 tokens: -1 (clipped), 0, +1, +1
    assert np.allclose(got, [0.09, -0.1, 0.01, 0.01], atol=1e-7)
    # a reference that follows routings moves its beta along them: the
    # seeded one at the first forward, one rule's step on at the second
    follow = [np.zeros((2, 2, 64), np.int32), np.ones((2, 2, 64), np.int32)]
    ref = zaya.reference(toy, SEED, follow=follow)
    rate = toy["router_balancing"]["rate"]
    seeded = np.asarray(ref.fixed(1, 0)["beta"])
    assert np.allclose(np.asarray(ref.fixed(1, 1)["beta"]) - seeded,
                       rate * np.asarray([-1.0, 1.0, 1.0, 1.0]), atol=1e-7)
    assert np.allclose(np.asarray(ref.fixed(1, 2)["beta"]) - seeded,
                       rate * np.asarray([0.0, 0.0, 2.0, 2.0]), atol=1e-7)
    # ... a free-running one keeps the seeded beta, as does a
    # configuration without the rule
    assert (np.asarray(zaya.reference(toy, SEED).fixed(1, 1)["beta"])
            == seeded).all()
    plain = {k: v for k, v in toy.items() if k != "router_balancing"}
    assert zaya.sizes(plain)["balance_rate"] == 0.0
    # the program's layers know no rule: a training forward leaves beta
    router, w = _router()
    before = np.asarray(router.beta._data).copy()
    router.train()
    router(w)
    assert (np.asarray(router.beta._data) == before).all()


def test_no_gradient_reaches_beta_and_the_gate_carries_the_routers():
    router, w = _router()
    ids, gates = router(w)
    gates.sum().backward()
    assert router.beta.stop_gradient
    assert getattr(router.beta, "grad", None) is None
    assert float(jnp.abs(router.w3.grad._data).max()) > 0.0
    assert float(jnp.abs(router.wd.grad._data).max()) > 0.0


def test_the_router_computes_in_float32_whatever_the_storage():
    router, w = _router()
    with no_grad():
        ids32, g32 = router(w)
        router._cast_params("bfloat16")
        stored = {n: p._data.astype(jnp.float32)
                  for n, p in router.named_parameters()}
        ids16, g16 = router(paddle.to_tensor(w._data.astype(jnp.bfloat16)))
    assert g16.dtype == np.float32
    # against float32 arithmetic on the SAME stored (bfloat16) values
    want = nn.layer.moe.mlp_router(
        w._data.astype(jnp.bfloat16).astype(jnp.float32),
        router.beta._data.astype(jnp.float32),
        *(stored[n] for n in ("wd", "bd", "w1", "b1", "w2", "b2", "w3", "b3")))
    assert np.allclose(np.asarray(g16._data), np.asarray(want[1]),
                       rtol=1e-5, atol=1e-6)


def test_dropless_every_token_once_and_any_order_of_tokens(program):
    """A permutation of the tokens gives the permuted output: no token's
    result depends on which others share its expert, or on how many."""
    model, _ = program
    layer = model.model.layers[0]
    rng = np.random.default_rng(7)
    w = rng.standard_normal((1, 96, 64)).astype(np.float32)
    perm = rng.permutation(96)
    before = np.asarray(layer.experts.tokens_per_expert._data).copy()
    with no_grad():
        ids, gates = layer.router(paddle.to_tensor(w))
        out = np.asarray(layer.experts(paddle.to_tensor(w), ids, gates)._data)
        ids2, gates2 = layer.router(paddle.to_tensor(w[:, perm]))
        out2 = np.asarray(layer.experts(paddle.to_tensor(w[:, perm]),
                                        ids2, gates2)._data)
    assert np.allclose(out2, out[:, perm], rtol=1e-5, atol=1e-7)
    after = np.asarray(layer.experts.tokens_per_expert._data)
    assert (after - before).sum() == 2 * 96     # each token exactly once
    # a capacity would show here: send EVERY token to one expert
    with no_grad():
        one = paddle.to_tensor(np.zeros((1, 96, 1), np.int32))
        full = np.asarray(layer.experts(paddle.to_tensor(w), one,
                                        gates)._data)
    assert (np.abs(full).max(axis=-1) > 0).all()    # none dropped


def test_trains_through_to_static_and_adamw_like_the_dense_model(toy):
    import paddle_tpu.optimizer as popt
    from paddle_tpu.analysis import recompile_guard

    cfg = toy
    trainer = zaya.Trainer(cfg, SEED)
    rng = np.random.default_rng(9)
    tok = rng.integers(0, cfg["held"]["vocab_rows"], (2, 33)).astype(np.int32)
    losses = [trainer.step(tok[:, :-1], tok[:, 1:]) for _ in range(2)]
    with recompile_guard() as guard:
        losses += [trainer.step(tok[:, :-1], tok[:, 1:]) for _ in range(5)]
    assert guard.count() == 0                  # two traces, then steady
    before = trainer.tokens_per_expert()
    losses.append(trainer.step(tok[:, :-1], tok[:, 1:]))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    assert isinstance(trainer.opt, popt.AdamW)
    counts = trainer.tokens_per_expert()
    assert counts.shape == (2, 4) and (counts.sum(axis=1) == 8 * 64).all()
    # the compiled step returns the expert every token met: the counter's
    # last increment is its histogram
    chosen = trainer.routing()
    assert chosen.shape == (2, 2, 32) and chosen.dtype == np.int32
    for block in range(2):
        assert (np.bincount(chosen[block].reshape(-1), minlength=4)
                == (counts - before)[block]).all()
    # no optimizer holds the frozen router; the family's balancing rule
    # ran inside the compiled step, on the float32 buffer beta
    seeded = dict(zip((l[2] for l in zaya._all_leaves(cfg)),
                      zaya.make_all(cfg, SEED)))
    named = dict(trainer.model.named_parameters())
    named.update(trainer.model.named_buffers())
    moved = {name for name, arr in seeded.items()
             if not bool((named[name]._data == arr).all())}
    betas = {n for n in seeded if n.endswith("router.beta")}
    assert {n for n in moved if ".router." in n} == betas and len(betas) == 2
    for name in betas:
        assert named[name]._data.dtype == jnp.float32
        step = np.abs(np.asarray(named[name]._data - seeded[name])).max()
        assert 0.0 < step <= 8 * cfg["router_balancing"]["rate"] + 1e-6
    # (a norm's or a temperature's 1.0 seldom rounds a bfloat16 step away)
    assert {n for n in seeded if n.endswith(("_proj.weight", "experts.w_gu",
                                             "embed_tokens.weight"))} <= moved
    held = {p.name for p in trainer.params}
    assert set(trainer.opt._accumulators["moment1"]) == held
    parameters = dict(trainer.model.named_parameters())
    assert len(held) == len(zaya.leaves(cfg)) == len(parameters) - 2 * 8
    assert set(trainer.grad_norms()) == {
        f"{l[0]}/{l[1]}" for l in zaya.leaves(cfg)}


def test_the_blocks_scopes_are_in_the_compiled_step(cfg):
    model, _ = zaya._build_model(cfg, 1)
    tok = jnp.zeros((1, 32), jnp.int32)

    def forward(tok):
        return model(paddle.to_tensor(tok))._data

    with no_grad():
        text = jax.jit(forward).lower(tok).as_text(debug_info=True)
    for scope in ("cca.project", "cca.conv", "cca.attention", "moe.router",
                  "moe.permute", "moe.experts", "moe.combine"):
        assert scope in text, scope


def test_config_keeps_the_published_names_and_refuses_what_it_cannot_do():
    c = ZayaConfig()
    assert (c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
            c.head_dim, c.num_experts, c.num_experts_per_tok,
            c.moe_intermediate_size, c.router_hidden_size, c.cca_time0,
            c.cca_time1, c.rope_theta, c.partial_rotary_factor) == (
        2048, 8, 2, 128, 16, 1, 2048, 256, 2, 2, 5e6, 0.5)
    with pytest.raises(NotImplementedError):
        ZayaForCausalLM(ZayaConfig.tiny(tie_word_embeddings=False))
    with pytest.raises(NotImplementedError):
        ZayaForCausalLM(ZayaConfig.tiny(num_experts_per_tok=2))
    model = ZayaForCausalLM(ZayaConfig.tiny())
    assert not hasattr(model, "init_cache")        # training path only
    assert "lm_head.weight" not in dict(model.named_parameters())
