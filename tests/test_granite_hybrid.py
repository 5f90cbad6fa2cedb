"""``models/granite_hybrid.py`` (GraniteHybridForCausalLM, training path)
against its plain reference
``chipbench/families/granite_hybrid_reference.py`` on seeded weights, at a
toy size on the CPU (the scan's, the convolution's and flash's kernels
interpreted): both kinds of mixer, four chunks of the recurrence (two
published chunks of 256: the fault ``forget`` drops one carry), a held
share of layers — and piece by piece what each part must do, each of
Granite's four multipliers caught when left out.

Tolerances: program and reference both run in float32 here (the
program's model is widened with ``model.float()``), so only the order of
sums differs: logits and loss agree to 1e-5 of their scale, a gradient
leaf to 1e-4 of its largest entry.

``conftest._SLOW_FILES`` does not list this file: it guards new code.
"""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from chipbench.families import granite_hybrid
from chipbench.families import granite_hybrid_reference as gr
from paddle_tpu.base.tape import no_grad
from paddle_tpu.models import GraniteHybridConfig, GraniteHybridForCausalLM
from paddle_tpu.models import granite_hybrid as gmodel

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2**31 + 11
SEQ = 512


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "tests", "chipbench", "configs",
                           "toy-granite-hybrid.json"),
              encoding="utf-8") as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def ids(cfg):
    rng = np.random.default_rng(0)
    tok = rng.integers(0, cfg["held"]["vocab_rows"],
                       (1, SEQ + 1)).astype(np.int32)
    return tok[:, :-1], tok[:, 1:]


def _program(cfg):
    model, params = granite_hybrid._build_model(cfg, SEED)
    model.float()          # the program in float32: only the code differs
    return model, params


@pytest.fixture(scope="module")
def program(cfg):
    return _program(cfg)


def _logits(model, ids):
    with no_grad():
        return np.asarray(model(paddle.to_tensor(ids))._data)


@pytest.fixture(scope="module")
def got_logits(program, ids):
    return _logits(program[0], ids[0])


def _grads(reference, ids):
    want = {}
    for group, grads in reference.loss_and_grads(reference.get, *ids):
        want.update({f"{group}/{k}": np.asarray(v) for k, v in grads.items()})
    return reference.loss, want


@pytest.fixture(scope="module")
def wanted(cfg, ids):
    """The reference's loss and every leaf's gradient."""
    return _grads(granite_hybrid.reference(cfg, SEED), ids)


def test_the_toy_holds_both_kinds_of_mixer_in_the_lists_order(cfg):
    z = granite_hybrid.sizes(cfg)
    # published layers 1-4 of 8: layer_types[1:5]
    assert z["layer_kinds"] == ["mamba", "mamba", "attention", "mamba"]
    assert (z["embed_scale"], z["residual_scale"], z["attn_scale"],
            z["logit_divide"]) == (12, 0.22, 0.0625, 8)
    names = [f"{l[0]}/{l[1]}" for l in granite_hybrid.leaves(cfg)]
    assert {"embed/wte", "h.0/ssm.w_in", "h.0/ssm.conv.w", "h.0/ssm.conv.b",
            "h.0/ssm.dt_bias", "h.0/ssm.a_log", "h.0/ssm.d",
            "h.0/ssm.norm.g", "h.0/ssm.w_out", "h.2/wq", "h.2/wk", "h.2/wo",
            "h.3/w1", "head/norm_f.g"} <= set(names)
    assert "head/lm_head" not in names            # the head is embed/wte
    assert not any(n.startswith("h.2/ssm") or n.startswith("h.1/wq")
                   for n in names)


def test_layer_types_order_is_honoured():
    types = ["mamba", "attention", "attention", "mamba"]
    model = GraniteHybridForCausalLM(GraniteHybridConfig.tiny(
        layer_types=types, first_layer=1, held_layers=3))
    assert model.model.layer_ids == [1, 2, 3]
    assert [layer.layer_type for layer in model.model.layers] == types[1:]
    assert [hasattr(layer, "mamba") for layer in model.model.layers] == [
        False, False, True]
    with pytest.raises(ValueError, match="names every published layer"):
        GraniteHybridConfig.tiny(layer_types=types[:3])
    with pytest.raises(ValueError, match="layer_types"):
        GraniteHybridConfig.tiny(layer_types=["mamba", "rwkv"] * 2)
    with pytest.raises(ValueError, match="one group"):
        GraniteHybridConfig.tiny(mamba_n_groups=2)
    # the published list: attention at 5, 15, 25, 35 of 40
    full = GraniteHybridConfig()
    assert [i for i, t in enumerate(full.layer_types)
            if t == "attention"] == [5, 15, 25, 35]
    assert full.head_dim == 64


def test_logits_agree_with_the_reference(cfg, got_logits, ids):
    want = np.asarray(granite_hybrid.reference(cfg, SEED).logits(ids[0]))
    assert got_logits.shape == want.shape == (1, SEQ, 96)
    assert np.abs(got_logits - want).max() <= 1e-5 * np.abs(want).max()


def test_loss_and_every_leafs_gradient_agree(cfg, program, wanted, ids):
    model, params = program
    loss = model.loss(paddle.to_tensor(ids[0]), paddle.to_tensor(ids[1]))
    loss.backward()
    ref_loss, want = wanted
    assert abs(float(loss) - ref_loss) <= 1e-5 * ref_loss
    names = [f"{leaf[0]}/{leaf[1]}" for leaf in granite_hybrid.leaves(cfg)]
    assert sorted(names) == sorted(want) and len(names) == len(params)
    for name, p in zip(names, params):
        scale = np.abs(want[name]).max()
        assert scale > 0, name               # every leaf is reached
        assert np.abs(np.asarray(p.grad._data) - want[name]).max() \
            <= 1e-4 * scale, name
    # the counter is this forward's: per Mamba layer the state's share
    # that outlives a published chunk, by the reference's own arithmetic
    carry = np.asarray(model.chunk_carry())
    assert carry.shape == (3,) and ((0.0 < carry) & (carry < 1.0)).all()


def test_the_tied_leafs_gradient_is_the_sum_of_both_uses(cfg, wanted, ids):
    """``embed/wte`` is read by the lookup and by the head: the
    reference's gradient of each use alone, added, is the leaf's."""
    reference = granite_hybrid.reference(cfg, SEED)
    tokens, labels = jnp.asarray(ids[0][0]), jnp.asarray(ids[1][0])
    xs = reference._forward(reference.get, jnp.asarray(ids[0]))
    _, (_, head_use, dx) = reference._head_bwd(
        reference.get("head"), reference.get("embed")["wte"], xs[-1][0],
        labels)
    for i in reversed(range(len(reference.kinds))):
        _, dx = reference._block_bwd[reference.kinds[i]](
            reference.get(f"h.{i}"), xs[i][0], dx)
    lookup_use = jnp.zeros_like(head_use).at[tokens].add(12 * dx)
    whole = wanted[1]["embed/wte"]
    scale = np.abs(whole).max()
    assert np.abs(np.asarray(head_use + lookup_use) - whole).max() \
        <= 1e-5 * scale
    for use in (head_use, lookup_use):       # neither use alone is it
        assert np.abs(np.asarray(use) - whole).max() > 1e-2 * scale


@pytest.mark.parametrize("key, other", [
    ("embedding_multiplier", 1.0), ("residual_multiplier", 1.0),
    ("attention_multiplier", 64.0), ("logits_scaling", 1.0)])
def test_each_of_the_four_multipliers_changes_the_result(cfg, got_logits,
                                                         ids, key, other):
    changed = dict(cfg, **{key: other})
    model, _ = _program(changed)
    got = _logits(model, ids[0])
    scale = np.abs(got_logits).max()
    assert np.abs(got - got_logits).max() > 1e-3 * scale
    # and the reference, told the same, follows
    want = np.asarray(granite_hybrid.reference(changed, SEED).logits(ids[0]))
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


def test_attention_has_no_position_term(cfg):
    """Feed one period of 128 tokens twice. Without a position term the
    second copy's keys are the first's again, so the last row's softmax
    runs over the same 128 keys, each counted twice, and gives row 127's
    output; any position term would tell the two copies apart."""
    from paddle_tpu.models.granite_hybrid import NoPEAttention

    paddle.seed(3)
    attn = NoPEAttention(GraniteHybridConfig.tiny())
    rng = np.random.default_rng(1)
    period = rng.standard_normal((1, 128, 64)).astype(np.float32)
    twice = np.concatenate([period, period], axis=1)
    with no_grad():
        out = np.asarray(attn(paddle.to_tensor(twice))._data)
    # rows inside the second copy see a part of it once more than the
    # rest: no position term does not mean no context
    assert np.abs(out[:, 128:] - out[:, :128]).max() > 1e-3
    assert np.abs(out[:, 255] - out[:, 127]).max() <= 1e-5 * np.abs(out).max()
    assert not hasattr(attn, "rope") and attn.scale == 0.015625


def test_the_gate_comes_before_the_norm():
    y = jnp.asarray(np.random.default_rng(2).standard_normal((1, 4, 128)),
                    jnp.float32)
    z = jnp.asarray(np.random.default_rng(3).standard_normal((1, 4, 128)),
                    jnp.float32)
    g = jnp.ones((128,))
    got = gmodel.ssm_gate(y, z, g, 1e-5)
    gated = np.asarray(y).reshape(1, 4, 128) * np.asarray(
        z / (1 + jnp.exp(-z)))
    want = gated / np.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5)
    assert np.abs(np.asarray(got) - want).max() <= 1e-5
    after = np.asarray(y).reshape(1, 4, 128)
    after = after / np.sqrt((after ** 2).mean(-1, keepdims=True) + 1e-5) \
        * np.asarray(z / (1 + jnp.exp(-z)))
    assert np.abs(np.asarray(got) - after).max() > 1e-2


def test_chunk_carry_is_the_states_share_that_outlives_a_chunk():
    rates = jnp.full((1, 512, 2), 0.01)
    a = jnp.array([-1.0, -4.0])
    want = (np.exp(-2.56) + np.exp(-10.24)) / 2
    assert abs(float(gmodel.chunk_carry(rates, a, 256)) - want) <= 1e-6
    # a tail that is no whole chunk is left out
    assert abs(float(gmodel.chunk_carry(
        jnp.full((1, 600, 2), 0.01), a, 256)) - want) <= 1e-6


@pytest.mark.parametrize("precision", ["fp8", "forget"])
def test_the_control_and_the_fault_move_the_reference(cfg, wanted, ids,
                                                      precision):
    """float8 operands, and a state zeroed at every 256th token, each give
    another loss and other gradients than the float32 reference: far
    beyond what separates program and reference (1e-4)."""
    loss, want = wanted
    low_loss, low = _grads(granite_hybrid.reference(cfg, SEED, precision),
                           ids)
    assert low_loss != loss
    moved = [n for n in want
             if np.abs(low[n] - want[n]).max() > 1e-2 * np.abs(want[n]).max()]
    assert {"h.0/ssm.a_log", "h.0/ssm.dt_bias"} <= set(moved)
    if precision == "fp8":       # every matmul's operands
        assert {"h.0/ssm.w_in", "h.2/wq", "embed/wte"} <= set(moved)
    assert gr.FORGET_EVERY == 256 == cfg["mamba_chunk_size"]
