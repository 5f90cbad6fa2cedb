"""``models/minicpm_sala.py`` (MiniCPMSALAForCausalLM, training path)
against its plain reference
``chipbench/families/minicpm_sala_reference.py`` on seeded weights, at a
toy size on the CPU (the lightning and sparse-attention kernels
interpreted): both kinds of mixer, two chunks of the recurrence, four key
blocks with one free pick, a held share of layers — and piece by piece
what each part must do, the muP scalings each caught when left out.

A token's set of blocks is discrete, so the reference FOLLOWS the sets
the program chose (the benchmark's way); its own selection is held to
the program's beside that.

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
from chipbench.families import minicpm_sala
from chipbench.families import minicpm_sala_reference as mr
from paddle_tpu.base.tape import no_grad
from paddle_tpu.models import MiniCPMSALAConfig, MiniCPMSALAForCausalLM
from paddle_tpu.models import minicpm_sala as mmodel

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2**31 + 11
SEQ = 256


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "tests", "chipbench", "configs",
                           "toy-minicpm-sala.json"), encoding="utf-8") as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def ids(cfg):
    rng = np.random.default_rng(0)
    tok = rng.integers(0, cfg["held"]["vocab_rows"],
                       (2, SEQ + 1)).astype(np.int32)
    return tok[:, :-1], tok[:, 1:]


def _program(cfg):
    model, params = minicpm_sala._build_model(cfg, SEED)
    model.float()          # the program in float32: only the code differs
    return model, params


@pytest.fixture(scope="module")
def program(cfg):
    return _program(cfg)


def _tables(model, ids):
    """The sets the program's tokens read, as the job hands them on."""
    tables = []
    with no_grad():
        logits = model(paddle.to_tensor(ids), tables)
    chosen = np.concatenate([np.asarray(t._data) for t in tables], axis=1)
    return np.asarray(logits._data), np.moveaxis(
        minicpm_sala.pack(chosen), 1, 0)


@pytest.fixture(scope="module")
def followed(program, ids):
    return _tables(program[0], ids[0])


@pytest.fixture(scope="module")
def wanted(cfg, ids, followed):
    """The reference's loss and every leaf's gradient, along the
    program's sets."""
    reference = minicpm_sala.reference(cfg, SEED, follow=[followed[1]])
    want = {}
    for group, grads in reference.loss_and_grads(reference.get, *ids):
        want.update({f"{group}/{k}": np.asarray(v) for k, v in grads.items()})
    return reference.loss, want, reference.first_routing


def test_the_toy_holds_both_kinds_of_mixer(cfg):
    z = minicpm_sala.sizes(cfg)
    # published layers 1-4 of 8
    assert z["layer_kinds"] == ["minicpm4", "lightning-attn",
                                "lightning-attn", "minicpm4"]
    assert abs(z["residual_scale"] - 1.4 / np.sqrt(8)) < 1e-12
    assert z["logit_divide"] == 4.0 and z["scale_emb"] == 12
    names = [f"{l[0]}/{l[1]}" for l in minicpm_sala.leaves(cfg)]
    assert {"h.0/wq", "h.0/wg", "h.0/q_norm.g", "h.1/lin.wq", "h.1/lin.wg",
            "h.1/lin.o_norm.g", "h.2/lin.k_norm.g", "h.3/wk", "h.3.w2/w",
            "embed/wte", "head/lm_head"} <= set(names)


def test_logits_agree_with_the_reference_and_so_do_the_sets(cfg, followed,
                                                            ids):
    got, sets = followed
    reference = minicpm_sala.reference(cfg, SEED)
    want = reference.logits(ids[0])       # by the reference's OWN sets
    assert got.shape == want.shape == (2, SEQ, 96)
    assert sets.shape == (4, 2, SEQ)      # 2 sparse layers x 2 kv groups
    own = np.concatenate([o for o, _ in reference.routing], axis=1)
    own = np.moveaxis(minicpm_sala.pack(np.where(own < 4, own, -1)), 1, 0)
    assert np.mean(own != sets) <= 0.01   # both float32: a rounding apart
    if (own == sets).all():
        assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
    table = minicpm_sala.unpack(sets)
    assert table.shape == (4, 2, SEQ, 3)
    assert (table[..., 0] == 0).all()                     # block 0 forced
    assert (table[:, :, :64, 1:] == 65535).all()          # one valid block
    assert (table[:, :, 192:, 2] == 3).all()              # its own block
    free = table[:, :, 192:, 1]
    assert ((free == 1) | (free == 2)).all() and len(np.unique(free)) == 2


def test_loss_and_every_leafs_gradient_agree(cfg, program, wanted, ids):
    """The reference's loss and its gradient in every leaf, and the
    counters read this forward's tables."""
    model, params = program
    loss = model.loss(paddle.to_tensor(ids[0]), paddle.to_tensor(ids[1]))
    loss.backward()
    ref_loss, want, _ = wanted
    assert abs(float(loss) - ref_loss) <= 1e-5 * ref_loss
    names = [f"{leaf[0]}/{leaf[1]}" for leaf in minicpm_sala.leaves(cfg)]
    assert sorted(names) == sorted(want) and len(names) == len(params)
    for name, p in zip(names, params):
        scale = np.abs(want[name]).max()
        assert scale > 0, name               # every leaf is reached
        assert np.abs(np.asarray(p.grad._data) - want[name]).max() \
            <= 1e-4 * scale, name
    _counters_are_one_forwards(model)


def _counters_are_one_forwards(model):
    # 2 rows x 2 kv groups x 256 tokens a sparse layer; 1, 2, 3, 3 blocks
    # a token of the four key blocks, of which the band (the first block
    # and a window of one) serves 1, 2, 2, 2
    assert (np.asarray(model.query_rows()) == 2 * 2 * SEQ).all()
    assert (np.asarray(model.blocks_chosen()) == 2.25 * 2 * 2 * SEQ).all()
    assert (np.asarray(model.band_blocks()) == 1.75 * 2 * 2 * SEQ).all()


def test_the_counters_are_the_last_forwards_and_do_not_add_up(program, ids):
    """An int32 sum over a run's steps would wrap after a thousand steps
    of 16,384 tokens: a second forward leaves the counters where the
    first put them."""
    model, _ = program
    with no_grad():
        for _ in range(2):
            model(paddle.to_tensor(ids[0]))
            _counters_are_one_forwards(model)


def test_the_family_refuses_a_recomputation_the_decoder_has_not(cfg):
    with pytest.raises(ValueError, match="recompute"):
        minicpm_sala._build_model(
            dict(cfg, training={"recompute": "layer"}), SEED)


@pytest.mark.parametrize("key, wrong", [("scale_emb", 1.0),
                                        ("scale_depth", 1.4 * 8 ** 0.5),
                                        ("dim_model_base", 64)],
                         ids=["scale_emb", "scale_depth", "logit_divide"])
def test_each_mup_scaling_is_caught_when_left_out(cfg, followed, ids, key,
                                                  wrong):
    """x_0 = 12 Embed, c = 1.4 / sqrt(8) on both residual branches,
    logits / (64 / 16): a program without one of them (the value that
    makes it a factor of 1) is far from the reference."""
    model, _ = _program(dict(cfg, **{key: wrong}))
    got, _ = _tables(model, ids[0])
    want = followed[0]       # the sound program's: the reference's to 1e-5
    assert np.abs(got - want).max() > 0.05 * np.abs(want).max()


def test_two_adamw_steps_through_the_compiled_step(cfg, ids):
    """``jit.to_static`` over model and AdamW (the family's trainer) in
    float32: both steps' losses, the first gradient's norm in every leaf,
    both moments and every leaf's change after two steps are the
    reference's, which follows the sets the compiled step returned."""
    o = dict(cfg["optimizer"], stochastic_rounding=False,
             moment_dtype="float32")
    toy = dict(cfg, optimizer=o, dtype="float32")
    trainer = minicpm_sala.Trainer(toy, SEED)
    rng = np.random.default_rng(1)
    tok = rng.integers(0, 96, (2, SEQ + 1)).astype(np.int32)
    batches = [ids, (tok[:, :-1], tok[:, 1:])]
    losses, follow = [], []
    for n, batch in enumerate(batches):
        losses.append(trainer.step(*batch))
        follow.append(trainer.routing())
        if n == 0:
            grads = trainer.grad_norms()
    assert follow[0].shape == (4, 2, SEQ) and follow[0].dtype.itemsize == 6
    want = minicpm_sala.reference_training(toy, SEED, batches, follow=follow)
    for got, ref in zip(losses, want["loss"]):
        assert abs(got - ref) <= 1e-5 * ref
    for name, norms in (("grad_norm", grads),
                        ("moment_norm", trainer.moment_norms()),
                        ("delta_norm", trainer.delta_norms())):
        assert sorted(norms) == sorted(want[name])
        for leaf, got in norms.items():
            assert abs(got - want[name][leaf]) <= 5e-3 * want[name][leaf], \
                (name, leaf)
    assert len(want["routing"]) == 4 and want["routing"][0][0].shape == \
        (2, SEQ)
    trainer.free()
    blocks, rows = minicpm_sala.sparse_counters()
    # the last step's alone: 2 rows x 2 kv groups x 256 tokens, 2.25 blocks
    assert rows == [2 * 2 * SEQ] * 2 and blocks == [2304] * 2


# -- piece by piece ----------------------------------------------------------


def test_a_set_of_64_is_one_element_and_back():
    rng = np.random.default_rng(3)
    table = np.full((2, 3, 5, 64), -1, np.int32)
    for row in table.reshape(-1, 64):
        n = rng.integers(1, 65)
        row[rng.permutation(64)[:n]] = rng.permutation(256)[:n]
    sets = minicpm_sala.pack(table)
    assert sets.shape == (2, 3, 5) and sets.dtype.itemsize == 128
    back = minicpm_sala.unpack(sets)
    assert back.shape == table.shape and back.dtype == np.int32
    for got, row in zip(back.reshape(-1, 64), table.reshape(-1, 64)):
        held = np.sort(row[row >= 0])
        assert (got[:len(held)] == held).all()            # ascending
        assert (got[len(held):] == 0xFFFF).all()          # padding last
    # a set is its members, whatever their order in the row
    shuffled = rng.permuted(table, axis=-1)
    assert (minicpm_sala.pack(shuffled) == sets).all()
    other = table.copy()
    other[0, 0, 0, np.argmax(other[0, 0, 0] >= 0)] = 300
    assert (minicpm_sala.pack(other) != sets).sum() == 1


def test_the_recurrence_token_by_token_is_the_kernels():
    """The reference's scan (never a chunked form) against the program's
    kernel, and its fault: a state zeroed every 64 tokens."""
    from paddle_tpu.ops.lightning_attention import lightning_attention

    ks = jax.random.split(jax.random.key(0), 3)
    q, k, v = (jax.random.normal(key, (256, 4, 128)) for key in ks)
    want = mr.recurrence(q, k, v)
    got = lightning_attention(q[None], k[None], v[None], mr.slopes(4))[0]
    assert float(jnp.abs(got - want).max()) <= 1e-4 * float(
        jnp.abs(want).max())
    forgot = mr.recurrence(q, k, v, forget=True)
    apart = jnp.concatenate([mr.recurrence(q[i:i + 64], k[i:i + 64],
                                           v[i:i + 64])
                             for i in range(0, 256, 64)])
    assert float(jnp.abs(forgot - apart).max()) <= 1e-5 * float(
        jnp.abs(want).max())
    assert float(jnp.abs(forgot - want).max()) > 0.1 * float(
        jnp.abs(want).max())


def test_the_references_selection_is_the_programs_rule():
    from paddle_tpu.ops.sparse_attention import select_blocks

    rule = dict(kernel_size=32, kernel_stride=16, block_size=64, topk=4,
                init_blocks=1, window_size=128)
    ks = jax.random.split(jax.random.key(1), 2)
    q = jax.random.normal(ks[0], (512, 4, 128))
    k = jax.random.normal(ks[1], (512, 2, 128))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(select_blocks(q[None], k[None], **rule))[0]
    want = np.asarray(mr.select(q, k, **rule))
    assert want.shape == got.shape == (2, 512, 4)
    assert (np.where(want < 8, want, -1) == got).all()
    # the "local" fault's sets: block 0 and the two up to the token's own
    must, valid = mr.forced_blocks(jnp.arange(512), 8, block_size=64,
                                   init_blocks=1, window_size=128)
    assert (np.asarray(must.sum(-1))[[0, 64, 128, 511]] == [1, 2, 3, 3]).all()
    assert (np.asarray(valid.sum(-1))[[0, 64, 511]] == [1, 2, 8]).all()


def test_norms_rope_and_gate_are_the_references():
    x = jax.random.normal(jax.random.key(4), (1, 8, 2 * 32)) * 3.0
    g = 1.0 + 0.1 * jax.random.normal(jax.random.key(5), (32,))
    plain = mmodel.head_norm(x, g, 1e-6, 2)
    want = mr._norm(x[0].reshape(8, 2, 32), g, 1e-6)
    np.testing.assert_allclose(np.asarray(plain[0]), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    turned = mmodel.head_norm(x, g, 1e-6, 2, 10000.0)
    np.testing.assert_allclose(np.asarray(turned[0]),
                               np.asarray(mr.rope(want, 10000.0, 32)),
                               rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(turned[:, 0] - plain[:, 0]).max()) == 0.0  # angle 0
    np.testing.assert_allclose(                                   # a rotation
        np.asarray(jnp.sum(turned * turned, -1)),
        np.asarray(jnp.sum(plain * plain, -1)), rtol=1e-5)
    o = jax.random.normal(jax.random.key(6), (1, 8, 2, 32))
    z = jax.random.normal(jax.random.key(7), (1, 8, 64))
    w = 1.0 + 0.1 * jax.random.normal(jax.random.key(8), (64,))
    np.testing.assert_allclose(
        np.asarray(mmodel.lightning_gate(o, z, w, 1e-6)[0]),
        np.asarray(mr._norm(o[0].reshape(8, 64), w, 1e-6)
                   * jax.nn.sigmoid(z[0])), rtol=1e-5, atol=1e-6)


def test_the_stack_is_declared_by_the_published_list():
    c = MiniCPMSALAConfig()          # MiniCPM-SALA's
    assert c.mixer_types.count("minicpm4") == 8
    assert [i for i, t in enumerate(c.mixer_types) if t == "minicpm4"] == \
        [0, 9, 16, 17, 22, 29, 30, 31]
    assert abs(c.residual_scale - 0.24749) < 1e-5
    with paddle.LazyGuard():
        model = MiniCPMSALAForCausalLM(MiniCPMSALAConfig(
            held_layers=4, vocab_rows=9181))
    assert model.model.layer_ids == [0, 1, 2, 3]
    assert [l.mixer_type for l in model.model.layers] == \
        ["minicpm4"] + ["lightning-attn"] * 3
    shapes = {n: tuple(p._lazy_init[1]) for n, p in model.named_parameters()}
    sparse, lin = "model.layers.0.self_attn.", "model.layers.1.linear_attn."
    assert shapes[sparse + "q_proj.weight"] == (4096, 4096)
    assert shapes[sparse + "k_proj.weight"] == (4096, 256)
    assert shapes[sparse + "z_proj.weight"] == (4096, 4096)
    assert shapes[sparse + "q_norm_weight"] == (128,)
    assert shapes[lin + "v_proj.weight"] == (4096, 4096)
    assert shapes[lin + "o_norm_weight"] == (4096,)
    assert shapes["model.layers.2.mlp.gate_proj.weight"] == (4096, 16384)
    assert shapes["lm_head.weight"] == (4096, 9181)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 1_184_654_336
    with pytest.raises(ValueError):
        MiniCPMSALAConfig(mixer_types=["minicpm4"] * 3)
    with pytest.raises(ValueError):
        MiniCPMSALAConfig(lightning_nkv=8)
