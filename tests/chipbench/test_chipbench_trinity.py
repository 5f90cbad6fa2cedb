"""Family ``afmoe`` in the benchmark: what ``BENCHMARK.json`` and the
configuration's file promise for Trinity-Large-Preview, a toy
configuration through the ``train_routed`` job on the CPU, the readers
PR 32 brought on hand-made traces and ring events, and ``shapes_afmoe``'s
counts against brute force."""
import copy
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, peaks, shapes, shapes_afmoe
from chipbench.families import afmoe
from chipbench.jobs import train_routed
from toy_bench import BENCH, ROOT

CELL = "train-trinity-5l-8k"
V5E = peaks.peaks_for("TPU v5 lite")
NEW = ("flash_fwd_roofline.window", "flash_bwd_roofline.window",
       "moe_gmm_roofline.held", "moe_held_rows_ratio")


def _toy_bench():
    """The toy benchmark plus an afmoe cell, added as a later PR adds one:
    a configuration file, a traffic file, entries."""
    b = copy.deepcopy(BENCH)
    b["configs"].append({
        "name": "toy-afmoe", "source": "none (a test's toy)",
        "file": "tests/chipbench/configs/toy-afmoe.json", "reduced": [],
        "why": "CPU tests"})
    b["workloads"].append({
        "name": "toy-afmoe-train", "config": "toy-afmoe",
        "traffic": "toy-afmoe-train", "chips": 1, "why": "family afmoe"})
    b["end_to_end"][0]["workloads"].append("toy-afmoe-train")
    for name in NEW + ("moe_expert_load_peak", "flash_fwd_roofline.gqa",
                       "flash_bwd_roofline.gqa"):
        counter = name.startswith("moe_") and "roofline" not in name
        b["per_layer"].append({
            "name": name, "unit": "ratio" if counter else "%",
            "better": "lower",
            "source": "program_counter" if counter else "device_trace",
            "layer": "x", "moves": "train_tokens_per_s",
            "workloads": ["toy-afmoe-train"]})
    return b


@pytest.fixture(scope="module")
def bench():
    return harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


@pytest.fixture(scope="module")
def cell(bench):
    return harness.Cell(bench, CELL)


@pytest.fixture(scope="module")
def toy_run():
    return harness.run(_toy_bench(), "toy-afmoe-train", 2**31 + 5, 0.5, True,
                       allow_cpu=True, control="fp8")


# -- the toy cell through the harness ----------------------------------------


def test_toy_afmoe_cell_end_to_end(toy_run):
    line, detail = toy_run
    assert line["correct"] is True, detail["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {c["name"] for c in detail["checks"]} == {
        "loss_gap.step1", "loss_gap.step2", "grad_norm_gap.worst_leaf",
        "moment_norm_gap.worst_leaf", "delta_norm_gap.worst_matrix",
        "compiles_in_window", "route_flip_share.mean"}
    # off the chip only counts: no share of a roofline, no time
    assert set(line["metrics"]) == {
        "compiles_in_window.train", "moe_expert_load_peak",
        "moe_held_rows_ratio"}
    assert 1.0 <= line["metrics"]["moe_expert_load_peak"]["value"] <= 4.0
    # the held four of eight experts got about their half of the pairs
    assert 0.4 <= line["metrics"]["moe_held_rows_ratio"]["value"] <= 1.6


def test_the_control_fails_the_comparison_the_program_passes(toy_run):
    _, detail = toy_run
    notes = detail["notes"]
    by_name = {c["name"]: c for c in detail["checks"]}
    for name in ("grad_norm_gap.worst_leaf", "moment_norm_gap.worst_leaf"):
        check = by_name[name]
        assert check["ok"] and notes["control." + name] > check["limit"]
    assert 0.0 <= notes["control.route_flip_share.mean"] <= 1.0
    assert notes["program_memory_peak_bytes"] == 0      # the CPU has none


def test_both_counters_are_read_once_after_the_window(toy_run):
    line, _ = toy_run
    counts, pairs = afmoe.moe_counters()
    counts, pairs = np.asarray(counts), np.asarray(pairs)
    # [routed blocks, held] and [routed blocks]; every step of the run,
    # each token twice (top-2) a block
    assert counts.shape == (3, 4) and pairs.shape == (3,)
    assert (pairs == (line["attempted"] + 3) * 2 * 128 * 2).all()
    assert (counts.sum(axis=1) < pairs).all() and (counts > 0).all()


def test_a_train_step_that_returns_its_state_unchanged_is_not_correct(
        monkeypatch):
    real = afmoe.Trainer.step

    def frozen(self, ids, labels):
        keep = [jnp.array(p._data, copy=True) for p in self.params]
        loss = real(self, ids, labels)
        for p, a in zip(self.params, keep):
            p._data = a
        return loss

    monkeypatch.setattr(afmoe.Trainer, "step", frozen)
    line, detail = harness.run(_toy_bench(), "toy-afmoe-train", 77, 0.2,
                               False, allow_cpu=True)
    assert line["correct"] is False
    assert "delta_norm_gap.worst_matrix" in {
        c["name"] for c in detail["checks"] if not c["ok"]}


def test_a_set_packs_into_one_integer_and_back():
    rng = np.random.default_rng(0)
    ids = np.stack([rng.permutation(256)[:4] for _ in range(50)])
    codes = afmoe.pack(ids.reshape(5, 10, 4), 256)
    assert codes.shape == (5, 10) and codes.dtype == np.int64
    back = afmoe.unpack(codes, 256, 4)
    assert (back == np.sort(ids, -1).reshape(5, 10, 4)).all()
    # the order a set is given in is not part of it; one member is
    assert (afmoe.pack(ids[:, ::-1], 256) == codes.reshape(-1)).all()
    other = ids.copy()
    other[:, 0] = (other[:, 0] + 1) % 256
    differs = afmoe.pack(other, 256) != codes.reshape(-1)
    assert differs.sum() >= 45           # (a swap inside the set is none)
    assert train_routed.flip_share(codes[None], codes[None]) == 0.0
    assert afmoe.pack([[255, 255, 255, 255]], 256)[0] == 2**32 - 1


def test_the_balancing_rule_moves_the_held_experts_alone():
    bias = jnp.zeros((16,))
    ids = jnp.asarray([[2, 3, 3, 9]] * 8)          # 32 pairs, even share 2
    out = np.asarray(afmoe.rebalanced(bias, ids, 0.01, 2, 4))
    assert (out[:2] == 0).all() and (out[6:] == 0).all()
    # expert 2: 8 pairs (4x its share) -> down; 3: 16 -> down (clipped);
    # 4 and 5: none -> up by the whole rate
    np.testing.assert_allclose(out[2:6], [-0.01, -0.01, 0.01, 0.01],
                               rtol=1e-6)


# -- the readers on hand-made traces -----------------------------------------


def _facts(cell, events, **kw):
    return dict({"trace": {"devices": {0: events}, "spans": []},
                 "family": cell.family, "config": cell.config, "batch": 1,
                 "seq": 8192, "peaks": V5E, "on_chip": True}, **kw)


def _flash_events(tag, fwd_us, dq_us, dkv_us, n):
    ev = []
    for i in range(n):
        ev += [(f"%{tag}_fwd.{i} = ", i * 10**7, int(fwd_us * 1e3)),
               (f"%{tag}_bwd_dq.{i} = ", i * 10**7 + 10**6, int(dq_us * 1e3)),
               (f"%{tag}_bwd_dkv.{i} = ", i * 10**7 + 2 * 10**6,
                int(dkv_us * 1e3))]
    return ev


def test_the_window_readers_count_the_pairs_inside_the_window(cell):
    fwd = cell.reader("flash_fwd_roofline.window")
    bwd = cell.reader("flash_bwd_roofline.window")
    one = shapes_afmoe.flash_window_fwd_flops(8192, 48, 128, 4096) \
        / V5E.bf16_flops * 1e6
    window = _flash_events("flash_window", 2 * one, 2 * one, 2 * one, 4)
    # the one full layer's events beside them, four times slower: the
    # window readers do not see them, the causal readers see them alone
    full = shapes.flash_fwd_flops(8192, 48, 128) / V5E.bf16_flops * 1e6
    causal = _flash_events("flash", 4 * full, 4 * full, 4 * full, 1)
    facts = _facts(cell, window + causal)
    assert fwd(facts) == pytest.approx(50.0, rel=1e-3)
    assert bwd(facts) == pytest.approx(50.0, rel=1e-3)
    assert cell.reader("flash_fwd_roofline.gqa")(facts) == \
        pytest.approx(25.0, rel=1e-3)
    assert cell.reader("flash_bwd_roofline.gqa")(facts) == \
        pytest.approx(25.0, rel=1e-3)
    # a recomputed block runs its forward twice: twice the events, twice
    # the work, the same share
    again = window + [(f"%flash_window_fwd.{9 + i} = ", 10**9 + i * 10**7,
                       int(2 * one * 1e3)) for i in range(4)]
    assert fwd(_facts(cell, again)) == pytest.approx(50.0, rel=1e-3)
    # nothing to read: no trace, a program without the kernels (as the
    # parent commit is), a family without a window
    assert fwd(_facts(cell, [], trace=None)) is None
    assert bwd(_facts(cell, causal)) is None and fwd(_facts(cell, causal)) \
        is None
    zaya = harness.Cell(harness.load_json(os.path.join(
        ROOT, "BENCHMARK.json")), "train-zaya1-6l-4k")
    assert fwd(_facts(zaya, window)) is None
    assert bwd(_facts(zaya, window)) is None


def _record(counts, pairs):
    from paddle_tpu import obs

    obs.instant("moe.tokens_per_expert", counts=counts)
    obs.instant("moe.pairs_routed", pairs=pairs)


def test_the_held_readers_take_their_rows_from_the_counters(cell):
    ratio = cell.reader("moe_held_rows_ratio")
    gmm = cell.reader("moe_gmm_roofline.held")
    # 10 steps of 8192 x 4 pairs a block, four routed blocks; the held 8
    # of 256 got exactly their share: 1024 rows a block and step
    pairs = [10 * 32768] * 4
    _record([[1280] * 8] * 4, pairs)
    assert ratio(_facts(cell, [])) == pytest.approx(1.0)
    z = afmoe.sizes(cell.config)
    per_event = sum(shapes_afmoe.gmm_bound_seconds(t, k, n, 8, 2, V5E)
                    for t, k, n in shapes_afmoe.held_gmm_calls(z, 1024.0)) / 2
    us = 1e6 * per_event

    def events(n_gmm, n_tgmm):
        return ([(f"%moe_gmm.{i} = bf16[32768,6144]{{1,0}} custom-call(",
                  i * 10**7, int(2 * us * 1e3)) for i in range(n_gmm)]
                + [(f"%moe_tgmm.{i} = bf16[8,3072,6144]{{2,1,0}} custom-call(",
                    10**10 + i * 10**7, int(2 * us * 1e3))
                   for i in range(n_tgmm)])

    # every event at twice its bound: 50%, whether a block's forward ran
    # once (4 moe_gmm a block and step) or was recomputed (6)
    assert gmm(_facts(cell, events(16, 8))) == pytest.approx(50.0, rel=1e-3)
    assert gmm(_facts(cell, events(24, 8))) == pytest.approx(50.0, rel=1e-3)
    # twice the rows for the same time: more work was done
    _record([[2560] * 8] * 4, pairs)
    assert ratio(_facts(cell, [])) == pytest.approx(2.0)
    assert gmm(_facts(cell, events(16, 8))) > 50.0
    # the BYTES bind at these rows: the matrices of 8 experts for 1024 rows
    for t, k, n in shapes_afmoe.held_gmm_calls(z, 1024.0):
        assert 2.0 * t * k * n / V5E.bf16_flops \
            < 0.6 * shapes_afmoe.gmm_bound_seconds(t, k, n, 8, 2, V5E)
    # nothing to read: no events, no trace, a family without counters
    assert gmm(_facts(cell, [("%fusion.1 = f32[8]{0} fusion(", 0, 5)])) is None
    assert gmm(_facts(cell, [], trace=None)) is None
    zaya = harness.Cell(harness.load_json(os.path.join(
        ROOT, "BENCHMARK.json")), "train-zaya1-6l-4k")
    assert ratio(_facts(zaya, [])) is None
    assert gmm(_facts(zaya, events(16, 8))) is None


def test_the_load_peak_reads_the_held_experts_alone(cell):
    # the accepted reader, as it stands: busiest held expert over the even
    # share AMONG the held
    _record([[128] * 8, [256] + [110] * 7, [128] * 8, [128] * 8], [40960] * 4)
    assert cell.reader("moe_expert_load_peak")({}) == pytest.approx(
        256 * 8 / (256 + 770))


# -- arithmetic and promises -------------------------------------------------


def test_shapes_against_brute_force():
    for seq, window in ((64, 16), (100, 37), (50, 50), (40, 400), (33, 1)):
        t, j = np.arange(seq)[:, None], np.arange(seq)[None, :]
        assert shapes_afmoe.window_pairs(seq, window) == \
            int(((j <= t) & (t - j < window)).sum())
    assert shapes_afmoe.window_pairs(64, None) == shapes.causal_pairs(64)
    assert shapes_afmoe.window_pairs(8192, 4096) == 25_167_872
    assert shapes.causal_pairs(8192) == 33_558_528
    assert shapes_afmoe.flash_window_fwd_flops(8192, 48, 128, None) == \
        shapes.flash_fwd_flops(8192, 48, 128)
    assert shapes_afmoe.flash_window_bwd_flops(8192, 48, 128, None) == \
        shapes.flash_bwd_flops(8192, 48, 128)


def test_parameters_and_flops_of_the_configuration_as_run(cell):
    cfg = cell.config
    assert afmoe.total_params(cfg) == cfg["params_as_run"] == 1_603_993_856
    z = afmoe.sizes(cfg)
    assert z["layer_kinds"] == [(4096, False), (4096, True), (None, True),
                                (4096, True), (4096, True)]
    by_group = {}
    for g, _, _, shape, kind, _ in afmoe._all_leaves(cfg):
        if kind != "buffer":
            by_group[g.split(".gu")[0].split(".dn")[0]] = by_group.get(
                g.split(".gu")[0].split(".dn")[0], 0) + int(np.prod(shape))
    assert by_group["h.0"] == 176_173_312            # the dense layer
    assert by_group["h.1"] == 318_517_504            # a routed layer
    assert by_group["embed"] == 76_873_728
    assert shapes_afmoe.attention_params(z) == 62_914_816 - 256
    # a token meets 4 x 8 / 256 of an expert in a routed block
    assert shapes_afmoe.expert_visits_per_token(z) == 0.125
    met = shapes_afmoe.matmul_params_met(z)
    assert met == pytest.approx(635.3e6, rel=1e-3)
    assert shapes_afmoe.attention_flops_per_token(z, 8192) == \
        pytest.approx(1.21e9, rel=5e-3)
    assert afmoe.train_flops_per_token(cfg, 8192) == \
        pytest.approx(5.02e9, rel=3e-3)
    # the experts' gradients reach HBM: 905,969,664 parameters
    experts = sum(int(np.prod(l[3])) for l in afmoe._all_leaves(cfg)
                  if l[1] == "w")
    assert experts == 905_969_664


# the catalog row ``Trinity-Large-Preview`` (model-configs guide,
# architectures.jsonl), copied: a test reads nothing outside its checkout
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 3072, "intermediate_size": 12288,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 15,
    "load_balance_coeff": 5e-05, "max_position_embeddings": 262144,
    "model_type": "afmoe", "moe_intermediate_size": 3072,
    "mup_enabled": True, "n_group": 1, "num_attention_heads": 48,
    "num_dense_layers": 6, "num_expert_groups": 1, "num_experts": 256,
    "num_experts_per_tok": 4, "num_hidden_layers": 60,
    "num_key_value_heads": 8, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.448,
    "score_func": "sigmoid", "sliding_window": 4096,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}
SOURCE = ("https://huggingface.co/arcee-ai/Trinity-Large-Preview/blob/main/"
          "config.json")


def test_the_configuration_keeps_every_published_key(bench, cell):
    cfg = cell.config
    for key, value in PUBLISHED.items():
        assert cfg[key] == value, key
    assert cfg["source"] == SOURCE
    assert cfg["layer_types"][5:10] == [
        "sliding_attention", "sliding_attention", "full_attention",
        "sliding_attention", "sliding_attention"]
    assert cfg["held"] == {"layers": 5, "first_layer": 5, "experts": 8,
                           "first_expert": 0, "vocab_rows": 25024}
    assert cfg["held"]["vocab_rows"] * 8 == cfg["vocab_size"]
    assert cfg["held"]["experts"] * 32 == cfg["num_experts"]
    entry = [c for c in bench["configs"] if c["name"] == "trinity-large-5l-e8"]
    assert entry[0]["reduced"] == cfg["reduced"] == [
        "held.layers", "held.experts", "held.vocab_rows"]
    assert set(cfg["reduced_from"]) >= set(cfg["reduced"])
    marks = " ".join(cfg["assumed"])
    assert all(f"[A{i}]" in marks for i in range(1, 6))
    assert "32 chips" in cfg["deployment"]
    assert {"left out", "weights", "router", "bias"} <= set(cfg["changed"])
    assert cfg["training"]["recompute"] == "mlp"
    assert cfg["frozen"] == ["router."]
    assert set(cfg["optimizer"]) == set(harness.Cell(
        bench, "train-1p3b-2k").config["optimizer"])
    assert cell.traffic == dict(cell.traffic, kind="train_routed", batch=1,
                                seq=8192, trace_seconds=6)


def test_benchmark_json_gains_the_cell(bench):
    cells = [w["name"] for w in bench["workloads"]]
    assert CELL in cells and bench["workloads"][cells.index(CELL)]["chips"] == 1
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed == {"train_tokens_per_s", "flash_fwd_roofline.gqa",
                      "flash_bwd_roofline.gqa", "moe_expert_load_peak", *NEW}
    # the plain moe_gmm_roofline takes T x k rows a call: not this cell's
    assert [m["workloads"] for m in bench["per_layer"]
            if m["name"] == "moe_gmm_roofline"] == [["train-zaya1-6l-4k"]]
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "train_tokens_per_s"
    reported = {m["name"] for m in harness.Cell(bench, CELL).per_layer()}
    assert {"train_mfu", "step_ms.train", "device_idle_share.train",
            "compiles_in_window.train", *NEW} <= reported


def test_the_cells_before_it_are_as_their_prs_left_them(bench):
    """A new entry goes to the END of its list (the builder's contract
    reads one put in the middle as a change to what was there), so
    ``test_chipbench_zaya``'s test of BENCHMARK.json, which asks that ITS
    cell be the last, is marked expected-to-fail in ``tests/conftest.py``
    until a ``benchmark`` PR rewords that one assertion. Here its whole
    body runs, every assertion of it, on the lists up to that cell: the
    entries PR 26 left are where and what they were."""
    import test_chipbench_zaya as zaya_tests

    cells = [w["name"] for w in bench["workloads"]]
    assert cells[:2] == ["train-1p3b-2k", zaya_tests.CELL]
    assert cells.index(CELL) > 1
    zaya_tests.test_benchmark_json_gains_the_cell_and_nothing_else_moves(
        dict(bench, workloads=bench["workloads"][:2]))


def test_matrices_held_to_their_rounding_and_the_frozen_router(cell):
    names = afmoe.matrix_leaves(cell.config)
    assert {"h.0/w1", "h.1.gu/w", "h.1.dn/w", "h.2/wq", "h.2/shared.w2",
            "embed/wte", "head/lm_head"} <= set(names)
    held = {f"{l[0]}/{l[1]}" for l in afmoe.leaves(cell.config)}
    assert set(names) <= held and "h.1/q_norm.g" in held
    assert not [n for n in held if "router" in n]
    assert "h.1/router.w" in afmoe.matrix_leaves(
        dict(cell.config, frozen=[]))


def test_serving_is_refused_by_name():
    for fn in (afmoe.served_gaps, afmoe.control_gaps,
               afmoe.kv_bytes_per_token):
        with pytest.raises(NotImplementedError):
            fn({}, 1)
    with pytest.raises(NotImplementedError):
        afmoe.Server({}, 1)


def test_seeded_arrays_one_by_one_equal_all_at_once():
    cfg = harness.Cell(_toy_bench(), "toy-afmoe-train").config
    every = afmoe.make_all(cfg, 2**31 + 9)
    spec = afmoe._all_leaves(cfg)
    assert len(every) == len(spec)
    for i in (0, 3, 15, 27, len(spec) - 1):
        one = afmoe.make_leaf(cfg, 2**31 + 9, i)
        assert one.shape == tuple(spec[i][3])
        assert (np.asarray(every[i].astype(jnp.float32))
                == np.asarray(one.astype(jnp.float32))).all()
    bias = [i for i, l in enumerate(spec) if l[1] == "router.bias"]
    assert len(bias) == 3 and every[bias[0]].dtype == jnp.float32
    assert float(jnp.abs(every[bias[0]]).max()) > 0.0
