"""Family ``minicpm_sala`` in the benchmark: what ``BENCHMARK.json`` and
the configuration's file promise for MiniCPM-SALA, a toy configuration
through the ``train_routed`` job on the CPU (the fp8 control and BOTH of
the architecture's own faults fail the comparison the program passes),
the five readers PR 38 brought on hand-made traces and counters, and
``shapes_minicpm_sala``'s counts against brute force."""
import copy
import os

import numpy as np
import pytest

from chipbench import harness, peaks, shapes_minicpm_sala
from chipbench.families import minicpm_sala
from toy_bench import BENCH, ROOT

CELL = "train-minicpmsala-4l-16k"
CONFIG = "minicpm-sala-9b-4l"
TOY = "toy-minicpm-sala-train"
V5E = peaks.peaks_for("TPU v5 lite")
KERNEL_READERS = ("lightning_fwd_roofline", "lightning_bwd_roofline",
                  "sparse_attn_fwd_roofline", "sparse_attn_bwd_roofline")
NEW = KERNEL_READERS + ("sparse_blocks_per_query",)


def _toy_bench():
    """The toy benchmark plus a minicpm_sala cell, added as a later PR
    adds one: a configuration file, a traffic file, entries."""
    b = copy.deepcopy(BENCH)
    b["configs"].append({
        "name": "toy-minicpm-sala", "source": "none (a test's toy)",
        "file": "tests/chipbench/configs/toy-minicpm-sala.json",
        "reduced": [], "why": "CPU tests"})
    b["workloads"].append({
        "name": TOY, "config": "toy-minicpm-sala", "traffic": TOY,
        "chips": 1, "why": "family minicpm_sala"})
    b["end_to_end"][0]["workloads"].append(TOY)
    for name in NEW:
        counter = name == "sparse_blocks_per_query"
        b["per_layer"].append({
            "name": name, "unit": "blocks" if counter else "%",
            "better": "lower",
            "source": "program_counter" if counter else "device_trace",
            "layer": "x", "moves": "train_tokens_per_s", "workloads": [TOY]})
    return b


@pytest.fixture(scope="module")
def bench():
    return harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


@pytest.fixture(scope="module")
def cell(bench):
    return harness.Cell(bench, CELL)


@pytest.fixture(scope="module")
def toy_run():
    """(line, detail, the program's counters as that run left them)."""
    line, detail = harness.run(_toy_bench(), TOY, 2**31 + 5, 0.5, True,
                               allow_cpu=True, control="fp8")
    return line, detail, minicpm_sala.sparse_counters()


# -- the toy cell through the harness ----------------------------------------


def test_toy_minicpm_sala_cell_end_to_end(toy_run):
    line, detail, _ = toy_run
    assert line["correct"] is True, detail["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {c["name"] for c in detail["checks"]} == {
        "loss_gap.step1", "loss_gap.step2", "grad_norm_gap.worst_leaf",
        "moment_norm_gap.worst_leaf", "delta_norm_gap.worst_matrix",
        "compiles_in_window", "route_flip_share.mean"}
    # off the chip only counts: no share of a roofline, no time
    assert set(line["metrics"]) == {"compiles_in_window.train",
                                    "sparse_blocks_per_query"}
    # four key blocks, topk 3: 1, 2, 3, 3 blocks a token
    assert line["metrics"]["sparse_blocks_per_query"]["value"] == 2.25


def test_the_control_fails_the_comparison_the_program_passes(toy_run):
    _, detail, _ = toy_run
    notes = detail["notes"]
    by_name = {c["name"]: c for c in detail["checks"]}
    for name in ("grad_norm_gap.worst_leaf", "moment_norm_gap.worst_leaf"):
        check = by_name[name]
        assert check["ok"] and notes["control." + name] > check["limit"]
    assert 0.0 <= notes["control.route_flip_share.mean"] <= 1.0


@pytest.mark.parametrize("fault", ["forget", "local"])
def test_each_of_the_architectures_faults_fails_it_too(fault):
    """``forget``: the reference with its recurrences' state zeroed every
    64 tokens; ``local``: with every followed set cut to its forced
    blocks. Put in the program's place, each must fail at least one
    limit the program passes."""
    line, detail = harness.run(_toy_bench(), TOY, 2**31 + 6, 0.2, False,
                               allow_cpu=True, control=fault)
    assert line["correct"] is True, detail["checks"]
    limits = {c["name"]: c["limit"] for c in detail["checks"]}
    failed = [n for n, lim in limits.items()
              if n != "compiles_in_window" and n != "route_flip_share.mean"
              and detail["notes"]["control." + n] > lim]
    assert failed, detail["notes"]
    assert "grad_norm_gap.worst_leaf" in failed


def test_the_counters_are_read_once_after_the_window(toy_run):
    line, _, (blocks, rows) = toy_run
    # two sparse layers; 2 rows x 2 kv groups x 256 tokens a step and
    # layer: the LAST step's alone, however many the run made (an int32
    # sum over a long run's steps would wrap)
    assert line["attempted"] >= 1 and rows == [2 * 2 * 256] * 2
    assert [b / r for b, r in zip(blocks, rows)] == [2.25, 2.25]


def test_a_train_step_that_returns_its_state_unchanged_is_not_correct(
        monkeypatch):
    import jax.numpy as jnp

    real = minicpm_sala.Trainer.step

    def frozen(self, ids, labels):
        keep = [jnp.array(p._data, copy=True) for p in self.params]
        loss = real(self, ids, labels)
        for p, a in zip(self.params, keep):
            p._data = a
        return loss

    monkeypatch.setattr(minicpm_sala.Trainer, "step", frozen)
    line, detail = harness.run(_toy_bench(), TOY, 77, 0.2, False,
                               allow_cpu=True)
    assert line["correct"] is False
    assert "delta_norm_gap.worst_matrix" in {
        c["name"] for c in detail["checks"] if not c["ok"]}


# -- the readers on hand-made traces -----------------------------------------


def _facts(cell, events, **kw):
    return dict({"trace": {"devices": {0: events}, "spans": []},
                 "family": cell.family, "config": cell.config, "batch": 1,
                 "seq": 16384, "peaks": V5E, "on_chip": True}, **kw)


def _events(name, n, ns):
    return [(f"%{name}.{i} = (bf16[1,16384,4096]{{2,1,0}}) custom-call(",
             i * 10**9, ns) for i in range(n)]


@pytest.mark.parametrize("way", ["fwd", "bwd"])
def test_the_lightning_readers_divide_by_the_bytes(cell, way):
    reader = cell.reader(f"lightning_{way}_roofline")
    z = minicpm_sala.sizes(cell.config)
    flops = getattr(shapes_minicpm_sala, f"lightning_{way}_flops")(16384, z)
    nbytes = getattr(shapes_minicpm_sala, f"lightning_{way}_bytes")(
        16384, z, 2)
    bound = shapes_minicpm_sala.bound_seconds(flops, nbytes, V5E)
    # q, k, v, o at 32 heads of 128 in bf16: 536.9 MB a forward pass,
    # 0.655 ms at the HBM's peak against 0.17 ms of FLOPs
    assert bound == nbytes / V5E.hbm_bytes_per_s > flops / V5E.bf16_flops
    if way == "fwd":
        assert nbytes == 536_870_912 and flops == 4 * 128 * 128 * 32 * 16384
        assert bound == pytest.approx(0.655e-3, rel=2e-3)
    ns = int(4 * bound * 1e9)
    name = f"lightning_{way}"
    # every pass at four times its bound: 25%, three layers or six passes
    # (a recomputed forward is a pass)
    assert reader(_facts(cell, _events(name, 3, ns))) == \
        pytest.approx(25.0, rel=1e-3)
    assert reader(_facts(cell, _events(name, 6, ns))) == \
        pytest.approx(25.0, rel=1e-3)
    # a kernel of the same family that does not write the result counts
    # in the time and is no pass; the other direction's kernel is neither
    other = "lightning_bwd" if way == "fwd" else "lightning_fwd"
    helper = _events(name + "_states", 3, ns)
    assert reader(_facts(cell, _events(name, 3, ns) + helper)) == \
        pytest.approx(12.5, rel=1e-3)
    assert reader(_facts(cell, _events(name, 3, ns)
                         + _events(other, 3, ns))) == \
        pytest.approx(25.0, rel=1e-3)
    _nothing_to_read(cell, reader, name)


@pytest.mark.parametrize("way", ["fwd", "bwd"])
def test_the_sparse_readers_divide_by_the_flops_of_the_rules_pairs(cell, way):
    reader = cell.reader(f"sparse_attn_{way}_roofline")
    z = minicpm_sala.sizes(cell.config)
    pairs = shapes_minicpm_sala.sparse_pairs(16384, z["rule"])
    flops = getattr(shapes_minicpm_sala, f"sparse_attn_{way}_flops")(16384, z)
    # 58.3M pairs a head (43% of the causal 134.2M), 4 d forward, 8 d back
    assert pairs == 58_335_232 and pairs / (16384 * 16385 // 2) == \
        pytest.approx(0.4346, rel=1e-3)
    assert flops == (4 if way == "fwd" else 8) * 128 * 32 * pairs
    bound = flops / V5E.bf16_flops
    if way == "fwd":
        assert bound == pytest.approx(4.85e-3, rel=2e-3)
    ns = int(10 * bound * 1e9)
    name = f"sparse_attn_{way}"
    # one sparse layer at ten times its bound: 10%; a recomputed forward
    # is a second pass
    assert reader(_facts(cell, _events(name, 1, ns))) == \
        pytest.approx(10.0, rel=1e-3)
    assert reader(_facts(cell, _events(name, 2, ns))) == \
        pytest.approx(10.0, rel=1e-3)
    assert reader(_facts(cell, _events(name, 1, ns)
                         + _events(name + "_dkv", 1, ns))) == \
        pytest.approx(5.0, rel=1e-3)
    _nothing_to_read(cell, reader, name)


def _nothing_to_read(cell, reader, name):
    # a fusion that only USES the kernel's result is not the kernel
    user = [(f"%fusion.9 = bf16[16384,4096]{{1,0}} fusion(%{name}.1)", 0, 5)]
    assert reader(_facts(cell, user)) is None
    # no trace, a program without the kernel (as the parent commit is), a
    # family without such layers
    assert reader(_facts(cell, [], trace=None)) is None
    assert reader(_facts(cell, [("%moe_gmm.1 = bf16[8]{0} custom-call(",
                                 0, 5)])) is None
    other = harness.Cell(harness.load_json(os.path.join(
        ROOT, "BENCHMARK.json")), "train-qwen3next-4l-16k")
    assert reader(_facts(other, _events(name, 3, 1000))) is None


def test_blocks_per_query_is_the_counters_ratio(cell):
    from paddle_tpu import obs

    reader = cell.reader("sparse_blocks_per_query")
    # 10 steps of 16384 tokens x 2 kv groups in the one sparse layer
    obs.instant("sparse.blocks_chosen", blocks=[10 * 2 * 919_552])
    obs.instant("sparse.query_rows", rows=[10 * 2 * 16384])
    assert reader(_facts(cell, [])) == pytest.approx(56.125)
    assert reader(dict(_facts(cell, []), family=object())) is None


# -- arithmetic and promises -------------------------------------------------


def test_shapes_against_brute_force(cell):
    z = minicpm_sala.sizes(cell.config)
    rule = z["rule"]
    for seq in (64, 640, 4096, 4160, 16384):
        blocks = pairs = 0
        for t in range(seq):
            own = t // 64
            read = min(own + 1, 64)
            blocks += read
            pairs += (read - 1) * 64 + t % 64 + 1
        assert shapes_minicpm_sala.sparse_blocks(seq, rule) == blocks
        assert shapes_minicpm_sala.sparse_pairs(seq, rule) == pairs
    # up to 4,096 tokens every valid block is read: dense causal attention
    assert shapes_minicpm_sala.sparse_pairs(4096, rule) == 4096 * 4097 // 2
    assert shapes_minicpm_sala.sparse_blocks_per_query(4096, rule) == 32.5
    assert shapes_minicpm_sala.sparse_blocks_per_query(16384, rule) == 56.125
    # the recurrence, a token and head: k v^T and S^T q
    per = 2 * 2 * 128 * 128
    assert shapes_minicpm_sala.lightning_fwd_flops(100, z) == per * 32 * 100
    assert shapes_minicpm_sala.lightning_bwd_flops(100, z) == \
        2 * per * 32 * 100
    assert shapes_minicpm_sala.lightning_fwd_bytes(1, z, 2) == 4 * 4096 * 2
    assert shapes_minicpm_sala.lightning_bwd_bytes(7, z, 2) == \
        2 * shapes_minicpm_sala.lightning_fwd_bytes(7, z, 2)
    assert shapes_minicpm_sala.mixer_params(z, "minicpm4") == \
        3 * 4096 * 4096 + 2 * 4096 * 256 == 52_428_800
    assert shapes_minicpm_sala.mixer_params(z, "lightning-attn") == \
        5 * 4096 * 4096 == 83_886_080


def test_parameters_and_flops_of_the_configuration_as_run(cell):
    cfg = cell.config
    assert minicpm_sala.total_params(cfg) == cfg["params_as_run"] \
        == 1_184_654_336                       # issue 38: 1,184.6M
    z = minicpm_sala.sizes(cfg)
    assert z["layer_kinds"] == ["minicpm4"] + ["lightning-attn"] * 3
    by_group = {}
    for g, _, _, shape, _, _ in minicpm_sala._all_leaves(cfg):
        g = ".".join(g.split(".")[:2])          # a block with its SwiGLU
        by_group[g] = by_group.get(g, 0) + int(np.prod(shape))
    mlp = 3 * 4096 * 16384
    assert mlp == 201_326_592
    assert by_group["h.0"] == 52_428_800 + mlp + 2 * 128 + 2 * 4096
    assert by_group["h.1"] == 83_886_080 + mlp + 2 * 128 + 3 * 4096
    assert by_group["embed"] == 9181 * 4096 == 37_605_376
    # the dense SwiGLU is 73% of the blocks' parameters
    blocks = sum(v for g, v in by_group.items() if g.startswith("h."))
    assert 4 * mlp / blocks == pytest.approx(0.726, abs=2e-3)
    met = shapes_minicpm_sala.matmul_params_met(z)
    assert met == 52_428_800 + 3 * 83_886_080 + 4 * mlp + 4096 * 9181
    # 7.08 GFLOP a token: 6.88 of matmul parameters, 0.175 the sparse
    # layer's pairs, 0.019 the recurrence
    assert 6.0 * met == pytest.approx(6.882e9, rel=1e-3)
    assert shapes_minicpm_sala.mixer_flops_per_token(z, 16384) == \
        pytest.approx(0.1938e9, rel=2e-3)
    assert minicpm_sala.train_flops_per_token(cfg, 16384) == \
        pytest.approx(7.076e9, rel=1e-3)
    # the state alone is 44% of the chip at 6 bytes a parameter
    assert 6 * cfg["params_as_run"] / 16e9 == pytest.approx(0.444, abs=2e-3)


# the catalog row ``MiniCPM-SALA`` (model-configs guide,
# architectures.jsonl), copied: a test reads nothing outside its checkout
PUBLISHED = {
    "attention_bias": False, "attn_use_rope": False, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 16384,
    "lightning_head_dim": 128, "lightning_nh": 32, "lightning_nkv": 32,
    "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
    "max_position_embeddings": 524288, "model_type": "minicpm_sala",
    "num_attention_heads": 32, "num_hidden_layers": 32,
    "num_key_value_heads": 2, "qk_norm": True, "rand_init": False,
    "rms_norm_eps": 1e-06, "vocab_size": 73448, "rope_theta": 10000,
    "scale_emb": 12, "scale_depth": 1.4, "mup_denominator": 32,
    "dim_model_base": 256, "tie_word_embeddings": False,
    "use_output_gate": True, "use_output_norm": True,
    "attn_use_output_gate": True}
SPARSE_AT = (0, 9, 16, 17, 22, 29, 30, 31)
SOURCE = "https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json"


def test_the_configuration_keeps_every_published_key(bench, cell):
    cfg = cell.config
    for key, value in PUBLISHED.items():
        assert cfg[key] == value, key
    assert cfg["mixer_types"] == [
        "minicpm4" if i in SPARSE_AT else "lightning-attn"
        for i in range(32)]
    assert cfg["source"] == SOURCE and cfg["family"] == "minicpm_sala"
    assert cfg["held"] == {"layers": 4, "first_layer": 0, "vocab_rows": 9181}
    # the guide's floors: a whole period (1 sparse : 3 lightning, the
    # published 8 : 24) and four layers, an eighth of the vocabulary
    held = cfg["mixer_types"][:4]
    assert held.count("minicpm4") * 3 == held.count("lightning-attn")
    assert cfg["held"]["vocab_rows"] * 8 == cfg["vocab_size"]
    entry = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert entry[0]["reduced"] == cfg["reduced"] == [
        "held.layers", "held.vocab_rows"]
    assert entry[0]["source"] == SOURCE
    assert set(cfg["reduced_from"]) >= set(cfg["reduced"])
    marks = " ".join(cfg["assumed"])
    assert all(f"[A{i}]" in marks for i in (1, 2, 3))
    assert "v5e-8" in cfg["deployment"] and "eight pipeline stages" in \
        cfg["deployment"] and "layers 0 to 3" in cfg["deployment"]
    assert {"left out", "weights"} <= set(cfg["changed"])
    assert "dense_len" in cfg["changed"]["left out"]
    assert cfg["changed"]["weights"]["scales"] == {}
    assert {k: cfg["sparse_config"][k] for k in minicpm_sala._RULE} == dict(
        kernel_size=32, kernel_stride=16, block_size=64, topk=64,
        init_blocks=1, window_size=2048)
    assert cfg["training"]["recompute"] == "none"
    assert cfg["optimizer"] == harness.Cell(
        bench, "train-1p3b-2k").config["optimizer"]
    assert set(cfg["limits"]["train"]) == {
        "loss_gap", "grad_norm_gap", "moment_norm_gap", "delta_norm_gap",
        "route_flip_share"}
    assert cell.traffic == dict(cell.traffic, kind="train_routed", batch=1,
                                seq=16384, trace_seconds=6)


def test_benchmark_json_gains_one_configuration_and_one_cell(bench):
    cells = [w["name"] for w in bench["workloads"]]
    assert CELL in cells and bench["workloads"][cells.index(CELL)] == dict(
        bench["workloads"][cells.index(CELL)], config=CONFIG,
        traffic="train-16k", chips=1)
    assert [w["name"] for w in bench["workloads"]
            if w["config"] == CONFIG] == [CELL]
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed == {"train_tokens_per_s", *NEW}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in KERNEL_READERS:
        ops = "lightning" if name.startswith("lightning") else "sparse"
        assert by_name[name] == dict(
            by_name[name], unit="%", source="device_trace", better="higher",
            layer=f"kernels (ops/{ops}_attention.py)",
            moves="train_tokens_per_s", workloads=[CELL])
    assert by_name["sparse_blocks_per_query"]["source"] == "program_counter"
    # membership, never last place: a later cell is appended after this one
    reported = {m["name"] for m in harness.Cell(bench, CELL).per_layer()}
    assert {"train_mfu", "step_ms.train", "device_idle_share.train",
            "compiles_in_window.train", "step_compiled_gib",
            "setup_step_traces", *NEW} <= reported
    for name in NEW:
        assert os.path.isfile(os.path.join(
            ROOT, "chipbench", "layer_metrics", name + ".py"))


def test_the_cells_before_it_are_as_their_prs_left_them(bench):
    """PR 34's test pops the LAST name of every list that holds its cell
    and asks that it be its own, and PR 36's asks that its six metrics be
    the last of ``per_layer``; issue 38 has this cell appended to
    ``train_tokens_per_s``'s list and five metrics to ``per_layer``, so
    both are marked expected-to-fail in ``tests/conftest.py`` until a
    ``benchmark`` PR rewords the assertions. Here their whole bodies run
    on the benchmark without PR 38's entries: what PRs 34 and 36 left is
    where and what it was."""
    import test_chipbench_compile_spans as spans_tests
    import test_chipbench_qwen3next as qwen_tests

    def upto(entries, name):
        names = [e if isinstance(e, str) else e["name"] for e in entries]
        return entries[:names.index(name)]

    # the benchmark as it was: every list cut where PR 38's entries begin
    # (what a later PR appends comes after them and falls away with them)
    before = copy.deepcopy(bench)
    before["configs"] = upto(before["configs"], CONFIG)
    before["workloads"] = upto(before["workloads"], CELL)
    before["per_layer"] = upto(before["per_layer"], NEW[0])
    for m in before["end_to_end"] + before["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"] = upto(m["workloads"], CELL)
    qwen_tests.test_benchmark_json_gains_one_configuration_and_one_cell(
        before)
    qwen_tests.test_the_cells_before_it_are_as_their_prs_left_them(before)
    spans_tests.test_the_entries_in_benchmark_json(before)


def test_matrices_held_to_their_rounding(cell):
    cfg = cell.config
    names = minicpm_sala.matrix_leaves(cfg)
    assert {"h.0/wq", "h.0/wk", "h.0/wg", "h.1/lin.wq", "h.1/lin.wo",
            "h.2.w1/w", "h.3.w2/w", "embed/wte", "head/lm_head"} <= set(names)
    held = {f"{l[0]}/{l[1]}" for l in minicpm_sala.leaves(cfg)}
    assert set(names) <= held
    assert {"h.0/q_norm.g", "h.1/lin.o_norm.g", "head/norm_f.g"} <= held \
        - set(names)


def test_serving_is_refused_by_name():
    for fn in (minicpm_sala.served_gaps, minicpm_sala.control_gaps,
               minicpm_sala.kv_bytes_per_token):
        with pytest.raises(NotImplementedError):
            fn({}, 1)
    with pytest.raises(NotImplementedError):
        minicpm_sala.Server({}, 1)


def test_seeded_arrays_one_by_one_equal_all_at_once():
    import jax.numpy as jnp

    cfg = harness.Cell(_toy_bench(), TOY).config
    every = minicpm_sala.make_all(cfg, 2**31 + 9)
    spec = minicpm_sala._all_leaves(cfg)
    assert len(every) == len(spec)
    kinds = {}
    for i, leaf in enumerate(spec):
        kinds.setdefault(leaf[4], i)
    assert set(kinds) == {"normal", "ones"}
    for i in [0, len(spec) - 1, *kinds.values()]:
        one = minicpm_sala.make_leaf(cfg, 2**31 + 9, i)
        assert one.shape == tuple(spec[i][3])
        assert (np.asarray(every[i].astype(jnp.float32))
                == np.asarray(one.astype(jnp.float32))).all()
