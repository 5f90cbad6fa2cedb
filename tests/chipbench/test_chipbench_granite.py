"""Family ``granite_hybrid`` in the benchmark: what ``BENCHMARK.json`` and
the configuration's file promise for granite-4.0-h-micro, a toy
configuration through the ``train`` job on the CPU (the fp8 control and
the architecture's own fault ``forget`` fail the comparison the program
passes), the five readers PR 40 brought on hand-made traces and counters,
and ``shapes_granite``'s counts against brute force."""
import copy
import os

import numpy as np
import pytest

from chipbench import harness, peaks, shapes_granite
from chipbench.families import granite_hybrid
from toy_bench import BENCH, ROOT

CELL = "train-granite4h-10l-16k"
CONFIG = "granite-4.0-h-micro-10l"
TOY = "toy-granite-hybrid-train"
V5E = peaks.peaks_for("TPU v5 lite")
KERNEL_READERS = ("ssd_fwd_roofline", "ssd_bwd_roofline",
                  "conv_silu_fwd_roofline", "conv_silu_bwd_roofline")
NEW = KERNEL_READERS + ("ssd_chunk_carry",)
JOINED = ("flash_fwd_roofline.gqa", "flash_bwd_roofline.gqa")


def _toy_bench():
    """The toy benchmark plus a granite_hybrid cell, added as a later PR
    adds one: a configuration file, a traffic file, entries."""
    b = copy.deepcopy(BENCH)
    b["configs"].append({
        "name": "toy-granite-hybrid", "source": "none (a test's toy)",
        "file": "tests/chipbench/configs/toy-granite-hybrid.json",
        "reduced": [], "why": "CPU tests"})
    b["workloads"].append({
        "name": TOY, "config": "toy-granite-hybrid", "traffic": TOY,
        "chips": 1, "why": "family granite_hybrid"})
    b["end_to_end"][0]["workloads"].append(TOY)
    for name in NEW:
        counter = name == "ssd_chunk_carry"
        b["per_layer"].append({
            "name": name, "unit": "%", "better": "higher",
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
    """(line, detail, the program's counter as that run left it)."""
    line, detail = harness.run(_toy_bench(), TOY, 2**31 + 5, 0.3, True,
                               allow_cpu=True, control="fp8")
    return line, detail, granite_hybrid.ssd_counters()


# -- the toy cell through the harness ----------------------------------------


def test_toy_granite_cell_end_to_end(toy_run):
    line, detail, _ = toy_run
    assert line["correct"] is True, detail["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {c["name"] for c in detail["checks"]} == {
        "loss_gap.step1", "loss_gap.step2", "grad_norm_gap.worst_leaf",
        "moment_norm_gap.worst_leaf", "delta_norm_gap.worst_matrix",
        "compiles_in_window"}
    # off the chip only counts: no share of a roofline, no time
    assert set(line["metrics"]) == {"compiles_in_window.train",
                                    "ssd_chunk_carry"}
    assert 0.0 < line["metrics"]["ssd_chunk_carry"]["value"] < 100.0


def test_the_control_fails_the_comparison_the_program_passes(toy_run):
    _, detail, _ = toy_run
    notes = detail["notes"]
    by_name = {c["name"]: c for c in detail["checks"]}
    for name in ("grad_norm_gap.worst_leaf", "moment_norm_gap.worst_leaf"):
        check = by_name[name]
        assert check["ok"] and notes["control." + name] > check["limit"]


def test_the_architectures_own_fault_fails_it_too():
    """``forget``: the reference with its recurrences' state zeroed every
    256 tokens (a chunked scan that drops its carry), put in the program's
    place, must fail at least one limit the program passes."""
    line, detail = harness.run(_toy_bench(), TOY, 2**31 + 6, 0.2, False,
                               allow_cpu=True, control="forget")
    assert line["correct"] is True, detail["checks"]
    limits = {c["name"]: c["limit"] for c in detail["checks"]}
    failed = [n for n, lim in limits.items() if n != "compiles_in_window"
              and detail["notes"]["control." + n] > lim]
    assert "grad_norm_gap.worst_leaf" in failed, detail["notes"]


def test_the_counter_is_read_once_after_the_window(toy_run):
    line, _, carry = toy_run
    # three Mamba layers of the toy's four: the LAST step's means, however
    # many steps the run made
    assert line["attempted"] >= 1 and len(carry) == 3
    assert all(0.0 < c < 1.0 for c in carry)
    assert line["metrics"]["ssd_chunk_carry"]["value"] == \
        pytest.approx(100.0 * sum(carry) / 3)
    from paddle_tpu import obs

    # one event a run (``Trainer.free()``), not one a step
    events = [e["args"]["carry"] for e in obs.ring().dump()
              if e.get("name") == "ssd.chunk_carry"]
    assert events.count(carry) == 1


def test_a_train_step_that_returns_its_state_unchanged_is_not_correct(
        monkeypatch):
    import jax.numpy as jnp

    real = granite_hybrid.Trainer.step

    def frozen(self, ids, labels):
        keep = [jnp.array(p._data, copy=True) for p in self.params]
        loss = real(self, ids, labels)
        for p, a in zip(self.params, keep):
            p._data = a
        return loss

    monkeypatch.setattr(granite_hybrid.Trainer, "step", frozen)
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


@pytest.mark.parametrize("kernel", ["ssd", "conv_silu"])
@pytest.mark.parametrize("way", ["fwd", "bwd"])
def test_the_readers_divide_by_the_binding_bound(cell, kernel, way):
    reader = cell.reader(f"{kernel}_{way}_roofline")
    z = granite_hybrid.sizes(cell.config)
    flops = getattr(shapes_granite, f"{kernel}_{way}_flops")(16384, z)
    nbytes = getattr(shapes_granite, f"{kernel}_{way}_bytes")(16384, z, 2)
    bound = shapes_granite.bound_seconds(flops, nbytes, V5E)
    # the BYTES bind both kernels; the scan by a little: x and y at 64
    # heads of 64, B, C at 128 and dt at 64 in bf16 are 278.9 MB a forward
    # pass, 0.341 ms at the HBM's peak against 0.262 ms of FLOPs
    assert bound == nbytes / V5E.hbm_bytes_per_s > flops / V5E.bf16_flops
    if (kernel, way) == ("ssd", "fwd"):
        assert nbytes == 278_921_216 and flops == 6 * 64 * 128 * 64 * 16384
        assert bound == pytest.approx(0.3406e-3, rel=2e-3)
        assert flops / V5E.bf16_flops == pytest.approx(0.2616e-3, rel=2e-3)
    if (kernel, way) == ("conv_silu", "fwd"):
        assert nbytes == 2 * 4352 * 2 * 16384
        assert bound == pytest.approx(0.3482e-3, rel=2e-3)
    ns = int(4 * bound * 1e9)
    name = f"{kernel}_{way}"
    # every pass at four times its bound: 25%, nine layers or eighteen
    # passes (a recomputed forward is a pass)
    assert reader(_facts(cell, _events(name, 9, ns))) == \
        pytest.approx(25.0, rel=1e-3)
    assert reader(_facts(cell, _events(name, 18, ns))) == \
        pytest.approx(25.0, rel=1e-3)
    # a kernel of the same family that does not write the result counts
    # in the time and is no pass; the other direction's kernel is neither
    other = f"{kernel}_{'bwd' if way == 'fwd' else 'fwd'}"
    helper = _events(name + "_states", 9, ns)
    assert reader(_facts(cell, _events(name, 9, ns) + helper)) == \
        pytest.approx(12.5, rel=1e-3)
    assert reader(_facts(cell, _events(name, 9, ns)
                         + _events(other, 9, ns))) == \
        pytest.approx(25.0, rel=1e-3)
    # a fusion that only USES the kernel's result is not the kernel
    user = [(f"%fusion.9 = bf16[16384,4096]{{1,0}} fusion(%{name}.1)", 0, 5)]
    assert reader(_facts(cell, user)) is None
    # no trace, a program without the kernel (as the parent commit is), a
    # family without such layers
    assert reader(_facts(cell, [], trace=None)) is None
    assert reader(_facts(cell, [("%moe_gmm.1 = bf16[8]{0} custom-call(",
                                 0, 5)])) is None
    other_cell = harness.Cell(harness.load_json(os.path.join(
        ROOT, "BENCHMARK.json")), "train-minicpmsala-4l-16k")
    assert reader(_facts(other_cell, _events(name, 9, 1000))) is None


def test_the_scans_kernels_are_not_each_others(cell):
    """``ssd_fwd``'s reader does not count ``gdn_fwd`` or
    ``lightning_fwd`` events, nor theirs its."""
    mine = _events("ssd_fwd", 9, 10**6)
    assert cell.reader("ssd_fwd_roofline")(
        _facts(cell, _events("gdn_fwd", 3, 10**6)
               + _events("lightning_fwd", 3, 10**6))) is None
    for theirs, where in (("gdn_fwd_roofline", "train-qwen3next-4l-16k"),
                          ("lightning_fwd_roofline",
                           "train-minicpmsala-4l-16k")):
        other = harness.Cell(harness.load_json(os.path.join(
            ROOT, "BENCHMARK.json")), where)
        assert other.reader(theirs)(_facts(other, mine)) is None


def test_chunk_carry_is_the_counters_mean(cell):
    from paddle_tpu import obs

    reader = cell.reader("ssd_chunk_carry")
    obs.instant("ssd.chunk_carry", carry=[0.5, 0.25, 0.0])
    assert reader(_facts(cell, [])) == pytest.approx(25.0)
    assert reader(dict(_facts(cell, []), family=object())) is None


# -- arithmetic and promises -------------------------------------------------


def test_shapes_against_brute_force(cell):
    z = granite_hybrid.sizes(cell.config)
    # the recurrence, one token and head, operation by operation over the
    # state's [P, N] entries: decay it, form dt x B^T, add it, and read the
    # state with C (a product and a sum an entry): 5 a state entry. The
    # formula counts 6 (the issue's reckoning: the decay as a product and a
    # sum); it may err high by that sixth and never low — the BYTES bind
    # either way, so no share is flattered by it
    ops = 0
    for _ in range(64):
        for _ in range(128):
            ops += 1 + 1 + 1 + 2
    per = 6 * 64 * 128
    assert ops == 5 * 64 * 128 and ops <= per <= 1.2 * ops
    assert shapes_granite.ssd_fwd_flops(100, z) == per * 64 * 100
    assert shapes_granite.ssd_bwd_flops(100, z) == 2 * per * 64 * 100
    # x, y [64 x 64], B, C [128], dt [64] a token, in bf16
    assert shapes_granite.ssd_fwd_bytes(1, z, 2) == \
        2 * (2 * 4096 + 2 * 128 + 64)
    assert shapes_granite.ssd_bwd_bytes(7, z, 2) == \
        2 * shapes_granite.ssd_fwd_bytes(7, z, 2)
    assert shapes_granite.ssm_channels(z) == 4352
    assert shapes_granite.conv_silu_fwd_flops(3, z) == \
        sum(2 for _ in range(4352) for _ in range(4)) * 3
    assert shapes_granite.conv_silu_fwd_bytes(3, z, 2) == 2 * 4352 * 2 * 3
    assert shapes_granite.conv_silu_bwd_bytes(3, z, 2) == 3 * 4352 * 2 * 3
    assert shapes_granite.mixer_params(z, "mamba") == \
        2048 * 8512 + 4096 * 2048 == 25_821_184
    assert shapes_granite.mixer_params(z, "attention") == \
        2 * 2048 * 2048 + 2 * 2048 * 512 == 10_485_760


def test_parameters_and_flops_of_the_configuration_as_run(cell):
    cfg = cell.config
    assert granite_hybrid.total_params(cfg) == cfg["params_as_run"] \
        == 797_850_560                         # issue 40
    z = granite_hybrid.sizes(cfg)
    assert z["layer_kinds"] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    by_group = {}
    for g, _, _, shape, _ in granite_hybrid.leaves(cfg):
        by_group[g] = by_group.get(g, 0) + int(np.prod(shape))
    mlp = 3 * 2048 * 8192
    assert mlp == 50_331_648
    assert by_group["h.0"] == 25_847_232 + mlp + 2 * 2048 == 76_182_976
    assert by_group["h.5"] == 10_485_760 + mlp + 2 * 2048 == 60_821_504
    assert by_group["embed"] == 25088 * 2048 == 51_380_224
    assert by_group["head"] == 2048           # the final norm: the head is tied
    met = shapes_granite.matmul_params_met(z)
    assert met == 9 * 25_821_184 + 10_485_760 + 10 * mlp + 2048 * 25088
    # 5.07 GFLOP a token: 4.79 of matmul parameters, 0.20 the attention
    # layer's pairs at 12 d, 0.085 the nine recurrences
    assert 6.0 * met == pytest.approx(4.785e9, rel=1e-3)
    assert shapes_granite.mixer_flops_per_token(z, 16384) == \
        pytest.approx(0.2863e9, rel=2e-3)
    assert granite_hybrid.train_flops_per_token(cfg, 16384) == \
        pytest.approx(5.072e9, rel=1e-3)
    # the head is 6% of the FLOPs as run
    assert 6 * 2048 * 25088 / granite_hybrid.train_flops_per_token(
        cfg, 16384) == pytest.approx(0.061, abs=2e-3)
    # the state alone is 30% of the chip's 16 GB at 6 bytes a parameter
    assert 6 * cfg["params_as_run"] / 16e9 == pytest.approx(0.299, abs=2e-3)


# the catalog row ``granite-4.0-h-micro`` (model-configs guide,
# architectures.jsonl), copied: a test reads nothing outside its checkout
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "logits_scaling": 8, "mamba_chunk_size": 256,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
    "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_n_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 32,
    "num_experts_per_tok": 0, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352}
ATTENTION_AT = (5, 15, 25, 35)
SOURCE = ("https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/"
          "config.json")


def test_the_configuration_keeps_every_published_key(bench, cell):
    cfg = cell.config
    for key, value in PUBLISHED.items():
        assert cfg[key] == value, key
    assert cfg["layer_types"] == [
        "attention" if i in ATTENTION_AT else "mamba" for i in range(40)]
    assert cfg["source"] == SOURCE and cfg["family"] == "granite_hybrid"
    assert cfg["held"] == {"layers": 10, "first_layer": 0,
                           "vocab_rows": 25088}
    # the guide's floors: a whole period (9 mamba : 1 attention, the
    # published 36 : 4) and four layers, an eighth of the vocabulary
    held = cfg["layer_types"][:10]
    assert held.count("mamba") == 9 * held.count("attention")
    assert cfg["held"]["vocab_rows"] * 4 == cfg["vocab_size"]
    entry = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert entry[0]["reduced"] == cfg["reduced"] == [
        "held.layers", "held.vocab_rows"]
    assert entry[0]["source"] == SOURCE
    assert set(cfg["reduced_from"]) >= set(cfg["reduced"])
    marks = " ".join(cfg["assumed"])
    assert all(f"[A{i}]" in marks for i in (1, 2, 3))
    assert "v5e-4" in cfg["deployment"] and "four pipeline stages" in \
        cfg["deployment"] and "layers 0 to 9" in cfg["deployment"]
    assert {"left out", "weights"} <= set(cfg["changed"])
    assert "packing" in cfg["changed"]["left out"]
    start = cfg["changed"]["weights"]["ssm_start"]
    assert (start["A"], start["dt"]) == ([1.0, 16.0], [0.001, 0.1])
    assert set(cfg["changed"]["weights"]["scales"]) == {"ssm.conv.w",
                                                        "ssm.conv.b"}
    assert cfg["training"]["recompute"] == "mlp"
    assert "GiB" in cfg["training"]["why"]
    assert cfg["optimizer"] == harness.Cell(
        bench, "train-1p3b-2k").config["optimizer"]
    assert set(cfg["limits"]["train"]) == {
        "loss_gap", "grad_norm_gap", "moment_norm_gap", "delta_norm_gap"}
    assert cell.traffic == dict(cell.traffic, kind="train", batch=1,
                                seq=16384, trace_seconds=6)


def test_benchmark_json_gains_one_configuration_and_one_cell(bench):
    cells = [w["name"] for w in bench["workloads"]]
    assert CELL in cells and bench["workloads"][cells.index(CELL)] == dict(
        bench["workloads"][cells.index(CELL)], config=CONFIG,
        traffic="train-16k-plain", chips=1)
    assert [w["name"] for w in bench["workloads"]
            if w["config"] == CONFIG] == [CELL]
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed == {"train_tokens_per_s", *JOINED, *NEW}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in KERNEL_READERS:
        ops = "mamba2_ssd" if name.startswith("ssd") else "conv_silu"
        assert by_name[name] == dict(
            by_name[name], unit="%", source="device_trace", better="higher",
            layer=f"kernels (ops/{ops}.py)", moves="train_tokens_per_s",
            workloads=[CELL])
    assert by_name["ssd_chunk_carry"]["source"] == "program_counter"
    # membership, never last place: a later cell is appended after this one
    reported = {m["name"] for m in harness.Cell(bench, CELL).per_layer()}
    assert {"train_mfu", "step_ms.train", "device_idle_share.train",
            "compiles_in_window.train", "step_compiled_gib",
            "setup_step_traces", "step_cold_compile_s", *JOINED,
            *NEW} <= reported
    for name in NEW:
        assert os.path.isfile(os.path.join(
            ROOT, "chipbench", "layer_metrics", name + ".py"))


def test_the_cells_before_it_are_as_their_prs_left_them(bench):
    """PR 38's test cuts every list where ITS entries begin and then runs
    PR 34's body, which pops the LAST name of every list that holds PR
    34's cell and asks that it be its own; issue 40 has this cell appended
    to the two ``.gqa`` lists, which hold PR 34's cell and not PR 38's, so
    PR 38's test is marked expected-to-fail in ``tests/conftest.py`` until
    a ``benchmark`` PR rewords the assertions (the chain is four tests
    long: PERF.md section 7). Here its whole body and PR 38's own
    ``gains`` run on the benchmark without PR 40's entries: what PRs 34,
    36 and 38 left is where and what it was."""
    import test_chipbench_minicpm_sala as sala_tests

    before = copy.deepcopy(bench)
    before["configs"] = [c for c in before["configs"] if c["name"] != CONFIG]
    before["workloads"] = [w for w in before["workloads"]
                           if w["name"] != CELL]
    before["per_layer"] = [m for m in before["per_layer"]
                           if m["name"] not in NEW]
    for m in before["end_to_end"] + before["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"] = [w for w in m["workloads"] if w != CELL]
    assert CELL not in str(before)
    sala_tests.test_benchmark_json_gains_one_configuration_and_one_cell(
        before)
    sala_tests.test_the_cells_before_it_are_as_their_prs_left_them(before)


def test_matrices_held_to_their_rounding(cell):
    cfg = cell.config
    names = granite_hybrid.matrix_leaves(cfg)
    assert {"h.0/ssm.w_in", "h.0/ssm.w_out", "h.5/wq", "h.5/wk", "h.5/wo",
            "h.2/w1", "h.9/w2", "embed/wte"} <= set(names)
    held = {f"{l[0]}/{l[1]}" for l in granite_hybrid.leaves(cfg)}
    assert set(names) <= held and len(names) == 53
    # the taps (17,408 numbers at Conv1d's scale) make too few bfloat16
    # roundings a step to be held to their expectation; vectors never are
    assert {"h.0/ssm.conv.w", "h.0/ssm.conv.b", "h.0/ssm.a_log",
            "h.0/ssm.norm.g", "head/norm_f.g"} <= held - set(names)


def test_seeded_arrays_one_by_one_equal_all_at_once():
    import jax
    import jax.numpy as jnp

    cfg = harness.Cell(_toy_bench(), TOY).config
    every = granite_hybrid.make_all(cfg, 2**31 + 9)
    spec = granite_hybrid.leaves(cfg)
    assert len(every) == len(spec)
    kinds = {}
    for i, leaf in enumerate(spec):
        kinds.setdefault(leaf[4], i)
    assert set(kinds) == {"normal", "ones", "a_log", "dt_bias"}
    for i in [0, len(spec) - 1, *kinds.values()]:
        one = granite_hybrid.make_leaf(cfg, 2**31 + 9, i)
        assert one.shape == tuple(spec[i][3])
        assert (np.asarray(every[i].astype(jnp.float32))
                == np.asarray(one.astype(jnp.float32))).all()
    # the decays start as the configuration says: A in [0.5, 4], the
    # softplus of dt_bias in [0.001, 0.02] (bfloat16 storage: 1%)
    a = np.exp(np.asarray(every[kinds["a_log"]].astype(jnp.float32)))
    dt = np.asarray(jax.nn.softplus(
        every[kinds["dt_bias"]].astype(jnp.float32)))
    assert (0.49 <= a).all() and (a <= 4.04).all() and len(set(a)) == 2
    assert (0.00099 <= dt).all() and (dt <= 0.0202).all()
    # the taps at Conv1d's start, not at 0.02
    taps = np.asarray(every[[l[1] for l in spec].index(
        "ssm.conv.w")].astype(jnp.float32))
    assert 0.2 < taps.std() < 0.4
