"""Family ``zaya`` in the benchmark: a toy configuration through the
``train_routed`` job on the CPU, the four readers PR 26 brought on hand-made
traces and ring events, the FLOP/byte arithmetic, and what
``BENCHMARK.json`` and the configuration's file promise."""
import copy
import os

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, peaks, shapes_zaya
from chipbench.families import zaya, zaya_reference
from chipbench.jobs import train_routed
from toy_bench import BENCH, ROOT

CELL = "train-zaya1-6l-4k"
V5E = peaks.peaks_for("TPU v5 lite")


def _toy_bench():
    """The toy benchmark plus a zaya cell, added as a later PR adds one:
    a configuration file, a traffic file, entries."""
    b = copy.deepcopy(BENCH)
    b["configs"].append({
        "name": "toy-zaya", "source": "none (a test's toy)",
        "file": "tests/chipbench/configs/toy-zaya.json", "reduced": [],
        "why": "CPU tests"})
    b["workloads"].append({
        "name": "toy-zaya-train", "config": "toy-zaya",
        "traffic": "toy-zaya-train", "chips": 1, "why": "family zaya"})
    b["end_to_end"][0]["workloads"].append("toy-zaya-train")
    for name, unit, source in (
            ("moe_expert_load_peak", "ratio", "program_counter"),
            ("moe_gmm_roofline", "%", "device_trace"),
            ("flash_fwd_roofline.gqa", "%", "device_trace"),
            ("flash_bwd_roofline.gqa", "%", "device_trace")):
        b["per_layer"].append({
            "name": name, "unit": unit, "better": "lower", "source": source,
            "layer": "x", "moves": "train_tokens_per_s",
            "workloads": ["toy-zaya-train"]})
    return b


@pytest.fixture(scope="module")
def bench():
    return harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


@pytest.fixture(scope="module")
def cell(bench):
    return harness.Cell(bench, CELL)


@pytest.fixture(scope="module")
def toy_run():
    return harness.run(_toy_bench(), "toy-zaya-train", 2**31 + 5, 0.5, True,
                       allow_cpu=True, control="fp8")


def test_toy_zaya_cell_end_to_end(toy_run):
    line, detail = toy_run
    assert line["correct"] is True, detail["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {c["name"] for c in detail["checks"]} == {
        "loss_gap.step1", "loss_gap.step2", "grad_norm_gap.worst_leaf",
        "moment_norm_gap.worst_leaf", "delta_norm_gap.worst_matrix",
        "compiles_in_window", "route_flip_share.mean"}
    # off the chip only counts: no share of a roofline, no time
    assert set(line["metrics"]) == {"compiles_in_window.train",
                                    "moe_expert_load_peak"}
    peak = line["metrics"]["moe_expert_load_peak"]["value"]
    assert 1.0 <= peak <= 4.0           # 4 experts: 4.0 = one took all


def test_the_control_runs_in_the_programs_place(toy_run):
    _, detail = toy_run
    notes = detail["notes"]
    assert {"control.grad_norm_gap.worst_leaf", "control.loss_gap.step1",
            "control.delta_norm_gap.worst_matrix",
            "control.route_flip_share.mean"} <= set(notes)
    assert 0.0 <= notes["control.route_flip_share.mean"] <= 1.0
    # the reference follows the program's routing, so the norms read off
    # the timed trainer are arithmetic against arithmetic: a precision
    # lower fails them, the program does not
    by_name = {c["name"]: c for c in detail["checks"]}
    for name in ("grad_norm_gap.worst_leaf", "moment_norm_gap.worst_leaf"):
        check = by_name[name]
        assert check["ok"] and notes["control." + name] > check["limit"]
        assert notes["control." + name] > 3 * check["value"]
    assert notes["program_memory_peak_bytes"] == 0      # the CPU has none


def test_the_counter_is_read_once_after_the_window(toy_run):
    from paddle_tpu import obs

    events = [e for e in obs.ring().dump()
              if e["name"] == "moe.tokens_per_expert"]
    assert events
    counts = np.asarray(events[-1]["args"]["counts"])
    line, _ = toy_run
    # [blocks, E]; every step of the run, each token once a block
    assert counts.shape == (2, 4)
    assert (counts.sum(axis=1) == (line["attempted"] + 3) * 2 * 128).all()


def test_a_train_step_that_returns_its_state_unchanged_is_not_correct(
        monkeypatch):
    real = zaya.Trainer.step

    def frozen(self, ids, labels):
        keep = [jnp.array(p._data, copy=True) for p in self.params]
        loss = real(self, ids, labels)
        for p, a in zip(self.params, keep):
            p._data = a
        return loss

    monkeypatch.setattr(zaya.Trainer, "step", frozen)
    line, detail = harness.run(_toy_bench(), "toy-zaya-train", 77, 0.2, False,
                               allow_cpu=True)
    assert line["correct"] is False
    assert "delta_norm_gap.worst_matrix" in {
        c["name"] for c in detail["checks"] if not c["ok"]}


# -- the readers on hand-made traces -----------------------------------------


def _facts(cell, events, **kw):
    return dict({"trace": {"devices": {0: events}, "spans": []},
                 "family": cell.family, "config": cell.config, "batch": 1,
                 "seq": 4096, "peaks": V5E, "on_chip": True}, **kw)


def _gmm_events(us_gmm, us_tgmm, blocks=6):
    ev, t = [], 0
    for _ in range(blocks):
        for i in range(4):
            ev.append((f"%moe_gmm.{i} = bf16[4096,4096]{{1,0}} custom-call(",
                       t, int(us_gmm * 1e3)))
            t += 10**6
        for i in range(2):
            ev.append((f"%moe_tgmm.{i} = bf16[16,2048,4096]{{2,1,0}} "
                       "custom-call(", t, int(us_tgmm * 1e3)))
            t += 10**6
    ev.append(("%fusion.1 = bf16[4096,2048]{1,0} fusion(%moe_gmm.3)", t, 10**6))
    return ev


def test_moe_gmm_roofline_on_a_hand_made_trace(cell):
    read = cell.reader("moe_gmm_roofline")
    z = zaya.sizes(cell.config)
    bound = sum(
        max((shapes_zaya.gmm_flops if name == "moe_gmm"
             else shapes_zaya.tgmm_flops)(t, k, n) / V5E.bf16_flops,
            (shapes_zaya.gmm_bytes if name == "moe_gmm"
             else shapes_zaya.tgmm_bytes)(t, k, n, 16, 2)
            / V5E.hbm_bytes_per_s)
        for name, t, k, n in shapes_zaya.block_kernel_calls(z, 4096))
    # six calls a block, each at exactly twice its bound -> 50%
    per_call_us = 1e6 * bound / 6
    got = read(_facts(cell, _gmm_events(2 * per_call_us, 2 * per_call_us)))
    assert got == pytest.approx(50.0, rel=1e-3)
    # two steps' worth of events in the trace: the same share
    twice = _gmm_events(2 * per_call_us, 2 * per_call_us, blocks=12)
    assert read(_facts(cell, twice)) == pytest.approx(50.0, rel=1e-3)
    # nothing to read: no trace, or a program without the kernels
    assert read(_facts(cell, [], trace=None)) is None
    assert read(_facts(cell, [("%fusion.1 = f32[8]{0} fusion(", 0, 5)])) is None


def test_which_bound_binds_each_grouped_matmul_at_the_cells_shapes():
    # 256 rows an expert: every call is bound by its BYTES, by a tenth
    # (gate-up: 0.389 ms of bytes against 0.349 ms of FLOPs; PERF.md §3)
    for t, k, n in ((4096, 2048, 4096), (4096, 2048, 2048)):
        flops_s = shapes_zaya.gmm_flops(t, k, n) / V5E.bf16_flops
        bytes_s = shapes_zaya.gmm_bytes(t, k, n, 16, 2) / V5E.hbm_bytes_per_s
        assert 0.8 <= flops_s / bytes_s <= 1.0      # bytes bind, within 20%
        assert shapes_zaya.tgmm_bytes(t, k, n, 16, 2) == \
            shapes_zaya.gmm_bytes(t, k, n, 16, 2)


def _flash_events(fwd_us, dq_us, dkv_us, n=6):
    ev = []
    for i in range(n):
        ev += [(f"%flash_fwd.{i} = ", i * 10**7, int(fwd_us * 1e3)),
               (f"%flash_bwd_dq.{i} = ", i * 10**7 + 10**6, int(dq_us * 1e3)),
               (f"%flash_bwd_dkv.{i} = ", i * 10**7 + 2 * 10**6,
                int(dkv_us * 1e3))]
    return ev


def test_the_gqa_flash_readers_take_heads_and_head_size_from_sizes(cell):
    from chipbench import shapes

    fwd = cell.reader("flash_fwd_roofline.gqa")
    bwd = cell.reader("flash_bwd_roofline.gqa")
    one = shapes.flash_fwd_flops(4096, 8, 128) / V5E.bf16_flops * 1e6
    events = _flash_events(2 * one, 2 * one, 2 * one)
    assert fwd(_facts(cell, events)) == pytest.approx(50.0, rel=1e-3)
    # backward: twice the forward's FLOPs over dq + dk/dv = 4x its time
    assert bwd(_facts(cell, events)) == pytest.approx(50.0, rel=1e-3)
    # the dense readers would take d_head = 2048 // 8 = 256: twice this
    dense = harness.Cell(harness.load_json(os.path.join(
        ROOT, "BENCHMARK.json")), "train-1p3b-2k").reader("flash_fwd_roofline")
    assert dense(_facts(cell, events)) == pytest.approx(100.0, rel=1e-3)
    assert fwd(_facts(cell, [], trace=None)) is None
    assert bwd(_facts(cell, [("%fusion.2 = ", 0, 5)])) is None


def test_moe_expert_load_peak_reads_the_newest_counter_event(cell):
    from paddle_tpu import obs

    read = cell.reader("moe_expert_load_peak")
    obs.instant("moe.tokens_per_expert", counts=[[10, 10, 10, 10]])
    obs.instant("moe.tokens_per_expert",
                counts=[[25, 25, 25, 25], [70, 10, 10, 10], [0, 0, 0, 0]])
    assert read({}) == pytest.approx(2.8)        # 70 of 100 over 4 experts


# -- arithmetic and promises -------------------------------------------------


def test_parameters_and_flops_of_the_configuration_as_run(cell):
    cfg = cell.config
    assert zaya.total_params(cfg) == cfg["params_as_run"] == 1_312_543_436
    z = zaya.sizes(cfg)
    block = [l for l in zaya._all_leaves(cfg)
             if l[0] in ("h.0", "h.0.gu", "h.0.dn")]
    assert sum(int(np.prod(l[3])) for l in block) == 207_566_626
    # a token meets ONE expert of sixteen
    met = shapes_zaya.block_matmul_params_met(z)
    assert met == (5_242_880 + 327_680 + 659_456 + 3 * 2048 * 2048)
    per_token = zaya.train_flops_per_token(cfg, 4096)
    assert per_token == pytest.approx(1.231e9, rel=2e-3)
    head_share = 6 * 2048 * 32784 / per_token
    assert 0.32 <= head_share <= 0.34            # published: 37%


def test_the_configuration_keeps_every_published_width(bench, cell):
    cfg = cell.config
    for key, value in {
            "hidden_size": 2048, "num_attention_heads": 8,
            "num_key_value_heads": 2, "head_dim": 128, "num_experts": 16,
            "num_experts_per_tok": 1, "moe_intermediate_size": 2048,
            "router_hidden_size": 256, "cca_time0": 2, "cca_time1": 2,
            "partial_rotary_factor": 0.5, "num_hidden_layers": 40,
            "vocab_size": 262272, "rms_norm_eps": 1e-05,
            "tie_word_embeddings": True}.items():
        assert cfg[key] == value, key
    assert cfg["rope_parameters"]["hybrid"]["rope_theta"] == 5000000
    assert cfg["layer_types"] == ["hybrid"] * 40
    assert cfg["held"] == {"layers": 6, "vocab_rows": 32784, "experts": 16}
    assert cfg["held"]["vocab_rows"] * 8 == cfg["vocab_size"]
    entry = [c for c in bench["configs"] if c["name"] == "zaya1-8b-d6"][0]
    assert entry["reduced"] == cfg["reduced"] == ["held.layers",
                                                  "held.vocab_rows"]
    assert set(cfg["reduced_from"]) >= set(cfg["reduced"])
    marks = " ".join(cfg["assumed"])
    assert all(f"[A{i}]" in marks for i in range(1, 6))
    assert set(cfg["optimizer"]) == set(harness.Cell(
        bench, "train-1p3b-2k").config["optimizer"])


def test_benchmark_json_gains_the_cell_and_nothing_else_moves(bench):
    cells = [w["name"] for w in bench["workloads"]]
    assert cells[-1] == CELL and cells[0] == "train-1p3b-2k"
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", [])}
    # the four program-span metrics keep the dense cell alone: a test of
    # tests/chipbench pins their lists (test_chipbench_program_spans.py)
    assert listed == {
        "train_tokens_per_s", "moe_gmm_roofline", "flash_fwd_roofline.gqa",
        "flash_bwd_roofline.gqa", "moe_expert_load_peak"}
    for m in bench["per_layer"]:
        if m["name"] in ("flash_fwd_roofline", "flash_bwd_roofline"):
            assert m["workloads"] == ["train-1p3b-2k"]   # d_head = hidden/heads
    reported = {m["name"] for m in harness.Cell(bench, CELL).per_layer()}
    assert {"train_mfu", "step_ms.train", "device_idle_share.train",
            "compiles_in_window.train"} <= reported


def test_seeded_arrays_one_by_one_equal_all_at_once(cell):
    cfg = harness.Cell(_toy_bench(), "toy-zaya-train").config
    every = zaya.make_all(cfg, 2**31 + 9)
    spec = zaya._all_leaves(cfg)
    assert len(every) == len(spec)
    for i in (0, 5, 19, 21, 22, 23, len(spec) - 1):
        one = zaya.make_leaf(cfg, 2**31 + 9, i)
        assert one.shape == tuple(spec[i][3])
        assert (np.asarray(every[i].astype(jnp.float32))
                == np.asarray(one.astype(jnp.float32))).all()
    # a scaled leaf: the router's last matrix is 250 x (toy: 500 x) wider
    w3 = [i for i, l in enumerate(spec) if l[1] == "router.w3"][0]
    wd = [i for i, l in enumerate(spec) if l[1] == "router.wd"][0]
    ratio = (float(jnp.std(every[w3].astype(jnp.float32)))
             / float(jnp.std(every[wd].astype(jnp.float32))))
    assert ratio == pytest.approx(
        cfg["changed"]["weights"]["scales"]["router.w3"], rel=0.3)
    assert float(jnp.abs(every[spec.index(
        [l for l in spec if l[1] == "beta"][0])]).max()) > 0.0


def test_matrices_too_small_for_their_rounding_are_not_held_to_it(cell):
    names = zaya.matrix_leaves(cell.config)
    assert "h.0.gu/w" in names and "h.0.dn/w" in names and "h.0/wq" in names
    assert "embed/wte" in names and "h.0/conv1.w" in names
    # the vectors are not, nor the matrices of the frozen router
    assert not {"h.0/norm1.g", "h.0/conv1.b", "h.0/tau", "h.0/router.w3",
                "h.0/router.wd", "h.0/conv0.w"} & set(names)
    held = {f"{l[0]}/{l[1]}" for l in zaya.leaves(cell.config)}
    assert set(names) <= held and "h.0/tau" in held
    assert cell.config["frozen"] == ["router."]
    assert not [n for n in held if "router" in n]
    trained = dict(cell.config, frozen=[])
    assert "h.0/router.wd" in zaya.matrix_leaves(trained)
    assert "h.0/router.w3" not in zaya.matrix_leaves(trained)


def test_serving_is_refused_by_name():
    for fn in (zaya.served_gaps, zaya.control_gaps, zaya.kv_bytes_per_token):
        with pytest.raises(NotImplementedError):
            fn({}, 1)
    with pytest.raises(NotImplementedError):
        zaya.Server({}, 1)


def test_the_fp8_control_moves_the_reference_logits():
    cfg = harness.Cell(_toy_bench(), "toy-zaya-train").config
    ids = np.random.default_rng(0).integers(0, 96, (1, 48)).astype(np.int32)
    ref, low = zaya.reference(cfg, 31), zaya.reference(cfg, 31, "fp8")
    a, b = ref.logits(ids), low.logits(ids)
    assert np.abs(a - b).max() > 1e-3 * np.abs(a).max()
    assert 0.0 <= train_routed.flip_share(
        [e for e, _ in ref.routing], [e for e, _ in low.routing]) < 0.5
    stats = zaya_reference.routing_stats(ref.routing, 4)
    assert 0.25 <= stats["top1_prob_mean"] <= 1.0


def test_a_temperatures_second_moment_is_not_compared_the_rest_of_it_is():
    cfg = harness.Cell(_toy_bench(), "toy-zaya-train").config
    rng = np.random.default_rng(3)
    tok = rng.integers(0, 96, (2, 1, 33)).astype(np.int32)
    out = zaya.reference_training(
        cfg, 31, [(t[:, :-1], t[:, 1:]) for t in tok])
    held = {f"{l[0]}/{l[1]}" for l in zaya.leaves(cfg)}
    assert set(out["grad_norm"]) == set(out["delta_norm"]) == held
    assert {"h.0/tau", "h.1/tau"} <= held
    moments = set(out["moment_norm"])
    assert {"m/" + n for n in held} <= moments
    assert {"v/" + n for n in held} - moments == {"v/h.0/tau", "v/h.1/tau"}
