"""BENCHMARK.json against the contract, and the timed path broken
underneath: ``correct`` must come out false."""
import json
import os
import re

import jax.numpy as jnp
import pytest

from chipbench import harness
from toy_bench import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["command"]) <= 32
    assert all(_line(w) and not w.startswith("/") and ".." not in w
               for w in bench["command"])
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p)
        assert os.path.isdir(os.path.join(ROOT, p))


def test_configs(bench):
    assert 1 <= len(bench["configs"]) <= 24
    names = [c["name"] for c in bench["configs"]]
    files = [c["file"] for c in bench["configs"]]
    assert len(set(names)) == len(names) and len(set(files)) == len(files)
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        body = harness.load_json(os.path.join(ROOT, c["file"]))
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not re.search(r"(_dim|_rank|n_embd|n_inner|hidden|head)",
                                 key), "a width may never be reduced"
        assert body["reduced"] == c["reduced"]
        for key in ("source", "changed", "assumed", "deployment", "family"):
            assert key in body
        assert len(body["source"]) <= 200


def test_workloads(bench):
    cells = bench["workloads"]
    assert 1 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    configs = {c["name"] for c in bench["configs"]}
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert _line(w["why"])
        cell = harness.Cell(bench, w["name"])   # every file found by name
        assert cell.traffic["kind"] and callable(cell.job.run)
        assert cell.end_to_end() and cell.per_layer()
        for m in cell.per_layer():
            assert callable(cell.reader(m["name"]))
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 4)


def test_metrics(bench):
    e2e, layer = bench["end_to_end"], bench["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(layer) <= 128
    names = [m["name"] for m in e2e + layer]
    assert len(set(names)) == len(names)
    cells = {w["name"] for w in bench["workloads"]}
    assert "setup_s" in {m["name"] for m in e2e}
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    by_name = {m["name"]: m for m in e2e}
    for m in layer:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and _line(m["layer"])
        target = by_name[m["moves"]]       # moves ONE end-to-end metric
        # ... which each of its cells reports (no list of its own: every
        # cell that reports that end-to-end metric)
        for cell in m.get("workloads", target.get("workloads", cells)):
            assert cell in target.get("workloads", cells)
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for m in e2e + layer:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for cell in cells:   # setup_s, one more end-to-end metric, one per-layer
        mine = [m for m in e2e if cell in m.get("workloads", cells)]
        assert len(mine) >= 2
        assert harness.Cell(bench, cell).per_layer()


# -- the control: the reference in the precision below must fail ------------


def test_the_fp8_control_fails_a_serving_cell_the_program_passes():
    cell = harness.Cell(BENCH, "toy-closed")
    # enough checked tokens (~200) whatever the speed of the machine
    traffic = dict(cell.traffic, check_requests=48)
    line, detail = harness.run(BENCH, "toy-closed", 3, 3.0, False,
                               allow_cpu=True, control="fp8", traffic=traffic)
    limits = cell.config["limits"]["serve"]
    assert detail["notes"]["checked_tokens"] >= 100
    assert line["correct"] is True, detail["checks"]
    notes = detail["notes"]
    # the control has to fail one of the cell's numbers, not each
    assert (notes["control.served_token_gap.mean"] > limits["token_gap_mean"]
            or notes["control.served_token_gap.widest"]
            > limits["token_gap_widest"])


def test_the_fp8_control_fails_a_training_cell_the_program_passes():
    line, detail = harness.run(BENCH, "toy-train", 4, 0.3, False,
                               allow_cpu=True, control="fp8")
    limits = harness.Cell(BENCH, "toy-train").config["limits"]["train"]
    assert line["correct"] is True, detail["checks"]
    assert detail["notes"]["control.grad_norm_gap.worst_leaf"] \
        > limits["grad_norm_gap"]


# -- the timed path broken underneath ----------------------------------------


def test_a_train_step_that_returns_its_state_unchanged_is_not_correct(
        monkeypatch):
    from chipbench.families import gpt2

    real = gpt2.Trainer.step

    def frozen(self, ids, labels):
        keep = [jnp.array(p._data, copy=True) for p in self.params]
        loss = real(self, ids, labels)
        for p, a in zip(self.params, keep):
            p._data = a                  # the step's update is thrown away
        return loss

    monkeypatch.setattr(gpt2.Trainer, "step", frozen)
    line, detail = harness.run(BENCH, "toy-train", 77, 0.5, False,
                               allow_cpu=True)
    assert line["correct"] is False
    bad = {c["name"] for c in detail["checks"] if not c["ok"]}
    assert "delta_norm_gap.worst_matrix" in bad


@pytest.mark.parametrize("key,factor,caught_by", [
    ("lr", 1.25, "delta_norm_gap.worst_matrix"),
    ("lr", 0.8, "delta_norm_gap.worst_matrix"),
    ("beta2", 0.99 / 0.999, "moment_norm_gap.worst_leaf"),
    ("beta1", 0.8 / 0.9, "moment_norm_gap.worst_leaf"),
])
def test_an_optimizer_off_the_configuration_is_not_correct(
        monkeypatch, key, factor, caught_by):
    """The program's AdamW given another learning rate or decay rate than
    the configuration states (the reference follows the configuration)."""
    from chipbench.families import gpt2

    real = gpt2.Trainer.__init__

    def off(self, cfg, seed):
        opt = dict(cfg["optimizer"])
        opt[key] *= factor
        real(self, dict(cfg, optimizer=opt), seed)
        self.cfg = cfg      # grad_norms reads the moment as configured

    monkeypatch.setattr(gpt2.Trainer, "__init__", off)
    line, detail = harness.run(BENCH, "toy-train", 80, 0.2, False,
                               allow_cpu=True)
    assert line["correct"] is False
    assert caught_by in {c["name"] for c in detail["checks"] if not c["ok"]}


def test_a_closed_loop_fails_a_request_that_never_returns():
    from chipbench.jobs import serve_closed

    class R:
        def __init__(self, t_submit, t_done):
            self.t_submit, self.t_done = t_submit, t_done

    src = serve_closed.Clients({"arrivals": {"clients": 2}}, [])
    back, slow, hung = R(0.0, 4.0), R(7.0, None), R(1.0, None)
    got = src.attempted([back, slow, hung], 10.0)
    assert got == [back, hung]      # out for 9 s where the longest took 4
    assert src.attempted([slow, hung], 10.0) == [slow, hung]  # none returned


def test_a_train_step_that_leaves_out_part_of_the_batch_is_not_correct(
        monkeypatch):
    from chipbench.families import gpt2

    real = gpt2.Trainer.step

    def half(self, ids, labels):
        ids, labels = ids.copy(), labels.copy()
        ids[1:], labels[1:] = ids[:1], labels[:1]   # row 0 stands in for all
        return real(self, ids, labels)

    monkeypatch.setattr(gpt2.Trainer, "step", half)
    line, detail = harness.run(BENCH, "toy-train", 78, 0.5, False,
                               allow_cpu=True)
    assert line["correct"] is False


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from paddle_tpu.inference import serving

    real = serving.ContinuousBatchingEngine._append_token

    def altered(self, req, tok):
        if len(req.out) == 1 and not str(req.req_id).startswith("warm"):
            tok = (int(tok) + 7) % 500
        return real(self, req, tok)

    monkeypatch.setattr(serving.ContinuousBatchingEngine, "_append_token",
                        altered)
    line, detail = harness.run(BENCH, "toy-closed", 79, 0.5, False,
                               allow_cpu=True)
    assert line["correct"] is False
    assert "served_token_gap.widest" in [
        c["name"] for c in detail["checks"] if not c["ok"]]
