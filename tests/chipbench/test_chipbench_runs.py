"""Each job kind end to end at a toy configuration on the CPU, through
the test's own call of ``harness.run`` (the command line has no CPU
mode); the plain reference against ``GPTForCausalLM``; the control and
the broken timed paths, which must come out as not correct."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from chipbench import harness
from chipbench.families import gpt2
from toy_bench import BENCH, ROOT

LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
COUNTS = {"compiles_in_window.train", "compiles_in_window.serve",
          "engine_tokens_per_step", "kv_blocks_peak_share"}


def _run(cell, seed, trace=True, **kw):
    return harness.run(BENCH, cell, seed, 1.0, trace, allow_cpu=True, **kw)


@pytest.fixture(scope="module")
def runs():
    return {cell: _run(cell, 2**31 + 17 + n)
            for n, cell in enumerate(("toy-train", "toy-open", "toy-closed"))}


@pytest.mark.parametrize("cell", ["toy-train", "toy-open", "toy-closed"])
def test_job_kind_end_to_end(runs, cell):
    line, detail = runs[cell]
    assert set(line) == LINE_KEYS            # untraced off-chip: no breakdown
    json.dumps(line)
    assert line["correct"] is True, detail["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    # off the chip only counts are reported: no time, rate or share
    assert line["metrics"] and set(line["metrics"]) <= COUNTS
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"}
    assert {c["name"] for c in detail["checks"]} >= {"compiles_in_window"}
    assert all(c["value"] <= c["limit"] for c in detail["checks"])


def test_untraced_run_reports_no_device_metric_off_chip():
    line, detail = _run("toy-closed", 5, trace=False)
    assert line["metrics"] == {} and line["correct"]
    assert detail["end_to_end"]["serve_tokens_per_s"] > 0  # kept off the line


def test_open_loop_attempts_only_what_is_due_before_the_drain(runs):
    line, _ = runs["toy-open"]
    # 12 requests/s, a 1 s window, the last 0.3 s are for draining
    assert 0 < line["attempted"] <= round(12.0 * 0.7) + 3


def test_cli_without_a_chip_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "chipbench", "--workload", "train-1p3b-2k",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "correct" not in p.stdout
    assert "no accelerator" in p.stderr


# -- the reference against the program, and the control ---------------------


@pytest.fixture(scope="module")
def toy_cfg():
    return harness.Cell(BENCH, "toy-train").config


def _program_logits(cfg, seed, ids):
    import paddle_tpu as paddle
    from paddle_tpu.base.tape import no_grad

    model, _ = gpt2._build_model(cfg, seed)
    model.float()  # the program in float32: only the code differs
    model.eval()
    with no_grad():
        return np.asarray(model(paddle.to_tensor(ids[None]))._data)[0]


def test_reference_agrees_with_gptforcausallm(toy_cfg):
    seed = 31
    ids = np.random.default_rng(0).integers(0, 500, 48).astype(np.int32)
    got = _program_logits(toy_cfg, seed, ids)
    ref = gpt2.reference(toy_cfg, seed)
    best, chosen, first = ref.position_stats(ids, got.argmax(-1))
    # float32 against float32: agreement to rounding
    assert np.abs(got.max(-1) - best).max() < 1e-4
    assert np.abs(best - chosen).max() < 1e-4
    # ... and the same check fails when the matmuls run in 8 bits
    low = gpt2.reference(toy_cfg, seed, "fp8")
    lbest, _, _ = low.position_stats(ids, got.argmax(-1))
    assert np.abs(got.max(-1) - lbest).max() > 1e-3


def test_make_leaf_equals_make_all(toy_cfg):
    from chipbench import weights

    spec = gpt2._spec(toy_cfg)
    every = weights.make_all(spec, 2**31 + 5, jnp.bfloat16)
    for i in (0, 3, 14, len(spec) - 1):
        one = weights.make_leaf(spec, 2**31 + 5, i, jnp.bfloat16)
        assert (np.asarray(every[i].astype(jnp.float32))
                == np.asarray(one.astype(jnp.float32))).all()
    other = weights.make_leaf(spec, 6, 0, jnp.bfloat16)
    assert not (np.asarray(every[0].astype(jnp.float32))
                == np.asarray(other.astype(jnp.float32))).all()
