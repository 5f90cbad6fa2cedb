"""Test configuration.

Tests run on a virtual 8-device CPU mesh so every parallelism path is
exercisable without a TPU pod (SURVEY.md §4 implication (c): fake/CPU mesh
backend). Must configure BEFORE jax initializes a backend.
"""
import os

prev = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in prev:
    os.environ["XLA_FLAGS"] = (
        prev + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

# tier-1 is the CPU lane whatever the machine holds: pin the config too
# (a config default can outrank the env var)
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# What the driver's lane leaves out: it runs `-m 'not slow'` over six
# workers (`--dist loadfile`, a 1470 s limit), and every test of a file
# named here is marked `slow` unless the test carries a marker of its
# own. A file leaves this list when its tests guard a path a benchmark
# cell runs and fit the lane (ROADMAP D14 has each file's seconds); a
# single heavy test of a quick file is marked `slow` where it stands.
_SLOW_FILES = {
    "test_advice_fixes.py",       # torch-parity ctc/grid_sample sweeps
    "test_auto_checkpoint.py",    # kill-and-relaunch subprocess
    "test_convergence.py",        # real training-to-target runs
    "test_auto_parallel.py",
    "test_auto_tuner.py",         # measured-step tune loop
    "test_distributed.py",
    "test_distribution.py",       # 25 scipy-validated distributions
    "test_fft_sparse.py",
    "test_generation.py",
    "test_grad_sweep.py",
    "test_graft_entry.py",        # 8-device GSPMD + pipeline dryrun
    "test_hapi_metric.py",
    "test_hybrid_parallel.py",
    "test_io.py",
    "test_moe.py",
    "test_namespace_parity.py",
    "test_namespace_parity2.py",
    "test_nn_layers.py",
    "test_paged_attention.py",
    "test_parity_modules.py",
    "test_ring_attention.py",
    "test_rnn.py",
    "test_sharding_and_io.py",
    "test_store_rpc.py",          # spawns subprocesses
    "test_unet.py",
    "test_vision.py",
    "test_sparse_nn.py",          # point-cloud training runs
    "test_multi_controller.py",   # spawns 2 jax.distributed processes
    "test_serving.py",            # continuous-batching vs generate()
    "test_quant_exec.py",         # int8 serving end-to-end
    "test_shm_ring.py",           # multi-process dataloader epochs
    "test_fused_layers.py",       # fused-transformer decode parity
    "test_launch.py",             # launcher subprocess spawns
    # ISSUE 4 robustness lane (`pytest -m robustness`): engine-backed
    # overload/supervisor tests; pure-controller units are marked quick
    "test_admission.py",
    "test_supervisor.py",
    # ISSUE 10 async-pipelining lane: the core parity/recompile/metric
    # gates are explicitly marked quick; the full matrix (spec/int8/
    # disagg-role engines compile extra programs) rides the slow lane
    "test_serving_overlap.py",
}


def pytest_configure(config):
    config.addinivalue_line("markers", "quick: fast subset (< 5 min total)")
    config.addinivalue_line("markers", "slow: heavyweight tests (CI shard 2)")
    config.addinivalue_line(
        "markers",
        "analysis: graft-lint static-analysis + recompile-sanitizer gate "
        "(standalone via `pytest -m analysis`, < 60 s)")
    config.addinivalue_line(
        "markers",
        "kernels: Pallas kernel numerics lane — fp8 GEMM quality gates, "
        "interpret-mode on CPU (standalone via `pytest -m kernels`)")
    config.addinivalue_line(
        "markers",
        "robustness: overload-control / chaos / self-healing serving "
        "suite (standalone via `pytest -m robustness`)")
    config.addinivalue_line(
        "markers",
        "cluster: replica-router / prefix-cache / multi-process serving "
        "suite (standalone via `pytest -m cluster`)")
    config.addinivalue_line(
        "markers",
        "spec: speculative-decoding + int8-KV quick lane "
        "(standalone via `pytest -m spec`)")
    config.addinivalue_line(
        "markers",
        "disagg: disaggregated prefill/decode + KV-handoff suite "
        "(quick-lane units; the 2-process kill test rides the slow "
        "lane; standalone via `pytest -m disagg`)")
    config.addinivalue_line(
        "markers",
        "trainfault: fault-tolerant training suite — anomaly detection/"
        "rollback/peer-snapshot/telemetry units (quick lane; the "
        "2-process kill->peer-RAM-resume proof rides the slow lane; "
        "standalone via `pytest -m trainfault`)")
    config.addinivalue_line(
        "markers",
        "overlap: async host/device pipelining suite — overlap-vs-sync "
        "token-exactness matrix, device-state invariants, recompile "
        "pin, crash-mid-pipeline recovery (standalone via "
        "`pytest -m overlap`)")
    config.addinivalue_line(
        "markers",
        "obs: observability suite — metrics registry units, legacy-"
        "stats parity, health-schema pin, trace stitch/export "
        "(quick-lane; the 2-process stitched trace rides the slow "
        "lane; standalone via `pytest -m obs`)")
    config.addinivalue_line(
        "markers",
        "slo: load-harness + fleet-SLO suite — seeded open-loop "
        "schedule determinism, attainment math, tenant labels, "
        "cardinality cap, KVStore aggregation (quick-lane; the real "
        "multi-process router aggregation proof rides the slow lane; "
        "standalone via `pytest -m slo`)")
    config.addinivalue_line(
        "markers",
        "race: graft-race lane — RACE001/LOCK001/LOCK002 static-rule "
        "fixtures, the TracedLock lockdep sanitizer units, the seeded "
        "two-lock deadlock proof (static + runtime + hang dump), the "
        "thread.preempt chaos site, and the CLI gate (quick-lane; the "
        "sanitizer-overhead A/B rides the slow lane; standalone via "
        "`pytest -m race`)")
    config.addinivalue_line(
        "markers",
        "mc2: real 2-process multi-controller lane — launcher-spawned "
        "jax.distributed workers running cross-process collectives, "
        "DP/TP/sharding-3/pipeline parity, and the kill-one-rank "
        "sharded elastic resume proof (standalone via `pytest -m mc2`)")
    config.addinivalue_line(
        "markers",
        "alerts: SLO-alerting + regression-sentinel suite — burn-rate "
        "math vs hand-computed windows, alert lifecycle determinism "
        "under seeded flapping, absence detection, bench-ledger "
        "regression verdicts, CLI exit codes, loadgen parity "
        "(quick-lane; standalone via `pytest -m alerts`)")
    config.addinivalue_line(
        "markers",
        "autoscale: closed-loop fleet-control suite — burn-driven "
        "scale-up/-down hysteresis, feed-forward floor, chaos spawn "
        "backoff + alert visibility, draining placement, mid-drain "
        "SIGKILL zero-loss, WFQ/token-bucket tenant isolation, and "
        "the host-RAM prefix-cache tier (quick-lane; standalone via "
        "`pytest -m autoscale`)")
    config.addinivalue_line(
        "markers",
        "own: graft-own lane — OWN001/OWN002/OWN003 resource-lifecycle "
        "static-rule fixtures, the ResourceLedger leak-sanitizer units "
        "(conservation vs a live BlockManager, leak naming, leak.hold "
        "chaos), the static+runtime double proof on one seeded leak, "
        "and the CLI gate (quick-lane; the ledger-overhead A/B rides "
        "the slow lane; standalone via `pytest -m own`)")


# ONE assertion each of two tests under a benchmark path (tests/chipbench is one of
# BENCHMARK.json's ``paths``: only a ``benchmark`` PR may edit it) that a
# later PR's required entry ended: PR 26's test asks that ITS cell be the
# LAST of BENCHMARK.json's workloads (``cells[-1] == CELL``). PR 32 had to
# add a cell, and the builder's contract says where: "Put new entries at
# the end of their lists: one put first or in the middle reads as a change
# to what was there", which refuses the PR. So the new cell is last, that
# test cannot pass as worded, and it is expected to fail, strictly: the
# marker must go once a ``benchmark`` PR turns the assertion into
# membership. Nothing it asserts goes unasserted meanwhile:
# tests/chipbench/test_chipbench_trinity.py::
# test_the_cells_before_it_are_as_their_prs_left_them calls its body on
# the lists up to its cell.
_OUTDATED = {
    "test_chipbench_zaya.py::"
    "test_benchmark_json_gains_the_cell_and_nothing_else_moves":
        "PR 32 appended a cell after train-zaya1-6l-4k (PERF.md section 7)",
    # the same, one PR on: PR 32's test asks that the metrics it brought
    # list ITS cell alone (``m["workloads"] == [CELL]``), and issue 34
    # has its cell appended to two of those lists (moe_gmm_roofline.held,
    # moe_held_rows_ratio). tests/chipbench/test_chipbench_qwen3next.py::
    # test_the_cells_before_it_are_as_their_prs_left_them calls its body
    # on the benchmark without PR 34's entries.
    "test_chipbench_trinity.py::test_benchmark_json_gains_the_cell":
        "PR 34 appended its cell to two lists PR 32 brought (PERF.md "
        "section 7)",
    # and two PRs on: PR 34's test pops the LAST name of every list that
    # holds its cell and asks that it be its own, and PR 36's asks that
    # its six metrics be the LAST of ``per_layer``; issue 38 has a cell
    # appended to train_tokens_per_s's list and five metrics to
    # ``per_layer``. tests/chipbench/test_chipbench_minicpm_sala.py::
    # test_the_cells_before_it_are_as_their_prs_left_them calls both
    # bodies on the benchmark without PR 38's entries.
    "test_chipbench_qwen3next.py::"
    "test_the_cells_before_it_are_as_their_prs_left_them":
        "PR 38 appended its cell to train_tokens_per_s's list after "
        "PR 34's (PERF.md section 7)",
    "test_chipbench_compile_spans.py::test_the_entries_in_benchmark_json":
        "PR 38 appended five per-layer metrics after PR 36's six (PERF.md "
        "section 7)",
    # and two PRs on again: PR 38's test cuts the lists where ITS entries
    # begin and then runs PR 34's body, which pops the last name of every
    # list that holds PR 34's cell; issue 40 has a cell appended to the
    # two ``.gqa`` flash lists, which hold PR 34's cell and not PR 38's.
    # tests/chipbench/test_chipbench_granite.py::
    # test_the_cells_before_it_are_as_their_prs_left_them calls its body
    # and PR 38's own ``gains`` on the benchmark without PR 40's entries.
    "test_chipbench_minicpm_sala.py::"
    "test_the_cells_before_it_are_as_their_prs_left_them":
        "PR 40 appended its cell to the two .gqa flash lists after PR 34's "
        "(PERF.md section 7)",
    # and four PRs on: PR 40's test takes ITS entries out and then runs
    # PR 38's bodies down the same chain; issue 44 has a cell appended to
    # seven lists that hold PR 32's or PR 34's cell (the two .window and
    # the two .gqa flash lists, moe_gmm_roofline.held, moe_held_rows_ratio,
    # moe_expert_load_peak) and to train_tokens_per_s's.
    # tests/chipbench/test_chipbench_smallthinker.py::
    # test_the_cells_before_it_are_as_their_prs_left_them calls its body
    # and PR 40's own ``gains`` on the benchmark without PR 44's entries;
    # that file's own tests are written by membership, so the next PR
    # need not mark it.
    "test_chipbench_granite.py::"
    "test_the_cells_before_it_are_as_their_prs_left_them":
        "PR 44 appended its cell to seven per-layer lists after PR 32's and "
        "PR 34's (PERF.md section 7)",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        why = _OUTDATED.get("::".join(item.nodeid.split("/")[-1:]))
        if why:
            item.add_marker(pytest.mark.xfail(reason=why, strict=True))
        # explicit per-test/module markers win over the file lists
        # (a file-level default must not drag a marked-slow test into
        # the quick lane or vice versa)
        if (item.get_closest_marker("slow") is not None
                or item.get_closest_marker("quick") is not None):
            continue
        name = os.path.basename(str(item.fspath))
        item.add_marker(
            pytest.mark.slow if name in _SLOW_FILES else pytest.mark.quick
        )


@pytest.fixture(autouse=True)
def _fixed_seed():
    import paddle_tpu

    paddle_tpu.seed(2024)
    yield
