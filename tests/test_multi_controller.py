"""Real multi-controller execution (round-4 verdict Next #2).

Spawns the framework launcher, which starts 2 actual worker processes;
each calls jax.distributed.initialize (via init_parallel_env), forms
the 4-device global mesh across both processes, runs one eager
collective from each family (all_reduce / all_gather / send+recv)
across the process boundary, and trains a DP step whose loss must match
a serial full-batch run. This is the class of evidence the
single-controller 8-vdev mesh cannot provide: coordination-service
rendezvous, per-process device locality, process-spanning collectives.

ref: test/legacy_test/test_dist_base.py:952 (spawn trainers, compare
losses), test/collective/test_communication_api_base.py:28.
"""
import os
import socket
import subprocess
import sys

import jax
import pytest

# the workers need 2 virtual CPU devices per process AND a working
# cross-process CPU collectives implementation. Newer jax provides
# jax_num_cpu_devices (and defaults CPU collectives to gloo); 0.4.37
# lacks that option but the workers fall back to
# XLA_FLAGS=--xla_force_host_platform_device_count=2 plus
# jax_cpu_collectives_implementation=gloo. Only a build with NEITHER
# path (no device-count control or no gloo) skips.
pytestmark = [
    pytest.mark.skipif(
        not ("jax_num_cpu_devices" in jax.config.values
             or "jax_cpu_collectives_implementation" in jax.config.values),
        reason="this jax build has neither jax_num_cpu_devices nor the "
               "XLA_FLAGS+gloo fallback the 2-process workers require"),
    pytest.mark.mc2,
]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "_mc_worker.py")


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.slow
def test_launcher_two_process_collectives_and_dp_parity(tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # workers run by absolute script path: repo root must be importable
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    # the workers manage their own device count; drop the test
    # harness's 8-vdev forcing so each worker gets jax_num_cpu_devices=2
    env.pop("XLA_FLAGS", None)
    log_dir = str(tmp_path / "logs")
    port = _free_port()
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--master", f"127.0.0.1:{port}", "--nproc", "2",
         "--max_restart", "0", "--log_dir", log_dir,
         "--job_id", "mc", WORKER],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=480,
    )
    logs = {}
    for r in (0, 1):
        path = os.path.join(log_dir, f"mc.rank{r}.log")
        logs[r] = open(path).read() if os.path.exists(path) else "<missing>"
    detail = (f"launcher rc={proc.returncode}\nstderr:\n{proc.stderr[-1500:]}"
              + "".join(f"\n--- rank{r} ---\n{logs[r][-3000:]}" for r in logs))
    assert proc.returncode == 0, detail
    for r in (0, 1):
        assert f"MC_WORKER_OK rank {r}" in logs[r], detail
        assert "collectives OK" in logs[r], detail
        assert "flight recorder OK" in logs[r], detail
        assert "DP loss parity OK" in logs[r], detail
        # hybrid-parallel schedules with the mesh SPANNING the process
        # boundary: TP (mp axis pairs devices across processes),
        # sharding stage 3 (4-way shard axis, shard 2|3 on process 1),
        # and the scan+ppermute pipeline (stage 1 on process 1)
        assert "TP loss parity OK" in logs[r], detail
        assert "sharding3 loss parity OK" in logs[r], detail
        assert "pipeline loss parity OK" in logs[r], detail
