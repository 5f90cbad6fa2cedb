"""Pod-scale elastic training: kill-one-rank resume + reshard (ISSUE 16).

Three runs of ``tests/_elastic_shard_worker.py`` over one model/data
schedule:

1. **Reference** — solo (1 process x 2 devices), uninjected, 8 steps.
2. **Pod wave** — the real launcher, 2 processes x 1 device, the
   ("sharding", 2) mesh CROSSING the process boundary, stage-3
   group-sharded under a TrainingSupervisor publishing SHARDED peer-RAM
   snapshots. ``PADDLE_CHAOS=train.kill_rank.1@6=kill`` SIGKILLs rank 1
   at its 6th executed step; the launcher tears down rank 0 and exits
   nonzero.
3. **Elastic resume** — solo again, SAME scratch dir. The dead wave's
   heartbeats age out (world 2→1: a re-mesh), resume() takes the
   consistent cut (min over both saved ranks = step 4), gathers BOTH
   ranks' shard payloads, restores through the cross-topology reshard
   (``reshard_resumes`` increments), replays step 5 (charged to the
   goodput ledger's rollback bucket via the telemetry high-water mark),
   and finishes 6..8.

The final loss of run 3 must equal run 1 **bitwise** (hex-compared
f32): with a 2-way sharding axis every reduction is a 2-term sum, and
f32 addition of two terms is order-insensitive, so the gloo
cross-process wave and the XLA single-process waves agree to the bit.
"""
import os
import re
import socket
import subprocess
import sys

import jax
import pytest

pytestmark = [
    pytest.mark.skipif(
        not ("jax_num_cpu_devices" in jax.config.values
             or "jax_cpu_collectives_implementation" in jax.config.values),
        reason="this jax build has neither jax_num_cpu_devices nor the "
               "XLA_FLAGS+gloo fallback the 2-process workers require"),
    pytest.mark.mc2,
]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "_elastic_shard_worker.py")


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _base_env(scratch):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)  # workers pick their own device count
    env.pop("PADDLE_CHAOS", None)
    env["ELASTIC_DIR"] = scratch
    env["TOTAL_STEPS"] = "8"
    return env


def _solo(scratch, *, settle=0.0, timeout=300):
    env = _base_env(scratch)
    env["ELASTIC_SHARD_MODE"] = "solo"
    env["MC_LOCAL_DEVICES"] = "2"
    if settle:
        env["ELASTIC_SETTLE_S"] = str(settle)
    return subprocess.run([sys.executable, "-u", WORKER], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=timeout)


def _grab(pattern, text):
    m = re.search(pattern, text)
    assert m, f"{pattern!r} not found in:\n{text[-4000:]}"
    return m.group(1)


@pytest.mark.slow
def test_kill_one_rank_elastic_resume_bitwise_parity(tmp_path):
    # 1. uninjected reference
    ref = _solo(str(tmp_path / "ref"))
    assert ref.returncode == 0, ref.stdout[-4000:] + ref.stderr[-4000:]
    assert "ESHARD_OK rank 0" in ref.stdout
    ref_hex = _grab(r"final_loss_hex=([0-9a-f]{8})", ref.stdout)

    # 2. pod wave: 2 processes x 1 device, kill rank 1 mid-pretrain
    pod = str(tmp_path / "pod")
    env = _base_env(pod)
    env["ELASTIC_SHARD_MODE"] = "dist"
    env["MC_LOCAL_DEVICES"] = "1"
    env["PADDLE_CHAOS"] = "train.kill_rank.1@6=kill"
    log_dir = str(tmp_path / "logs")
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--master", f"127.0.0.1:{_free_port()}", "--nproc", "2",
         "--max_restart", "0", "--log_dir", log_dir,
         "--job_id", "es", WORKER],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=480)
    logs = {}
    for r in (0, 1):
        path = os.path.join(log_dir, f"es.rank{r}.log")
        logs[r] = open(path).read() if os.path.exists(path) else "<missing>"
    detail = (f"launcher rc={proc.returncode}\nstderr:\n{proc.stderr[-1500:]}"
              + "".join(f"\n--- rank{r} ---\n{logs[r][-3000:]}" for r in logs))
    # the kill propagates: rank 1 dies -9, the launcher reaps the pod
    assert proc.returncode != 0, detail
    for r in (0, 1):
        assert f"rank {r}: ELASTIC world=2" in logs[r], detail
        assert f"rank {r}: RESUME next_step=1" in logs[r], detail
        assert f"ESHARD_OK rank {r}" not in logs[r], detail

    # 3. elastic resume on the SAME scratch, shrunk world
    res = _solo(pod, settle=2.0)
    out = res.stdout
    assert res.returncode == 0, out[-4000:] + res.stderr[-4000:]
    assert "ESHARD_OK rank 0" in out, out[-4000:]
    # re-mesh: the dead pod aged out, this wave registers alone
    assert "ELASTIC world=1" in out, out[-4000:]
    # consistent cut: min over BOTH saved ranks' peer snapshots (4),
    # gathered from the saved world [0, 1], not the current world [0]
    assert _grab(r"RESUME next_step=(\d+)", out) == "5", out[-4000:]
    assert "gather_ranks=[0, 1]" in out, out[-4000:]
    # the restore crossed topologies: saved world=2 → target world=1
    assert _grab(r"reshard_resumes=(\d+)", out) == "1", out[-4000:]
    # bitwise: resumed pod run == uninjected solo run, to the bit
    res_hex = _grab(r"final_loss_hex=([0-9a-f]{8})", out)
    assert res_hex == ref_hex, (
        f"final loss diverged: resumed={res_hex} reference={ref_hex}\n"
        + out[-4000:])
    # goodput ledger: the replayed step (5 ≤ telemetry high-water)
    # charges rollback, the resume wall charges checkpoint
    rollback = float(_grab(r"rollback=([0-9.]+)", out))
    checkpoint = float(_grab(r"checkpoint=([0-9.]+)", out))
    assert rollback > 0.0, out[-4000:]
    assert checkpoint > 0.0, out[-4000:]
