"""Launcher / spawn tests.

Reference pattern: test/legacy_test/test_launch_coverage.py,
test_spawn_and_init_parallel_env.py — env injection, process
management, restart-on-failure, log capture.
"""
import os
import subprocess
import sys
import textwrap

import pytest

from paddle_tpu.distributed.launch.main import _build_env, _parse_args, launch


class TestEnvInjection:
    def test_env_vars(self):
        args = _parse_args(
            ["--nnodes", "2", "--rank", "1", "--nproc", "2",
             "--master", "h:123", "train.py"]
        )
        env = _build_env(args, local_rank=1)
        assert env["JAX_COORDINATOR_ADDRESS"] == "h:123"
        assert env["JAX_NUM_PROCESSES"] == "4"
        assert env["JAX_PROCESS_ID"] == "3"
        assert env["PADDLE_TRAINER_ID"] == "3"
        assert env["PADDLE_TRAINERS_NUM"] == "4"
        assert env["PADDLE_LOCAL_RANK"] == "1"

    def test_script_args_passthrough(self):
        args = _parse_args(["train.py", "--lr", "0.1"])
        assert args.training_script == "train.py"
        assert args.training_script_args == ["--lr", "0.1"]


class TestLaunch:
    def _script(self, tmp_path, body):
        p = tmp_path / "train.py"
        p.write_text(textwrap.dedent(body))
        return str(p)

    def test_success_and_logs(self, tmp_path):
        script = self._script(
            tmp_path,
            """
            import os
            print("rank", os.environ["PADDLE_TRAINER_ID"], "of",
                  os.environ["PADDLE_TRAINERS_NUM"])
            """,
        )
        log_dir = str(tmp_path / "logs")
        rc = launch(["--nproc", "2", "--log_dir", log_dir, script])
        assert rc == 0
        logs = sorted(os.listdir(log_dir))
        assert len(logs) == 2
        content = (tmp_path / "logs" / logs[0]).read_text()
        assert "rank 0 of 2" in content

    @pytest.mark.quick
    def test_several_ranks_on_one_host_is_the_cpu_topology(
            self, tmp_path, monkeypatch, capsys):
        """One process for each chip: ranks on one host are pinned to
        the CPU (and the launcher says so); asking for the TPU with
        --nproc > 1 is refused, for launch and for spawn."""
        from paddle_tpu.distributed import spawn

        script = self._script(
            tmp_path, "import os; print(os.environ['JAX_PLATFORMS'])\n")
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        with pytest.raises(RuntimeError, match="CPU test topology"):
            launch(["--nproc", "2", "--log_dir", str(tmp_path / "l"), script])
        with pytest.raises(RuntimeError, match="CPU test topology"):
            spawn(print, nprocs=2)
        monkeypatch.delenv("JAX_PLATFORMS")
        log_dir = tmp_path / "logs"
        assert launch(["--nproc", "2", "--log_dir", str(log_dir), script]) == 0
        assert "CPU test topology" in capsys.readouterr().err
        assert all(p.read_text().strip() == "cpu" for p in log_dir.iterdir())

    def test_failure_restarts_then_fails(self, tmp_path):
        script = self._script(tmp_path, "import sys; sys.exit(7)\n")
        rc = launch(
            ["--nproc", "1", "--max_restart", "1",
             "--log_dir", str(tmp_path / "logs"), script]
        )
        assert rc == 7


class TestSpawn:
    def test_spawn_runs_ranks(self, tmp_path):
        # spawn pickles func: use a subprocess driver script
        driver = tmp_path / "driver.py"
        driver.write_text(textwrap.dedent(f"""
            import os, sys
            sys.path.insert(0, {str(os.getcwd())!r})
            from paddle_tpu.distributed import spawn

            def work(out_dir):
                rank = os.environ["PADDLE_TRAINER_ID"]
                open(os.path.join(out_dir, f"r{{rank}}"), "w").write("ok")

            if __name__ == "__main__":
                spawn(work, args=({str(tmp_path)!r},), nprocs=2)
        """))
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        r = subprocess.run([sys.executable, str(driver)], env=env,
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr[-500:]
        assert (tmp_path / "r0").exists() and (tmp_path / "r1").exists()
