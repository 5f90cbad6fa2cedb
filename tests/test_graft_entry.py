"""The driver-visible entry points must keep working.

__graft_entry__.py: entry() compiles single-device; dryrun_multichip
runs BOTH phases — GSPMD placement (dp,fsdp,mp) and the scan+ppermute
pipeline (dp,pp,mp) — on the virtual 8-device CPU mesh.

chip_smoke.py (the bring-up contract, quick lane): the flagged tiny CPU
mode runs every phase and exits 0; without a chip the default mode
exits non-zero and prints no result; the compile cache goes
where JAX_COMPILATION_CACHE_DIR says or to one fixed in-checkout path;
an unknown device_kind is an error, never a default peak."""
import json
import os
import subprocess
import sys
import types

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def _run(*argv, env=None):
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    base["JAX_PLATFORMS"] = "cpu"
    base.update(env or {})
    return subprocess.run([sys.executable, *argv], cwd=_REPO, env=base,
                          capture_output=True, text=True, timeout=600)


@pytest.mark.quick
class TestBringUp:
    def test_chip_smoke_tiny_cpu_mode_runs_every_phase(self):
        r = _run("chip_smoke.py", "--tiny-cpu")
        assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
        lines = r.stdout.strip().splitlines()
        for phase in ("env", "kernels/flash", "trainer", "server"):
            assert any(f"phase={phase} ok" in ln for ln in lines), phase
        # every line says where it ran; a CPU run names no rate
        assert all(ln.startswith("[smoke] platform=cpu ")
                   for ln in lines[:-1])
        assert not any(w in r.stdout for w in ("step_ms", "tokens/s", "mfu"))
        result = json.loads(lines[-1])
        assert result["ok"] is True
        assert result["device"]["platform"] == "cpu"

    def test_no_chip_no_result(self):
        """The default mode needs the accelerator: non-zero exit, a
        one-line reason, and no result line — not even a metric's name."""
        r = _run("chip_smoke.py")
        assert r.returncode != 0
        assert r.stdout.strip() == "", r.stdout
        assert "no accelerator" in r.stderr.splitlines()[-1], r.stderr

    def test_compile_cache_is_placed_from_outside_or_fixed(self, tmp_path):
        code = ("from paddle_tpu.utils.compile_cache import "
                "enable_compile_cache as e; import jax; "
                "print(e()); print(jax.config.jax_compilation_cache_dir)")
        outs = [_run("-c", code).stdout.split() for _ in range(2)]
        # unset: the same in-checkout, git-ignored path from two processes
        assert outs[0] == outs[1] == [os.path.join(_REPO, ".jax_cache")] * 2
        ignored = subprocess.run(
            ["git", "check-ignore", "-q", ".jax_cache/x"], cwd=_REPO)
        assert ignored.returncode == 0
        # set: honoured (jax reads the variable itself), nothing overridden
        outside = str(tmp_path / "cache")
        out = _run("-c", code,
                   env={"JAX_COMPILATION_CACHE_DIR": outside}).stdout.split()
        assert out == [outside, outside]

    def test_attention_kernel_error_propagates(self, monkeypatch):
        """No silent fallback: when the Pallas path is selected and the
        kernel raises, scaled_dot_product_attention raises — it does not
        quietly become the S x S jnp path."""
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F
        from paddle_tpu.nn.functional import attention
        from paddle_tpu.ops import flash_attention

        def refuse(*a, **k):
            raise RuntimeError("Mosaic refused this kernel")

        monkeypatch.setattr(attention, "_use_pallas", lambda *a: True)
        monkeypatch.setattr(flash_attention, "flash_attention_fwd", refuse)
        q = paddle.ones([1, 128, 2, 64])
        with pytest.raises(RuntimeError, match="Mosaic refused"):
            F.scaled_dot_product_attention(q, q, q, is_causal=True)

    def test_unknown_device_kind_is_an_error(self):
        from paddle_tpu.device.peaks import chip_peaks

        v5e = chip_peaks(types.SimpleNamespace(device_kind="TPU v5 lite"))
        assert (v5e.bf16_flops, v5e.int8_ops, v5e.hbm_bytes_per_s,
                v5e.hbm_bytes) == (197e12, 393e12, 819e9, 16e9)
        for kind in ("TPU v5", "cpu", "TPU v99"):  # no substring matching
            with pytest.raises(LookupError, match="no published peaks"):
                chip_peaks(types.SimpleNamespace(device_kind=kind))


def test_entry_compiles():
    import __graft_entry__ as g
    import jax

    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    assert out.shape == (2, 32, 256)


def test_dryrun_multichip_both_phases(capsys):
    import __graft_entry__ as g

    g.dryrun_multichip(8)
    out = capsys.readouterr().out
    assert "dryrun_multichip(8): mesh=(dp=2,fsdp=2,mp=2)" in out
    assert "OK" in out
    assert "dryrun pipeline(8): mesh=(dp=2,pp=2,mp=2)" in out
    # every phase ended OK (each raises on a loss mismatch); the last
    # line is the real 2-process launcher phase
    assert "ring-attention" in out and "8-expert MoE" in out
    assert "dryrun mc(2proc): 2-process launcher rc=0" in \
        out.strip().splitlines()[-1]
