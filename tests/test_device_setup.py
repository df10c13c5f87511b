"""Process set-up around the device: where the compile cache lives, how
the kernels pick compiled or interpreted mode, the peak table, the
one-process-per-chip rule of the process scheduler, and the refusal of
``chip_smoke.py`` to run without a TPU."""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

from repro.launch.roofline import CHIP_PEAKS, chip_peaks, roofline_terms
from repro.runtime import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")


def _env(**overrides):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + (os.pathsep + env["PYTHONPATH"]
                               if env.get("PYTHONPATH") else "")
    env["JAX_PLATFORMS"] = "cpu"
    for k, v in overrides.items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    return env


# ---------------------------------------------------------------------------
# compile cache
# ---------------------------------------------------------------------------

def test_env_cache_dir_wins_over_explicit(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path / "from-env"))
    assert compile_cache.cache_dir(str(tmp_path / "explicit")) == \
        str(tmp_path / "from-env")
    monkeypatch.delenv(compile_cache.ENV)
    assert compile_cache.cache_dir(str(tmp_path / "explicit")) == \
        str(tmp_path / "explicit")
    assert compile_cache.cache_dir() == compile_cache.DEFAULT_DIR


def test_default_cache_dir_is_fixed_inside_the_checkout():
    code = ("from repro.runtime.compile_cache import cache_dir; "
            "print(cache_dir())")
    env = _env(**{compile_cache.ENV: None})
    seen = {subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, check=True,
                           cwd=cwd).stdout.strip()
            for cwd in (ROOT, SRC)}
    assert seen == {os.path.join(ROOT, ".jax-cache")}


def test_cli_writes_its_compile_cache_only_where_the_env_says(tmp_path):
    env_dir, flag_dir = tmp_path / "env-cache", tmp_path / "flag-cache"
    subprocess.run(
        [sys.executable, "-m", "repro", "sweep", "--dry-run",
         "--engine", "jax", "--compile-cache", str(flag_dir)],
        env=_env(**{compile_cache.ENV: str(env_dir)}), check=True,
        capture_output=True, text=True, timeout=300)
    assert any(env_dir.iterdir())
    assert not flag_dir.exists()


def test_configure_refuses_to_move_a_configured_cache(monkeypatch,
                                                      tmp_path):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    monkeypatch.setattr(compile_cache, "_configured",
                        str(tmp_path / "a"))
    assert compile_cache.configure(str(tmp_path / "a")) == \
        str(tmp_path / "a")
    with pytest.raises(RuntimeError, match="already configured"):
        compile_cache.configure(str(tmp_path / "b"))


# ---------------------------------------------------------------------------
# kernel mode and peaks
# ---------------------------------------------------------------------------

def test_kernel_mode_follows_the_backend(monkeypatch):
    import jax

    from repro.kernels import interpret_mode
    assert interpret_mode() is (jax.default_backend() == "cpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert interpret_mode() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        interpret_mode()


def test_peak_table_knows_v5e_and_refuses_unknown_kinds():
    v5e = chip_peaks("TPU v5 lite")
    assert v5e["flops_bf16"] == 197e12 and v5e["hbm_bw"] == 819e9
    with pytest.raises(ValueError, match="TPU v9 imaginary"):
        chip_peaks("TPU v9 imaginary")
    with pytest.raises(ValueError):
        roofline_terms(1e12, 1e9, 0.0, 1, device_kind="cpu")
    terms = roofline_terms(197e12, 819e9, 0.0, 1,
                           device_kind="TPU v5 lite")
    assert terms["compute_s"] == pytest.approx(1.0)
    assert terms["memory_s"] == pytest.approx(1.0)
    assert set(CHIP_PEAKS) == {"TPU v5 lite"}


# ---------------------------------------------------------------------------
# one process per chip
# ---------------------------------------------------------------------------

class _FakeProc:
    def __init__(self, env):
        self.env = env
        self.returncode = None

    def poll(self):
        return self.returncode


def test_process_scheduler_gives_the_accelerator_to_one_live_worker(
        monkeypatch, tmp_path):
    from repro.launch.campaign import CampaignRunner
    monkeypatch.setattr(subprocess, "Popen",
                        lambda cmd, env: _FakeProc(env))
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    runner = CampaignRunner("polybench-2mm", ("systolic",),
                            scheduler="process", cache_dir=str(tmp_path))
    first = runner._spawn_worker(0, str(tmp_path))
    second = runner._spawn_worker(1, str(tmp_path))
    assert "JAX_PLATFORMS" not in first.env
    assert second.env["JAX_PLATFORMS"] == "cpu"
    first.returncode = -9                 # the holder died: hand it over
    third = runner._spawn_worker(2, str(tmp_path))
    assert "JAX_PLATFORMS" not in third.env


def test_process_scheduler_refuses_a_parent_that_loaded_jax(monkeypatch,
                                                            tmp_path):
    import jax  # noqa: F401 - the parent has jax loaded

    from repro.launch.campaign import CampaignRunner
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    runner = CampaignRunner("polybench-2mm", ("systolic",),
                            scheduler="process", cache_dir=str(tmp_path))
    with pytest.raises(RuntimeError, match="has not imported jax"):
        runner.run()


# ---------------------------------------------------------------------------
# chip_smoke.py
# ---------------------------------------------------------------------------

def test_chip_smoke_refuses_the_cpu(tmp_path):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        env=_env(**{compile_cache.ENV: str(tmp_path)}),
        capture_output=True, text=True, timeout=300, cwd=ROOT)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout
