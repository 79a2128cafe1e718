"""chip_smoke.py off the chip: it refuses the CPU, and its phases run end to
end at a small size with the kernels in interpret mode."""
import dataclasses
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro import configs
from repro.launch import serve

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def test_refuses_cpu_without_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=300, cwd=ROOT)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "platform=cpu" in proc.stdout


@pytest.fixture
def small_smoke(monkeypatch):
    monkeypatch.setattr(chip_smoke, "SERVE_ARGV", [
        "--arch", "llada-8b", "--mode", "es", "--paged", "--early-advance",
        "--prompt-len", "16", "--gen-length", "16", "--block-length", "8",
        "--page-size", "8"])
    monkeypatch.setattr(chip_smoke, "SLOTS_PER_CHIP", 2)
    monkeypatch.setattr(chip_smoke, "N_REQUESTS", 3)
    monkeypatch.setattr(chip_smoke, "model_config", lambda: dataclasses.replace(
        configs.reduced(configs.get_config("llada-8b")), n_layers=4,
        param_dtype="bfloat16", compute_dtype="bfloat16"))


def test_one_chip_phases_pass_in_interpret_mode(small_smoke, capsys):
    errs = chip_smoke.one_chip(chip_smoke.CompileClock(), interpret=True)
    assert errs == []
    out = capsys.readouterr().out
    assert "serve[xla]: completed=3/3" in out
    assert "serve[pallas]: completed=3/3" in out
    for name in chip_smoke.TOL:
        assert f"kernel {name}:" in out


def test_compile_cache_follows_env(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert serve.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # JAX reads the env
