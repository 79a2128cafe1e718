"""bench/run.py: refusal without a chip, and whole runs at a tiny size on
the CPU through the test-only entry (``run_cell``), for both cells."""
import json
import os
import subprocess
import sys
import time

import pytest

from bench import run as runmod

ROOT = runmod.ROOT


def test_refuses_cpu_without_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "llada-chat-overload", "--seed", str(2**33), "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=120, cwd=str(ROOT))
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_outside_a_checkout(tmp_path):
    """Only BENCHMARK.json and the benchmark's files: no program to run."""
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "llada-chat-overload",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120, cwd=tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.mark.parametrize("config,traffic", [
    ("llada-8b-l8", "chat-poisson"),          # MHA, one lane, open loop
    ("dream-7b-l7", "docqa-backlog"),         # GQA with bias, two lanes,
])                                            # closed loop
def test_tiny_run_is_correct(tiny, config, traffic):
    """At float32 the served tokens are the reference's best at every
    commit of every pass kind: the widest gap is zero to rounding."""
    cell = tiny(config, traffic)
    res = runmod.run_cell(cell, 2**35 + 9, 3.0, False,
                          t_start=time.monotonic(), cache=False)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    import jax
    dev = jax.devices()[0]
    assert res["device"]["platform"] == dev.platform
    assert res["device"]["kind"] == dev.device_kind
    names = {m.name for m in cell.end_to_end}
    assert set(res["metrics"]) == names
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["checks"]["gap_widest"]["value"] < 1e-4
    assert res["checks"]["gap_mean"]["value"] < 1e-6
    json.dumps(res)
