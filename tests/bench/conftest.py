"""Shared pieces of the benchmark's tests: the checkout root on the import
path, and tiny copies of the cells that run on the CPU."""
import copy
import dataclasses
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def _set_flag(argv, flag, value):
    argv[argv.index(flag) + 1] = value


def tiny_cell(config: str, traffic: str, dtype: str = "float32",
              rate: float = 3.0):
    """A cell of ``bench/configs/<config>.json`` and
    ``bench/traffic/<traffic>.json`` at a size the CPU runs in seconds: the
    same code paths, traffic law and flags, with small widths, prompts to
    64, outputs to four blocks of 32, pages of 32 (the XLA lowering takes
    any page) and an open loop's lead-in cut to 1 s."""
    from bench import spec
    # the chat cell's end-to-end metrics; the tails only for its open loop
    e2e = tuple(m for m in
                spec.cell_metrics(spec.benchmark(), "llada-chat-overload")[0]
                if m.workloads is None or traffic == "chat-poisson")
    cell = spec.Cell(
        name=f"tiny-{config}", chips=1,
        config=spec.load_json(spec.BENCH / "configs" / f"{config}.json"),
        traffic=spec.load_json(spec.BENCH / "traffic" / f"{traffic}.json"),
        traffic_name=traffic,
        limits=spec.load_json(spec.BENCH / "limits" / "llada-chat-overload.json"),
        end_to_end=e2e, per_layer=())
    cfg = copy.deepcopy(cell.config)
    cfg["model"].update(n_layers=4, d_model=64, n_heads=4, head_dim=16,
                        n_kv_heads=2 if cfg["model"]["qkv_bias"] else 4,
                        d_ff=128, vocab_size=503, param_dtype=dtype,
                        compute_dtype=dtype)
    argv = cfg["serve_argv"]
    _set_flag(argv, "--prompt-len", "64")
    _set_flag(argv, "--gen-length", "128")
    _set_flag(argv, "--page-size", "32")
    if "--shards" in argv:
        _set_flag(argv, "--shards", "2")
        _set_flag(argv, "--batch", "8")
    else:
        _set_flag(argv, "--batch", "4")
    cfg["check_tokens"] = 96
    mix = copy.deepcopy(cell.traffic)
    mix["prompt_tokens"].update(min=4, max=64)
    if "median" in mix["prompt_tokens"]:
        mix["prompt_tokens"]["median"] = 20
    if mix["loop"] == "open":
        mix["arrival"]["rate_per_s"] = rate
        mix["lead_in_s"] = 1.0
    mix.pop("output_tokens", None)
    mix["output_blocks"] = {"dist": "choice", "values": [1, 2, 3, 4],
                            "weights": [0.4, 0.3, 0.2, 0.1]}
    return dataclasses.replace(cell, config=cfg, traffic=mix)


@pytest.fixture
def tiny():
    return tiny_cell
