"""Each metric reader on a hand-made run record, and the harness finding a
configuration, a mix and a metric added under new names."""
import json
import shutil

import pytest

from bench import spec

PEAK = 100.0e12


def record():
    """Window 10 s, block 32, two lanes, after a lead-in.  Request 0: due 0,
    admitted 0.5, blocks at 2, 3 and 5 (ends inside).  Request 1: due 4,
    admitted 6, blocks at 7 and 12 (the second after the close).  Request
    2: due 9, never admitted.  Request 3: closed loop, no due, one block at
    8.  Request 4: due -3 in the lead-in, blocks at -1 and 1.5."""
    return {
        "seconds": 10.0, "chips": 2, "block_length": 32,
        "model": {"n_layers": 2, "d_model": 4, "n_heads": 1, "n_kv_heads": 1,
                  "head_dim": 4, "d_ff": 8, "vocab_size": 5},
        "es": {"stage_layers": [0], "keep": [1]},
        "requests": [
            {"due": 0.0, "admit": 0.5, "blocks": [2.0, 3.0, 5.0],
             "n_blocks": 3, "prompt_tokens": 3, "lane": 0},
            {"due": 4.0, "admit": 6.0, "blocks": [7.0, 12.0],
             "n_blocks": 2, "prompt_tokens": 3, "lane": 1},
            {"due": 9.0, "admit": None, "blocks": [], "n_blocks": 1,
             "prompt_tokens": 3, "lane": 1},
            {"due": None, "admit": 1.0, "blocks": [8.0], "n_blocks": 1,
             "prompt_tokens": 3, "lane": 0},
            {"due": -3.0, "admit": -2.5, "blocks": [-1.0, 1.5],
             "n_blocks": 2, "prompt_tokens": 3, "lane": 1},
        ],
        "steps": [
            {"t0": -1.0, "t1": -0.5, "lane": 0,
             "rows": [[3, 2, "prompt_refresh"]]},
            {"t0": 0.0, "t1": 1.0, "lane": 0,
             "rows": [[3, 3, "prompt_refresh"]]},
            {"t0": 1.0, "t1": 2.0, "lane": 0,
             "rows": [[3, 3, "skip_decode"], [3, 1, "prompt_refresh"]]},
            {"t0": 2.0, "t1": 3.0, "lane": 1, "rows": [[3, 2, "block_refresh"]]},
            {"t0": 3.0, "t1": 4.0, "lane": 1, "rows": []},
            {"t0": 10.5, "t1": 11.0, "lane": 1,
             "rows": [[3, 2, "prompt_refresh"]]},
        ],
        "rounds": [[-1.0, -0.5], [0.0, 1.0], [1.0, 2.0], [2.0, 3.5], [3.5, 4.0],
                   [10.5, 11.0]],
        "lane_tokens": [4 * 32, 1 * 32], "setup_s": 12.5,
        "peaks": {"bf16_flops": PEAK},
        "trace": {"busy_s": 0.6, "window_s": 0.8, "idle_share": 0.25},
    }


def read(name, rec=None):
    return spec.reader(name)(record() if rec is None else rec)


def test_end_to_end_readers():
    assert read("setup_s") == 12.5
    # blocks committed in [0, 10]: 2, 3, 5, 7, 8, 1.5 -> 6 blocks of 32
    assert read("tokens_per_s") == pytest.approx(6 * 32 / 10.0)
    # first blocks of the requests due in the window: 2 - 0 and 7 - 4
    # (request 2 failed; request 4 was due in the lead-in)
    assert read("first_block_p90_s") == pytest.approx(2.0 + 0.9 * 1.0)
    # gaps whose later block commits inside: 1, 2 (request 0) and 2.5
    # (request 4); 7 -> 12 ends after the close
    assert read("block_gap_p90_s") == pytest.approx(2.0 + 0.8 * 0.5)


def test_per_layer_readers():
    assert read("queue_wait_p90_s") == pytest.approx(0.5 + 0.9 * 1.5)
    assert read("lane_imbalance") == pytest.approx(128 / 80)
    # the four rounds that start inside the window: 1, 1, 1.5 and 0.5 s
    assert read("step_ms") == pytest.approx(1000.0)
    # steps with rows in the window (not the lead-in's): 3, of which 2 had
    # a prompt refresh
    assert read("refresh_step_share") == pytest.approx(100 * 2 / 3)
    assert read("device_idle_share") == pytest.approx(25.0)
    from bench import work
    rec = record()
    flops = sum(work.step_flops(rec["model"], rec["es"], k, p, n, 32)
                for s in rec["steps"][1:5] for p, n, k in s["rows"])
    # over the 4 s of the window's scheduler steps on 2 chips
    assert read("step_mfu") == pytest.approx(100 * flops / (4.0 * 2 * PEAK))


def test_readers_find_nothing_to_read():
    rec = record()
    rec.update(lane_tokens=[96], trace=None, peaks=None, rounds=[])
    rec["requests"] = [r for r in rec["requests"] if r["due"] is None]
    for name in ("lane_imbalance", "device_idle_share", "step_mfu",
                 "step_ms", "first_block_p90_s",
                 "block_gap_p90_s", "queue_wait_p90_s"):
        assert read(name, rec) is None, name


def test_every_named_metric_has_a_reader():
    b = spec.benchmark()
    for m in b["end_to_end"] + b["per_layer"]:
        assert callable(spec.reader(m["name"])), m["name"]


def test_peaks_table():
    assert spec.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        spec.peaks("TPU v99")


def test_new_config_mix_and_metric_by_name_alone(tmp_path):
    """A later PR adds a cell with its own config, mix, limits and metric
    as new files and new entries; no existing file changes."""
    root = tmp_path / "checkout"
    shutil.copytree(spec.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    b = spec.benchmark()
    b["configs"].append(dict(b["configs"][0], name="llada-8b-l4",
                             file="bench/configs/llada-8b-l4.json"))
    b["workloads"].append({"name": "llada-chat-bursty", "config": "llada-8b-l4",
                           "traffic": "chat-bursty", "chips": 1, "why": "x"})
    b["per_layer"].append({"name": "blocks_per_step", "unit": "blocks",
                           "better": "higher", "source": "program_counter",
                           "layer": "engine", "moves": "tokens_per_s",
                           "workloads": ["llada-chat-bursty"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    cfg = spec.load_json(spec.BENCH / "configs" / "llada-8b-l8.json")
    cfg["model"]["n_layers"] = 4
    (root / "bench/configs/llada-8b-l4.json").write_text(json.dumps(cfg))
    mix = spec.load_json(spec.BENCH / "traffic" / "chat-poisson.json")
    mix["arrival"]["rate_per_s"] = 0.5
    (root / "bench/traffic/chat-bursty.json").write_text(json.dumps(mix))
    shutil.copy(spec.BENCH / "limits" / "llada-chat-overload.json",
                root / "bench/limits/llada-chat-bursty.json")
    (root / "bench/metrics/blocks_per_step.py").write_text(
        "def read(rec):\n    return 1.5\n")
    cell = spec.load_cell("llada-chat-bursty", root=root)
    assert cell.config["model"]["n_layers"] == 4
    assert cell.traffic["arrival"]["rate_per_s"] == 0.5
    names = [m.name for m in cell.per_layer]
    assert "blocks_per_step" in names and "step_ms" not in names
    # end-to-end metrics without a workloads list reach the new cell too
    assert [m.name for m in cell.end_to_end] == ["setup_s", "tokens_per_s"]
    assert spec.reader("blocks_per_step", root)({}) == 1.5
    with pytest.raises(KeyError):
        spec.load_cell("no-such-cell", root=root)
