"""The trace reduction: busy union, idle share and gap attribution, by
hand on a made-up trace and on a small trace recorded on a TPU v5e (three
steps of the LLaDA cell, trimmed to its device ops and bench spans)."""
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).parent / "data" / "trace_v5e_llada.json.gz"


def made_up():
    """Two devices over one 100 ns step.  Device 0 runs ``a`` 10-40 and,
    nested in a while op 50-90, ``b`` 55-70; device 1 runs ``a`` 0-100 ns.
    The host is inside ``bench.stream_cb`` from 40 to 50."""
    dev = lambda ev: {"name": "XLA Ops", "events": ev}
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            dev([["%a = f32[] add()", 10, 30], ["%while.3 = (f32[]) while()", 50, 40],
                 ["%b = f32[] mul()", 55, 15]]),
            {"name": "XLA Modules", "events": [["jit_step", 0, 100]]}]},
        {"name": "/device:TPU:1", "lines": [dev([["%a = f32[] add()", 0, 100]])]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["bench.sched_step", 0, 100], ["bench.stream_cb", 40, 10],
            ["$other.py:1 f", 0, 100]]}]},
    ]}


def test_reduction_by_hand():
    r = trace.reduce(made_up(), min_gap_ns=1)
    # device 0 busy 30 + 40 = 70 of 100; device 1 busy 100
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(85e-9)
    assert r["idle_share"] == pytest.approx(0.15)
    ops = dict(r["device_ops"])
    assert ops["a"] == pytest.approx((30 + 100) / 2 * 1e-9)
    assert ops["while.3"] == pytest.approx(20e-9)
    # idle on device 0: 0-10 and 90-100 inside the step, 40-50 in stream_cb
    gaps = dict(r["idle_gaps"])
    assert gaps["bench.sched_step"] == pytest.approx(10e-9)
    assert gaps["bench.stream_cb"] == pytest.approx(5e-9)


def test_nothing_to_read():
    t = made_up()
    t["planes"][2]["lines"][0]["events"] = []
    assert trace.reduce(t) is None
    assert trace.reduce({"planes": [t["planes"][2]]}) is None


def test_union_and_names():
    assert trace.union([["x", 0, 5], ["y", 3, 8], ["z", 10, 12]], 1, 11) == [
        (1, 8), (10, 11)]
    assert trace.op_name("%fusion.12 = bf16[8]{0} fusion(...)") == "fusion.12"


def test_recorded_v5e_trace():
    t = trace.load(str(DATA))
    names = [p["name"] for p in t["planes"]]
    assert "/device:TPU:0" in names
    r = trace.reduce(t)
    assert 0.5 < r["window_s"] < 0.6
    assert 0.0 < r["idle_share"] < 0.2
    assert r["busy_s"] == pytest.approx(r["window_s"] * (1 - r["idle_share"]))
    assert len(r["device_ops"]) == 10
    # the prompt-refresh step's conditional pass is the longest op
    assert r["device_ops"][0][0].startswith("cond.")
    assert r["idle_gaps"][0][0] == "bench.sched_step"
