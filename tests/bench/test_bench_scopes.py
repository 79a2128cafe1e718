"""The named reduction (``bench/scopes.py``), its five readers and
``bench/passes.py``: by hand on a made-up trace and HLO text, on the
recorded v5e traces, and one tiny run on the CPU."""
import dataclasses
import gzip
import json
import time
from pathlib import Path

import pytest

from bench import passes, scopes, spec, trace

DATA = Path(__file__).parent / "data"
STEP = "jit(_engine_step)"
SCOPES = {
    "cond.1": f"{STEP}/es.skip_decode/cond",
    "fusion.2": f"{STEP}/es.skip_decode/cond/branch_1_fun/while/body/"
                "es.attention/dot_general",
    "cond.3": f"{STEP}/es.block_refresh/cond",
    "cond.5": f"{STEP}/es.prompt_refresh/cond",
    "while.6": f"{STEP}/es.prompt_refresh/cond/branch_1_fun/while",
    "fusion.7": f"{STEP}/es.prompt_refresh/cond/branch_1_fun/cond/"
                "branch_0_fun/while/body/es.attention/exp",
    "cond.8": f"{STEP}/es.prompt_refresh/cond/branch_1_fun/cond",
}


def made_up():
    """Device 0 runs the step module 4-100 and another module 100-130.  In
    the step: the skip pass ran (``cond.1`` 10-40 around its branch's
    attention op ``fusion.2`` 15-30), the block-refresh conditional took
    its identity branch (``cond.3`` 40-41), the prompt refresh ran
    (``cond.5`` 50-90 around ``while.6`` 52-88, ``cond.8`` 54-80 and
    ``fusion.7`` 55-70).  The other module runs a ``fusion.2`` of its own
    105-125.  Device 1 runs the step 4-100 with the skip pass 10-30 around
    ``fusion.2`` 12-28.  The host: one ``bench.sched_step`` 0-130 around
    ``es.sched.step`` 2-128 (admit 2-4, dispatch 4-12, wait 12-92, retire
    92-110); the step starts on the device as its dispatch starts."""
    def ops(ev):
        return {"name": "XLA Ops",
                "events": [[f"%{n} = f32[] op()", s, e - s] for n, s, e in ev]}

    def mods(ev):
        return {"name": "XLA Modules",
                "events": [[n, s, e - s] for n, s, e in ev]}
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            ops([("cond.1", 10, 40), ("fusion.2", 15, 30), ("cond.3", 40, 41),
                 ("cond.5", 50, 90), ("while.6", 52, 88), ("cond.8", 54, 80),
                 ("fusion.7", 55, 70), ("fusion.2", 105, 125)]),
            mods([("jit__engine_step(7)", 4, 100), ("jit_other(3)", 100, 130)])]},
        {"name": "/device:TPU:1", "lines": [
            ops([("cond.1", 10, 30), ("fusion.2", 12, 28)]),
            mods([("jit__engine_step(7)", 4, 100)])]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["bench.sched_step", 0, 130], ["es.sched.step", 2, 126],
            ["es.sched.admit", 2, 2], ["es.engine.dispatch", 4, 8],
            ["es.engine.wait", 12, 80], ["es.sched.retire", 92, 18]]}]},
    ]}


def test_scope_map_from_hlo_text():
    hlo = "\n".join([
        "HloModule jit__engine_step, is_scheduled=true, entry_computation_"
        "layout={(f32[2]{0})->f32[2]{0}}",
        "%fused_computation.3 (param_0: f32[2]) -> f32[2] {",
        '  ROOT %exp.1 = f32[2]{0} exponential(%param_0), metadata={op_name='
        f'"{STEP}/es.prompt_refresh/cond/branch_1_fun/es.attention/exp" '
        'stack_frame_id=3}',
        "}",
        "ENTRY %main.9 (p: f32[2]) -> f32[2] {",
        "  %cond.57 = (f32[2]{0}) conditional(%p, %t, %t), branch_"
        f'computations={{%a, %b}}, metadata={{op_name="{STEP}/'
        'es.prompt_refresh/cond" stack_frame_id=5}',
        '  %copy.2 = f32[2]{0} copy(%p), metadata={op_name="jit(f)/copy"}',
        "  ROOT %fusion.4 = f32[2]{0} fusion(%p), kind=kLoop",
        "}"])
    module, m = scopes.scope_map(hlo)
    assert module == "jit__engine_step"
    assert m == {"exp.1": f"{STEP}/es.prompt_refresh/cond/branch_1_fun/"
                          "es.attention/exp",
                 "cond.57": f"{STEP}/es.prompt_refresh/cond"}
    assert scopes.innermost(m["exp.1"]) == "es.attention"
    assert scopes.pass_of(m["cond.57"]) == "es.prompt_refresh"
    assert scopes.pass_of(m["exp.1"]) is None
    # a conditional nested in a pass (gathered refresh) is not the pass
    assert scopes.pass_of(SCOPES["cond.8"]) is None


def test_module_restriction():
    per_dev = scopes.module_ops(made_up(), "jit__engine_step")
    assert [n for n, _, _ in per_dev[0]] == [
        "cond.1", "fusion.2", "cond.3", "cond.5", "while.6", "cond.8",
        "fusion.7"]
    assert [n for n, _, _ in per_dev[1]] == ["cond.1", "fusion.2"]
    # a plane without the modules line attributes nothing
    t = made_up()
    del t["planes"][1]["lines"][1]
    assert scopes.module_ops(t, "jit__engine_step")[1] == []


def test_named_reduction_by_hand():
    t = made_up()
    r = scopes.reduce(t, "jit__engine_step", SCOPES, min_gap_ns=1)
    base = trace.reduce(t, min_gap_ns=1)
    for k in ("busy_s", "window_s", "idle_share"):
        assert r[k] == base[k]
    p = r["passes"]
    # device 0's skip pass 30 ns, device 1's 20 ns: mean over chips
    assert p["es.skip_decode"]["ms"] == pytest.approx(25e-6)
    assert p["es.skip_decode"]["runs"] == 1
    # the identity branch is no run
    assert p["es.block_refresh"] == {"runs": 0, "ms": None}
    assert p["es.prompt_refresh"]["ms"] == pytest.approx(40e-6)
    assert p["es.prompt_refresh"]["runs"] == 0.5
    assert p["es.partial_refresh"]["ms"] is None
    # attention 15 + 15 (device 0, not the other module's fusion.2) and 16
    # (device 1) of busy 91 and 20
    assert r["attention_share"] == pytest.approx(46 / 111)
    # the step 126 ns less its wait 80
    assert r["sched_steps"] == 1
    assert r["sched_step_ms"] == pytest.approx(126e-6)
    assert r["sched_host_ms"] == pytest.approx(46e-6)
    ops = dict(r["device_ops"])
    assert ops["cond.5[es.prompt_refresh]"] == pytest.approx(20e-9)
    assert ops["cond.1[es.skip_decode]"] == pytest.approx(25e-9)
    assert ops["fusion.2[es.attention]"] == pytest.approx((15 + 16) / 2 * 1e-9)
    assert ops["fusion.2"] == pytest.approx(10e-9)     # the other module
    assert ops["cond.3[es.block_refresh]"] == pytest.approx(0.5e-9)
    # idle time split among the innermost spans over it.  Device 0 idle
    # 0-10 (the bench step 0-2, admit 2-4, dispatch 4-10), 41-50 (wait),
    # 90-105 (wait 90-92, retire 92-105), 125-130 (the step to 128, the
    # bench step); device 1 idle 0-10 as device 0, 30-130 (wait 30-92,
    # retire 92-110, the step 110-128, the bench step 128-130)
    assert r["clock_lead_ms"] == 0
    gaps = dict(r["idle_gaps"])
    assert gaps == pytest.approx({
        "bench.sched_step": 4e-9, "es.sched.admit": 2e-9,
        "es.engine.dispatch": 6e-9, "es.engine.wait": 36.5e-9,
        "es.sched.retire": 15.5e-9, "es.sched.step": 10.5e-9})
    assert sum(gaps.values()) == pytest.approx(
        base["window_s"] - base["busy_s"])


def test_device_clock_ahead_of_the_host():
    """Device planes 3 ns ahead: the step starts before its dispatch, by
    which the gaps are moved back onto the host's clock."""
    t = made_up()
    for p in t["planes"][:2]:
        for ln in p["lines"]:
            ln["events"] = [[n, s - 3, d] for n, s, d in ln["events"]]
    r = scopes.reduce(t, "jit__engine_step", SCOPES, min_gap_ns=1)
    assert r["clock_lead_ms"] == pytest.approx(3e-6)
    # device 0's first gap, 0-7 on its clock, is 3-10 on the host's:
    # admit 3-4, dispatch 4-10 (device 1 alike)
    gaps = dict(r["idle_gaps"])
    assert gaps["es.engine.dispatch"] == pytest.approx(6e-9)
    assert gaps["es.sched.admit"] == pytest.approx(1e-9)


def test_window_is_the_benchmarks():
    """Spans and ops outside the ``bench.sched_step`` window are not read."""
    t = made_up()
    t["planes"][2]["lines"][0]["events"][0] = ["bench.sched_step", 0, 45]
    r = scopes.reduce(t, "jit__engine_step", SCOPES, min_gap_ns=1)
    assert r["window_s"] == pytest.approx(45e-9)
    assert r["passes"]["es.prompt_refresh"]["ms"] is None
    assert r["sched_steps"] == 1          # it starts inside


def test_nothing_to_read():
    t = made_up()
    t["planes"][2]["lines"][0]["events"] = []
    assert scopes.reduce(t, "jit__engine_step", SCOPES) is None
    r = scopes.reduce(made_up(), "jit_no_such_module", SCOPES)
    assert all(v["ms"] is None for v in r["passes"].values())
    assert r["attention_share"] is None


def test_readers():
    rec = {"trace": scopes.reduce(made_up(), "jit__engine_step", SCOPES)}
    read = {n: spec.reader(n)(rec) for n in passes.NAMED}
    assert read["skip_decode_pass_ms"] == pytest.approx(25e-6)
    assert read["block_refresh_pass_ms"] is None
    assert read["prompt_refresh_pass_ms"] == pytest.approx(40e-6)
    assert read["attention_share"] == pytest.approx(100 * 46 / 111)
    assert read["sched_host_ms"] == pytest.approx(46e-6)
    # a summary of bench/trace.py alone, or none, holds nothing to read
    for rec in ({"trace": {"busy_s": 1.0, "window_s": 2.0,
                           "idle_share": 0.5}}, {"trace": None}, {}):
        assert all(spec.reader(n)(rec) is None for n in passes.NAMED)


def test_older_recorded_trace_reads_the_same():
    """The trace recorded before the spans existed: no modules line, no
    ``es.`` spans.  The base numbers are ``bench.trace.reduce``'s."""
    t = trace.load(str(DATA / "trace_v5e_llada.json.gz"))
    r = scopes.reduce(t, "jit__engine_step", {})
    base = trace.reduce(t)
    for k in ("busy_s", "window_s", "idle_share"):
        assert r[k] == base[k]
    assert r["device_ops"] == base["device_ops"]
    # the same idle time, split among the benchmark's spans
    assert sum(t for _, t in r["idle_gaps"]) == pytest.approx(
        sum(t for _, t in base["idle_gaps"]))
    assert r["idle_gaps"][0][0] == "bench.sched_step"
    assert r["sched_steps"] == 0 and r["sched_host_ms"] is None


@pytest.mark.parametrize("traced", [False, True])
def test_tiny_passes_run(tiny, traced):
    """The diagnostic run at a tiny size on the CPU: every metric of the
    cell from one run, and with a trace the step's scopes and the host
    spans (the CPU has no device plane, so nothing is reduced)."""
    cell = dataclasses.replace(
        tiny("llada-8b-l8", "chat-poisson"),
        per_layer=spec.load_cell("llada-chat-overload").per_layer)
    line, raw, hlo = passes.measure(cell, 2**34 + 5, 3.0, traced,
                                    t_start=time.monotonic(), cache=False)
    assert line["window_compiles"] == 0 and line["step_traces"] == 1
    assert line["metrics"]["tokens_per_s"] > 0
    assert line["metrics"]["step_ms"] > 0
    assert set(passes.NAMED) <= set(line["metrics"])
    if not traced:
        assert raw is None and hlo is None
        return
    module, m = scopes.scope_map(hlo)
    assert module == "jit__engine_step"
    assert {scopes.pass_of(v) for v in m.values()} >= {
        "es.skip_decode", "es.block_refresh", "es.prompt_refresh"}
    names = {n for n, _, _ in scopes.spans(raw)}
    assert {"bench.sched_step", "es.sched.step", "es.engine.wait"} <= names


def test_sample_keeps_the_steps_module_and_spans():
    t = made_up()
    s, m = passes.sample(t, "jit__engine_step", SCOPES)
    assert m["module"] == "jit__engine_step"
    assert set(m["scopes"]) == {"cond.1", "fusion.2", "cond.3", "cond.5",
                                "while.6", "cond.8", "fusion.7"}
    # times from the step's start (2 ns); the other module's op is gone
    ops = s["planes"][0]["lines"][0]["events"]
    assert ops[0] == ["cond.1", 8, 30] and len(ops) == 7
    r = scopes.reduce(s, m["module"], m["scopes"], min_gap_ns=1)
    assert r["passes"]["es.skip_decode"]["ms"] == pytest.approx(25e-6)
    assert r["sched_host_ms"] == pytest.approx(46e-6)
    # past the size limit, short ops go first and pass conditionals stay
    s, _ = passes.sample(t, "jit__engine_step", SCOPES, limit=1)
    assert [n for n, _, _ in s["planes"][0]["lines"][0]["events"]] == [
        "cond.1", "cond.3", "cond.5"]


def recorded():
    with gzip.open(DATA / "trace_v5e_llada_named.scopes.json.gz", "rt") as f:
        m = json.load(f)
    return (trace.load(str(DATA / "trace_v5e_llada_named.json.gz")),
            m["module"], m["scopes"])


def test_recorded_named_trace():
    """Four steps of ``llada-chat-overload`` on a TPU v5e (two of them
    with a prompt refresh), recorded by ``bench/passes.py --save``: every
    pass ran, the top conditionals carry their pass names and the idle
    time its host phases."""
    t, module, m = recorded()
    r = scopes.reduce(t, module, m)
    p = {k: v["ms"] for k, v in r["passes"].items()}
    assert all(p[k] > 0 for k in scopes.PASSES[:3]), p
    assert p["es.prompt_refresh"] > 5 * p["es.skip_decode"]
    assert p["es.partial_refresh"] is None
    conds = [n for n, _ in r["device_ops"] if n.startswith("cond.")][:3]
    assert {n.split("[")[1].rstrip("]") for n in conds} == set(
        scopes.PASSES[:3]), conds
    gaps = dict(r["idle_gaps"])
    assert {"es.sched.admit", "es.sched.after", "es.sched.retire"} <= set(gaps)
    # all but the gaps under ``min_gap_ns`` (10 µs) are named
    idle = r["window_s"] - r["busy_s"]
    assert 0.99 * idle <= sum(gaps.values()) <= idle
    assert 0.0 < r["attention_share"] < 1.0
    assert 0.0 < r["sched_host_ms"] < r["sched_step_ms"]


def test_recorded_passes_run_between_dispatch_and_wait():
    """On the shared clock every pass conditional of a step runs between
    the step's ``es.engine.dispatch`` start and its ``es.engine.wait`` end,
    once the device planes are moved back by their lead over the host
    (on this v5e 0.95 ms: a step's module starts that long before its
    dispatch on the raw clocks)."""
    t, module, m = recorded()
    host = scopes.spans(t)
    lead = scopes.clock_lead(t, module, host)
    assert 0 < lead < 2_000_000
    steps = [s for s in host if s[0] == "es.sched.step"]
    checked = 0
    for ops in scopes.module_ops(t, module):
        for n, s, e in ops:
            if scopes.pass_of(m.get(n, "")) is None:
                continue
            s, e = s + lead, e + lead
            step = [st for st in steps if st[1] <= s < st[2]]
            if not step:
                continue            # the next step's, cut by the sample
            child = {c[0]: c for c in host
                     if step[0][1] <= c[1] and c[2] <= step[0][2]}
            assert child["es.engine.dispatch"][1] <= s, n
            assert e <= child["es.engine.wait"][2], n
            checked += 1
    assert checked >= 3 * len(steps)
