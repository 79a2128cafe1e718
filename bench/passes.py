#!/usr/bin/env python3
"""Per-pass device time, attention share and host time per step of a cell.

    python3 bench/passes.py --workload <name> --seed <n> --seconds <s> \\
        [--trace 0|1] [--save <path>]

Runs the cell's window as ``bench/run.py`` does (the same server, warm-up,
traffic and traced tail of ``harness.TRACE_S`` seconds), without the
follow-up and the check against the reference, and prints one JSON line:
every metric of the cell read from this one run (end-to-end and per-layer
alike, so ``--trace 1`` against ``--trace 0`` on one seed gives what
tracing costs) and, with ``--trace 1``, the five metrics of the named
passes and phases (``bench/scopes.py``), each pass's runs per step, the
mean ``es.sched.step``, and the breakdown under scoped names.  The scope
map comes from the compiled step's HLO text, read after the window.
``--save`` writes a few steps of the traced tail around its longest
scheduler step, trimmed to the step's module and the ``bench.``/``es.``
spans, to ``<path>.json.gz`` and their scope map to
``<path>.scopes.json.gz``.  Needs the chip, as ``bench/run.py`` does.
"""
from __future__ import annotations

import time

T_START = time.monotonic()   # set-up is counted from here

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness, spec  # noqa: E402
from bench import run as runmod  # noqa: E402
from bench import scopes as scopemod  # noqa: E402
from bench import trace as tracemod  # noqa: E402
from bench import traffic as trafficmod  # noqa: E402

NAMED = ("skip_decode_pass_ms", "block_refresh_pass_ms",
         "prompt_refresh_pass_ms", "attention_share", "sched_host_ms")


def measure(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
            t_start: float = T_START, root: Path = spec.ROOT,
            cache: bool = True) -> tuple[dict, dict | None, str | None]:
    """One window of ``cell``.  Returns the result line, and with
    ``trace`` the normalised trace of the tail and the compiled step's
    HLO text (else None, None)."""
    import jax

    if cache:
        harness.configure_compile_cache()
    clock = harness.compile_clock()
    config = cell.config
    served, params = harness.build(config, seed)
    jax.block_until_ready(params)
    del params
    vocab = config["model"]["vocab_size"]
    harness.warm_up(served, cell.traffic, seed, vocab)
    planned = trafficmod.plan(cell.traffic, seed, seconds, vocab,
                              served.args.block_length)
    loop = harness.Loop(served, cell.traffic, spans=trace)
    trace_dir = harness.CACHE / "passes-trace"
    started = []

    def on_tick(now):
        if trace and not started and now >= seconds - harness.TRACE_S:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            started.append(now)

    lead_in = float(cell.traffic.get("lead_in_s", 0.0))
    setup_s = time.monotonic() - t_start
    compiles0 = clock.compiles
    loop.run(planned, seconds, lead_in, on_tick=on_tick)
    window_compiles = clock.compiles - compiles0
    raw = hlo = summary = None
    if started:
        jax.profiler.stop_trace()
        raw = tracemod.load_dir(str(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        lane = served.lanes[0]
        hlo = lane.engine.compiled_step_text(lane.params, lane.state,
                                             lane._enc_out)
        summary = scopemod.reduce(raw, *scopemod.scope_map(hlo))
    devs = jax.devices()
    peaks = spec.peaks(devs[0].device_kind, root) \
        if devs[0].platform == "tpu" else None
    record = harness.make_record(loop, seconds, config, cell.chips, setup_s,
                                 peaks, summary)
    names = [m.name for m in cell.end_to_end + cell.per_layer] + list(NAMED)
    line = {"metrics": {n: spec.reader(n, root)(record) for n in names},
            "window_compiles": window_compiles,
            "step_traces": served.sched.engine.step_trace_count,
            "device": {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": len(devs)}}
    if summary is not None:
        steps = summary["sched_steps"]
        per_step = {p: v["runs"] / steps if steps else None
                    for p, v in summary["passes"].items()}
        passes_ms = sum(v["ms"] * per_step[p]
                        for p, v in summary["passes"].items()
                        if v["ms"] is not None)
        line["named"] = {
            "passes": summary["passes"], "runs_per_step": per_step,
            "sched_steps": steps, "sched_step_ms": summary["sched_step_ms"],
            # the passes' device time per step and the host's time in it,
            # against the step as the host saw it
            "passes_and_host_ms": passes_ms + summary["sched_host_ms"]
            if summary["sched_host_ms"] is not None else None,
            "clock_lead_ms": summary["clock_lead_ms"],
            "busy_s": summary["busy_s"], "window_s": summary["window_s"]}
        line["breakdown"] = {"device_ops": summary["device_ops"],
                             "idle_gaps": summary["idle_gaps"]}
    return line, raw, hlo


def sample(trace: dict, module: str, scopes: dict, steps: int = 4,
           limit: int = 140_000) -> tuple[dict, dict]:
    """``steps`` scheduler steps of ``trace`` from the one before its
    longest ``es.sched.step``: the step module's device ops (instruction
    names only) and module executions, and the ``bench.``/``es.`` spans,
    with times from the first step's start.  Ops shorter than a floor,
    raised until the compressed trace fits ``limit`` bytes, are left out,
    pass conditionals never.  Returns the trace and its scope map."""
    sched = [sp for sp in scopemod.spans(trace) if sp[0] == "es.sched.step"]
    longest = max(range(len(sched)), key=lambda i: sched[i][2] - sched[i][1])
    first = max(0, min(longest - 1, len(sched) - steps))
    lo, hi = sched[first][1], sched[min(first + steps, len(sched)) - 1][2]

    def keep(events):
        return [[n, s - lo, e - s] for n, s, e in events if s < hi and e > lo]

    for floor_ns in (0, 1_000, 2_000, 5_000, 10_000, 20_000, 50_000,
                     100_000):
        planes = []
        for p, ops in zip([p for p in trace["planes"]
                           if tracemod.DEVICE_PLANE.match(p["name"])],
                          scopemod.module_ops(trace, module)):
            kept = [o for o in ops if o[2] - o[1] >= floor_ns
                    or scopemod.pass_of(scopes.get(o[0], ""))]
            runs = [[n, s, s + d] for ln in p["lines"]
                    if ln["name"] == scopemod.MODULE_LINE
                    for n, s, d in ln["events"]
                    if scopemod.in_module(n, module)]
            planes.append({"name": p["name"], "lines": [
                {"name": tracemod.OPS_LINE, "events": keep(kept)},
                {"name": scopemod.MODULE_LINE, "events": keep(runs)}]})
        planes.append({"name": "/host:CPU", "lines": [
            {"name": "spans", "events": keep(scopemod.spans(trace))}]})
        out = {"planes": planes}
        if len(gzip.compress(json.dumps(out).encode())) <= limit:
            break
    names = {n for p in planes for ln in p["lines"] for n, _, _ in ln["events"]}
    return out, {"module": module,
                 "scopes": {n: s for n, s in scopes.items() if n in names}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--save", default=None)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    problem = runmod.device_check(cell.chips)
    if problem:
        print(f"bench: {problem}; this benchmark runs on the chip only",
              file=sys.stderr)
        return 2
    line, raw, hlo = measure(cell, args.seed, args.seconds, bool(args.trace))
    if args.save and raw is not None:
        trimmed, scopes = sample(raw, *scopemod.scope_map(hlo))
        tracemod.save(trimmed, args.save + ".json.gz")
        with gzip.open(args.save + ".scopes.json.gz", "wt") as f:
            json.dump(scopes, f, sort_keys=True)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
