#!/usr/bin/env python3
"""Run one benchmark cell once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (configuration, traffic mix, metrics, limits) is found by name
from ``BENCHMARK.json``.  An open-loop mix may start its arrivals a
lead-in before the window.  ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a run that also records
a profiler trace of the window's last seconds.  Every run checks what the
timed path served against ``bench/reference.py``.  The last line of
stdout is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``, each number compared beside its limit (also the last lines of
stderr).  Without a TPU, or with fewer chips than the cell asks for, it
exits with 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()   # set-up is counted from here

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

from bench import harness, spec  # noqa: E402
from bench import reference as refmod  # noqa: E402
from bench import trace as tracemod  # noqa: E402
from bench import traffic as trafficmod  # noqa: E402


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_check(chips: int) -> str | None:
    """Why this machine cannot run the cell, or None."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        return f"no TPU: JAX finds {devs[0].platform} devices only"
    if len(devs) < chips:
        return f"the cell asks for {chips} chips, JAX finds {len(devs)}"
    return None


def pct(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, float), q))


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float = T_START, root: Path = spec.ROOT,
             patch=None, control: str | None = None, cache: bool = True,
             gaps: dict | None = None) -> dict:
    """One run; returns the result object.  ``patch(served)`` may alter
    the served path after warm-up (fault tests).  ``control`` ("int8" or
    "fp8") puts the reference in that precision in the program's place:
    the numbers compared are then those of the tokens it puts first, at
    each position the program committed (``bench/control.py``; never in a
    benchmark run).  ``gaps``, when given, receives the statistics of the
    served tokens and of the control's.  ``cache=False`` leaves JAX's
    persistent cache alone (tests)."""
    import jax

    cache_dir = harness.configure_compile_cache() if cache else "off"
    clock = harness.compile_clock()
    config = cell.config
    log(f"cell {cell.name}: config {config['name']}, traffic "
        f"{cell.traffic_name}, seed {seed}, {seconds} s, trace {int(trace)}; "
        f"compile cache {cache_dir}")
    devs = jax.devices()
    t = time.monotonic()
    served, params = harness.build(config, seed)
    jax.block_until_ready(params)
    del params
    log(f"built: weights and server in {time.monotonic() - t:.2f} s "
        f"({clock.compiles} backend compiles, {clock.cache_hits} cache hits)")
    vocab = config["model"]["vocab_size"]
    t = time.monotonic()
    harness.warm_up(served, cell.traffic, seed, vocab)
    log(f"warm-up: {time.monotonic() - t:.2f} s; step traced "
        f"{served.sched.engine.step_trace_count} time(s)")
    if patch is not None:
        patch(served)
    args = served.args
    planned = trafficmod.plan(cell.traffic, seed, seconds, vocab,
                              args.block_length)
    if max(p.n_blocks for p in planned) * args.block_length > args.gen_length \
            or max(len(p.prompt) for p in planned) > args.prompt_len:
        raise SystemExit("bench: the traffic asks for more than the server's "
                         "--prompt-len / --gen-length hold")
    loop = harness.Loop(served, cell.traffic, spans=trace)
    trace_dir = harness.CACHE / "trace"
    started = []

    def on_tick(now):
        if trace and not started and now >= seconds - harness.TRACE_S:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # the bench.* spans suffice
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            started.append(now)

    # set-up ends where the offered load starts: the lead-in is traffic
    lead_in = float(cell.traffic.get("lead_in_s", 0.0))
    setup_s = time.monotonic() - t_start
    compiles0 = clock.compiles
    loop.run(planned, seconds, lead_in, on_tick=on_tick)
    window_compiles = clock.compiles - compiles0
    if started:
        jax.profiler.stop_trace()
    if cell.traffic["loop"] == "open":
        loop.follow_up(seconds)
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devs[:cell.chips])
    summary = None
    if started:
        summary = tracemod.reduce(tracemod.load_dir(str(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
    device_kind = devs[0].device_kind
    # on the chip a kind missing from the table stops the run; a CPU run
    # (tests only) has no peaks and reports no utilisation
    peaks = spec.peaks(device_kind, root) if devs[0].platform == "tpu" \
        else None
    record = harness.make_record(loop, seconds, config, cell.chips, setup_s,
                                 peaks, summary)
    step_traces = served.sched.engine.step_trace_count
    lateness = [t.submit - t.due for t in loop.tracked.values()
                if t.due is not None]
    if lateness:
        log(f"generator lateness (due -> submit): p50 "
            f"{pct(lateness, 50):.4f} s, p99 {pct(lateness, 99):.4f} s, "
            f"max {max(lateness):.4f} s over {len(lateness)} requests")
    log(f"window: {loop.sched_steps_in_window} scheduler steps after a "
        f"{lead_in} s lead-in, "
        f"{len(loop.tracked)} requests submitted, "
        f"{sum(1 for t in loop.tracked.values() if len(t.block_t) == t.n_blocks)}"
        f" finished, backend compiles in the window {window_compiles}, "
        f"setup {setup_s:.2f} s")

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = spec.reader(m.name, root)(record)
        if v is not None:
            metrics[m.name] = {"value": v, "unit": m.unit}

    # the check: the program's state goes first, then the reference runs
    lb = loop.lb
    attempted = len(loop.tracked)
    failed = sum(1 for t in loop.tracked.values()
                 if t.request.error is not None
                 or (t.due is not None and not t.block_t))
    picked = harness.sample(loop, seed, config["check_tokens"])
    mismatches = sum(harness.transcript_mismatches(t, lb) for t in picked)
    sem = refmod.semantics(config, harness.serve_widths(served.args))
    stalled = loop.stalled_row_steps
    loop.served = loop.sched = None
    loop.lanes = []
    del served
    gc.collect()
    t = time.monotonic()
    res = harness.check(sem, config["model"], seed, picked, lb, control)
    log(f"reference: {len(picked)} requests in {time.monotonic() - t:.2f} s")
    for who, stats in res.items():
        for pk, st in (stats or {}).items():
            log(f"{who}: gaps over {pk} tokens: " + ", ".join(
                f"{k} {v!r}" for k, v in st.items()))
    if gaps is not None:
        gaps.update(res)
    in_place = res["control"] if control else res["served"]

    # the numbers compared (bench/limits/<cell>.json holds the readings
    # each limit was set from): the widest gap catches one grossly wrong
    # token, the mean gap a path that is slightly off everywhere
    limits = cell.limits["checks"]
    every = in_place.get("all", {"widest": 0.0, "mean": 0.0, "tokens": 0})
    checks = {
        "gap_widest": (every["widest"], limits["gap_widest"]["limit"]),
        "gap_mean": (every["mean"], limits["gap_mean"]["limit"]),
        "compared_tokens_missing": (0 if every["tokens"] else 1, 0),
        "failed_requests": (failed, 0),
        "stalled_row_steps": (stalled, 0),
        "transcript_mismatches": (mismatches, 0),
        "window_compiles": (window_compiles, 0),
        "step_traces": (step_traces, 1),
    }
    correct = all(v <= lim for v, lim in checks.values())

    device = {"platform": devs[0].platform, "kind": device_kind,
              "count": len(devs), "memory_peak_bytes": int(memory_peak)}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        if summary is not None:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            result["breakdown"] = {"device_ops": summary["device_ops"],
                                   "idle_gaps": summary["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"check {k} = {v!r} limit <= {lim!r} "
            f"{'ok' if v <= lim else 'FAIL'}")
    return result


def main(argv=None) -> int:
    args = parse(argv)
    cell = spec.load_cell(args.workload)
    problem = device_check(cell.chips)
    if problem:
        print(f"bench: {problem}; this benchmark runs on the chip only",
              file=sys.stderr)
        return 2
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
