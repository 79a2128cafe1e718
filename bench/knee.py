#!/usr/bin/env python3
"""Sweep an open-loop cell's arrival rate once, to find its knee.

    python3 bench/knee.py --workload <name> --rates 1.0,1.2,1.4 \\
        --seconds 40 --seed <n>

One process builds and warms the cell's server, then offers the mix at
each rate for the mix's lead-in and ``--seconds`` (the same seed, so the
same multiset of lengths), and after each window drains the system.  Per rate it prints
one JSON line: tokens committed per second, the queue depth (requests
submitted and not admitted) sampled once a second, its mean over the
middle and the last third of the window, and the p90 of the queue wait in
each third.  The knee is the highest rate whose queue does not grow over
the window; a benchmark cell runs its mix below it at a fixed rate.
Needs the chip, as ``bench/run.py`` does.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

from bench import harness, spec  # noqa: E402
from bench import run as runmod  # noqa: E402
from bench import traffic as trafficmod  # noqa: E402


def sweep_one(served, mix, rate, seconds, seed) -> dict:
    vocab = served.cfg.vocab_size
    planned = trafficmod.plan(mix, seed, seconds, vocab,
                              served.gen.block_length, rate_per_s=rate)
    loop = harness.Loop(served, mix)
    depth = []

    def on_tick(now):
        if now >= len(depth):
            depth.append((now, loop._queued()))

    loop.run(planned, seconds, float(mix.get("lead_in_s", 0.0)),
             on_tick=on_tick)
    tokens = sum(1 for t in loop.tracked.values() for b in t.block_t
                 if 0 <= b <= seconds) * loop.lb / seconds
    thirds = [seconds / 3, 2 * seconds / 3]

    def third(i):
        lo, hi = ([0] + thirds + [seconds])[i:i + 2]
        d = [q for t, q in depth if lo <= t < hi]
        waits = [t.request.admit_s - loop.t0 - t.due for t in
                 loop.tracked.values() if lo <= t.due < hi and t.request.admit_s]
        return (float(np.mean(d)) if d else None,
                float(np.percentile(waits, 90)) if waits else None)

    (q_mid, w_mid), (q_end, w_end) = third(1), third(2)
    t = time.monotonic()
    served.sched.drain()
    return {"rate_per_s": rate, "tokens_per_s": tokens,
            "requests": len(planned), "queue_mid": q_mid, "queue_last": q_end,
            "wait_p90_mid_s": w_mid, "wait_p90_last_s": w_end,
            "queue_samples": [q for _, q in depth],
            "drain_s": time.monotonic() - t}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    a = ap.parse_args(argv)
    cell = spec.load_cell(a.workload)
    if cell.traffic["loop"] != "open":
        print("knee: the cell's mix is not an open loop", file=sys.stderr)
        return 2
    problem = runmod.device_check(cell.chips)
    if problem:
        print(f"knee: {problem}", file=sys.stderr)
        return 2
    harness.configure_compile_cache()
    served, params = harness.build(cell.config, a.seed)
    del params
    harness.warm_up(served, cell.traffic, a.seed, served.cfg.vocab_size)
    for rate in [float(r) for r in a.rates.split(",")]:
        print(json.dumps(sweep_one(served, cell.traffic, rate, a.seconds,
                                   a.seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
