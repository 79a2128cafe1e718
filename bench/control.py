#!/usr/bin/env python3
"""Readings for the limits of ``correct``: the program's and the control's.

    python3 bench/control.py --workload <name> --seconds <s> \\
        --seeds <a,b,...> [--control-seeds <a,b,...>] [--precision fp8]
        [--out <file>]

Runs the cell once per seed in this one process, at the cell's own load
and window.  On ``--seeds`` the program is compared, as in a benchmark
run.  On ``--control-seeds`` the reference in ``--precision`` (int8 or
fp8) is put in the program's place: the numbers compared are those of the
tokens it puts first at each position the program committed, read under
the float32 reference, and ``correct`` has to come out false.  Prints one
JSON line per run (``correct``, the checks, the gap statistics per pass
kind) and, last, per number compared, the lower reading (the program's
largest over every run) and the upper reading (the control's smallest).
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

from bench import run as runmod  # noqa: E402
from bench import spec  # noqa: E402


def readings(rows: list[dict]) -> dict:
    """Per number compared: the lower reading, the program's largest over
    the runs, and the upper, the control's smallest."""
    lower, upper = {}, {}
    for r in rows:
        for who, bound, pick in (("served", lower, max),
                                 ("control", upper, min)):
            st = (r["gaps"].get(who) or {}).get("all")
            if st is None:
                continue
            for num in ("widest", "mean"):
                k = f"gap_{num}"
                bound[k] = st[num] if k not in bound else pick(bound[k], st[num])
    return {"lower": lower, "upper": upper}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--precision", default="fp8", choices=("fp8", "int8"))
    ap.add_argument("--out", default=None)
    a = ap.parse_args(argv)
    cell = spec.load_cell(a.workload)
    problem = runmod.device_check(cell.chips)
    if problem:
        print(f"control: {problem}", file=sys.stderr)
        return 2
    runs = [(int(s), None) for s in a.seeds.split(",") if s] + \
        [(int(s), a.precision) for s in a.control_seeds.split(",") if s]
    rows = []
    for seed, ctl in runs:
        gaps: dict = {}
        res = runmod.run_cell(cell, seed, a.seconds, False,
                              t_start=time.monotonic(), control=ctl, gaps=gaps)
        row = {"seed": seed, "control": ctl, "correct": res["correct"],
               "checks": res["checks"], "gaps": gaps,
               "metrics": res["metrics"],
               "memory_peak_bytes": res["device"]["memory_peak_bytes"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = readings(rows)
    print(json.dumps(summary), flush=True)
    if a.out:
        Path(a.out).parent.mkdir(parents=True, exist_ok=True)
        Path(a.out).write_text(
            "\n".join(json.dumps(r) for r in rows + [summary]) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
