"""Profiler trace: capture, normalisation and reduction to numbers.

``normalise`` turns JAX's ``ProfileData`` into plain data::

    {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [[name, start_ns, dur_ns], ...]}]}]}

and everything after that works on that form, so the reduction can be
checked on a small recorded trace without a chip.  Device planes are those
named ``/device:<TPU|GPU>:<n>``; their ``XLA Ops`` line holds the
operations.  Host spans are the benchmark's own ``bench.*`` annotations.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Optional

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


def normalise(profile) -> dict:
    planes = []
    for plane in profile.planes:
        lines = []
        for line in plane.lines:
            ev = [[e.name, int(e.start_ns), int(e.duration_ns)]
                  for e in line.events]
            lines.append({"name": line.name, "events": ev})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def load_dir(path: str) -> dict:
    """The normalised trace of the newest ``.xplane.pb`` under ``path``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return normalise(ProfileData.from_file(files[-1]))


def save(trace: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def op_name(event_name: str) -> str:
    """A device op's instruction name (``%fusion.12 = bf16[...] ...`` ->
    ``fusion.12``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def device_ops(trace: dict) -> list[list]:
    """Per device plane, its operations as [name, start_ns, end_ns].  A
    control-flow op (a ``while`` or ``conditional``) spans the ops of its
    body, so the lists nest."""
    out = []
    for p in trace["planes"]:
        if DEVICE_PLANE.match(p["name"]):
            ops = [[op_name(n), s, s + d] for ln in p["lines"]
                   if ln["name"] == OPS_LINE for n, s, d in ln["events"]]
            out.append(ops)
    return out


def host_spans(trace: dict) -> list[list]:
    """The benchmark's host spans as [name, start_ns, end_ns]."""
    return sorted([[n, s, s + d] for p in trace["planes"]
                   if not DEVICE_PLANE.match(p["name"])
                   for ln in p["lines"] for n, s, d in ln["events"]
                   if n.startswith(SPAN_PREFIX)], key=lambda e: e[1])


def union(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Merged intervals, clipped to [lo, hi]."""
    iv = sorted((max(s, lo), min(e, hi)) for _, s, e in intervals
                if e > lo and s < hi)
    out: list[list[int]] = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def window(trace: dict) -> Optional[tuple[int, int]]:
    """The traced window: first start to last end of ``bench.sched_step``
    spans (steady steps only, whatever the profiler caught around them)."""
    steps = [s for s in host_spans(trace) if s[0] == "bench.sched_step"]
    if not steps:
        return None
    return steps[0][1], max(e for _, _, e in steps)


def reduce(trace: dict, top: int = 10, min_gap_ns: int = 10_000) -> Optional[dict]:
    """busy_s and window_s (mean over devices), the idle share, the top
    device ops by total time and the longest idle gaps by the host span
    they fall in.  None when the trace holds no step or no device op."""
    win = window(trace)
    per_dev = [d for d in device_ops(trace) if d]
    if win is None or not per_dev:
        return None
    lo, hi = win
    busy = [sum(e - s for s, e in union(ops, lo, hi)) for ops in per_dev]
    op_time: dict[str, int] = {}
    for ops in per_dev:
        for n, s, e in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                op_time[n] = op_time.get(n, 0) + d
    spans = host_spans(trace)
    gaps: dict[str, int] = {}
    for ops in per_dev:
        merged = union(ops, lo, hi)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e - s < min_gap_ns:
                continue
            mid = (s + e) // 2
            inside = [sp for sp in spans if sp[1] <= mid < sp[2]]
            # the innermost span (latest start) says what the host did
            name = max(inside, key=lambda sp: sp[1])[0] if inside \
                else "outside bench spans"
            gaps[name] = gaps.get(name, 0) + (e - s)
    n_dev = len(per_dev)
    win_s = (hi - lo) / 1e9
    busy_s = sum(busy) / n_dev / 1e9
    return {
        "busy_s": busy_s,
        "window_s": win_s,
        "idle_share": 1.0 - busy_s / win_s if win_s > 0 else None,
        "device_ops": [[n, t / n_dev / 1e9] for n, t in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, t / n_dev / 1e9] for n, t in
                      sorted(gaps.items(), key=lambda kv: -kv[1])[:top]],
    }
