"""Named passes and phases in a profiler trace.

The program names its work on both sides of the shared clock:

* device: ``jax.named_scope`` on the engine step's pass conditionals
  (``es.skip_decode``, ``es.block_refresh``, ``es.prompt_refresh``,
  ``es.partial_refresh``) and on the attention read (``es.attention``).
  A scope lives in the ``op_name`` metadata of the compiled step's HLO,
  not in the trace, whose device events carry instruction names
  (``cond.57``): ``scope_map`` reads the metadata from the compiled text
  (``DiffusionEngine.compiled_step_text``).
* host: ``jax.profiler.TraceAnnotation`` spans inside
  ``StreamScheduler.step`` (``es.sched.step`` around ``es.sched.admit``,
  ``es.sched.prepare``, ``es.engine.dispatch``, ``es.engine.wait``,
  ``es.sched.after``, ``es.sched.retire``, ``es.sched.grow``).

``reduce`` works on the normalised trace of ``bench/trace.py`` and keeps
its window (the ``bench.sched_step`` spans), so what it adds sits beside
``bench.trace.reduce``'s numbers unchanged.  Instruction names repeat
across modules (``fusion.12``), so device ops are attributed only inside
the step's module, found by interval on each device plane's
``XLA Modules`` line.  The device planes' clock can run up to ~1 ms
ahead of the host spans' on a v5e (a step starts before its dispatch),
enough to misname a ~4 ms idle gap: gaps are moved back by that lead
(``clock_lead``, measured in each trace) before the host spans over them
name them.
"""
from __future__ import annotations

import bisect
import re
from typing import Optional

from bench import trace as tracemod

MODULE_LINE = "XLA Modules"
PASSES = ("es.skip_decode", "es.block_refresh", "es.prompt_refresh",
          "es.partial_refresh")
ATTENTION = "es.attention"
SPAN_PREFIXES = ("bench.", "es.")

_MODULE = re.compile(r"^HloModule ([^\s,]+)")
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?"
                    r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")


def scope_map(hlo_text: str) -> tuple[str, dict[str, str]]:
    """The module's name and, for each instruction under an ``es.`` scope,
    its ``op_name`` (``jit(_engine_step)/es.skip_decode/cond``)."""
    m = _MODULE.search(hlo_text)
    module = m.group(1) if m else ""
    scopes = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m and "es." in m.group(2):
            scopes[m.group(1)] = m.group(2)
    return module, scopes


def innermost(op_name: str) -> Optional[str]:
    """The innermost ``es.`` scope of an ``op_name`` path."""
    parts = [p for p in op_name.split("/") if p.startswith("es.")]
    return parts[-1] if parts else None


def pass_of(op_name: str) -> Optional[str]:
    """The pass whose own conditional this is (``…/es.skip_decode/cond``),
    else None (ops inside a pass, a conditional nested in one)."""
    parts = op_name.split("/")
    if len(parts) >= 2 and parts[-1] == "cond" and parts[-2] in PASSES:
        return parts[-2]
    return None


def in_module(name: str, module: str) -> bool:
    # the line names an execution ``jit__engine_step(1234)`` on the chip
    return name == module or name.startswith(module + "(")


def module_ops(trace: dict, module: str) -> list[list]:
    """Per device plane (in ``bench.trace.device_ops``'s order), its ops
    that start inside an execution of ``module``, as [name, start_ns,
    end_ns] by start; none on a plane without the ``XLA Modules`` line."""
    out = []
    for p in trace["planes"]:
        if not tracemod.DEVICE_PLANE.match(p["name"]):
            continue
        runs = sorted((s, s + d) for ln in p["lines"]
                      if ln["name"] == MODULE_LINE
                      for n, s, d in ln["events"] if in_module(n, module))
        starts = [s for s, _ in runs]
        ops = []
        for ln in p["lines"]:
            if ln["name"] != tracemod.OPS_LINE:
                continue
            for n, s, d in ln["events"]:
                i = bisect.bisect_right(starts, s) - 1
                if i >= 0 and s < runs[i][1]:
                    ops.append([tracemod.op_name(n), s, s + d])
        out.append(sorted(ops, key=lambda o: o[1]))
    return out


def spans(trace: dict) -> list[list]:
    """Host spans of the benchmark (``bench.``) and the program (``es.``)
    as [name, start_ns, end_ns], by start."""
    return sorted([[n, s, s + d] for p in trace["planes"]
                   if not tracemod.DEVICE_PLANE.match(p["name"])
                   for ln in p["lines"] for n, s, d in ln["events"]
                   if n.startswith(SPAN_PREFIXES)], key=lambda e: e[1])


def pass_runs(ops: list[list], scopes: dict, lo: int, hi: int) -> dict:
    """Per pass, the durations (ns) of its conditional's executions that
    start in [lo, hi) and ran the pass's own branch (``branch_1_fun``: an
    op of that branch lies inside), not the identity branch."""
    starts = [o[1] for o in ops]
    runs: dict[str, list[int]] = {p: [] for p in PASSES}
    for n, s, e in ops:
        p = pass_of(scopes.get(n, ""))
        if p is None or not lo <= s < hi:
            continue
        branch = f"/{p}/cond/branch_1_fun/"
        i = bisect.bisect_left(starts, s)
        j = bisect.bisect_right(starts, e)
        if any(ops[k][2] <= e and branch in scopes.get(ops[k][0], "")
               for k in range(i, j)):
            runs[p].append(e - s)
    return runs


def sched_host(trace_spans: list[list], lo: int, hi: int) -> list[tuple]:
    """Per ``es.sched.step`` span that starts in [lo, hi): its length and
    the time of its ``es.engine.wait`` children, in ns."""
    out = []
    for n, s, e in trace_spans:
        if n != "es.sched.step" or not lo <= s < hi:
            continue
        wait = sum(we - ws for wn, ws, we in trace_spans
                   if wn == "es.engine.wait" and s <= ws and we <= e)
        out.append((e - s, wait))
    return out


def clock_lead(trace: dict, module: str, host: list[list]) -> int:
    """How far (ns) the device planes' clock runs ahead of the host spans':
    the most by which an execution of ``module`` starts before the nearest
    ``es.engine.dispatch`` span, which no execution can really do (up to
    ~1 ms on a v5e).  0 without both."""
    starts = [sp[1] for sp in host if sp[0] == "es.engine.dispatch"]
    if not starts:
        return 0
    lead = 0
    for p in trace["planes"]:
        if not tracemod.DEVICE_PLANE.match(p["name"]):
            continue
        for ln in p["lines"]:
            if ln["name"] != MODULE_LINE:
                continue
            for n, s, _ in ln["events"]:
                if in_module(n, module):
                    lead = max(lead, min(starts, key=lambda d: abs(d - s)) - s)
    return lead


def split_gap(lo: int, hi: int, host: list[list]) -> dict[str, int]:
    """The idle interval [lo, hi) on the host's clock, cut at span edges:
    each piece is named by the innermost span over it (the latest start,
    then the earliest end)."""
    over = [sp for sp in host if sp[1] < hi and sp[2] > lo]
    cuts = sorted({lo, hi} | {x for sp in over for x in sp[1:] if lo < x < hi})
    out: dict[str, int] = {}
    for a, b in zip(cuts, cuts[1:]):
        cover = [sp for sp in over if sp[1] <= a and b <= sp[2]]
        name = max(cover, key=lambda sp: (sp[1], -sp[2]))[0] if cover \
            else "outside spans"
        out[name] = out.get(name, 0) + (b - a)
    return out


def reduce(trace: dict, module: str, scopes: dict, top: int = 10,
           min_gap_ns: int = 10_000) -> Optional[dict]:
    """``bench.trace.reduce``'s numbers over the same window, with its
    ``device_ops`` named by their innermost scope (``cond.57[es.prompt_
    refresh]``) and the idle time of its gaps split among the innermost
    spans of either prefix over them (``es.sched.retire``), on the host's
    clock (``clock_lead``); and besides, per pass, the runs and their
    mean ms (mean over chips; None without a run), the share of device
    busy time under ``es.attention``, the mean ``es.sched.step`` and the
    mean host time in one (the step less its ``es.engine.wait``), in ms.
    None where ``bench.trace.reduce`` is."""
    base = tracemod.reduce(trace, top=top, min_gap_ns=min_gap_ns)
    if base is None:
        return None
    lo, hi = tracemod.window(trace)
    planes = [(all_ops, mod) for all_ops, mod in
              zip(tracemod.device_ops(trace), module_ops(trace, module))
              if all_ops]
    n_dev = len(planes)

    runs = [pass_runs(mod, scopes, lo, hi) for _, mod in planes]
    passes = {}
    for p in PASSES:
        means = [sum(r[p]) / len(r[p]) / 1e6 for r in runs if r[p]]
        passes[p] = {"runs": sum(len(r[p]) for r in runs) / n_dev,
                     "ms": sum(means) / len(means) if means else None}

    attention = busy = 0
    op_time: dict[str, int] = {}
    for all_ops, mod in planes:
        att = [o for o in mod if ATTENTION in scopes.get(o[0], "").split("/")]
        attention += sum(e - s for s, e in tracemod.union(att, lo, hi))
        busy += sum(e - s for s, e in tracemod.union(all_ops, lo, hi))
        scoped = {(n, s): innermost(scopes.get(n, "")) for n, s, _ in mod}
        for n, s, e in all_ops:
            t = min(e, hi) - max(s, lo)
            if t <= 0:
                continue
            scope = scoped.get((n, s))
            key = f"{n}[{scope}]" if scope else n
            op_time[key] = op_time.get(key, 0) + t

    host = spans(trace)
    lead = clock_lead(trace, module, host)
    gaps: dict[str, int] = {}
    for all_ops, _ in planes:
        merged = tracemod.union(all_ops, lo, hi)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e - s < min_gap_ns:
                continue
            for name, t in split_gap(s + lead, e + lead, host).items():
                gaps[name] = gaps.get(name, 0) + t

    steps = sched_host(host, lo, hi)
    return dict(
        base,
        device_ops=[[n, t / n_dev / 1e9] for n, t in
                    sorted(op_time.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=[[n, t / n_dev / 1e9] for n, t in
                   sorted(gaps.items(), key=lambda kv: -kv[1])[:top]],
        passes=passes,
        clock_lead_ms=lead / 1e6,
        # None where no op of the step's module was found to read
        attention_share=attention / busy
        if busy and any(mod for _, mod in planes) else None,
        sched_steps=len(steps),
        sched_step_ms=sum(t for t, _ in steps) / len(steps) / 1e6
        if steps else None,
        sched_host_ms=sum(t - w for t, w in steps) / len(steps) / 1e6
        if steps else None,
    )
