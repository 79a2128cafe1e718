"""Finding a cell's parts by name.

``BENCHMARK.json`` at the checkout root names each cell's configuration,
traffic mix and metrics.  Everything that belongs to one of them lives in a
file of its own, found here by name, so a later cell, mix or metric is
added by adding files and entries:

* ``bench/configs/<config>.json``   model sizes, served flags, the cut
* ``bench/traffic/<traffic>.json``  loop kind, arrivals, length laws
* ``bench/metrics/<metric>.py``     one reader, ``read(record) -> float|None``
* ``bench/limits/<workload>.json``  the limits that decide ``correct``
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    kind: str                      # "end_to_end" | "per_layer"
    moves: Optional[str] = None
    layer: Optional[str] = None
    workloads: Optional[tuple] = None


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    traffic_name: str
    limits: dict
    end_to_end: tuple              # Metric, in BENCHMARK.json order
    per_layer: tuple


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def _metrics(spec: dict, kind: str) -> list[Metric]:
    out = []
    for m in spec[kind]:
        wl = m.get("workloads")
        out.append(Metric(m["name"], m["unit"], m["better"], m["source"], kind,
                          m.get("moves"), m.get("layer"),
                          None if wl is None else tuple(wl)))
    return out


def cell_metrics(spec: dict, workload: str) -> tuple[tuple, tuple]:
    """The cell's end-to-end and per-layer metrics.  A metric with a
    ``workloads`` list belongs to those cells; a per-layer metric without
    one belongs to every cell that reports the metric it ``moves``."""
    e2e = tuple(m for m in _metrics(spec, "end_to_end")
                if m.workloads is None or workload in m.workloads)
    names = {m.name for m in e2e}
    layer = tuple(m for m in _metrics(spec, "per_layer")
                  if (workload in m.workloads if m.workloads is not None
                      else m.moves in names))
    return e2e, layer


def load_cell(workload: str, root: Path = ROOT,
              spec: Optional[dict] = None) -> Cell:
    spec = benchmark(root) if spec is None else spec
    entry = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in spec['workloads']]}")
    e2e, layer = cell_metrics(spec, workload)
    bench = root / "bench"
    return Cell(
        name=workload, chips=int(entry["chips"]),
        config=load_json(bench / "configs" / f"{entry['config']}.json"),
        traffic=load_json(bench / "traffic" / f"{entry['traffic']}.json"),
        traffic_name=entry["traffic"],
        limits=load_json(bench / "limits" / f"{workload}.json"),
        end_to_end=e2e, per_layer=layer)


def peaks(kind: str, root: Path = ROOT) -> dict:
    """The published peaks of one device kind (``bench/peaks.json``); a kind
    missing from the table is an error, never a default."""
    table = load_json(root / "bench" / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json"
                       f" (known: {sorted(table)})")
    return table[kind]


def reader(name: str, root: Path = ROOT) -> Callable[[dict], Optional[float]]:
    """``bench/metrics/<name>.py``'s ``read``, imported from its path (a
    metric name may hold dots)."""
    path = root / "bench" / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
