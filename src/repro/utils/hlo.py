"""HLO text analysis: collective-communication byte accounting for the roofline.

``compiled.cost_analysis()`` reports FLOPs and HBM bytes but not collective
traffic, so we parse the (optimized) HLO text and sum the operand sizes of
every collective op.  This is the "collective term" input for
EXPERIMENTS.md §Roofline.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

_COLLECTIVE_OPS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_DTYPE_BYTES = {
    "pred": 1,
    "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

# e.g.  %all-reduce.5 = f32[16,4096]{1,0} all-reduce(f32[16,4096]{1,0} %x), ...
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_LINE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.*?)\s+(" + "|".join(_COLLECTIVE_OPS) + r")(?:-start|-done)?\("
)


def cost_analysis_dict(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a plain dict (empty when the backend
    reports nothing)."""
    cost = compiled.cost_analysis()
    return dict(cost) if cost else {}


@dataclass
class CollectiveStats:
    bytes_by_kind: dict = field(default_factory=dict)
    count_by_kind: dict = field(default_factory=dict)

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())

    @property
    def total_count(self) -> int:
        return sum(self.count_by_kind.values())

    def as_dict(self) -> dict:
        return {
            "total_bytes": self.total_bytes,
            "total_count": self.total_count,
            "bytes_by_kind": dict(self.bytes_by_kind),
            "count_by_kind": dict(self.count_by_kind),
        }


def _shape_bytes(shape_text: str) -> int:
    """Sum byte sizes of all array shapes in an HLO result-type string.

    Handles tuples like ``(f32[8,128], f32[8,128])`` by summing every
    ``dtype[dims]`` occurrence.
    """
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_text):
        nbytes = _DTYPE_BYTES.get(dtype)
        if nbytes is None:
            continue
        numel = 1
        if dims:
            for d in dims.split(","):
                if d:
                    numel *= int(d)
        total += numel * nbytes
    return total


def collective_stats(hlo_text: str) -> CollectiveStats:
    """Sum result-shape bytes of every collective op in HLO text.

    We count each collective once by its *result* size (for -start/-done async
    pairs only the -start line carries the op name with operands; -done lines
    are also matched, so we skip them explicitly).
    """
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        m = _LINE_RE.match(line)
        if m is None:
            continue
        # Skip async -done halves: their defining op name appears as
        # e.g. `all-gather-done(`; detect via the raw line.
        kind = m.group(2)
        if f"{kind}-done(" in line:
            continue
        nbytes = _shape_bytes(m.group(1))
        stats.bytes_by_kind[kind] = stats.bytes_by_kind.get(kind, 0) + nbytes
        stats.count_by_kind[kind] = stats.count_by_kind.get(kind, 0) + 1
    return stats
