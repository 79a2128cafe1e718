"""Helpers the metric readers share: requests and steps of a run record.
Times are seconds on the window's clock: 0 opens it, ``seconds`` closes it,
and a lead-in runs before 0."""
from __future__ import annotations

import numpy as np


def p90(values):
    """The 90th percentile (numpy's linear rule), or None without values."""
    return float(np.percentile(np.asarray(values, float), 90)) if len(values) else None


def inside(rec, t):
    return 0.0 <= t <= rec["seconds"]


def due_in_window(rec):
    return [r for r in rec["requests"]
            if r["due"] is not None and 0.0 <= r["due"] < rec["seconds"]]


def steps_in_window(rec):
    return [s for s in rec["steps"] if 0.0 <= s["t0"] < rec["seconds"]]


def rounds_in_window(rec):
    return [(t0, t1) for t0, t1 in rec["rounds"] if 0.0 <= t0 < rec["seconds"]]
