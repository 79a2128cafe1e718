"""Share (%) of lane steps in the window in which at least one resident row
was at a prompt refresh, so the full-width refresh pass ran.  A row's pass
kind comes from its phase in its block, by the configuration's cadence."""
from bench.metrics import _common as _c


def read(rec):
    steps = [s for s in _c.steps_in_window(rec) if s["rows"]]
    if not steps:
        return None
    hit = sum(1 for s in steps
              if any(kind == "prompt_refresh" for _, _, kind in s["rows"]))
    return 100.0 * hit / len(steps)
