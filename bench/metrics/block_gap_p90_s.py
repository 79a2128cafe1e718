"""p90 over every gap between successive committed blocks of one request
whose later block commits inside the window (a chat request outlasts the
window, so its gaps are taken where they fall)."""
from bench.metrics import _common as _c


def read(rec):
    gaps = [b - a for r in rec["requests"]
            for a, b in zip(r["blocks"], r["blocks"][1:])
            if _c.inside(rec, b)]
    return _c.p90(gaps)
