"""p90 over the requests due in the window of (admission - due time); the
admission time is the scheduler's own ``Request.admit_s``."""
from bench.metrics import _common as _c


def read(rec):
    return _c.p90([r["admit"] - r["due"] for r in _c.due_in_window(rec)
                   if r["admit"] is not None])
