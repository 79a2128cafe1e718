"""p90 over the requests due in the window of (first block committed -
due time).  Requests are followed past the close until their first block
commits; one that never does is a failed request and is left out here."""
from bench.metrics import _common as _c


def read(rec):
    return _c.p90([r["blocks"][0] - r["due"] for r in _c.due_in_window(rec)
                   if r["blocks"]])
