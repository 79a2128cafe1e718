"""Mean host wall of one scheduler step in the window, in ms: admission,
the engine step to ``block_until_ready``, and the bookkeeping after it
(one round of every lane when the server has several).  Idle time between
steps is not counted."""
from bench.metrics import _common as _c


def read(rec):
    walls = [t1 - t0 for t0, t1 in _c.rounds_in_window(rec)]
    if not walls:
        return None
    return 1000.0 * sum(walls) / len(walls)
