"""Mean device ms of one run of the ``es.block_refresh`` pass in the traced
tail: an execution of its conditional in which the pass's own branch ran,
mean over the chips (``bench/scopes.py``).  None without a run."""


def read(rec):
    tr = rec.get("trace") or {}
    return (tr.get("passes") or {}).get("es.block_refresh", {}).get("ms")
