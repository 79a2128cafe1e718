"""Mean device ms of one run of the ``es.skip_decode`` pass in the traced
tail: an execution of its conditional in which the pass's own branch ran,
mean over the chips (``bench/scopes.py``).  None without a run."""


def read(rec):
    tr = rec.get("trace") or {}
    return (tr.get("passes") or {}).get("es.skip_decode", {}).get("ms")
