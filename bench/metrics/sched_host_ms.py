"""Mean host ms of one scheduler step in the traced tail: an
``es.sched.step`` span less its ``es.engine.wait`` child, over the spans
that start in the traced window (``bench/scopes.py``)."""


def read(rec):
    return (rec.get("trace") or {}).get("sched_host_ms")
