"""Share (%) of the traced window in which no operation ran on the device,
mean over the cell's chips (``bench/trace.py``)."""


def read(rec):
    tr = rec.get("trace")
    if not tr or tr.get("idle_share") is None:
        return None
    return 100.0 * tr["idle_share"]
