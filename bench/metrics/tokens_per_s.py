"""Output tokens committed inside the window over its seconds, all chips
of the cell together (a block commits ``block_length`` tokens)."""
from bench.metrics import _common as _c


def read(rec):
    n = sum(1 for r in rec["requests"] for t in r["blocks"] if _c.inside(rec, t))
    return n * rec["block_length"] / rec["seconds"]
