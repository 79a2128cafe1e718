"""Share (%) of the chips' bf16 peak that the scheduler steps of the window
reach: the operations each resident row needs in its own pass kind
(``bench/work.py``) over the summed wall of those steps x chips x peak.
A faster step raises it; the work is that of the ES-dLLM algorithm,
whatever pads or repeats it."""
from bench import work as _work
from bench.metrics import _common as _c


def read(rec):
    if not rec.get("peaks"):
        return None
    flops = sum(_work.step_flops(rec["model"], rec["es"], kind, plen, nb,
                                 rec["block_length"])
                for s in _c.steps_in_window(rec) for plen, nb, kind in s["rows"])
    wall = sum(t1 - t0 for t0, t1 in _c.rounds_in_window(rec))
    if not flops or wall <= 0:
        return None
    return 100.0 * flops / (wall * rec["chips"] * rec["peaks"]["bf16_flops"])
