"""Process start to the first timed step: imports, weights, server,
compile or cache load, warm-up."""


def read(rec):
    return rec["setup_s"]
