"""Share (%) of the traced window's device busy time covered by ops under
the ``es.attention`` scope, the attention read of every pass
(``bench/scopes.py``)."""


def read(rec):
    tr = rec.get("trace") or {}
    share = tr.get("attention_share")
    return None if share is None else 100.0 * share
