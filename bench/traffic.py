"""The one traffic generator: a mix's parameters in, requests out.

A mix file (``bench/traffic/<name>.json``) states the loop kind and the
laws of arrival gaps, prompt lengths and output blocks.  Every seed gets
the same multiset of gaps and lengths, drawn at evenly spaced quantiles of
each law, in an order of its own; so two seeds offer the same work and
differ only in how it lines up.  Token ids come from the seed too.

* ``"loop": "open"``: ``arrival.rate_per_s`` times the window gives the
  number of requests due in it; their gaps are exponential quantiles at
  that rate.  A lead-in (``lead_in_s``, default 0) before the window is
  planned the same way: it brings the server to its steady load before the
  window opens.
* ``"loop": "closed"``: ``requests`` requests with no due times; the load
  loop keeps ``backlog`` of them queued beyond the free slots.

Prompts follow ``prompt_tokens``; outputs follow ``output_tokens``
(rounded up to whole blocks of the server's block length) or
``output_blocks``.  Length laws (``dist``): ``lognormal`` (``median``,
``sigma``, clipped to ``min``..``max``), ``loguniform`` (``min``..``max``),
``choice`` (``values`` and ``weights``, split by largest remainder).
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Optional

import numpy as np


@dataclasses.dataclass
class Planned:
    """One request as the traffic plans it (times relative to the window)."""
    index: int
    prompt: np.ndarray            # [n] int32 token ids
    n_blocks: int
    due_s: Optional[float]        # None in a closed loop


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(law: dict, n: int) -> np.ndarray:
    """``n`` values of a length law at evenly spaced quantiles (sorted)."""
    u = _quantiles(n)
    kind = law["dist"]
    if kind == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(x) for x in u])
        v = law["median"] * np.exp(law["sigma"] * z)
        return np.clip(np.rint(v), law["min"], law["max"]).astype(np.int64)
    if kind == "loguniform":
        lo, hi = math.log(law["min"]), math.log(law["max"])
        return np.clip(np.rint(np.exp(lo + u * (hi - lo))),
                       law["min"], law["max"]).astype(np.int64)
    if kind == "choice":
        w = np.asarray(law["weights"], float)
        w = w / w.sum()
        counts = np.floor(w * n).astype(np.int64)
        rest = np.argsort(-(w * n - counts), kind="stable")
        counts[rest[: n - counts.sum()]] += 1
        return np.repeat(np.asarray(law["values"], np.int64), counts)
    raise ValueError(f"unknown length law {kind!r}")


def output_blocks(mix: dict, n: int, block_length: int) -> np.ndarray:
    """``n`` output lengths in blocks (sorted)."""
    if "output_tokens" in mix:
        toks = lengths(mix["output_tokens"], n)
        return -(-toks // block_length)
    return lengths(mix["output_blocks"], n)


def plan(mix: dict, seed: int, seconds: float, vocab_size: int,
         block_length: int, rate_per_s: Optional[float] = None
         ) -> list[Planned]:
    """The requests of one run.  ``rate_per_s`` overrides an open mix's
    rate (the knee sweep).  The lead-in and the window are planned apart,
    each with its own multiset, so every seed offers the window the same
    work."""
    rng = np.random.default_rng(seed)
    if mix["loop"] == "open":
        rate = rate_per_s or mix["arrival"]["rate_per_s"]
        if mix["arrival"]["process"] != "poisson":
            raise ValueError(f"unknown arrival process "
                             f"{mix['arrival']['process']!r}")
        lead = float(mix.get("lead_in_s", 0.0))
        spans = [(-lead, lead)] if lead > 0 else []
        spans.append((0.0, seconds))
        parts = []
        for start, span in spans:
            n = max(1, int(rate * span))
            gaps = rng.permutation(-np.log1p(-_quantiles(n)) / rate)
            # n arrivals spread over the span: the first is due at its start
            parts.append((n, start + span * (np.cumsum(gaps) - gaps)
                          / gaps.sum()))
    elif mix["loop"] == "closed":
        parts = [(int(mix["requests"]), [None] * int(mix["requests"]))]
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    out = []
    for n, due in parts:
        plens = rng.permutation(lengths(mix["prompt_tokens"], n))
        blocks = rng.permutation(output_blocks(mix, n, block_length))
        for i in range(n):
            prompt = rng.integers(3, vocab_size, int(plens[i])).astype(np.int32)
            out.append(Planned(len(out), prompt, int(blocks[i]),
                               None if due[i] is None else float(due[i])))
    return out
