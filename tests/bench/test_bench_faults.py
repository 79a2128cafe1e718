"""Faults planted under the timed path must come out as not correct, and
so must the lower-precision control.  Each drives a whole tiny run on the
CPU (``run_cell``) with the served engine's step broken after warm-up.
The cell runs on one chip, so there is no exchange between chips to leave
out."""
import dataclasses
import time

import jax
import jax.numpy as jnp
import pytest

from bench import run as runmod

# at float32 the tiny program reads a widest gap of 0 (test_bench_run);
# these limits stand for the cell's at this size
TINY_LIMITS = {"checks": {"gap_widest": {"limit": 1e-3},
                          "gap_mean": {"limit": 1e-5}}}


def _wrap_step(served, change):
    eng = served.sched.engine
    orig = eng._jit_step

    def step(params, state, enc):
        old = jax.tree_util.tree_map(jnp.copy, state)
        return change(old, orig(params, state, enc), eng)
    eng._jit_step = step


def unchanged(old, new, eng):
    return old


def half_left_out(old, new, eng):
    b = old.tokens.shape[0] // 2
    keep = {f: getattr(new, f).at[b:].set(getattr(old, f)[b:])
            for f in ("tokens", "conf", "pred", "bs", "blocks_left", "phase",
                      "iters", "active")}
    return new._replace(**keep)


def token_altered(old, new, eng):
    fresh = (old.tokens == eng.mask_id) & (new.tokens != eng.mask_id)
    vocab = eng.cfg.vocab_size
    return new._replace(tokens=jnp.where(fresh, (new.tokens + 1) % vocab,
                                         new.tokens))


@pytest.mark.parametrize("fault", [unchanged, half_left_out, token_altered])
def test_fault_is_not_correct(tiny, fault):
    cell = dataclasses.replace(tiny("llada-8b-l8", "chat-poisson"), limits=TINY_LIMITS)
    res = runmod.run_cell(cell, 4242, 3.0, False, t_start=time.monotonic(),
                          cache=False,
                          patch=lambda s: _wrap_step(s, fault))
    assert res["correct"] is False
    failing = [k for k, v in res["checks"].items() if v["value"] > v["limit"]]
    assert failing, res["checks"]


def test_control_is_not_correct(tiny):
    """The reference in float8 in the program's place: the tokens it puts
    first, read under the float32 reference, fail the limits that the
    program passes, and the run's own checks say so."""
    cell = dataclasses.replace(tiny("llada-8b-l8", "chat-poisson", rate=4.0),
                               limits=TINY_LIMITS)
    cell.config["check_tokens"] = 400
    gaps = {}
    res = runmod.run_cell(cell, 31337, 3.0, False, t_start=time.monotonic(),
                          cache=False, control="fp8", gaps=gaps)
    assert res["correct"] is False
    failing = {k for k, v in res["checks"].items() if v["value"] > v["limit"]}
    assert failing & {"gap_widest", "gap_mean"}, res["checks"]
    # the same run's served tokens pass: only the control fails
    served = gaps["served"]["all"]
    assert served["widest"] <= TINY_LIMITS["checks"]["gap_widest"]["limit"]
    assert served["mean"] <= TINY_LIMITS["checks"]["gap_mean"]["limit"]
