"""Seeded random weights, made on the device in one jitted call.

The benchmark owns the weights: ``logical`` draws them from ``--seed`` by
name, and ``for_program`` lays the same values out as the program's
parameter tree (padded vocabulary rows, stacked layer groups).  The
reference draws ``logical`` again after the window, so it takes nothing
that the program made.  Values are drawn in the served dtype.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def key_of(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (wider than 32 bits too)."""
    return jax.random.PRNGKey(
        int(np.random.default_rng(seed).integers(0, 2**31 - 1)))


def shapes(m: dict) -> dict:
    """Logical weight shapes of a model-size dict (a config's ``model``)."""
    d, h, hkv, dh = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    f, v, n = m["d_ff"], m["vocab_size"], m["n_layers"]
    s = {
        "embed": (v + 1, d),            # row v: the mask token
        "head": (d, v),
        "final_norm": (d,),
        "ln1": (n, d), "ln2": (n, d),
        "wq": (n, d, h * dh), "wk": (n, d, hkv * dh), "wv": (n, d, hkv * dh),
        "wo": (n, h * dh, d),
        "w_gate": (n, d, f), "w_up": (n, d, f), "w_down": (n, f, d),
    }
    if m["qkv_bias"]:
        s.update(bq=(n, h * dh), bk=(n, hkv * dh), bv=(n, hkv * dh))
    return s


def _scale(name: str, m: dict) -> float:
    if name in ("wo", "w_down"):
        return 0.02 / math.sqrt(2.0 * m["n_layers"])
    if name in ("ln1", "ln2", "final_norm"):
        return 0.1
    return 0.02


def logical(m: dict, key: jax.Array) -> dict:
    """Every weight by name, drawn in ``param_dtype`` (traceable)."""
    dt = jnp.dtype(m["param_dtype"])
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes(m).items())):
        w = jax.random.normal(jax.random.fold_in(key, i), shape, dt) \
            * jnp.asarray(_scale(name, m), dt)
        if name in ("ln1", "ln2", "final_norm"):
            w = w + jnp.asarray(1.0, dt)
        out[name] = w
    return out


def make_logical(m: dict, seed: int) -> dict:
    return jax.jit(lambda k: logical(m, k))(key_of(seed))


def for_program(m: dict, w: dict, vocab_padded: int) -> dict:
    """The program's parameter tree (``repro.models.model.Model.init``'s
    layout) holding the logical values; padded vocabulary rows are 0."""
    v = m["vocab_size"]
    embed = jnp.zeros((vocab_padded, m["d_model"]), w["embed"].dtype)
    head = jnp.zeros((m["d_model"], vocab_padded), w["head"].dtype)
    attn = {k: w[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv") if k in w}
    return {
        "embed": embed.at[: v + 1].set(w["embed"]),
        "final_norm": w["final_norm"],
        "lm_head": head.at[:, :v].set(w["head"]),
        "layers": {"0": {
            "ln1": w["ln1"], "attn": attn, "ln2": w["ln2"],
            "ffn": {k: w[k] for k in ("w_gate", "w_up", "w_down")},
        }},
    }


def make_program_params(m: dict, seed: int, model) -> dict:
    """One jitted call: the seed's weights in the program's layout, on the
    default device.  The layout is checked against the program's own
    ``init`` shapes, so a change of the tree fails here and not later."""
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    vp = want["embed"].shape[0]
    params = jax.jit(lambda k: for_program(m, logical(m, k), vp))(key_of(seed))
    got = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params)
    exp = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), want)
    if got != exp:
        raise ValueError(f"weight layout differs from the program's: "
                         f"{got} != {exp}")
    return params
