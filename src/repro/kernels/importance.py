"""Fused importance-score kernel (paper Eq. 1, TPU target).

    I_i = alpha * c_i + (1 - alpha) * ||Hn_i - Ho_i||_1 / (sqrt(d) * ||Ho_i||_2)

One VPU pass over the active block's hidden rows: both reductions (L1 of the
diff, L2 of the old row) are computed in a single read of Hn/Ho, fused with
the confidence blend — this otherwise costs three separate HBM sweeps in the
naive jnp lowering.

Both scores here are row-wise, so the grid tiles the token axis in
``BLOCK_K``-row tiles: a whole ``[T, d]`` f32 feature plane (768 x 4096 at
LLaDA-8B widths) would not fit the TPU's scoped VMEM.  The confidence and
the score travel as ``[B, K, 1]`` columns, so every block's last two
dimensions are 8-aligned or the array's own, as the TPU lowering requires.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_K = 128   # token rows per grid step (f32 [128, 4096] tile: 2 MB)


def _importance_kernel(hn_ref, ho_ref, conf_ref, out_ref, *, alpha: float, eps: float):
    hn = hn_ref[0].astype(jnp.float32)            # [bk, d]
    ho = ho_ref[0].astype(jnp.float32)            # [bk, d]
    conf = conf_ref[0].astype(jnp.float32)        # [bk, 1]
    d = hn.shape[-1]
    l1 = jnp.sum(jnp.abs(hn - ho), axis=-1, keepdims=True)       # [bk, 1]
    l2 = jnp.sqrt(jnp.sum(ho * ho, axis=-1, keepdims=True))      # [bk, 1]
    var = l1 / (jnp.sqrt(float(d)) * l2 + eps)
    out_ref[0] = alpha * conf + (1.0 - alpha) * var


def _variation_kernel(hn_ref, ho_ref, conf_ref, out_ref, *, alpha: float, eps: float):
    hn = hn_ref[0].astype(jnp.float32)            # [bk, d]
    ho = ho_ref[0].astype(jnp.float32)            # [bk, d]
    conf = conf_ref[0].astype(jnp.float32)        # [bk, 1]
    dot = jnp.sum(hn * ho, axis=-1, keepdims=True)               # [bk, 1]
    nn = jnp.sum(hn * hn, axis=-1, keepdims=True)
    no = jnp.sum(ho * ho, axis=-1, keepdims=True)
    cos = dot / (jnp.sqrt(nn * no) + eps)
    out_ref[0] = alpha * conf + (1.0 - alpha) * (1.0 - cos)


def _row_score(body, h_new, h_old, conf, *, interpret: bool):
    """Run a row-wise score ``body`` over ``[B, K, d]`` in token tiles."""
    b, k, d = h_new.shape
    bk = BLOCK_K if k % BLOCK_K == 0 else k
    rows = pl.BlockSpec((1, bk, d), lambda bi, ki: (bi, ki, 0))
    col = pl.BlockSpec((1, bk, 1), lambda bi, ki: (bi, ki, 0))
    return pl.pallas_call(
        body,
        grid=(b, k // bk),
        in_specs=[rows, rows, col],
        out_specs=col,
        out_shape=jax.ShapeDtypeStruct((b, k, 1), jnp.float32),
        interpret=interpret,
    )(h_new, h_old, conf[..., None]).reshape(b, k)


def importance_kernel(
    h_new: jax.Array,   # [B, K, d]
    h_old: jax.Array,   # [B, K, d]
    conf: jax.Array,    # [B, K]
    *,
    alpha: float,
    eps: float = 1e-8,
    interpret: bool = False,
) -> jax.Array:
    kernel = functools.partial(_importance_kernel, alpha=alpha, eps=eps)
    return _row_score(kernel, h_new, h_old, conf, interpret=interpret)


def variation_kernel(
    h_new: jax.Array,   # [B, K, d]
    h_old: jax.Array,   # [B, K, d]
    conf: jax.Array,    # [B, K]
    *,
    alpha: float,
    eps: float = 1e-8,
    interpret: bool = False,
) -> jax.Array:
    """Adaptive-cache refresh priority: alpha*conf + (1-alpha)*(1 - cosine).

    Same single-VPU-pass structure as :func:`importance_kernel` — the three
    reductions (dot, |Hn|^2, |Ho|^2) fuse into one read of each row."""
    kernel = functools.partial(_variation_kernel, alpha=alpha, eps=eps)
    return _row_score(kernel, h_new, h_old, conf, interpret=interpret)
