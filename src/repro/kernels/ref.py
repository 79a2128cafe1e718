"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth for the per-kernel allclose sweeps in
``tests/test_kernels_*.py``.  They are deliberately naive (materialized
attention scores, sequential SSM recurrence) and only used at test shapes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def attention_mask(
    q_pos: jax.Array,       # [B, Lq] int32
    kv_pos: jax.Array,      # [B, Lkv] int32 (-1 = invalid)
    *,
    window: int = 0,
    anchor: int = 0,
    causal: bool = False,
    bc_start: int = 0,
    bc_block: int = 0,
) -> jax.Array:
    """[B, Lq, Lkv] bool attention-allowed mask.

    Semantics (shared with the Pallas kernel):
      - kv_pos < 0 is always masked (padding / not-yet-filled cache rows);
      - ``causal``: kv_pos <= q_pos;
      - ``window > 0``: |q_pos - kv_pos| <= window, except kv_pos < anchor
        rows (prompt anchors) which are always attended (block-sparse
        long-context variant, DESIGN §5);
      - ``bc_block > 0`` (block-causal): prompt positions (< ``bc_start``)
        are block -1, position p >= bc_start is block
        (p - bc_start) // bc_block; a query attends its own and earlier
        blocks only;
      - default (window == 0, causal=False): full bidirectional (dLLM).
    """
    qp = q_pos[:, :, None]
    kp = kv_pos[:, None, :]
    mask = kp >= 0
    if causal:
        mask &= kp <= qp
    if window > 0:
        win = jnp.abs(qp - kp) <= window
        if anchor > 0:
            win |= kp < anchor
        mask &= win
    if bc_block > 0:
        qb = jnp.where(qp >= bc_start, (qp - bc_start) // bc_block, -1)
        kb = jnp.where(kp >= bc_start, (kp - bc_start) // bc_block, -1)
        mask &= kb <= qb
    return mask


def attention_reference(
    q: jax.Array,           # [B, Hq, Lq, D]
    k: jax.Array,           # [B, Hkv, Lkv, D]
    v: jax.Array,           # [B, Hkv, Lkv, D]
    q_pos: jax.Array,       # [B, Lq]
    kv_pos: jax.Array,      # [B, Lkv]
    *,
    window: int = 0,
    anchor: int = 0,
    causal: bool = False,
    bc_start: int = 0,
    bc_block: int = 0,
    softmax_scale: float | None = None,
) -> jax.Array:
    """Naive rectangular GQA attention with materialized scores."""
    b, hq, lq, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    scale = softmax_scale if softmax_scale is not None else 1.0 / (d**0.5)

    kk = jnp.repeat(k, group, axis=1)
    vv = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kk.astype(jnp.float32))
    scores = scores * scale
    mask = attention_mask(q_pos, kv_pos, window=window, anchor=anchor,
                          causal=causal, bc_start=bc_start, bc_block=bc_block)
    scores = jnp.where(mask[:, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    # rows where everything is masked: softmax of NEG_INF row is uniform; zero it
    any_valid = jnp.any(mask, axis=-1)[:, None, :, None]
    probs = jnp.where(any_valid, probs, 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vv.astype(jnp.float32))
    return out.astype(q.dtype)


def ssd_reference(
    x: jax.Array,           # [B, L, H, P]
    dt: jax.Array,          # [B, L, H]  (positive, post-softplus)
    a_log: jax.Array,       # [H]        (A = -exp(a_log) < 0)
    bmat: jax.Array,        # [B, L, G, N]
    cmat: jax.Array,        # [B, L, G, N]
    *,
    init_state: jax.Array | None = None,   # [B, H, N, P]
) -> tuple[jax.Array, jax.Array]:
    """Sequential SSD recurrence (Mamba-2, arXiv:2405.21060 eq. SSM):

        S_i = exp(dt_i * A) * S_{i-1} + dt_i * B_i x_i^T
        y_i = C_i^T S_i

    Returns (y [B,L,H,P], final_state [B,H,N,P]).
    """
    b, l, h, p = x.shape
    g, n = bmat.shape[2], bmat.shape[3]
    heads_per_group = h // g
    A = -jnp.exp(a_log.astype(jnp.float32))                   # [H]

    bm = jnp.repeat(bmat, heads_per_group, axis=2)            # [B, L, H, N]
    cm = jnp.repeat(cmat, heads_per_group, axis=2)

    if init_state is None:
        init_state = jnp.zeros((b, h, n, p), jnp.float32)

    def step(state, inp):
        x_i, dt_i, b_i, c_i = inp                             # [B,H,P],[B,H],[B,H,N],[B,H,N]
        decay = jnp.exp(dt_i.astype(jnp.float32) * A)[..., None, None]   # [B,H,1,1]
        contrib = (
            dt_i.astype(jnp.float32)[..., None, None]
            * b_i.astype(jnp.float32)[..., :, None]
            * x_i.astype(jnp.float32)[..., None, :]
        )                                                     # [B,H,N,P]
        state = decay * state + contrib
        y_i = jnp.einsum("bhn,bhnp->bhp", c_i.astype(jnp.float32), state)
        return state, y_i

    xs = (
        jnp.moveaxis(x, 1, 0),
        jnp.moveaxis(dt, 1, 0),
        jnp.moveaxis(bm, 1, 0),
        jnp.moveaxis(cm, 1, 0),
    )
    final, ys = jax.lax.scan(step, init_state, xs)
    y = jnp.moveaxis(ys, 0, 1).astype(x.dtype)                # [B, L, H, P]
    return y, final


def scatter_kv_reference(
    cache: jax.Array,       # [B, S, H, D]
    new: jax.Array,         # [B, K, H, D]
    idx: jax.Array,         # [B, K] int32
) -> jax.Array:
    """Per-batch row scatter: cache[b, idx[b, k]] = new[b, k]."""

    def one(c, n, i):
        return c.at[i].set(n.astype(c.dtype))

    return jax.vmap(one)(cache, new, idx)


def importance_reference(
    h_new: jax.Array,       # [B, K, d]
    h_old: jax.Array,       # [B, K, d]
    conf: jax.Array,        # [B, K]
    alpha: float,
    eps: float = 1e-8,
) -> jax.Array:
    """Paper Eq. 1:  I = a*c + (1-a) * ||Hn-Ho||_1 / (sqrt(d) * ||Ho||_2)."""
    d = h_new.shape[-1]
    diff = jnp.sum(jnp.abs(h_new.astype(jnp.float32) - h_old.astype(jnp.float32)), axis=-1)
    norm = jnp.sqrt(jnp.sum(jnp.square(h_old.astype(jnp.float32)), axis=-1))
    var = diff / (jnp.sqrt(float(d)) * norm + eps)
    return alpha * conf.astype(jnp.float32) + (1.0 - alpha) * var


def variation_reference(
    h_new: jax.Array,       # [B, K, d]
    h_old: jax.Array,       # [B, K, d]
    conf: jax.Array,        # [B, K]
    alpha: float,
    eps: float = 1e-8,
) -> jax.Array:
    """Adaptive-cache refresh priority (dLLM-Cache):

        V = a*c + (1-a) * (1 - cos(Hn, Ho))

    Cosine distance of the cached vs fresh feature row, blended with
    confidence using the same Eq.-1 alpha.  A zero cached row (cold start)
    gives cos = 0, i.e. maximal variation — the token is always eligible for
    refresh until it has been observed once.
    """
    hn = h_new.astype(jnp.float32)
    ho = h_old.astype(jnp.float32)
    dot = jnp.sum(hn * ho, axis=-1)
    nn = jnp.sum(hn * hn, axis=-1)
    no = jnp.sum(ho * ho, axis=-1)
    cos = dot / (jnp.sqrt(nn * no) + eps)
    return alpha * conf.astype(jnp.float32) + (1.0 - alpha) * (1.0 - cos)
