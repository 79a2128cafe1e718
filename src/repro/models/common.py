"""Shared neural building blocks (pure functional JAX, no flax).

Parameters are plain dict pytrees.  All blocks take an explicit ``cfg`` and
compute in ``cfg.compute_dtype`` with f32 accumulation where it matters.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

BIG_WINDOW = 1 << 30


def round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def padded_vocab(cfg: ModelConfig) -> int:
    """Vocab padded to a multiple of 256 (so the model axis always divides
    logits) with at least one pad row: the engines' mask token id is
    ``vocab_size``, and its embedding row must exist.  (LLaDA's 126,464 is
    already a multiple of 256; without the extra row the mask embedding
    gathers out of bounds, which ``jnp.take`` fills with NaN.)"""
    return round_up(cfg.vocab_size + 1, 256)


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(key, shape, scale: float = 0.02, dtype=jnp.float32):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# norms / activations
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return out.astype(x.dtype)


def gated_rms_norm(x: jax.Array, gate: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """Mamba-2 output norm: RMSNorm(x * silu(gate))."""
    return rms_norm(x * jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype), scale, eps)


def activation(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return lambda x: jax.nn.gelu(x, approximate=True)
    raise ValueError(f"unknown activation {name}")


# ---------------------------------------------------------------------------
# RoPE (half-split / NeoX convention, optional partial rotary for chatglm3)
# ---------------------------------------------------------------------------


def apply_rope(
    x: jax.Array,         # [B, K, H, Dh]
    positions: jax.Array, # [B, K] int32
    *,
    theta: float,
    fraction: float = 1.0,
) -> jax.Array:
    dh = x.shape[-1]
    rot = int(dh * fraction)
    rot -= rot % 2
    if rot == 0:
        return x
    half = rot // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions.astype(jnp.float32)[..., None] * inv_freq    # [B, K, half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    x1, x2 = x_rot[..., :half].astype(jnp.float32), x_rot[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), x_pass], axis=-1)


# ---------------------------------------------------------------------------
# gated MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------


def mlp_init(key, d_model: int, d_ff: int, n_layers: int, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    out_scale = 0.02 / max(2.0 * n_layers, 1.0) ** 0.5
    return {
        "w_gate": dense_init(k1, (d_model, d_ff), dtype=dtype),
        "w_up": dense_init(k2, (d_model, d_ff), dtype=dtype),
        "w_down": dense_init(k3, (d_ff, d_model), scale=out_scale, dtype=dtype),
    }


def mlp_apply(params: dict, x: jax.Array, act_name: str) -> jax.Array:
    act = activation(act_name)
    gate = act(x @ params["w_gate"])
    up = x @ params["w_up"]
    return (gate * up) @ params["w_down"]
