"""Self- and cross-attention layers with optional KV-cache scatter update.

Self-attention supports the three cache modes the diffusion engines use
(DESIGN §2): fresh (train), write-through (prefill: scatter all rows, attend
cache) and partial (decode: scatter only the active subset — paper Alg.1
lines 2–5).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models.common import apply_rope, dense_init


class KVCache(NamedTuple):
    """KV cache rows; optionally int8-quantized with per-(token, head) scales
    (beyond-paper memory optimization, EXPERIMENTS §Perf).

    Layouts: dense ``[B, S, Hkv, Dh]`` (one stripe per slot), or — when used
    as the pool of a :class:`PagedKVCache` — ``[P, page_size, Hkv, Dh]``
    shared across all slots and addressed through a block table."""
    k: jax.Array                        # [B, S, Hkv, Dh] (bf16/f32 or int8)
    v: jax.Array
    k_scale: Optional[jax.Array] = None  # [B, S, Hkv] f32 when quantized
    v_scale: Optional[jax.Array] = None

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None


class PagedKVCache(NamedTuple):
    """Block-table view over a shared KV page pool.

    ``cache`` holds pool-shaped arrays ``[num_pages, page_size, Hkv, Dh]``
    (plus ``[num_pages, page_size, Hkv]`` scale planes when quantized);
    ``block_tables[b, vp]`` maps slot ``b``'s virtual page ``vp`` (sequence
    positions ``[vp*ps, (vp+1)*ps)``) to a physical page, with ``-1`` for
    unmapped pages (masked on read, routed to the garbage page 0 on write).
    ``page_size`` is static — it parameterizes kernel grids, not data.

    Ownership contract (docs/ARCHITECTURE.md): this layer treats the pool
    as write-through and mapping-oblivious — it scatters every fresh row
    through the table unconditionally.  Page ownership lives one level up:
    the scheduler's ``PageAllocator`` refcounts physical pages, and a page
    mapped by several slots (refcount > 1, prefix sharing) is READ-ONLY in
    the sense that all sharers are guaranteed to scatter bit-identical
    content; when that guarantee is about to lapse the scheduler forks the
    page (``ops.fork_pages``) and repoints the block table BEFORE this
    layer runs again.
    """
    cache: KVCache
    block_tables: jax.Array              # [B, n_vpages] int32
    page_size: int

    @property
    def quantized(self) -> bool:
        return self.cache.quantized


def _quantize_rows(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """x [B, K, H, D] -> (int8 [B,K,H,D], scale [B,K,H])."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def attn_init(key, cfg: ModelConfig, *, cross: bool = False, dtype=jnp.float32,
              kv_width: int | None = None) -> dict:
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    d_kv_in = (kv_width or cfg.d_enc or d) if cross else d
    ks = jax.random.split(key, 4)
    out_scale = 0.02 / max(2.0 * cfg.n_layers, 1.0) ** 0.5
    p = {
        "wq": dense_init(ks[0], (d, h * dh), dtype=dtype),
        "wk": dense_init(ks[1], (d_kv_in, hkv * dh), dtype=dtype),
        "wv": dense_init(ks[2], (d_kv_in, hkv * dh), dtype=dtype),
        "wo": dense_init(ks[3], (h * dh, d), scale=out_scale, dtype=dtype),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((h * dh,), dtype)
        p["bk"] = jnp.zeros((hkv * dh,), dtype)
        p["bv"] = jnp.zeros((hkv * dh,), dtype)
    return p


def _project_qkv(params, cfg: ModelConfig, x, positions, *, rope: bool):
    b, k, _ = x.shape
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ params["wq"]
    if "bq" in params:
        q = q + params["bq"]
    q = q.reshape(b, k, h, dh)
    kk = x @ params["wk"]
    vv = x @ params["wv"]
    if "bk" in params:
        kk = kk + params["bk"]
        vv = vv + params["bv"]
    kk = kk.reshape(b, k, hkv, dh)
    vv = vv.reshape(b, k, hkv, dh)
    if rope:
        q = apply_rope(q, positions, theta=cfg.rope_theta, fraction=cfg.rope_fraction)
        kk = apply_rope(kk, positions, theta=cfg.rope_theta, fraction=cfg.rope_fraction)
    return q, kk, vv


def self_attention(
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,                  # [B, K, d] active rows
    positions: jax.Array,          # [B, K] global positions
    *,
    cache: Optional[KVCache | PagedKVCache] = None,
    slot_idx: Optional[jax.Array] = None,   # [B, K] cache rows to scatter
    kv_pos: Optional[jax.Array] = None,     # [B, S] cache validity (-1 invalid)
    causal: bool = False,
    window=0,                      # int or traced scalar (per-layer local attn)
    anchor: int = 0,
    bc_start: int = 0,             # block-causal: first generation position
    bc_block: int = 0,             # block-causal block length; 0 = off
    attn_impl: str = "xla",
    use_rope: bool = True,
    scatter_mask: Optional[jax.Array] = None,   # [B] rows whose scatters land
    token_mask: Optional[jax.Array] = None,     # [B, K] tokens whose K/V land
    window_limit: Optional[jax.Array] = None,   # [B] sliding-window horizon
) -> tuple[jax.Array, Optional[KVCache | PagedKVCache]]:
    """Returns (output [B, K, d], updated cache or None).

    ``scatter_mask`` (mixed-mode cadence) drops the cache update for rows a
    pass does not own: dense caches write back the carried row, the paged
    pool routes unowned rows to the garbage page.  ``token_mask`` (adaptive
    feature cache) gates individual tokens within owned rows — gated-out
    tokens keep their cached K/V (partial refresh).  Attention reads are
    unmasked — unowned rows still compute (one fused program), their
    outputs are discarded one level up.

    ``window_limit`` (sliding active-window attention) masks cache positions
    at or beyond the per-row exclusive horizon out of the read: one
    ``ops.window_kv_clamp`` of ``kv_pos`` at entry covers the dense and
    paged paths identically (every impl already masks ``kv_pos < 0``), and
    the paged read additionally walks a windowed block-table view so
    beyond-horizon pages never move through HBM.  Writes are NOT windowed —
    the cadence contract (every block entry is a full prefill) rewrites
    beyond-window rows before any read can see them."""
    b, k, _ = x.shape
    q, kk, vv = _project_qkv(params, cfg, x, positions, rope=use_rope)
    if window_limit is not None and kv_pos is not None:
        kv_pos = ops.window_kv_clamp(kv_pos, window_limit)

    if isinstance(cache, PagedKVCache):
        assert slot_idx is not None and kv_pos is not None
        return _paged_self_attention(
            params, q, kk, vv, cache, positions, slot_idx, kv_pos,
            causal=causal, window=window, anchor=anchor,
            bc_start=bc_start, bc_block=bc_block, attn_impl=attn_impl,
            scatter_mask=scatter_mask, token_mask=token_mask,
            window_limit=window_limit,
        )

    k_scale = v_scale = None
    if cache is not None:
        assert slot_idx is not None and kv_pos is not None
        if cache.quantized:
            k8, ks = _quantize_rows(kk)
            v8, vs = _quantize_rows(vv)
            cache = KVCache(
                ops.scatter_rows(cache.k, k8, slot_idx, row_mask=scatter_mask,
                                 token_mask=token_mask),
                ops.scatter_rows(cache.v, v8, slot_idx, row_mask=scatter_mask,
                                 token_mask=token_mask),
                ops.scatter_rows(cache.k_scale, ks, slot_idx,
                                 row_mask=scatter_mask, token_mask=token_mask),
                ops.scatter_rows(cache.v_scale, vs, slot_idx,
                                 row_mask=scatter_mask, token_mask=token_mask),
            )
            k_scale, v_scale = cache.k_scale, cache.v_scale
        else:
            cache = KVCache(
                ops.scatter_rows(cache.k, kk.astype(cache.k.dtype), slot_idx,
                                 row_mask=scatter_mask, token_mask=token_mask),
                ops.scatter_rows(cache.v, vv.astype(cache.v.dtype), slot_idx,
                                 row_mask=scatter_mask, token_mask=token_mask),
            )
        k_full, v_full, kv_positions = cache.k, cache.v, kv_pos
    else:
        k_full, v_full, kv_positions = kk, vv, positions

    # the read, not the K/V scatters: profiler traces name it by this scope
    with jax.named_scope("es.attention"):
        out = ops.attention(
            jnp.swapaxes(q, 1, 2),                   # [B, H, K, Dh]
            jnp.swapaxes(k_full, 1, 2) if k_scale is not None
            else jnp.swapaxes(k_full.astype(q.dtype), 1, 2),
            jnp.swapaxes(v_full, 1, 2) if v_scale is not None
            else jnp.swapaxes(v_full.astype(q.dtype), 1, 2),
            positions,
            kv_positions,
            causal=causal,
            window=window,
            anchor=anchor,
            bc_start=bc_start,
            bc_block=bc_block,
            impl=attn_impl,
            k_scale=None if k_scale is None else jnp.swapaxes(k_scale, 1, 2),
            v_scale=None if v_scale is None else jnp.swapaxes(v_scale, 1, 2),
        )
    out = jnp.swapaxes(out, 1, 2).reshape(b, k, -1)
    return out @ params["wo"], cache


def _paged_self_attention(
    params, q, kk, vv, cache: PagedKVCache, positions, slot_idx, kv_pos,
    *, causal, window, anchor, bc_start=0, bc_block=0, attn_impl,
    scatter_mask=None, token_mask=None, window_limit=None,
) -> tuple[jax.Array, PagedKVCache]:
    """Scatter fresh rows through the block table, attend the page pool.

    ``scatter_mask`` drops unowned rows' writes by handing the scatter a
    write view of the block table with those rows forced to -1 (unmapped ⇒
    garbage page) — reads keep the real table.  ``token_mask`` additionally
    gates individual tokens (adaptive partial refresh): gated-out tokens
    write back their current pool content, an exact no-op.  ``window_limit``
    hands the attention READ a windowed block-table view
    (``ops.window_block_tables``): beyond-horizon vpages read as unmapped,
    so the kernel's page walk DMA-elides them — scatters keep the real
    table (the next block's full prefill rewrites those rows before any
    read)."""
    b, k = slot_idx.shape
    pool, bt, ps = cache.cache, cache.block_tables, cache.page_size
    if pool.quantized:
        k8, ks = _quantize_rows(kk)
        v8, vs = _quantize_rows(vv)
        pool = KVCache(
            ops.scatter_rows_paged(pool.k, k8, slot_idx, bt, page_size=ps,
                                   row_mask=scatter_mask, token_mask=token_mask),
            ops.scatter_rows_paged(pool.v, v8, slot_idx, bt, page_size=ps,
                                   row_mask=scatter_mask, token_mask=token_mask),
            ops.scatter_rows_paged(pool.k_scale, ks, slot_idx, bt,
                                   page_size=ps, row_mask=scatter_mask,
                                   token_mask=token_mask),
            ops.scatter_rows_paged(pool.v_scale, vs, slot_idx, bt,
                                   page_size=ps, row_mask=scatter_mask,
                                   token_mask=token_mask),
        )
        k_scale, v_scale = pool.k_scale, pool.v_scale
    else:
        k_scale = v_scale = None
        pool = KVCache(
            ops.scatter_rows_paged(pool.k, kk.astype(pool.k.dtype), slot_idx,
                                   bt, page_size=ps, row_mask=scatter_mask,
                                   token_mask=token_mask),
            ops.scatter_rows_paged(pool.v, vv.astype(pool.v.dtype), slot_idx,
                                   bt, page_size=ps, row_mask=scatter_mask,
                                   token_mask=token_mask),
        )
    read_bt = ops.window_block_tables(bt, window_limit, ps)
    with jax.named_scope("es.attention"):
        out = ops.paged_attention(
            jnp.swapaxes(q, 1, 2),
            pool.k, pool.v,
            positions, kv_pos, read_bt,
            page_size=ps,
            causal=causal, window=window, anchor=anchor,
            bc_start=bc_start, bc_block=bc_block,
            impl=attn_impl,
            k_scale=k_scale, v_scale=v_scale,
        )
    out = jnp.swapaxes(out, 1, 2).reshape(b, k, -1)
    return out @ params["wo"], PagedKVCache(pool, bt, ps)


def cross_attention(
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,                   # [B, K, d]
    *,
    enc_out: Optional[jax.Array] = None,     # [B, E, d_enc]
    cache: Optional[KVCache] = None,         # precomputed cross-KV
    attn_impl: str = "xla",
) -> tuple[jax.Array, Optional[KVCache]]:
    """Cross-attention to (static) encoder tokens.  No RoPE on either side.

    If ``cache`` is provided its K/V are used directly; otherwise they are
    projected from ``enc_out`` and returned for caching.
    """
    b, k, _ = x.shape
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ params["wq"]).reshape(b, k, h, dh)
    if cache is None:
        assert enc_out is not None
        e = enc_out.shape[1]
        ck = (enc_out @ params["wk"]).reshape(b, e, hkv, dh)
        cv = (enc_out @ params["wv"]).reshape(b, e, hkv, dh)
        cache = KVCache(ck.astype(x.dtype), cv.astype(x.dtype))
    ck, cv = cache.k, cache.v
    e = ck.shape[1]
    q_pos = jnp.zeros((b, k), jnp.int32)
    kv_pos = jnp.broadcast_to(jnp.arange(e, dtype=jnp.int32)[None], (b, e))
    out = ops.attention(
        jnp.swapaxes(q, 1, 2),
        jnp.swapaxes(ck.astype(q.dtype), 1, 2),
        jnp.swapaxes(cv.astype(q.dtype), 1, 2),
        q_pos,
        kv_pos,
        impl=attn_impl,
    )
    out = jnp.swapaxes(out, 1, 2).reshape(b, k, -1)
    return out @ params["wo"], cache
