"""Rectangular flash attention Pallas kernel (TPU target).

This is the compute hot-spot of ES-dLLM's decode step: the *gathered* active
query subset (k <= block tokens, arbitrary positions) attends the *full*
KV cache.  The kernel streams KV HBM->VMEM in ``block_kv`` tiles while the
(small) Q tile stays resident, carrying the online-softmax running
(max, sum, acc) in VMEM scratch across the innermost (sequential) grid dim.

Mask semantics are position-based so gathered Q subsets work naturally:
  - kv_pos < 0            -> masked (padding / unfilled cache rows)
  - causal                -> kv_pos <= q_pos
  - window > 0            -> |q_pos - kv_pos| <= window, with kv_pos < anchor
                             always attended (prompt-anchor block-sparse
                             long-context variant, DESIGN §5)

Block shapes are MXU/VPU aligned: head_dim padded to a multiple of 128 by the
ops.py wrapper, block_q/block_kv multiples of 8 (f32) with 128-lane tiles.
The position planes travel as a ``[B, Lq, 1]`` column and a ``[B, 1, Lkv]``
row, so each block's last two dimensions are either the array's own or
8/128-aligned (the TPU lowering refuses anything else), and the mask
broadcast ``[bq, 1] x [1, bk]`` needs no relayout in the kernel.

Paged variant
-------------
``paged_flash_attention_kernel`` attends a *shared* KV pool
``[num_pages, Hkv, page_size, D]`` through a per-slot block table
``[B, n_vpages]``: the innermost (sequential) grid dimension walks the slot's
virtual pages and the K/V BlockSpec ``index_map`` resolves each one to its
physical page via scalar prefetch (the same trick scatter_kv.py uses for
output routing).  Unmapped entries (block table < 0) clamp to the reserved
garbage page 0 and are masked out through ``kv_pos < 0``; because the
index_map then repeats the same physical block, the Pallas pipeline elides
the redundant DMA — HBM traffic is proportional to *mapped* pages only.

That DMA-elision property is what memory manager v2 leans on: a prefix page
shared by several slots is fetched once per slot but stored once, and a
page that page-aligned eviction unmapped mid-request degrades to the
repeated-garbage-page case — the kernel needs no changes as sharing and
reclaim evolve, because both are pure block-table edits
(docs/ARCHITECTURE.md).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def window_block_tables(block_tables: jax.Array, limit: jax.Array | None,
                        page_size: int) -> jax.Array:
    """Windowed READ view of a block table: virtual pages whose first
    sequence position sits at or beyond the per-row exclusive horizon
    ``limit [B]`` are forced to -1.

    This is how the sliding active window reaches the paged kernel's
    block-table walk without touching the kernel body: a -1 entry clamps to
    the garbage page 0 in ``_page`` and its positions are already dead via
    ``ops.paged_kv_mask`` / ``ops.window_kv_clamp`` — and because consecutive
    -1 vpages repeat the same physical block, the Pallas pipeline elides the
    redundant DMA, so per-iteration KV HBM traffic scales with the window,
    not ``gen_length``.  A page straddling the horizon stays mapped (its
    beyond-limit positions are still position-masked), so the view only
    drops pages that contribute nothing.  Scatters keep the ORIGINAL table:
    beyond-window writes land on real pages but are rewritten by the next
    block's full prefill before any read can see them.  ``limit=None`` is
    the identity."""
    if limit is None:
        return block_tables
    n_vp = block_tables.shape[1]
    starts = jnp.arange(n_vp, dtype=jnp.int32) * page_size
    return jnp.where(starts[None, :] < limit[:, None], block_tables, -1)


def _flash_kernel(
    qpos_ref,   # [1, bq, 1] int32
    kvpos_ref,  # [1, 1, bk] int32
    q_ref,      # [1, 1, bq, D]
    k_ref,      # [1, 1, bk, D]
    v_ref,      # [1, 1, bk, D]
    o_ref,      # [1, 1, bq, D]
    acc_ref,    # VMEM [bq, D] f32
    m_ref,      # VMEM [bq, 1] f32
    l_ref,      # VMEM [bq, 1] f32
    *,
    scale: float,
    window: int,
    anchor: int,
    causal: bool,
    bc_start: int,
    bc_block: int,
    n_kv_blocks: int,
):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # [bq, D]
    k = k_ref[0, 0].astype(jnp.float32)          # [bk, D]
    v = v_ref[0, 0].astype(jnp.float32)          # [bk, D]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                     # [bq, bk]

    qp = qpos_ref[0]                              # [bq, 1]
    kp = kvpos_ref[0]                             # [1, bk]
    mask = kp >= 0
    if causal:
        mask &= kp <= qp
    if window > 0:
        win = jnp.abs(qp - kp) <= window
        if anchor > 0:
            win |= kp < anchor
        mask &= win
    if bc_block > 0:
        # block-causal: prompt rows (pos < bc_start) are block -1, generation
        # position p is block (p - bc_start) // bc_block; a query attends
        # only its own and earlier blocks.  bc_block == 0 compiles this out.
        qb = jnp.where(qp >= bc_start, (qp - bc_start) // bc_block, -1)
        kb = jnp.where(kp >= bc_start, (kp - bc_start) // bc_block, -1)
        mask &= kb <= qb
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                           # [bq, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m_prev - m_new)                # [bq, 1]
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0, :, :] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention_kernel(
    q: jax.Array,        # [B, Hq, Lq, D]   (Lq % block_q == 0, D % 128 == 0)
    k: jax.Array,        # [B, Hkv, Lkv, D] (Lkv % block_kv == 0)
    v: jax.Array,
    q_pos: jax.Array,    # [B, Lq] int32
    kv_pos: jax.Array,   # [B, Lkv] int32
    *,
    window: int = 0,
    anchor: int = 0,
    causal: bool = False,
    bc_start: int = 0,
    bc_block: int = 0,
    softmax_scale: float,
    block_q: int = 128,
    block_kv: int = 512,
    interpret: bool = False,
) -> jax.Array:
    b, hq, lq, d = q.shape
    hkv, lkv = k.shape[1], k.shape[2]
    group = hq // hkv
    assert lq % block_q == 0 and lkv % block_kv == 0 and d % 128 == 0

    n_q_blocks = lq // block_q
    n_kv_blocks = lkv // block_kv
    grid = (b, hq, n_q_blocks, n_kv_blocks)

    kernel = functools.partial(
        _flash_kernel,
        scale=softmax_scale,
        window=window,
        anchor=anchor,
        causal=causal,
        bc_start=bc_start,
        bc_block=bc_block,
        n_kv_blocks=n_kv_blocks,
    )

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, 1), lambda bi, h, qi, ki: (bi, qi, 0)),
            pl.BlockSpec((1, 1, block_kv), lambda bi, h, qi, ki: (bi, 0, ki)),
            pl.BlockSpec((1, 1, block_q, d), lambda bi, h, qi, ki: (bi, h, qi, 0)),
            pl.BlockSpec(
                (1, 1, block_kv, d), lambda bi, h, qi, ki: (bi, h // group, ki, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_kv, d), lambda bi, h, qi, ki: (bi, h // group, ki, 0)
            ),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), lambda bi, h, qi, ki: (bi, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, lq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q_pos[:, :, None], kv_pos[:, None, :], q, k, v)


def paged_flash_attention_kernel(
    q: jax.Array,             # [B, Hq, Lq, D]     (Lq % block_q == 0, D % 128 == 0)
    k_pool: jax.Array,        # [P, Hkv, ps, D]    shared page pool
    v_pool: jax.Array,
    q_pos: jax.Array,         # [B, Lq] int32
    kv_pos: jax.Array,        # [B, n_vpages * ps] int32 (-1 = masked)
    block_tables: jax.Array,  # [B, n_vpages] int32 physical page ids, -1 unmapped
    *,
    window: int = 0,
    anchor: int = 0,
    causal: bool = False,
    bc_start: int = 0,
    bc_block: int = 0,
    softmax_scale: float,
    block_q: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Flash attention over a block-table-addressed KV page pool.

    One grid step per (batch, head, q-tile, virtual page); the K/V
    ``index_map`` reads the prefetched block table to DMA the physical page.
    The kernel body is the dense ``_flash_kernel`` — only the routing differs.
    """
    b, hq, lq, d = q.shape
    num_pages, hkv, ps, dk = k_pool.shape
    group = hq // hkv
    n_vpages = block_tables.shape[1]
    assert dk == d and lq % block_q == 0 and kv_pos.shape[1] == n_vpages * ps

    kernel = functools.partial(
        _flash_kernel,
        scale=softmax_scale,
        window=window,
        anchor=anchor,
        causal=causal,
        bc_start=bc_start,
        bc_block=bc_block,
        n_kv_blocks=n_vpages,
    )

    def _page(bi, h, qi, ki, bt):
        # unmapped entries clamp to the garbage page 0 (reads are masked via
        # kv_pos < 0); repeated indices let the pipeline skip the re-fetch
        return jnp.maximum(bt[bi, ki], 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hq, lq // block_q, n_vpages),
        in_specs=[
            pl.BlockSpec((1, block_q, 1), lambda bi, h, qi, ki, bt: (bi, qi, 0)),
            pl.BlockSpec((1, 1, ps), lambda bi, h, qi, ki, bt: (bi, 0, ki)),
            pl.BlockSpec((1, 1, block_q, d), lambda bi, h, qi, ki, bt: (bi, h, qi, 0)),
            pl.BlockSpec(
                (1, 1, ps, d),
                lambda bi, h, qi, ki, bt: (_page(bi, h, qi, ki, bt), h // group, 0, 0),
            ),
            pl.BlockSpec(
                (1, 1, ps, d),
                lambda bi, h, qi, ki, bt: (_page(bi, h, qi, ki, bt), h // group, 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, d), lambda bi, h, qi, ki, bt: (bi, h, qi, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
    )
    # scalar-prefetch arg order: the kernel body ignores the leading bt ref
    def body(bt_ref, qpos_ref, kvpos_ref, q_ref, k_ref, v_ref, o_ref,
             acc_ref, m_ref, l_ref):
        del bt_ref
        kernel(qpos_ref, kvpos_ref, q_ref, k_ref, v_ref, o_ref,
               acc_ref, m_ref, l_ref)

    return pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, lq, d), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), q_pos[:, :, None], kv_pos[:, None, :],
      q, k_pool, v_pool)
