"""Public, jit-friendly wrappers around the Pallas kernels.

Every op has two interchangeable implementations:

  * ``impl="pallas"`` — the Pallas TPU kernel (``interpret=True`` on CPU so
    the kernel *body* is validated everywhere);
  * ``impl="xla"``    — a memory-sane pure-jnp lowering with identical math
    (chunked online-softmax attention, chunked SSD).  This is what the
    multi-pod dry-run lowers, since Mosaic kernels only compile on real TPUs.

``ref.py`` holds the naive oracles used by the allclose test sweeps.
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import (
    flash_attention_kernel,
    paged_flash_attention_kernel,
    window_block_tables,
)
from repro.kernels.importance import importance_kernel, variation_kernel
from repro.kernels.scatter_kv import (
    fork_pages_kernel,
    paged_scatter_kv_kernel,
    scatter_kv_kernel,
)
from repro.kernels.ssd_scan import ssd_chunk_kernel

Impl = Literal["xla", "pallas"]

NEG_INF = ref.NEG_INF


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def validate_page_lanes(page_size: int, *, interpret: bool | None) -> None:
    """Real-TPU guard for the paged kernels: the kv_pos / page tiles put
    ``page_size`` on the 128-wide lane dimension, so a pool compiled through
    Mosaic needs ``page_size >= 128`` (and a multiple of 128 to avoid
    padding waste).  Interpret mode (CPU tests) is exempt — it runs the
    kernel body without lane tiling.  ``interpret=None`` resolves the same
    way the kernel call sites do: interpret on CPU, compiled elsewhere."""
    if interpret is None:
        interpret = _on_cpu()
    if interpret:
        return
    if page_size < 128 or page_size % 128 != 0:
        raise ValueError(
            f"page_size={page_size} cannot compile for real TPU: the paged "
            f"Pallas kernels tile page_size on the 128-wide lane dimension, "
            f"so it must be a multiple of 128 (>= 128). Use page_size=128 "
            f"(or a larger multiple), or run with interpret=True / "
            f"impl='xla' for small-page CPU testing.")


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def attention(
    q: jax.Array,        # [B, Hq, Lq, D]
    k: jax.Array,        # [B, Hkv, Lkv, D]
    v: jax.Array,
    q_pos: jax.Array,    # [B, Lq] int32
    kv_pos: jax.Array,   # [B, Lkv] int32 (-1 = invalid)
    *,
    window=0,            # static int, or traced scalar (per-layer local:global)
    anchor: int = 0,
    causal: bool = False,
    bc_start: int = 0,   # block-causal: first generation position (static)
    bc_block: int = 0,   # block-causal block length; 0 compiles the mask out
    softmax_scale: float | None = None,
    impl: Impl = "xla",
    block_q: int = 128,
    block_kv: int = 512,
    kv_chunk: int = 1024,
    q_chunk: int = 2048,
    k_scale: jax.Array | None = None,   # [B, Hkv, Lkv]: int8 KV dequant scales
    v_scale: jax.Array | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Rectangular GQA attention with position-based masking.

    When ``k_scale``/``v_scale`` are given, k/v are int8 and dequantized
    *per KV chunk inside the scan* — the bf16 cache never materializes.
    """
    d = q.shape[-1]
    scale = softmax_scale if softmax_scale is not None else 1.0 / (d**0.5)
    if impl == "pallas":
        assert isinstance(window, int), "pallas path needs a static window"
        assert k_scale is None, "int8 KV dequant: XLA path only (for now)"
        return _attention_pallas(
            q, k, v, q_pos, kv_pos,
            window=window, anchor=anchor, causal=causal,
            bc_start=bc_start, bc_block=bc_block, scale=scale,
            block_q=block_q, block_kv=block_kv,
            interpret=_on_cpu() if interpret is None else interpret,
        )
    lq = q.shape[2]
    if lq > q_chunk and lq % q_chunk == 0:
        # tile long query spans: peak live tile is [q_chunk, kv_chunk]
        nq = lq // q_chunk
        qs = jnp.moveaxis(q.reshape(q.shape[0], q.shape[1], nq, q_chunk, d), 2, 0)
        qps = jnp.moveaxis(q_pos.reshape(q_pos.shape[0], nq, q_chunk), 1, 0)

        def one(args):
            qc, qpc = args
            return _attention_xla_chunked(
                qc, k, v, qpc, kv_pos,
                window=window, anchor=anchor, causal=causal,
                bc_start=bc_start, bc_block=bc_block, scale=scale,
                kv_chunk=kv_chunk, k_scale=k_scale, v_scale=v_scale,
            )

        # checkpointed: backward recomputes one q-tile at a time instead of
        # saving every tile's online-softmax accumulators
        out = jax.lax.map(jax.checkpoint(one), (qs, qps))
        return jnp.moveaxis(out, 0, 2).reshape(q.shape)
    return _attention_xla_chunked(
        q, k, v, q_pos, kv_pos,
        window=window, anchor=anchor, causal=causal,
        bc_start=bc_start, bc_block=bc_block, scale=scale,
        kv_chunk=kv_chunk, k_scale=k_scale, v_scale=v_scale,
    )


def _attention_pallas(q, k, v, q_pos, kv_pos, *, window, anchor, causal,
                      bc_start, bc_block, scale, block_q, block_kv, interpret):
    b, hq, lq, d = q.shape
    lkv = k.shape[2]
    bq = min(block_q, _round_up(lq, 8))
    bkv = min(block_kv, _round_up(lkv, 128))
    lq_p = _round_up(lq, bq)
    lkv_p = _round_up(lkv, bkv)
    d_p = _round_up(d, 128)

    qp = jnp.pad(q, ((0, 0), (0, 0), (0, lq_p - lq), (0, d_p - d)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, lkv_p - lkv), (0, d_p - d)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, lkv_p - lkv), (0, d_p - d)))
    qpos_p = jnp.pad(q_pos, ((0, 0), (0, lq_p - lq)))
    kvpos_p = jnp.pad(kv_pos, ((0, 0), (0, lkv_p - lkv)), constant_values=-1)

    out = flash_attention_kernel(
        qp, kp, vp, qpos_p.astype(jnp.int32), kvpos_p.astype(jnp.int32),
        window=window, anchor=anchor, causal=causal,
        bc_start=bc_start, bc_block=bc_block, softmax_scale=scale,
        block_q=bq, block_kv=bkv, interpret=interpret,
    )
    return out[:, :, :lq, :d]


def _attention_xla_chunked(q, k, v, q_pos, kv_pos, *, window, anchor, causal,
                           scale, kv_chunk, bc_start=0, bc_block=0,
                           k_scale=None, v_scale=None):
    """Online-softmax attention scanning KV in chunks (flash math in jnp).

    Never materializes the [Lq, Lkv] score matrix, so prefill at 32k/500k
    lowers with O(Lq * kv_chunk) live memory — this is the HLO the dry-run
    roofline reads.  Grouped-query heads are folded into the query axis:
    the ``group`` query heads of one KV head read its chunk as rows
    ``[g * Lq + i]`` of a ``[B, Hkv, group * Lq, D]`` query, so the chunk
    is never repeated per query head (a repeat that XLA materialises).
    """
    b, hq, lq, d = q.shape
    hkv, lkv = k.shape[1], k.shape[2]
    group = hq // hkv
    use_window = not (isinstance(window, int) and window == 0)

    ck = min(kv_chunk, lkv)
    lkv_p = _round_up(lkv, ck)
    k = jnp.pad(k, ((0, 0), (0, 0), (0, lkv_p - lkv), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, 0), (0, lkv_p - lkv), (0, 0)))
    kv_pos = jnp.pad(kv_pos, ((0, 0), (0, lkv_p - lkv)), constant_values=-1)
    n_chunks = lkv_p // ck

    quant = k_scale is not None
    if quant:
        k_scale = jnp.pad(k_scale, ((0, 0), (0, 0), (0, lkv_p - lkv)))
        v_scale = jnp.pad(v_scale, ((0, 0), (0, 0), (0, lkv_p - lkv)))
        kss = jnp.moveaxis(k_scale.reshape(b, hkv, n_chunks, ck), 2, 0)
        vss = jnp.moveaxis(v_scale.reshape(b, hkv, n_chunks, ck), 2, 0)
    else:
        kss = vss = jnp.zeros((n_chunks, 0), jnp.float32)   # placeholder xs

    # query head j * group + g is row g * Lq + i of KV head j's query
    lg = group * lq
    qf = q.astype(jnp.float32).reshape(b, hkv, lg, d)
    # [n_chunks, B, Hkv, ck, D] etc. — scanned over axis 0
    ks = jnp.moveaxis(k.reshape(b, hkv, n_chunks, ck, d), 2, 0)
    vs = jnp.moveaxis(v.reshape(b, hkv, n_chunks, ck, d), 2, 0)
    ps = jnp.moveaxis(kv_pos.reshape(b, n_chunks, ck), 1, 0)

    qp = jnp.tile(q_pos, (1, group))[:, None, :, None]  # [B,1,group*Lq,1]

    def step(carry, inp):
        m_prev, l_prev, acc = carry
        kc, vc, pc, ksc, vsc = inp                     # [B,Hkv,ck,D], ..., [B,ck]
        if quant:
            # dequantize inside the chunk: int8 rows never materialize wide
            kc = kc.astype(jnp.float32) * ksc[..., None]
            vc = vc.astype(jnp.float32) * vsc[..., None]
        kc = kc.astype(jnp.float32)
        vc = vc.astype(jnp.float32)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kc) * scale
        kp_ = pc[:, None, None, :]
        mask = kp_ >= 0
        if causal:
            mask &= kp_ <= qp
        if use_window:
            win = jnp.abs(qp - kp_) <= window
            if anchor > 0:
                win |= kp_ < anchor
            mask &= win
        if bc_block > 0:
            # block-causal (same term as the Pallas kernel): prompt rows are
            # block -1, generation position p is block (p - bc_start) //
            # bc_block; queries attend own + earlier blocks only
            qb = jnp.where(qp >= bc_start, (qp - bc_start) // bc_block, -1)
            kb = jnp.where(kp_ >= bc_start, (kp_ - bc_start) // bc_block, -1)
            mask &= kb <= qb
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, vc)
        return (m_new, l_new, acc), None

    init = (
        jnp.full((b, hkv, lg), NEG_INF, jnp.float32),
        jnp.zeros((b, hkv, lg), jnp.float32),
        jnp.zeros((b, hkv, lg, d), jnp.float32),
    )
    # checkpoint the chunk body: backward recomputes the [Lq, ck] score tile
    # instead of saving one per chunk (flash-attention recomputation)
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(step), init, (ks, vs, ps, kss, vss))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, hq, lq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Paged attention (block-table-addressed KV pool)
# ---------------------------------------------------------------------------


def gather_pages(
    pool: jax.Array,          # [P, ps, ...] shared page pool
    block_tables: jax.Array,  # [B, n_vpages] int32 page ids, -1 unmapped
) -> jax.Array:
    """Materialize the per-slot dense view ``[B, n_vpages * ps, ...]``.

    Unmapped virtual pages read the garbage page 0 — callers must mask those
    positions (``kv_pos < 0``) before the values can matter.
    """
    p, ps = pool.shape[:2]
    b, n_vp = block_tables.shape
    flat = pool.reshape((p * ps,) + pool.shape[2:])
    base = jnp.maximum(block_tables, 0)[..., None] * ps + jnp.arange(ps, dtype=jnp.int32)
    return jnp.take(flat, base.reshape(b, n_vp * ps), axis=0)


def paged_kv_mask(block_tables: jax.Array, kv_pos: jax.Array, page_size: int) -> jax.Array:
    """Force kv_pos to -1 wherever the virtual page is unmapped."""
    mapped = jnp.repeat(block_tables >= 0, page_size, axis=1)
    return jnp.where(mapped, kv_pos, -1)


def window_kv_clamp(kv_pos: jax.Array, limit: jax.Array | None) -> jax.Array:
    """Sliding active-window cut: force kv_pos to -1 at positions beyond the
    per-row exclusive horizon ``limit [B]`` (``core.schedule.window_limit``).

    Every attention path already masks ``kv_pos < 0`` (padding, unfilled
    rows, unmapped pages), so one clamp at the ``self_attention`` entry makes
    the window identical through the dense XLA path, the chunked lowering,
    and both Pallas kernels — no kernel-body change, and ``limit=None``
    (windowing disabled) is the identity."""
    if limit is None:
        return kv_pos
    return jnp.where(kv_pos < limit[:, None], kv_pos, -1)


def paged_attention(
    q: jax.Array,             # [B, Hq, Lq, D]
    k_pool: jax.Array,        # [P, ps, Hkv, D] shared page pool
    v_pool: jax.Array,
    q_pos: jax.Array,         # [B, Lq] int32
    kv_pos: jax.Array,        # [B, n_vpages * ps] int32 (-1 = invalid)
    block_tables: jax.Array,  # [B, n_vpages] int32 page ids, -1 unmapped
    *,
    page_size: int,
    window=0,
    anchor: int = 0,
    causal: bool = False,
    bc_start: int = 0,
    bc_block: int = 0,
    softmax_scale: float | None = None,
    impl: Impl = "xla",
    block_q: int = 128,
    kv_chunk: int = 1024,
    k_scale: jax.Array | None = None,   # [P, ps, Hkv]: int8 KV dequant scales
    v_scale: jax.Array | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Rectangular GQA attention over a paged KV pool.

    The virtual KV address space is ``n_vpages * page_size`` sequence
    positions; ``block_tables`` maps each slot's virtual page to a physical
    pool page.  Math is identical to :func:`attention` on the gathered dense
    cache — the XLA path literally lowers to that (bit-comparable on CPU),
    the Pallas path walks the block table in the kernel grid so only mapped
    pages move through HBM.
    """
    d = q.shape[-1]
    ps = page_size
    assert k_pool.shape[1] == ps and block_tables.shape[1] * ps == kv_pos.shape[1]
    scale = softmax_scale if softmax_scale is not None else 1.0 / (d**0.5)
    kv_pos = paged_kv_mask(block_tables, kv_pos.astype(jnp.int32), ps)
    if impl == "pallas":
        assert isinstance(window, int), "pallas path needs a static window"
        assert k_scale is None, "int8 KV dequant: XLA path only (for now)"
        return _paged_attention_pallas(
            q, k_pool, v_pool, q_pos, kv_pos, block_tables,
            window=window, anchor=anchor, causal=causal,
            bc_start=bc_start, bc_block=bc_block, scale=scale,
            block_q=block_q,
            interpret=_on_cpu() if interpret is None else interpret,
        )
    # XLA mirror: gather the mapped pages into the per-slot dense layout and
    # reuse the chunked online-softmax lowering — identical math to the dense
    # path, so dense-vs-paged stays bit-comparable in CPU tests.
    k_d = jnp.swapaxes(gather_pages(k_pool, block_tables), 1, 2)   # [B, Hkv, T, D]
    v_d = jnp.swapaxes(gather_pages(v_pool, block_tables), 1, 2)
    ks = vs = None
    if k_scale is not None:
        ks = jnp.swapaxes(gather_pages(k_scale, block_tables), 1, 2)  # [B, Hkv, T]
        vs = jnp.swapaxes(gather_pages(v_scale, block_tables), 1, 2)
    else:
        k_d = k_d.astype(q.dtype)
        v_d = v_d.astype(q.dtype)
    return _attention_xla_chunked(
        q, k_d, v_d, q_pos, kv_pos,
        window=window, anchor=anchor, causal=causal,
        bc_start=bc_start, bc_block=bc_block, scale=scale,
        kv_chunk=kv_chunk, k_scale=ks, v_scale=vs,
    )


def _paged_attention_pallas(q, k_pool, v_pool, q_pos, kv_pos, block_tables, *,
                            window, anchor, causal, bc_start, bc_block, scale,
                            block_q, interpret):
    b, hq, lq, d = q.shape
    ps = k_pool.shape[1]
    assert ps % 8 == 0, "page_size must be a multiple of 8 for the TPU kernel"
    validate_page_lanes(ps, interpret=interpret)
    bq = min(block_q, _round_up(lq, 8))
    lq_p = _round_up(lq, bq)
    d_p = _round_up(d, 128)

    qp = jnp.pad(q, ((0, 0), (0, 0), (0, lq_p - lq), (0, d_p - d)))
    # pool layout for the kernel: [P, Hkv, ps, D]
    kp = jnp.pad(jnp.swapaxes(k_pool, 1, 2), ((0, 0), (0, 0), (0, 0), (0, d_p - d)))
    vp = jnp.pad(jnp.swapaxes(v_pool, 1, 2), ((0, 0), (0, 0), (0, 0), (0, d_p - d)))
    qpos_p = jnp.pad(q_pos, ((0, 0), (0, lq_p - lq)))

    out = paged_flash_attention_kernel(
        qp, kp.astype(qp.dtype), vp.astype(qp.dtype),
        qpos_p.astype(jnp.int32), kv_pos.astype(jnp.int32),
        block_tables.astype(jnp.int32),
        window=window, anchor=anchor, causal=causal,
        bc_start=bc_start, bc_block=bc_block, softmax_scale=scale,
        block_q=bq, interpret=interpret,
    )
    return out[:, :, :lq, :d]


# ---------------------------------------------------------------------------
# SSD (Mamba-2)
# ---------------------------------------------------------------------------


def ssd(
    x: jax.Array,       # [B, L, H, P]
    dt: jax.Array,      # [B, L, H] positive
    a_log: jax.Array,   # [H]
    bmat: jax.Array,    # [B, L, G, N]
    cmat: jax.Array,    # [B, L, G, N]
    *,
    chunk: int = 64,
    init_state: jax.Array | None = None,    # [B, H, N, P] f32
    impl: Impl = "xla",
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Chunked SSD scan.  Returns (y [B,L,H,P], final_state [B,H,N,P])."""
    b, l, h, p = x.shape
    g, n = bmat.shape[2], bmat.shape[3]
    ck = min(chunk, l) if l % min(chunk, l) == 0 else chunk
    l_p = _round_up(l, ck)
    pad = l_p - l
    if pad:
        # dt=0 rows are exact no-ops: decay=exp(0)=1, contrib=0
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        bmat = jnp.pad(bmat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cmat = jnp.pad(cmat, ((0, 0), (0, pad), (0, 0), (0, 0)))

    if impl == "pallas":
        y_intra, contrib, decay, cs = ssd_chunk_kernel(
            x, dt, a_log, bmat, cmat, chunk=ck,
            interpret=_on_cpu() if interpret is None else interpret,
        )
    else:
        y_intra, contrib, decay, cs = _ssd_chunks_xla(x, dt, a_log, bmat, cmat, chunk=ck)

    nc = l_p // ck
    if init_state is None:
        init_state = jnp.zeros((b, h, n, p), jnp.float32)

    # inter-chunk state recurrence: S_{c} = decay_c * S_{c-1} + contrib_c
    def combine(left, right):
        d1, s1 = left
        d2, s2 = right
        return d1 * d2, s2 + d2[..., None, None] * s1

    decay_t = jnp.moveaxis(decay, 1, 0)                    # [nC, B, H]
    contrib_t = jnp.moveaxis(contrib, 1, 0)                # [nC, B, H, N, P]
    # fold the initial state into the first chunk's contribution
    contrib_t = contrib_t.at[0].add(decay_t[0][..., None, None] * init_state)
    _, states = jax.lax.associative_scan(combine, (decay_t, contrib_t))
    final_state = states[-1]                               # [B, H, N, P]
    # state *entering* chunk c
    s_in = jnp.concatenate([init_state[None], states[:-1]], axis=0)  # [nC,B,H,N,P]
    s_in = jnp.moveaxis(s_in, 0, 1)                        # [B, nC, H, N, P]

    heads_per_group = h // g
    cm = jnp.repeat(cmat, heads_per_group, axis=2)         # [B, L_p, H, N]
    cm = cm.reshape(b, nc, ck, h, n) * jnp.exp(cs).reshape(b, nc, ck, h)[..., None]
    y_inter = jnp.einsum("bcqhn,bchnp->bcqhp", cm.astype(jnp.float32), s_in)
    y = y_intra.astype(jnp.float32) + y_inter.reshape(b, l_p, h, p)
    return y[:, :l].astype(x.dtype), final_state


def _ssd_chunks_xla(x, dt, a_log, bmat, cmat, *, chunk):
    """Scan-over-chunks jnp version of the Pallas chunk kernel.

    Scanning (with a checkpointed body) keeps only ONE [Q, Q] decay/score
    tile live at a time — the vectorized form materializes [B, nC, Q, Q, H]
    (17 GiB/device for mamba2 at train_4k) and sinks the compile."""
    b, l, h, p = x.shape
    g, n = bmat.shape[2], bmat.shape[3]
    nc = l // chunk
    hpg = h // g
    a = -jnp.exp(a_log.astype(jnp.float32))                # [H]
    row = jnp.arange(chunk)[:, None]
    col = jnp.arange(chunk)[None, :]
    tri = row >= col                                       # [Q, Q]

    # [nC, B, Q, ...] scan layout
    xr = jnp.moveaxis(x.reshape(b, nc, chunk, h, p), 1, 0).astype(jnp.float32)
    dtr = jnp.moveaxis(dt.reshape(b, nc, chunk, h), 1, 0).astype(jnp.float32)
    br = jnp.moveaxis(bmat.reshape(b, nc, chunk, g, n), 1, 0).astype(jnp.float32)
    cr = jnp.moveaxis(cmat.reshape(b, nc, chunk, g, n), 1, 0).astype(jnp.float32)

    def one_chunk(_, inp):
        xc, dtc, bc, cc = inp                              # [B,Q,H,P], [B,Q,H], [B,Q,G,N] x2
        bc = jnp.repeat(bc, hpg, axis=2)                   # [B,Q,H,N]
        cc = jnp.repeat(cc, hpg, axis=2)
        da = dtc * a                                       # [B,Q,H]
        cs = jnp.cumsum(da, axis=1)
        lmat = jnp.where(
            tri[None, :, :, None],
            jnp.exp(cs[:, :, None, :] - cs[:, None, :, :]),
            0.0,
        )                                                  # [B,Q,Q,H]
        scores = jnp.einsum("bqhn,bkhn->bqkh", cc, bc) * lmat
        xdt = xc * dtc[..., None]                          # [B,Q,H,P]
        y_intra = jnp.einsum("bqkh,bkhp->bqhp", scores, xdt)
        bscale = bc * jnp.exp(cs[:, -1:, :] - cs)[..., None]
        contrib = jnp.einsum("bqhn,bqhp->bhnp", bscale, xdt)
        decay = jnp.exp(cs[:, -1, :])                      # [B, H]
        return None, (y_intra, contrib, decay, cs)

    _, (y_intra, contrib, decay, cs) = jax.lax.scan(
        jax.checkpoint(one_chunk), None, (xr, dtr, br, cr)
    )
    return (
        jnp.moveaxis(y_intra, 0, 1).reshape(b, l, h, p),
        jnp.moveaxis(contrib, 0, 1),                       # [B, nC, H, N, P]
        jnp.moveaxis(decay, 0, 1),                         # [B, nC, H]
        jnp.moveaxis(cs, 0, 1).reshape(b, l, h),
    )


# ---------------------------------------------------------------------------
# Scatter cache update
# ---------------------------------------------------------------------------


def scatter_rows(
    cache: jax.Array,   # [B, S, ...]
    new: jax.Array,     # [B, K, ...]
    idx: jax.Array,     # [B, K] int32
    *,
    row_mask: jax.Array | None = None,   # [B] bool: False rows scatter no-ops
    token_mask: jax.Array | None = None,  # [B, K] bool: False tokens keep cache
    impl: Impl = "xla",
    interpret: bool | None = None,
) -> jax.Array:
    """cache[b, idx[b, k]] = new[b, k] (per-batch row scatter).

    ``row_mask`` (mixed-mode cadence) turns unowned rows' updates into exact
    no-ops by replacing their fresh values with the carried cache rows — a
    gather-merge on the ``[B, K, ...]`` update, far cheaper than selecting
    over the whole cache, and it works unchanged through the Pallas kernel.
    ``token_mask`` (adaptive feature cache) is the same drain one axis finer:
    gated-out tokens of otherwise-owned rows keep their cached values, making
    the masked scatter the partial-update mechanism of variation-gated
    refresh.  The two masks compose (a token is written iff both pass).
    """
    if row_mask is not None or token_mask is not None:
        b, k = idx.shape
        keep = jnp.ones((b, k), bool)
        if row_mask is not None:
            keep &= row_mask[:, None]
        if token_mask is not None:
            keep &= token_mask
        old = jnp.take_along_axis(
            cache.reshape(b, cache.shape[1], -1), idx[..., None], axis=1)
        new = jnp.where(keep[..., None],
                        new.reshape(b, k, -1).astype(cache.dtype),
                        old).reshape(new.shape).astype(new.dtype)
    if impl == "pallas":
        shape = cache.shape
        c4 = cache.reshape(shape[0], shape[1], 1, -1) if cache.ndim != 4 else cache
        n4 = new.reshape(new.shape[0], new.shape[1], 1, -1) if new.ndim != 4 else new
        out = scatter_kv_kernel(
            c4, n4, idx, interpret=_on_cpu() if interpret is None else interpret
        )
        return out.reshape(shape)
    return ref.scatter_kv_reference(
        cache.reshape(cache.shape[0], cache.shape[1], -1),
        new.reshape(new.shape[0], new.shape[1], -1),
        idx,
    ).reshape(cache.shape)


def scatter_rows_paged(
    pool: jax.Array,          # [P, ps, ...] shared page pool
    new: jax.Array,           # [B, K, ...]
    idx: jax.Array,           # [B, K] int32 absolute sequence positions
    block_tables: jax.Array,  # [B, n_vpages] int32 page ids, -1 unmapped
    *,
    page_size: int,
    row_mask: jax.Array | None = None,   # [B] bool: False rows -> garbage page
    token_mask: jax.Array | None = None,  # [B, K] bool: False tokens keep pool
    impl: Impl = "xla",
    interpret: bool | None = None,
) -> jax.Array:
    """pool[bt[b, idx//ps], idx%ps] = new[b, k] (block-table row scatter).

    Rows whose virtual page is unmapped (bt < 0) land on the reserved garbage
    page 0 — never read back because readers mask ``kv_pos < 0`` there.
    ``row_mask`` (mixed-mode cadence) reuses exactly that drain: unowned
    rows see an all-unmapped WRITE view of their block-table row, so both
    the XLA and the Pallas lowering drop them without a new code path.
    ``token_mask`` (adaptive feature cache) gates individual tokens of
    owned rows: gated-out tokens gather their current pool content and write
    it straight back — an exact no-op through either lowering — so a partial
    refresh scatters only the variation-gated subset."""
    ps = page_size
    assert pool.shape[1] == ps
    if row_mask is not None:
        block_tables = jnp.where(row_mask[:, None], block_tables, -1)
    if token_mask is not None:
        b, k = idx.shape
        page = jnp.take_along_axis(block_tables, idx // ps, axis=1)   # [B, K]
        src = jnp.maximum(page, 0) * ps + idx % ps
        flat = pool.reshape((pool.shape[0] * ps, -1))
        old = jnp.take(flat, src.reshape(-1), axis=0).reshape(b, k, -1)
        new = jnp.where(token_mask[..., None],
                        new.reshape(b, k, -1).astype(flat.dtype),
                        old).reshape(new.shape).astype(new.dtype)
    if impl == "pallas":
        validate_page_lanes(ps, interpret=interpret)
        shape = pool.shape
        p4 = pool.reshape(shape[0], shape[1], 1, -1) if pool.ndim != 4 else pool
        n4 = new.reshape(new.shape[0], new.shape[1], 1, -1) if new.ndim != 4 else new
        out = paged_scatter_kv_kernel(
            p4, n4.astype(p4.dtype), idx, block_tables,
            interpret=_on_cpu() if interpret is None else interpret,
        )
        return out.reshape(shape)
    b, k = idx.shape
    page = jnp.take_along_axis(block_tables, idx // ps, axis=1)       # [B, K]
    dest = jnp.maximum(page, 0) * ps + idx % ps                       # flat pool rows
    flat = pool.reshape((pool.shape[0] * ps, -1))
    upd = new.reshape(b * k, -1).astype(flat.dtype)
    return flat.at[dest.reshape(-1)].set(upd).reshape(pool.shape)


def fork_pages(
    pool: jax.Array,          # [G, P, ps, ...] layer-group-stacked page pool
    src: jax.Array,           # [F] int32 physical source pages
    dst: jax.Array,           # [F] int32 physical destination pages
    *,
    impl: Impl = "xla",
    interpret: bool | None = None,
) -> jax.Array:
    """Copy-on-write page fork: ``pool[:, dst[f]] = pool[:, src[f]]``.

    The CoW half of prefix page sharing: when a slot holding a read-only
    (refcount > 1) page is about to receive a scatter, the scheduler forks the
    page onto a fresh one from the free list and repoints the slot's block
    table — the sharer keeps the original.  ``src[f] == dst[f]`` pairs are
    exact no-ops (the scheduler pads fork lists with ``(0, 0)``, the garbage
    page onto itself, to keep jitted shapes stable).  A real destination page
    never appears as a source in the same call — fresh pages come off the
    free list — so the in-place alias is race-free.

    Works on any pool-plane rank: K/V planes ``[G, P, ps, Hkv, Dh]`` and int8
    scale planes ``[G, P, ps, Hkv]`` are both flattened to ``[G, P, ps, M]``
    for the kernel and restored.
    """
    g, p, ps = pool.shape[:3]
    assert src.shape == dst.shape and src.ndim == 1
    if impl == "pallas":
        validate_page_lanes(ps, interpret=interpret)
        p4 = pool.reshape(g, p, ps, -1)
        out = fork_pages_kernel(
            p4, src, dst,
            interpret=_on_cpu() if interpret is None else interpret,
        )
        return out.reshape(pool.shape)
    # XLA mirror: gather the source pages, scatter onto the destinations.
    # Duplicate (0, 0) no-op pads write identical content, so scatter order
    # cannot matter — bit-comparable to the kernel.
    return pool.at[:, dst].set(pool[:, src])


# ---------------------------------------------------------------------------
# Importance score (Eq. 1)
# ---------------------------------------------------------------------------


def importance_score(
    h_new: jax.Array,   # [B, K, d]
    h_old: jax.Array,   # [B, K, d]
    conf: jax.Array,    # [B, K]
    *,
    alpha: float,
    eps: float = 1e-8,
    impl: Impl = "xla",
    interpret: bool | None = None,
) -> jax.Array:
    if impl == "pallas":
        return importance_kernel(
            h_new, h_old, conf, alpha=alpha, eps=eps,
            interpret=_on_cpu() if interpret is None else interpret,
        )
    return ref.importance_reference(h_new, h_old, conf, alpha, eps)


def variation_score(
    h_new: jax.Array,   # [B, K, d]
    h_old: jax.Array,   # [B, K, d]
    conf: jax.Array,    # [B, K]
    *,
    alpha: float,
    eps: float = 1e-8,
    impl: Impl = "xla",
    interpret: bool | None = None,
) -> jax.Array:
    """Adaptive-cache refresh priority: alpha*conf + (1-alpha)*(1-cosine)."""
    if impl == "pallas":
        return variation_kernel(
            h_new, h_old, conf, alpha=alpha, eps=eps,
            interpret=_on_cpu() if interpret is None else interpret,
        )
    return ref.variation_reference(h_new, h_old, conf, alpha, eps)


__all__ = [
    "attention",
    "paged_attention",
    "gather_pages",
    "paged_kv_mask",
    "window_kv_clamp",
    "window_block_tables",
    "validate_page_lanes",
    "ssd",
    "scatter_rows",
    "scatter_rows_paged",
    "fork_pages",
    "importance_score",
    "variation_score",
]
