"""Diffusion-LLM generation engines: vanilla, DualCache, and ES-dLLM.

All three share the block semi-autoregressive loop (LLaDA §3): the output is
generated block by block; within a block, a ``lax.while_loop`` runs denoising
iterations until every position is unmasked.  Shapes are fully static — the
active-set sizes per segment come from the (static) skip schedule — so one
compiled program serves every iteration and every block.

Engine modes
------------
* ``vanilla``   — full-sequence forward every iteration, no caches.
* ``dualcache`` — Fast-dLLM DualCache: out-of-block KV cached; each iteration
                  recomputes only the current block (Q=block, KV=cache).
* ``es``        — the paper: DualCache + early-skip.  At each skip stage the
                  active set shrinks to the top-k rows by importance (Eq. 1);
                  K/V/hidden/confidence caches are partially scatter-updated
                  for computed rows only (Alg. 1), with periodic prompt/block
                  refreshes (Table 5) bounding error accumulation.

Slot-based serving state
------------------------
All per-block progress is slot-addressable: the block offset ``bs`` is a
per-row ``[B]`` vector (a scalar is broadcast for the offline path), so
different batch rows may sit on different blocks of their own requests.
``EngineState`` extends the per-block caches with per-slot counters and an
``active`` mask; ``step()`` is ONE jitted program that advances every slot by
one denoising iteration regardless of which slots are prefilling, decoding,
or idle.

The within-block cadence is per-row too: ``EngineState.phase`` is a ``[B]``
vector, and every ``step()`` resolves each row's mode (prompt refresh /
block refresh / skip decode / idle) from its own phase
(``core.schedule.branch_index``).  The step executes up to three fused
sub-programs — a skip-decode pass, a block-refresh pass, and a full-sequence
prefill pass, each ``lax.cond``-gated on "any row in this mode" — with
per-row masks: a pass's cache scatters are dropped for rows it does not own
(dense: write-back of the carried row; paged: the write view of the block
table is forced to -1 so the scatter clamps to the garbage page), and its
confidence/prediction/indicator/kv_valid outputs merge per row.  Rows
therefore progress at their own denoising rate: a row whose block fully
unmasks can advance ``bs`` immediately (``early_advance=True``) instead of
idling to a shared boundary, and a freshly admitted row enters in prefill
mode (phase 0) on ANY iteration.  Per-request outputs are bit-identical to
the block-aligned cadence: post-completion idle iterations never changed
``tokens``/``kv_valid``, and the next block's prefill rebuilds every other
cache from those, so early advance only removes dead time (the lifetime
iteration counter jumps to ``blocks_done * steps_per_block`` at advance,
exactly the offline ``generate()`` numbering).

The mask token occupies the first padded-vocab slot (id == vocab_size), so it
is embeddable but never sampled.

Paged KV cache
--------------
With ``paged=True`` the self-attention KV caches become ONE pool
``[G, num_pages, page_size, Hkv, Dh]`` shared by every slot, addressed
through a per-slot block table ``EngineState.block_tables [B, T/page_size]``
(-1 = unmapped; page 0 is the reserved garbage page that unmapped reads and
writes clamp to).  Slot count is thereby decoupled from worst-case sequence
length: the scheduler admits on page availability, short requests map only
the pages they need, and per-slot ``prompt_start`` keeps pad prompt rows out
of attention (``kv_pos < 0``) and out of the pool (pad-only pages are never
mapped).  The offline ``generate()`` path uses an identity block table, and
the XLA paged lowering is bit-identical to the dense path, so dense-vs-paged
greedy outputs agree token for token.

Memory manager v2 hooks (docs/ARCHITECTURE.md has the full contract):

* **Sticky sparse eviction** — ``kv_valid`` is carried across refreshes and
  blocks (serving already did; ``generate()`` threads it through the block
  loop), and a prompt/block refresh can only *shrink* the retained set
  outside the current block: ``kv_valid' = evict(...) & (kv_valid |
  in_block)``.  Evicted rows are dead for the rest of the request, which is
  what lets the scheduler return fully-dead *pages* to the free list
  (``dead_page_report``) instead of leaving them masked-but-resident — an
  unmapped page and a masked row are indistinguishable to every reader.
* **Copy-on-write fork** — ``fork_pages`` copies physical pages inside every
  KV pool plane (``ops.fork_pages``); the scheduler calls it right before a
  refresh would scatter diverged content into a page shared by more than one
  slot (refcount > 1 ⇒ read-only).

Sampling under continuous batching draws with a per-row key chain
``fold_in(fold_in(base_key, sample_seed[b]), slot_iters[b])`` — a request's
stream depends only on its own seed and progress, so sampled generation is
bit-equal to its offline replay regardless of co-resident traffic, while
distinct rows (e.g. duplicate prompts) still sample independently.
"""
from __future__ import annotations

import functools
import math
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import GenerationConfig, ModelConfig
from repro.core import sampler as smp
from repro.core.schedule import (
    Segment,
    branch_index as resolve_branch_index,
    full_refresh_pred as resolve_full_refresh_pred,
    invariant_limit as resolve_invariant_limit,
    prompt_refresh_pred as resolve_refresh_pred,
    resolve_segments,
    window_limit as resolve_window_limit,
)
from repro.kernels import ops
from repro.models.model import ForwardCtx, Model

NEG_INF = -1e30


class BlockState(NamedTuple):
    tokens: jax.Array       # [B, T]
    caches: Any             # model caches ((), for vanilla)
    conf: jax.Array         # [B, Lb]  confidence cache
    pred: jax.Array         # [B, Lb]  predicted-token cache
    hidden: tuple           # per skip stage: [B, Lb, d] indicator cache
    kv_valid: jax.Array     # [B, T] bool — sparse-attention retention mask
    t: jax.Array            # iteration counter within the block
    key: jax.Array
    # adaptive feature cache (None unless gen.adaptive_cache): cached
    # probe-layer hidden states and last-observed per-token confidence —
    # the inputs of the variation-gated partial-refresh predicate
    feat: Optional[jax.Array] = None       # [B, T, d] f32
    conf_full: Optional[jax.Array] = None  # [B, T] f32


class EngineState(NamedTuple):
    """Slot-addressable serving state: BlockState fields + per-slot progress.

    Every per-request quantity is a ``[B]`` vector indexed by slot —
    including the within-block iteration ``phase``: each row resolves its
    own prefill/refresh/skip mode per step (mixed-mode cadence), so rows
    may sit on different blocks AND different iterations of those blocks.
    """
    tokens: jax.Array        # [B, T]
    caches: Any
    conf: jax.Array          # [B, Lb]
    pred: jax.Array          # [B, Lb]
    hidden: tuple
    kv_valid: jax.Array      # [B, T]
    bs: jax.Array            # [B] per-slot block offset (start of current block)
    blocks_left: jax.Array   # [B] blocks not yet completed (incl. current)
    phase: jax.Array         # [B] per-slot within-block iteration phase
    iters: jax.Array         # [B] per-slot lifetime iteration counter
    active: jax.Array        # [B] bool — slot holds a live request
    key: jax.Array
    prompt_start: jax.Array  # [B] first real (non-pad) prompt position
    sample_seeds: jax.Array  # [B] per-request sampling seed (folded into key)
    block_tables: Optional[jax.Array] = None  # [B, T/page_size] paged-KV map
    # adaptive feature cache (None / zeros unless gen.adaptive_cache)
    feat: Optional[jax.Array] = None          # [B, T, d] cached probe features
    conf_full: Optional[jax.Array] = None     # [B, T] last-observed confidence
    cache_refreshed: Optional[jax.Array] = None  # [B] cumulative tokens refreshed
    cache_eligible: Optional[jax.Array] = None   # [B] cumulative eligible tokens
    # poison detector plane: sticky per-row flag set the moment a step
    # produces any non-finite confidence/hidden/feature value for an active
    # row.  The scheduler quarantines flagged rows host-side (typed
    # PoisonedRequest, slot reset, pages scrubbed + freed) and clears the
    # flag.  None only for hand-built states (offline paths never read it).
    poisoned: Optional[jax.Array] = None      # [B] bool


def _row_scatter(buf: jax.Array, new: jax.Array, idx: jax.Array) -> jax.Array:
    """buf[b, idx[b, k]] = new[b, k] for 2-D/3-D row buffers."""
    return jax.vmap(lambda c, n, i: c.at[i].set(n.astype(c.dtype)))(buf, new, idx)


def _row_gather(buf: jax.Array, idx: jax.Array) -> jax.Array:
    if buf.ndim == 2:
        return jnp.take_along_axis(buf, idx, axis=1)
    return jnp.take_along_axis(buf, idx[..., None], axis=1)


class DiffusionEngine:
    def __init__(
        self,
        model: Model,
        gen: GenerationConfig,
        *,
        attn_impl: str = "xla",
        window_override: int = 0,
        anchor: int = 0,
        eos_id: int = 2,
        disallow_eos: bool = False,
        importance_impl: str = "xla",
        act_sharding=None,
        cache_shardings=None,
        kv_cache_dtype: str | None = None,   # 'int8' => quantized KV cache
        moe_sharding=None,
        inner_sharding=None,
        paged: bool = False,                 # paged KV pool + block tables
        page_size: int = 16,                 # tokens per KV page (paged only)
        kv_pages: int | None = None,         # pool pages incl. garbage page 0;
                                             # None => dense-equivalent sizing
        early_advance: bool = False,         # serving: advance a row's block
                                             # the moment it fully unmasks
                                             # (else: shared-boundary advance)
    ):
        self.model = model
        self.cfg = model.cfg
        self.gen = gen
        self.attn_impl = attn_impl
        self.window_override = window_override
        self.anchor = anchor
        self.eos_id = eos_id
        self.disallow_eos = disallow_eos
        self.importance_impl = importance_impl
        self.act_sharding = act_sharding
        self.cache_shardings = cache_shardings
        self.kv_cache_dtype = kv_cache_dtype
        self.moe_sharding = moe_sharding
        self.inner_sharding = inner_sharding
        self.paged = paged
        self.page_size = page_size if paged else 0
        self.kv_pages = kv_pages
        self.early_advance = early_advance
        if paged:
            assert gen.mode != "vanilla", "paged KV needs a cached engine mode"
            assert page_size > 0
            if attn_impl == "pallas":
                # fail at construction, not deep inside a trace: the TPU
                # kv_pos tiles need >= 128 lanes (interpret mode is exempt —
                # ops re-checks at the call site where `interpret` resolves)
                ops.validate_page_lanes(page_size, interpret=None)
        self._jit_run_block = jax.jit(self._run_block)   # compile once, reuse
        # donated state: the KV pool and slot planes update in place instead
        # of being held twice across the step (the scheduler reassigns
        # ``self.state`` with the return value and keeps no other reference)
        self._jit_step = jax.jit(self._engine_step, donate_argnums=(1,))
        # donated pool: the fork updates pages in place instead of copying
        # the whole pool (callers drop the pre-fork state immediately)
        self._jit_fork_kv = jax.jit(self._fork_kv_pools, donate_argnums=(0,))
        # preemption/quarantine page ops share the fork's donation contract
        self._jit_restore_kv = jax.jit(self._restore_kv_pools,
                                       donate_argnums=(0,))
        self._jit_scrub_kv = jax.jit(self._scrub_kv_pools, donate_argnums=(0,))
        self.step_trace_count = 0   # incremented per trace of _engine_step

        self.mask_id = self.cfg.vocab_size          # first padded-vocab slot
        lb = gen.block_length
        if gen.mode == "es":
            self.segments, self.active_sizes = resolve_segments(self.cfg, gen, lb)
        else:
            self.segments = [Segment(0, model.n_groups, None, None)]
            self.active_sizes = [lb]
        self.n_stages = sum(1 for s in self.segments if s.keep_k is not None)
        if gen.sparse_attention:
            assert model.period == 1, "sparse attention: period-1 archs only"
            assert self.n_stages > 0, (
                "sparse attention needs >=1 skip stage as its indicator probe; "
                "use a zero-ratio stage (SkipStage(l, 0.0)) for sparse-only mode"
            )
        self.n_per_step = max(1, -(-lb // gen.resolved_steps()))

        # adaptive cross-iteration feature cache (dLLM-Cache): partial
        # refreshes probe the shallow groups (up to the first skip-stage
        # boundary) over the full sequence, then recompute only the
        # variation-gated token subset through the deep groups.  Gated to
        # attention-only period-1 ES archs — the partial pass reuses the
        # decode-mode cache path, which for SSM/cross layers needs the
        # dense-rejoin machinery the gathered subset cannot provide.
        self.adaptive_cache = gen.adaptive_cache
        if self.adaptive_cache:
            assert gen.mode == "es", "adaptive feature cache: ES engine only"
            assert model.period == 1 and all(
                k == "attn" for k, _ in model.layer_info
            ), "adaptive feature cache: attention-only period-1 archs only"
            assert self.n_stages > 0, (
                "adaptive feature cache needs >=1 skip stage as its probe "
                "boundary; use a zero-ratio stage (SkipStage(l, 0.0))")
            self.cache_probe_groups = self.segments[0].group_hi
        # the serving step's prompt refresh runs one row at a time over the
        # refreshing rows only where every cache is the batch-free paged
        # pool: a row's block-table row then routes its K/V writes in place.
        # Dense KV and cross/SSM caches are batch-major, and keep the masked
        # pass over every slot.
        self.refresh_per_row = paged and all(
            k == "attn" for k, _ in model.layer_info)

    # ------------------------------------------------------------------
    # per-row block indexing
    # ------------------------------------------------------------------
    def _bs_rows(self, bs, b: int) -> jax.Array:
        """Normalize a block offset (scalar or [B]) to a per-row [B] vector."""
        bs = jnp.asarray(bs, jnp.int32)
        if bs.ndim == 0:
            bs = jnp.broadcast_to(bs, (b,))
        return bs

    def _block_cols(self, bs: jax.Array) -> jax.Array:
        """[B] block offsets -> [B, Lb] absolute column indices."""
        lb = self.gen.block_length
        return bs[:, None] + jnp.arange(lb, dtype=jnp.int32)[None]

    # ------------------------------------------------------------------
    # paged-KV + per-row sampling helpers
    # ------------------------------------------------------------------
    def _identity_block_tables(self, b: int, t_total: int) -> jax.Array:
        """Offline layout: slot b owns pages [1 + b*n_vp, 1 + (b+1)*n_vp)."""
        n_vp = t_total // self.page_size
        if self.kv_pages is not None:
            # out-of-range page ids would silently clamp-alias on gather —
            # an explicitly undersized pool must fail loudly offline
            assert b * n_vp + 1 <= self.kv_pages, (
                f"kv_pages={self.kv_pages} cannot hold {b} offline rows of "
                f"{n_vp} pages (+ garbage page)")
        return jnp.arange(1, b * n_vp + 1, dtype=jnp.int32).reshape(b, n_vp)

    def _row_args(self, st: BlockState, bs) -> tuple:
        """Default (iters, seeds, prompt_start, block_tables) for standalone
        steps (matches the offline ``generate()`` defaults)."""
        b, t_total = st.tokens.shape
        iters = jnp.broadcast_to(st.t, (b,)).astype(jnp.int32)
        seeds = jnp.arange(b, dtype=jnp.int32)
        prompt_start = jnp.zeros((b,), jnp.int32)
        bt = self._identity_block_tables(b, t_total) if self.paged else None
        return iters, seeds, prompt_start, bt

    def _row_keys(self, key: jax.Array, seeds: jax.Array,
                  iters: jax.Array) -> jax.Array:
        """[B] per-row draw keys: ``fold_in(fold_in(key, seed), iteration)``.

        The seed decorrelates rows (duplicate prompts must sample different
        completions); the lifetime iteration advances the chain.  Both are
        per-REQUEST quantities, so a request's sampling stream is independent
        of co-resident traffic — bit-equal offline replay under continuous
        batching."""
        return jax.vmap(
            lambda s, i: jax.random.fold_in(jax.random.fold_in(key, s), i)
        )(seeds, iters)

    def _window_limit(self, bs) -> Optional[jax.Array]:
        """[B] exclusive sliding-window horizon for rows at block offset
        ``bs`` (``core.schedule.window_limit``), or None when windowing is
        disabled (``window_blocks == 0``) so the clamp is compiled out and
        the program is structurally identical to the unwindowed engine.
        Every step derives the horizon from the row's own ``bs``, so the
        offline block loop, the mixed-mode serving step, and its one-row
        prompt refresh (which slices ``bs``) share one truth."""
        return resolve_window_limit(self.gen, bs)

    def _bc_args(self, t_total: int) -> dict:
        """Static block-causal mask parameters for a sequence of ``t_total``
        positions: the generation region starts at ``t_total - gen_length``
        (the padded prompt end — a trace-time constant for both the offline
        block loop and the fixed-shape serving state), and blocks are
        ``block_length`` wide.  ``bc_block == 0`` (bidirectional mode)
        compiles the mask term out of every attention lowering."""
        gen = self.gen
        if not gen.block_causal:
            return {}
        return {"bc_start": t_total - gen.gen_length,
                "bc_block": gen.block_length}

    def _invariant_limit(self, bs, iters, t_total: int) -> Optional[jax.Array]:
        """[B] exclusive FULL-refresh write horizon under block-causal
        attention (``core.schedule.invariant_limit``), or None when the mode
        is off so the refresh token mask is compiled out."""
        gen = self.gen
        return resolve_invariant_limit(gen, bs, iters,
                                       t_total - gen.gen_length)

    def _kv_pos(self, kv_valid, prompt_start) -> jax.Array:
        """[B, T] cache-validity positions: -1 for sparse-evicted rows and
        pad prompt rows (pos < prompt_start).  Unmapped virtual pages are
        masked one level down by ``ops.paged_attention`` (the single owner
        of the block-table invariant)."""
        t_total = kv_valid.shape[1]
        pos = jnp.arange(t_total, dtype=jnp.int32)[None]
        valid = kv_valid & (pos >= prompt_start[:, None])
        return jnp.where(valid, pos, -1)

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------
    def generate(
        self,
        params: dict,
        prompt: jax.Array,             # [B, P] int32
        key: jax.Array,
        enc_embeds: Optional[jax.Array] = None,
        *,
        prompt_start: Optional[jax.Array] = None,   # [B] first real prompt pos
        sample_seeds: Optional[jax.Array] = None,   # [B] per-row sampling seed
    ) -> jax.Array:
        """Generate ``gen.gen_length`` tokens after ``prompt``; returns [B, T].

        ``key`` is the *base* sampling key: every draw uses
        ``fold_in(fold_in(key, sample_seeds[b]), row_lifetime_iteration)``.
        ``sample_seeds`` defaults to the row index (duplicate prompts sample
        distinct completions); pass a request's serving-time seed to replay
        its continuous-batching output exactly.  ``prompt_start`` marks
        per-row pad prefixes to exclude from attention (the serving runtime's
        variable-length-prompt contract)."""
        gen = self.gen
        b, p = prompt.shape
        lb = gen.block_length
        assert gen.gen_length % lb == 0
        n_blocks = gen.gen_length // lb
        tokens = jnp.concatenate(
            [prompt.astype(jnp.int32),
             jnp.full((b, gen.gen_length), self.mask_id, jnp.int32)], axis=1
        )
        enc_out = None
        if enc_embeds is not None:
            enc_out = self.model.encode(params, enc_embeds, self.attn_impl)
        if prompt_start is None:
            prompt_start = jnp.zeros((b,), jnp.int32)
        if sample_seeds is None:
            sample_seeds = jnp.arange(b, dtype=jnp.int32)

        # sparse eviction is sticky across blocks: the retained set only ever
        # shrinks (outside the current block), so kv_valid threads through
        # the block loop exactly as EngineState carries it in serving.  The
        # adaptive feature cache's planes thread the same way (a mid-block
        # partial refresh reads confidences persisted by earlier blocks).
        t_total = p + gen.gen_length
        kv_valid = jnp.ones((b, t_total), bool)
        feat = conf_full = None
        if self.adaptive_cache:
            feat = jnp.zeros((b, t_total, self.cfg.d_model), jnp.float32)
            conf_full = jnp.zeros((b, t_total), jnp.float32)
        # the KV caches carry across blocks, mirroring how EngineState
        # threads them in serving.  Block-causal refreshes depend on it: the
        # invariant exemption leaves positions below the settled horizon
        # unwritten, which is only sound if the carried cache still holds
        # their (final) K/V.  Bidirectional mode is unaffected — its
        # block-entry prefill zeroes and rewrites every position anyway.
        caches = self._init_caches(b, t_total)
        for blk in range(n_blocks):
            bs = jnp.full((b,), p + blk * lb, jnp.int32)
            iters0 = jnp.full((b,), blk * gen.resolved_steps(), jnp.int32)
            tokens, kv_valid, feat, conf_full, caches = self._jit_run_block(
                params, tokens, kv_valid, feat, conf_full, caches, key, bs,
                iters0, sample_seeds, prompt_start, enc_out)
        return tokens

    # ------------------------------------------------------------------
    # per-block loop
    # ------------------------------------------------------------------
    def _run_block(self, params, tokens, kv_valid0, feat0, conf_full0,
                   caches0, key, bs, iters0, seeds, prompt_start, enc_out):
        gen = self.gen
        b, t_total = tokens.shape
        bs = self._bs_rows(bs, b)
        state = self.make_block_state(tokens, key)._replace(
            kv_valid=kv_valid0, feat=feat0, conf_full=conf_full0,
            caches=caches0)
        block_tables = self._identity_block_tables(b, t_total) if self.paged else None
        max_steps = gen.resolved_steps() + 1

        def cond(st: BlockState):
            blk_tok = _row_gather(st.tokens, self._block_cols(bs))
            any_masked = jnp.any(blk_tok == self.mask_id)
            return (st.t == 0) | (any_masked & (st.t < max_steps))

        def body(st: BlockState):
            outs = self._iteration_outputs(
                params, st, bs, enc_out, iters=iters0 + st.t, seeds=seeds,
                prompt_start=prompt_start, block_tables=block_tables)
            return self._apply_unmask(st, bs, *outs)

        state = jax.lax.while_loop(cond, body, state)
        return (state.tokens, state.kv_valid, state.feat, state.conf_full,
                state.caches)

    def _apply_unmask(self, st: BlockState, bs, caches, conf, pred, hidden,
                      kv_valid, feat=None, stats=None,
                      active: Optional[jax.Array] = None):
        gen = self.gen
        bs = self._bs_rows(bs, st.tokens.shape[0])
        cols = self._block_cols(bs)
        blk_tok = _row_gather(st.tokens, cols)
        is_masked = blk_tok == self.mask_id
        sel = smp.select_unmask(conf, is_masked, gen, self.n_per_step)
        if active is not None:
            sel = sel & active[:, None]
        new_blk = jnp.where(sel, pred, blk_tok)
        new_tokens = _row_scatter(st.tokens, new_blk, cols)
        conf_full = st.conf_full
        if self.adaptive_cache:
            # persist the block's freshest confidences at their absolute
            # positions: settled blocks keep their final values, giving past
            # response tokens the confidence term of the refresh priority
            conf_full = _row_scatter(st.conf_full, conf, cols)
        # the base key is never split: draws use fold_in(key, row_iteration),
        # which continuous batching reproduces per slot for bit-equal replay
        return BlockState(new_tokens, caches, conf, pred, hidden,
                          kv_valid, st.t + 1, st.key,
                          st.feat if feat is None else feat, conf_full)

    # ------------------------------------------------------------------
    # standalone steps (serving runtime & multi-pod dry-run)
    # ------------------------------------------------------------------
    def _init_caches(self, b: int, t_total: int):
        """Fresh zeroed model caches for a ``[b, t_total]`` layout (shared
        by ``make_block_state`` and the offline loop's carried-cache init)."""
        if self.gen.mode == "vanilla":
            return ()
        kv_pages = 0
        if self.paged:
            assert t_total % self.page_size == 0, (
                f"page_size {self.page_size} must divide the sequence {t_total}")
            # default pool: dense-equivalent (+ the reserved garbage page 0);
            # the serving scheduler passes a smaller kv_pages to oversubscribe
            kv_pages = self.kv_pages or b * (t_total // self.page_size) + 1
        return self.model.init_cache(
            b, t_total, self.gen.block_length, kv_dtype=self.kv_cache_dtype,
            kv_pages=kv_pages, page_size=self.page_size)

    def make_block_state(self, tokens: jax.Array, key: jax.Array) -> BlockState:
        b, t_total = tokens.shape
        lb = self.gen.block_length
        caches = self._init_caches(b, t_total)
        feat = conf_full = None
        if self.adaptive_cache:
            feat = jnp.zeros((b, t_total, self.cfg.d_model), jnp.float32)
            conf_full = jnp.zeros((b, t_total), jnp.float32)
        return BlockState(
            tokens=tokens, caches=caches,
            conf=jnp.zeros((b, lb), jnp.float32),
            pred=jnp.zeros((b, lb), jnp.int32),
            hidden=tuple(jnp.zeros((b, lb, self.cfg.d_model), jnp.float32)
                         for _ in range(self.n_stages)),
            kv_valid=jnp.ones((b, t_total), bool),
            t=jnp.zeros((), jnp.int32), key=key,
            feat=feat, conf_full=conf_full,
        )

    def decode_iteration(self, params, st: BlockState, bs) -> BlockState:
        """ONE steady-state ES iteration (paper Alg. 1): the op the decode
        dry-run shapes lower.  Refresh iterations lower via prefill()."""
        bs = self._bs_rows(bs, st.tokens.shape[0])
        iters, seeds, prompt_start, bt = self._row_args(st, bs)
        out = self._decode_step(params, bs, iters, seeds, prompt_start, bt,
                                st, skip=True)
        return self._apply_unmask(st, bs, *out)

    def prefill(self, params, st: BlockState, bs, enc_out=None) -> BlockState:
        """Cache initialization / prompt refresh as a standalone step."""
        bs = self._bs_rows(bs, st.tokens.shape[0])
        iters, seeds, prompt_start, bt = self._row_args(st, bs)
        out = self._prefill_step(params, bs, iters, seeds, prompt_start, bt,
                                 enc_out, st)
        return self._apply_unmask(st, bs, *out)

    def _iteration_outputs(self, params, st: BlockState, bs, enc_out, *,
                           iters, seeds, prompt_start, block_tables):
        """Branch-dispatched compute for ONE denoising iteration at phase
        ``st.t`` — shared by the offline block loop and the serving step so
        the prefill/refresh/skip cadence can never diverge between them.
        ``iters`` [B] is the per-row lifetime iteration and ``seeds`` [B] the
        per-request sampling seed (together: the draw-key index);
        ``prompt_start`` [B] masks pad prompt rows; ``block_tables`` routes
        the paged KV pool (None = dense).
        Returns ``(caches, conf, pred, hidden, kv_valid, feat, stats)``."""
        b = st.tokens.shape[0]
        zstats = jnp.zeros((b, 2), jnp.int32)
        if self.gen.mode == "vanilla":
            conf, pred, st = self._vanilla_compute(params, st, bs, enc_out,
                                                   iters, seeds)
            return (st.caches, conf, pred, st.hidden, st.kv_valid,
                    st.feat, zstats)
        # all offline rows share one lifetime iteration, so row 0's suffices
        # for the (scalar) switch index — the full/partial refresh split is a
        # function of the lifetime counter, not the phase alone
        branch = self._branch_index(st.t, iters[0])
        branches = [
            functools.partial(self._decode_step, params, bs, iters, seeds,
                              prompt_start, block_tables, skip=True),
            functools.partial(self._decode_step, params, bs, iters, seeds,
                              prompt_start, block_tables, skip=False),
            functools.partial(self._prefill_step, params, bs, iters, seeds,
                              prompt_start, block_tables, enc_out),
        ]
        if self.adaptive_cache:
            # branch 3 exists ONLY with the cache enabled: the disabled
            # engine's program is structurally unchanged (bit-identity)
            branches.append(
                functools.partial(self._partial_refresh_step, params, bs,
                                  iters, seeds, prompt_start, block_tables,
                                  enc_out))
        return jax.lax.switch(branch, branches, st)

    def _prompt_refresh_pred(self, t):
        """Prompt-refresh predicate on a phase ``t`` — works on python ints
        (host-side ``is_prompt_refresh``), numpy arrays (the scheduler's
        per-slot ``prompt_refresh_rows``), and traced arrays
        (``_branch_index``) alike, so there is exactly ONE cadence truth
        (``core.schedule.prompt_refresh_pred``)."""
        return resolve_refresh_pred(self.gen, t)

    def _branch_index(self, t: jax.Array, iters=None) -> jax.Array:
        """Phase -> branch (elementwise: scalar offline, ``[B]`` serving).
        ``iters`` (lifetime counter) splits scheduled refreshes into full
        (2) vs partial (3) when the adaptive feature cache is enabled."""
        return resolve_branch_index(self.gen, t, iters)

    # ------------------------------------------------------------------
    # slot-based continuous serving (runtime.scheduler drives this)
    # ------------------------------------------------------------------
    def init_engine_state(self, batch: int, prompt_len: int,
                          key: jax.Array) -> EngineState:
        """All-idle slot state for a serving loop of ``batch`` slots.

        ``prompt_len`` fixes the (padded) prompt region; the total sequence
        is ``prompt_len + gen_length``.  Idle slots hold mask tokens and an
        ``active=False`` row until the scheduler admits a request.
        """
        t_total = prompt_len + self.gen.gen_length
        tokens = jnp.full((batch, t_total), self.mask_id, jnp.int32)
        bst = self.make_block_state(tokens, key)
        block_tables = None
        if self.paged:
            # all slots start unmapped; the scheduler installs page mappings
            # at admission and clears them when the slot retires
            block_tables = jnp.full(
                (batch, t_total // self.page_size), -1, jnp.int32)
        return EngineState(
            tokens=bst.tokens, caches=bst.caches, conf=bst.conf, pred=bst.pred,
            hidden=bst.hidden, kv_valid=bst.kv_valid,
            bs=jnp.full((batch,), prompt_len, jnp.int32),
            blocks_left=jnp.zeros((batch,), jnp.int32),
            phase=jnp.zeros((batch,), jnp.int32),
            iters=jnp.zeros((batch,), jnp.int32),
            active=jnp.zeros((batch,), bool),
            key=bst.key,
            prompt_start=jnp.zeros((batch,), jnp.int32),
            sample_seeds=jnp.zeros((batch,), jnp.int32),
            block_tables=block_tables,
            feat=bst.feat, conf_full=bst.conf_full,
            cache_refreshed=jnp.zeros((batch,), jnp.int32),
            cache_eligible=jnp.zeros((batch,), jnp.int32),
            poisoned=jnp.zeros((batch,), bool),
        )

    # ------------------------------------------------------------------
    # memory manager v2 hooks (prefix sharing + page-aligned eviction)
    # ------------------------------------------------------------------
    def _fork_kv_pools(self, kv_caches, src, dst):
        impl = "pallas" if self.attn_impl == "pallas" else "xla"
        return jax.tree_util.tree_map(
            lambda pool: ops.fork_pages(pool, src, dst, impl=impl), kv_caches)

    def fork_pages(self, state: EngineState, src, dst) -> EngineState:
        """Copy-on-write fork: physical page ``src[i]`` is copied onto
        ``dst[i]`` in every self-attention KV pool plane (K, V, int8 scales,
        all layer groups).  The scheduler calls this right before a refresh
        would scatter diverged content into a shared (refcount > 1 ⇒
        read-only) page, then repoints the forking slot's block-table row at
        ``dst`` host-side.  The fork list is padded to a multiple of 8 with
        ``(0, 0)`` no-ops (garbage page onto itself) so the jitted copy
        program is shape-stable; the pool is donated, so the copy is
        genuinely in place — callers must drop the pre-fork state (the
        scheduler reassigns ``self.state`` with the return value)."""
        assert self.paged, "fork_pages is a paged-pool operation"
        src = np.asarray(src, np.int32).ravel()
        dst = np.asarray(dst, np.int32).ravel()
        assert src.shape == dst.shape
        if src.size == 0:
            return state
        pad = -(-src.size // 8) * 8 - src.size
        src = np.concatenate([src, np.zeros(pad, np.int32)])
        dst = np.concatenate([dst, np.zeros(pad, np.int32)])
        caches = dict(state.caches)
        caches["kv"] = self._jit_fork_kv(
            state.caches["kv"], jnp.asarray(src), jnp.asarray(dst))
        return state._replace(caches=caches)

    # ------------------------------------------------------------------
    # preemption spill/resume + quarantine page ops (failure handling,
    # docs/ARCHITECTURE.md §5a)
    # ------------------------------------------------------------------
    def _restore_kv_pools(self, kv_caches, pages, data):
        return jax.tree_util.tree_map(
            lambda pool, d: pool.at[:, pages].set(d.astype(pool.dtype)),
            kv_caches, data)

    def _scrub_kv_pools(self, kv_caches, pages):
        return jax.tree_util.tree_map(
            lambda pool: pool.at[:, pages].set(
                jnp.zeros((), pool.dtype)), kv_caches)

    def _pad_pages(self, pages) -> np.ndarray:
        """Pad a physical-page list to a multiple of 8 with garbage-page
        (0) no-ops so the jitted scatter programs stay shape-stable —
        exactly the ``fork_pages`` convention."""
        pages = np.asarray(pages, np.int32).ravel()
        pad = -(-pages.size // 8) * 8 - pages.size
        return np.concatenate([pages, np.zeros(pad, np.int32)])

    def spill_pages(self, state: EngineState, pages):
        """Gather the exact BYTES of physical pages ``pages`` from every
        self-attention KV pool plane to host memory.

        Returns a tree of numpy arrays matching the ``caches['kv']`` leaves
        with axis 1 reduced to ``len(pages)`` (in the given order) — the
        snapshot half of preemption.  Host-side and eager: the pool is not
        modified, and the spilled pages can be released to the allocator
        immediately after (nothing reads an unmapped page)."""
        assert self.paged, "spill_pages is a paged-pool operation"
        idx = jnp.asarray(np.asarray(pages, np.int32).ravel())
        return jax.tree_util.tree_map(
            lambda pool: np.asarray(pool[:, idx]), state.caches["kv"])

    def restore_pages(self, state: EngineState, pages, data) -> EngineState:
        """Scatter a ``spill_pages`` snapshot back into freshly allocated
        physical pages ``pages`` (same order as the spill) — the resume
        half of preemption.  The restored bytes must be exact: under
        block-causal invariant-refresh exemption, settled positions are
        never rewritten, so their K/V must already be final.  The page list
        is padded to a multiple of 8 with garbage-page no-ops (zeros) and
        the pool is donated, so callers must drop the pre-restore state."""
        assert self.paged, "restore_pages is a paged-pool operation"
        n = np.asarray(pages, np.int32).size
        assert n > 0
        pidx = self._pad_pages(pages)
        pad = pidx.size - n

        def pad_leaf(d):
            d = np.asarray(d)
            assert d.shape[1] == n, f"snapshot holds {d.shape[1]} pages, not {n}"
            if pad == 0:
                return d
            z = np.zeros((d.shape[0], pad) + d.shape[2:], d.dtype)
            return np.concatenate([d, z], axis=1)

        caches = dict(state.caches)
        caches["kv"] = self._jit_restore_kv(
            state.caches["kv"], jnp.asarray(pidx),
            jax.tree_util.tree_map(pad_leaf, data))
        return state._replace(caches=caches)

    def scrub_pages(self, state: EngineState, pages) -> EngineState:
        """Zero physical pages in every KV pool plane (quarantine hygiene:
        a poisoned row's non-finite K/V must not outlive the row, even
        though the next owner's admission prefill rewrites the page before
        reading it).  Donated pool — callers drop the pre-scrub state."""
        assert self.paged, "scrub_pages is a paged-pool operation"
        pages = np.asarray(pages, np.int32).ravel()
        if pages.size == 0:
            return state
        caches = dict(state.caches)
        caches["kv"] = self._jit_scrub_kv(
            state.caches["kv"], jnp.asarray(self._pad_pages(pages)))
        return state._replace(caches=caches)

    def is_prompt_refresh(self, phase: int) -> bool:
        """Whether the step at within-block iteration ``phase`` is a prompt
        refresh (``_branch_index`` branch 2) — the only branch that scatters
        into prompt pages.  The scheduler keys CoW forks and eviction
        reclaim on this; it shares ``_prompt_refresh_pred`` with
        ``_branch_index``, so the two cannot drift apart."""
        return bool(self._prompt_refresh_pred(int(phase)))

    def prompt_refresh_rows(self, phases) -> np.ndarray:
        """[B] bool — which slots' NEXT step is a prompt refresh, given the
        per-slot phase vector.  The per-row successor of
        ``is_prompt_refresh``: the scheduler keys CoW forks and eviction
        reclaim on the rows this flags (a refresh scatters into THAT row's
        prompt pages only), not on a global cadence."""
        return np.asarray(self._prompt_refresh_pred(
            np.asarray(phases, np.int64)))

    def dead_page_report(self, state: EngineState) -> np.ndarray:
        """[B, n_vpages] bool — mapped virtual pages every one of whose rows
        is dead (``kv_pos < 0``: sparse-evicted or pad) and that lie entirely
        before the slot's current block, i.e. can never be revived by the
        in-block retention override as ``bs`` only moves forward.  These are
        the pages the scheduler unmaps and returns to the free list; under
        sticky eviction nothing will ever read them again, and the next
        refresh's scatters to them clamp to the garbage page."""
        assert self.paged and state.block_tables is not None
        ps = self.page_size
        kv_valid = np.asarray(state.kv_valid)
        b, t = kv_valid.shape
        pos = np.arange(t, dtype=np.int32)[None]
        alive = kv_valid & (pos >= np.asarray(state.prompt_start)[:, None])
        page_alive = alive.reshape(b, t // ps, ps).any(axis=2)
        page_end = (np.arange(t // ps, dtype=np.int32) + 1) * ps
        settled = page_end[None, :] <= np.asarray(state.bs)[:, None]
        return (np.asarray(state.block_tables) >= 0) & ~page_alive & settled \
            & np.asarray(state.active)[:, None]

    def step(self, params, state: EngineState,
             enc_out: Optional[jax.Array] = None) -> EngineState:
        """ONE denoising iteration for every resident slot — a single jitted
        program whose shape is independent of which slots are prefilling,
        refreshing, skip-decoding, or idle (per-row mode masks).  ``state``
        is donated: callers must drop it and keep the returned state."""
        return self._jit_step(params, state, enc_out)

    def compiled_step_text(self, params, state: EngineState,
                           enc_out: Optional[jax.Array] = None) -> str:
        """The optimised HLO of the step compiled for these arguments' shapes
        and shardings.  Its ``op_name`` metadata maps the instruction names
        a profiler trace shows (``cond.57``) to the named scopes of the
        passes and of attention.  Lowering reuses the cached trace of the
        step (``step_trace_count`` stays as it is); compiling takes as long
        as the step's own compile unless the persistent cache holds it."""
        return self._jit_step.lower(params, state, enc_out).compile().as_text()

    def bind_state_shardings(self, state_shardings, param_shardings=None):
        """Rebind the jitted step with explicit ``EngineState`` shardings
        (multi-host step 2: ``sharding.specs.engine_state_pspecs`` →
        ``shardings_of``).  Under a data mesh each shard's slot planes — and
        through the block tables, its pages — stay local; XLA inserts no
        cross-shard collectives for the slot-parallel step.  Output keeps
        the input layout so the rebind composes with the scheduler's
        host-side state surgery."""
        self._jit_step = jax.jit(
            self._engine_step,
            in_shardings=(param_shardings, state_shardings, None),
            out_shardings=state_shardings, donate_argnums=(1,))

    def _merge_step_outputs(self, mask, old, new):
        """Per-row merge of one mode pass's ``(caches, conf, pred, hidden,
        kv_valid, feat, stats)`` into the carried tuple: rows in ``mask``
        take the pass's results, every other row keeps its carried state.

        Cache leaves split two ways: self-attention KV was already
        row-masked at the scatter (dense: write-back of the gathered old
        row; paged: the write view of the block table clamps dead rows to
        the garbage page), so the pass's KV is taken as-is — a per-row
        select is impossible on the shared page pool anyway.  Every other
        cache kind is batch-major ``[G, B, ...]`` and merges with a plain
        per-row select (cross K/V and SSM snapshots are overwritten
        wholesale by a pass, not scattered)."""
        o_caches, o_conf, o_pred, o_hidden, o_kv, o_feat, o_stats = old
        n_caches, n_conf, n_pred, n_hidden, n_kv, n_feat, n_stats = new
        caches = n_caches
        if o_caches != ():
            caches = dict(n_caches)
            for kind in ("cross", "ssm", "ssmh"):
                if o_caches.get(kind):
                    caches[kind] = jax.tree_util.tree_map(
                        lambda o, n: jnp.where(
                            mask.reshape((1, -1) + (1,) * (o.ndim - 2)), n, o),
                        o_caches[kind], n_caches[kind])
        m1 = mask[:, None]
        return (
            caches,
            jnp.where(m1, n_conf, o_conf),
            jnp.where(m1, n_pred, o_pred),
            tuple(jnp.where(mask[:, None, None], n, o)
                  for o, n in zip(o_hidden, n_hidden)),
            jnp.where(m1, n_kv, o_kv),
            None if o_feat is None else jnp.where(mask[:, None, None],
                                                  n_feat, o_feat),
            jnp.where(m1, n_stats, o_stats),
        )

    def _mixed_step_outputs(self, params, state: EngineState, st: BlockState,
                            enc_out):
        """Mixed-mode compute for ONE serving iteration: every row resolves
        its branch from its OWN phase, and up to three fused sub-programs run
        — each gated by ``lax.cond`` on "any active row in this mode", each
        masked to the rows it owns (on a paged attention-only engine the
        prompt refresh instead runs one row at a time over its own rows
        alone, ``_refresh_rows``).  The carried ``(caches, conf, pred,
        hidden, kv_valid)`` threads through the passes; their row sets are
        disjoint, so order cannot matter semantically (passes read only
        their own rows' cache state — attention never crosses rows, and
        shared paged pages belong to cohorts whose rows share a phase)."""
        bs = state.bs
        br = self._branch_index(state.phase, state.iters)        # [B]
        iters, seeds = state.iters, state.sample_seeds
        prompt_start, bt = state.prompt_start, state.block_tables
        b = st.tokens.shape[0]

        def carried(carry):
            return st._replace(caches=carry[0], conf=carry[1],
                               pred=carry[2], hidden=carry[3],
                               kv_valid=carry[4], feat=carry[5])

        def decode_pass(skip: bool, mask):
            def run(carry):
                out = self._decode_step(params, bs, iters, seeds,
                                        prompt_start, bt, carried(carry),
                                        skip=skip, row_mask=mask)
                return self._merge_step_outputs(mask, carry, out)
            return run

        def prefill_pass(mask):
            if self.refresh_per_row:
                return functools.partial(self._refresh_rows, params, state,
                                         st, mask)

            def run(carry):
                out = self._prefill_step(params, bs, iters, seeds,
                                         prompt_start, bt, enc_out,
                                         carried(carry), row_mask=mask)
                return self._merge_step_outputs(mask, carry, out)
            return run

        def partial_pass(mask):
            def run(carry):
                out = self._partial_refresh_step(params, bs, iters, seeds,
                                                 prompt_start, bt, enc_out,
                                                 carried(carry),
                                                 row_mask=mask)
                return self._merge_step_outputs(mask, carry, out)
            return run

        carry = (st.caches, st.conf, st.pred, st.hidden, st.kv_valid,
                 st.feat, jnp.zeros((b, 2), jnp.int32))
        skip_rows = state.active & (br == 0)
        noskip_rows = state.active & (br == 1)
        refresh_rows = state.active & (br == 2)
        # each pass's conditional carries a named scope (HLO op_name
        # metadata only): profiler traces name the passes by it
        with jax.named_scope("es.skip_decode"):
            carry = jax.lax.cond(jnp.any(skip_rows),
                                 decode_pass(True, skip_rows), lambda c: c,
                                 carry)
        with jax.named_scope("es.block_refresh"):
            carry = jax.lax.cond(jnp.any(noskip_rows),
                                 decode_pass(False, noskip_rows), lambda c: c,
                                 carry)
        with jax.named_scope("es.prompt_refresh"):
            carry = jax.lax.cond(jnp.any(refresh_rows),
                                 prefill_pass(refresh_rows), lambda c: c,
                                 carry)
        if self.adaptive_cache:
            # branch 3 is only ever emitted with the cache enabled; gating
            # statically keeps the disabled program byte-identical
            partial_rows = state.active & (br == 3)
            with jax.named_scope("es.partial_refresh"):
                carry = jax.lax.cond(jnp.any(partial_rows),
                                     partial_pass(partial_rows), lambda c: c,
                                     carry)
        return carry

    def _engine_step(self, params, state: EngineState, enc_out) -> EngineState:
        self.step_trace_count += 1        # python side effect: counts traces
        gen = self.gen
        lb = gen.block_length
        steps_pb = gen.resolved_steps()
        bs = state.bs
        st = BlockState(state.tokens, state.caches, state.conf, state.pred,
                        state.hidden, state.kv_valid, state.phase, state.key,
                        state.feat, state.conf_full)
        if gen.mode == "vanilla":
            conf, pred, st = self._vanilla_compute(
                params, st, bs, enc_out, iters=state.iters,
                seeds=state.sample_seeds)
            outs = (st.caches, conf, pred, st.hidden, st.kv_valid, st.feat,
                    jnp.zeros((bs.shape[0], 2), jnp.int32))
        else:
            outs = self._mixed_step_outputs(params, state, st, enc_out)
        stats = outs[6]
        st = self._apply_unmask(st, bs, *outs, active=state.active)

        # per-row poison detector: any non-finite value in a row's merged
        # confidence / indicator / feature planes marks the row.  The flag is
        # sticky (ORed in) and only ever set for active rows — idle rows
        # carry zeroed finite planes.  The scheduler retires flagged rows
        # host-side (typed PoisonedRequest) and resets the flag, so one bad
        # request cannot keep a slot or its pages hostage.
        poisoned = state.poisoned
        if poisoned is not None:
            bad = ~jnp.all(jnp.isfinite(st.conf), axis=1)
            for hh in st.hidden:
                bad |= ~jnp.all(jnp.isfinite(hh), axis=(1, 2))
            if st.feat is not None:
                bad |= ~jnp.all(jnp.isfinite(st.feat), axis=(1, 2))
            poisoned = poisoned | (bad & state.active)

        phase_used = state.phase
        phase = (phase_used + 1) % steps_pb

        # per-row block advancement: a row whose block fully unmasked moves
        # to its next block (or completes).  early_advance=True advances the
        # moment the block is done (its phase resets to 0, so its next step
        # prefills the new block — exactly the offline block-loop cadence);
        # early_advance=False defers to the row's own phase wrap, matching
        # the block-aligned scheduler.  Shapes stay static either way — the
        # predicate just masks the update off.
        blk_tok = _row_gather(st.tokens, self._block_cols(bs))
        blk_done = ~jnp.any(blk_tok == self.mask_id, axis=1)
        adv = state.active & blk_done
        if not self.early_advance:
            adv &= phase == 0
        blocks_left = state.blocks_left - adv.astype(jnp.int32)
        finished = adv & (blocks_left == 0)
        new_bs = jnp.where(adv & ~finished, bs + lb, bs)
        active = state.active & ~finished
        phase = jnp.where(adv, 0, phase)
        # lifetime draw-key numbering matches offline generate(): block blk
        # starts at blk * steps_pb, so an advance JUMPS the counter there —
        # the iterations early advance skips were no-ops with no draws.
        iters = jnp.where(
            adv, state.iters - phase_used + steps_pb,
            state.iters + state.active.astype(jnp.int32))

        return EngineState(
            tokens=st.tokens, caches=st.caches, conf=st.conf, pred=st.pred,
            hidden=st.hidden, kv_valid=st.kv_valid,
            bs=new_bs, blocks_left=blocks_left, phase=phase,
            iters=iters, active=active, key=st.key,
            prompt_start=state.prompt_start,
            sample_seeds=state.sample_seeds,
            block_tables=state.block_tables,
            feat=st.feat, conf_full=st.conf_full,
            cache_refreshed=state.cache_refreshed + stats[:, 0],
            cache_eligible=state.cache_eligible + stats[:, 1],
            poisoned=poisoned,
        )

    # ------------------------------------------------------------------
    # branches
    # ------------------------------------------------------------------
    def _ctx(self, mode, positions, **kw) -> ForwardCtx:
        # sequence-parallel constraint only pays off on full-sequence passes
        act = self.act_sharding if mode in ("prefill", "nocache") else None
        return ForwardCtx(
            positions=positions, mode=mode,
            window_override=self.window_override, anchor=self.anchor,
            attn_impl=self.attn_impl, act_sharding=act,
            cache_shardings=self.cache_shardings,
            moe_sharding=self.moe_sharding,
            inner_sharding=self.inner_sharding, **kw,
        )

    def _prefill_step(self, params, bs, iters, seeds, prompt_start,
                      block_tables, enc_out, st: BlockState,
                      row_mask: Optional[jax.Array] = None):
        """Full forward over the whole sequence: (re)builds every cache and
        the block's confidence/prediction/indicator caches (cache init &
        prompt refresh — paper §5.2 last paragraph).

        ``row_mask`` [B] marks the rows this pass OWNS under mixed-mode
        cadence (None = all rows, the offline/phase-aligned path): other
        rows still flow through the fused program — identical shapes, one
        compiled step — but their cache scatters are dropped
        (``ForwardCtx.scatter_mask``) and the caller merges their outputs
        away.  With a mask the carried caches are NOT zeroed: the refresh
        scatter covers every position of an owned row anyway, and zeroing
        would destroy the other rows' live cache state.

        Pad prompt rows (pos < prompt_start) are computed but masked out of
        every attention read (``kv_pos < 0``) and — in paged mode — never
        mapped, so they cost no pool pages; their scatters land on the
        garbage page.

        Under sparse eviction the refresh is *sticky*: rows outside the
        current block that a previous eviction dropped stay dead — they are
        masked out of this pass's attention reads, excluded from the probe,
        and can never re-enter the retained set.  Their K/V are still
        recomputed and scattered, but in paged mode the scheduler may have
        already unmapped their page (the scatter lands on the garbage page),
        which is exactly why stickiness is required for dense-vs-paged
        bit-identity."""
        model, gen = self.model, self.gen
        b, t_total = st.tokens.shape
        lb = gen.block_length
        cols = self._block_cols(bs)
        col = jnp.arange(t_total, dtype=jnp.int32)[None]
        in_block = (col >= bs[:, None]) & (col < (bs + lb)[:, None])
        # the current block is always attendable/retained; everything else
        # keeps its carried validity (sticky outside the block)
        attend_valid = st.kv_valid | in_block

        h = model.embed(params, st.tokens)
        pos = jnp.broadcast_to(jnp.arange(t_total, dtype=jnp.int32)[None], (b, t_total))
        # block-causal: positions below the invariant horizon already hold
        # their final K/V (a rewrite would be a value no-op), so the refresh
        # scatter exempts them — which is what keeps persistently shared
        # prompt pages read-only across requests.  None (bidirectional mode)
        # compiles the token mask out.
        inv = self._invariant_limit(bs, iters, t_total)
        refresh_tok = None if inv is None else (col >= inv[:, None])
        caches = st.caches
        if row_mask is None and inv is None:
            # phase-aligned path: every row rebuilds in this same pass, so
            # zeroing the whole cache (pool included) is correct; under a
            # row mask the other rows' cache state must survive, and the
            # refresh scatter rewrites every owned position regardless.
            # Under the block-causal exemption the invariant positions'
            # cached K/V must survive too, so zeroing is skipped there.
            caches = jax.tree_util.tree_map(jnp.zeros_like, caches)
        if self.cache_shardings is not None:
            caches = jax.tree_util.tree_map(
                jax.lax.with_sharding_constraint, caches, self.cache_shardings
            )
        kv_pos = self._kv_pos(attend_valid, prompt_start)
        ctx = self._ctx(
            "prefill", pos, kv_pos=kv_pos, slot_idx=pos,
            block_start=bs, enc_out=enc_out,
            block_tables=block_tables, page_size=self.page_size,
            scatter_mask=row_mask, refresh_mask=refresh_tok,
            window_limit=self._window_limit(bs), **self._bc_args(t_total),
        )
        hidden = []
        feat = st.feat
        for seg in self.segments:
            out = model.run_layers(params, h, ctx, caches,
                                   group_lo=seg.group_lo, group_hi=seg.group_hi)
            h, caches = out.h, out.caches
            if self.adaptive_cache and seg.group_hi == self.cache_probe_groups:
                # snapshot the probe-boundary features for every position:
                # the baseline the next partial refresh measures variation
                # against (unowned rows are merged away one level up)
                feat = h.astype(jnp.float32)
            if seg.keep_k is not None:
                hidden.append(_row_gather(h, cols).astype(jnp.float32))
        logits_blk = model.logits(params, _row_gather(h, cols))
        conf, pred = self._confidence(st, bs, logits_blk, iters, seeds)

        kv_valid = jnp.ones((b, t_total), bool)
        if gen.sparse_attention:
            keep = self._sparse_evict(params, caches, hidden, bs, st.tokens,
                                      prompt_start, block_tables,
                                      kv_valid=attend_valid)
            # sticky: a refresh can only shrink the retained set outside the
            # current block — dead rows stay dead (their page may be gone)
            kv_valid = keep & attend_valid
        stats = jnp.zeros((b, 2), jnp.int32)
        if self.adaptive_cache:
            # a full refresh recomputes every eligible past token: it counts
            # as "refreshed == eligible" toward the cache-hit gauges
            eligible = self._cache_eligible(st, bs, in_block, prompt_start,
                                            block_tables)
            n_el = jnp.sum(eligible, axis=1).astype(jnp.int32)
            stats = jnp.stack([n_el, n_el], axis=1)
        return caches, conf, pred, tuple(hidden), kv_valid, feat, stats

    def _decode_step(self, params, bs, iters, seeds, prompt_start,
                     block_tables, st: BlockState, *, skip: bool,
                     row_mask: Optional[jax.Array] = None):
        """One diffusion iteration on the current block (paper Alg. 1).

        ``skip=True`` applies the early-skip schedule; ``skip=False`` is the
        block-refresh variant (all rows computed, caches fully updated).
        ``row_mask`` [B] marks the rows this pass owns under mixed-mode
        cadence (None = all): unowned rows compute but their KV scatters
        are dropped and the caller discards their outputs."""
        model, gen = self.model, self.gen
        b, t_total = st.tokens.shape
        lb = gen.block_length

        blk_tok = _row_gather(st.tokens, self._block_cols(bs))
        h = model.embed(params, blk_tok)
        s_idx = jnp.broadcast_to(jnp.arange(lb, dtype=jnp.int32)[None], (b, lb))
        kv_pos = self._kv_pos(st.kv_valid, prompt_start)
        caches = st.caches
        hidden = list(st.hidden)
        conf_cache = st.conf

        wl = self._window_limit(bs)
        for seg in self.segments:
            ctx = self._ctx(
                "decode", bs[:, None] + s_idx, kv_pos=kv_pos,
                slot_idx=bs[:, None] + s_idx, block_idx=s_idx,
                block_tables=block_tables, page_size=self.page_size,
                scatter_mask=row_mask, window_limit=wl,
                **self._bc_args(t_total),
            )
            out = model.run_layers(params, h, ctx, caches,
                                   group_lo=seg.group_lo, group_hi=seg.group_hi)
            h, caches = out.h, out.caches
            if seg.keep_k is not None:
                i = seg.stage_idx
                h_old = _row_gather(hidden[i], s_idx)
                conf_s = _row_gather(conf_cache, s_idx)
                scores = ops.importance_score(
                    h.astype(jnp.float32), h_old, conf_s,
                    alpha=gen.alpha, impl=self.importance_impl,
                )
                hidden[i] = _row_scatter(hidden[i], h.astype(jnp.float32), s_idx)
                if skip:
                    _, sel = jax.lax.top_k(scores, seg.keep_k)
                    s_idx = jnp.take_along_axis(s_idx, sel, axis=1)
                    h = jnp.take_along_axis(h, sel[..., None], axis=1)

        logits = model.logits(params, h)                       # [B, |S|, V]
        row_keys = self._row_keys(st.key, seeds, iters)
        conf_new, pred_new = smp.confidence_and_pred(
            row_keys, logits, gen, self.cfg.vocab_size, self.mask_id
        )
        conf = _row_scatter(st.conf, conf_new, s_idx)
        pred = _row_scatter(st.pred, pred_new, s_idx)
        return (caches, conf, pred, tuple(hidden), st.kv_valid, st.feat,
                jnp.zeros((b, 2), jnp.int32))

    # ------------------------------------------------------------------
    # adaptive feature cache (branch 3)
    def _cache_eligible(self, st: BlockState, bs, in_block, prompt_start,
                        block_tables):
        """Past tokens whose cached K/V a partial refresh may recompute:
        attendable (not evicted), real (not left-pad), and outside the
        current block — the block pass owns those.  In paged mode the
        position's page must still be mapped: a refresh scatter to an
        unmapped page would land on the garbage page and silently lose the
        fresh values, so unmapped positions are never *selected* (their
        stale pool rows are unreachable anyway)."""
        t_total = st.tokens.shape[1]
        col = jnp.arange(t_total, dtype=jnp.int32)[None]
        eligible = st.kv_valid & ~in_block & (col >= prompt_start[:, None])
        if self.gen.block_causal:
            # a partial refresh only ever runs after the block-entry FULL
            # refresh wrote everything below bs with final tokens, and under
            # block-causal masking those K/V are iteration-invariant —
            # recomputing them buys nothing, and writing them would touch
            # persistently shared prompt pages
            eligible &= col >= bs[:, None]
        wl = self._window_limit(bs)
        if wl is not None:
            # beyond-window positions are masked from every attention read,
            # so refreshing them buys nothing — and in lazy serving their
            # pages may not be mapped yet (the offline identity table IS
            # mapped there, so the clamp keeps serving == offline replay)
            eligible &= col < wl[:, None]
        if self.paged:
            eligible &= jnp.repeat(block_tables >= 0, self.page_size, axis=1)
        return eligible

    def _partial_refresh_step(self, params, bs, iters, seeds, prompt_start,
                              block_tables, enc_out, st: BlockState,
                              row_mask: Optional[jax.Array] = None):
        """PARTIAL prompt refresh (branch 3, adaptive feature cache).

        The dLLM-Cache move: between FULL refreshes, run only the shallow
        probe groups over the whole sequence, measure per-token feature
        variation against the cached probe features (``st.feat``) blended
        with last-observed confidence (``st.conf_full``), and push just the
        top-``cache_refresh_fraction`` most-varied past tokens — those at or
        above ``cache_variation_threshold`` — through the deep groups to
        recompute their K/V.  Everything else keeps its cached K/V
        (token-masked scatters make the unselected writes exact no-ops).
        The carried caches are never zeroed here.  Ends with the standard
        all-rows block pass so the iteration still advances denoising.

        ``row_mask`` works exactly as in ``_prefill_step``: unowned rows
        flow through with scatters dropped, the caller merges them away."""
        model, gen = self.model, self.gen
        b, t_total = st.tokens.shape
        lb = gen.block_length
        gp = self.cache_probe_groups
        col = jnp.arange(t_total, dtype=jnp.int32)[None]
        in_block = (col >= bs[:, None]) & (col < (bs + lb)[:, None])
        attend_valid = st.kv_valid | in_block
        kv_pos = self._kv_pos(attend_valid, prompt_start)

        # 1. shallow probe: full-sequence pass over groups [0, gp) — their
        # K/V refresh everywhere (cheap) and the boundary hidden state is
        # the fresh feature vector
        h = model.embed(params, st.tokens)
        pos = jnp.broadcast_to(jnp.arange(t_total, dtype=jnp.int32)[None],
                               (b, t_total))
        wl = self._window_limit(bs)
        ctx = self._ctx(
            "prefill", pos, kv_pos=kv_pos, slot_idx=pos,
            block_start=bs, enc_out=enc_out,
            block_tables=block_tables, page_size=self.page_size,
            scatter_mask=row_mask, window_limit=wl,
            **self._bc_args(t_total),
        )
        out = model.run_layers(params, h, ctx, st.caches,
                               group_lo=0, group_hi=gp)
        h_probe, caches = out.h, out.caches
        feat = h_probe.astype(jnp.float32)

        # 2. variation-gated selection: static top-R by score, then a
        # per-token threshold mask (so a quiet sequence refreshes fewer
        # than R tokens — the filler slots become masked no-op scatters)
        scores = ops.variation_score(
            feat, st.feat, st.conf_full,
            alpha=gen.alpha, impl=self.importance_impl,
        )
        eligible = self._cache_eligible(st, bs, in_block, prompt_start,
                                        block_tables)
        cand = jnp.where(eligible, scores, -jnp.inf)
        r = max(1, min(t_total,
                       math.ceil(gen.cache_refresh_fraction * (t_total - lb))))
        val, sel = jax.lax.top_k(cand, r)
        tok_ok = jnp.isfinite(val) & (val >= gen.cache_variation_threshold)

        # 3. deep refresh of the selected subset: decode-mode pass over the
        # gathered rows through groups [gp, G); the token mask drops the
        # below-threshold / ineligible-filler scatters so their cached K/V
        # survive bit-exactly
        h_sel = jnp.take_along_axis(h_probe, sel[..., None], axis=1)
        dctx = self._ctx(
            "decode", sel, kv_pos=kv_pos, slot_idx=sel,
            block_tables=block_tables, page_size=self.page_size,
            scatter_mask=row_mask, refresh_mask=tok_ok, window_limit=wl,
            **self._bc_args(t_total),
        )
        out = model.run_layers(params, h_sel, dctx, caches,
                               group_lo=gp, group_hi=model.n_groups)
        caches = out.caches

        # 4. standard block-refresh pass on the partially refreshed caches
        out7 = self._decode_step(params, bs, iters, seeds, prompt_start,
                                 block_tables, st._replace(caches=caches),
                                 skip=False, row_mask=row_mask)
        stats = jnp.stack([jnp.sum(tok_ok, axis=1),
                           jnp.sum(eligible, axis=1)],
                          axis=1).astype(jnp.int32)
        return out7[:5] + (feat, stats)

    def _refresh_rows(self, params, state: EngineState, st: BlockState,
                      mask, carry):
        """Prompt refresh of the rows in ``mask`` alone, one row at a time.

        A loop over the ``sum(mask)`` refreshing rows, taken in slot order
        (stable argsort), runs ``_prefill_step`` on a one-row batch: the
        row's planes are sliced out of the carry and its outputs put back.
        The paged pools are batch-free ([G, P, ps, H, D] leaves addressed
        through ``block_tables``), so the row's own block-table row routes
        its K/V scatters to its pages and the pool threads through the loop
        with nothing gathered.  A step with one refreshing row pays one
        full-sequence row, not one for every slot.

        The serial passes compute what one masked pass over the batch does:
        attention never crosses rows, and the only pages two rows share are
        a greedy prefix cohort's (identical bytes on both rows) or prompt
        pages exempt from rewrite under block-causal attention.  The
        engine's stack is attention-only, so no encoder output is read."""
        order = jnp.argsort(~mask)       # stable: refreshing rows first
        owned = jnp.ones((1,), bool)     # a one-row pass owns its row

        def body(i, carry):
            r = order[i]

            def row(a):
                return None if a is None else \
                    jax.lax.dynamic_slice_in_dim(a, r, 1)

            def put(full, new):
                return None if full is None else \
                    jax.lax.dynamic_update_slice_in_dim(
                        full, new.astype(full.dtype), r, 0)

            caches, conf, pred, hidden, kv_valid, feat, stats = carry
            st_r = st._replace(
                tokens=row(st.tokens), caches=caches, conf=row(conf),
                pred=row(pred), hidden=tuple(row(h) for h in hidden),
                kv_valid=row(kv_valid), feat=row(feat),
                conf_full=row(st.conf_full))
            out = self._prefill_step(
                params, row(state.bs), row(state.iters),
                row(state.sample_seeds), row(state.prompt_start),
                row(state.block_tables), None, st_r, row_mask=owned)
            n_caches, n_conf, n_pred, n_hidden, n_kv, n_feat, n_stats = out
            return (n_caches, put(conf, n_conf), put(pred, n_pred),
                    tuple(put(o, n) for o, n in zip(hidden, n_hidden)),
                    put(kv_valid, n_kv), put(feat, n_feat),
                    put(stats, n_stats))

        return jax.lax.fori_loop(0, jnp.sum(mask, dtype=jnp.int32), body,
                                 carry)

    def _vanilla_compute(self, params, st: BlockState, bs, enc_out,
                         iters=None, seeds=None):
        """Full-sequence forward, no caches (the original LLaDA loop)."""
        model = self.model
        b, t_total = st.tokens.shape
        bs = self._bs_rows(bs, b)
        if iters is None:   # standalone probes (benchmarks) draw at phase t
            iters = jnp.broadcast_to(st.t, (b,)).astype(jnp.int32)
        if seeds is None:
            seeds = jnp.arange(b, dtype=jnp.int32)
        h = model.embed(params, st.tokens)
        pos = jnp.broadcast_to(jnp.arange(t_total, dtype=jnp.int32)[None], (b, t_total))
        ctx = self._ctx("nocache", pos, enc_out=enc_out,
                        **self._bc_args(t_total))
        out = model.run_layers(params, h, ctx, None)
        logits_blk = model.logits(params, _row_gather(out.h, self._block_cols(bs)))
        conf, pred = self._confidence(st, bs, logits_blk, iters, seeds)
        return conf, pred, st

    # ------------------------------------------------------------------
    def _confidence(self, st: BlockState, bs, logits_blk, iters, seeds):
        if self.disallow_eos:
            blk_tok = _row_gather(st.tokens, self._block_cols(bs))
            rev = jnp.flip(jnp.cumsum(jnp.flip(blk_tok == self.mask_id, 1), 1), 1)
            mask_after = (rev - (blk_tok == self.mask_id)) > 0
            logits_blk = smp.disallow_premature_eos(logits_blk, mask_after, self.eos_id)
        row_keys = self._row_keys(st.key, seeds, iters)
        return smp.confidence_and_pred(
            row_keys, logits_blk, self.gen, self.cfg.vocab_size, self.mask_id
        )

    # ------------------------------------------------------------------
    # Sparse-dLLM-style cache eviction (App. C.3.2 integration)
    # ------------------------------------------------------------------
    def _sparse_evict(self, params, caches, hidden, bs, tokens,
                      prompt_start=None, block_tables=None, kv_valid=None):
        """Score out-of-block cache rows by the attention they receive from
        the current block's queries at the first skip-stage layer; retain the
        top ``sparse_retention`` fraction (kernel-size mean pooling).

        Positions the block can never attend — pad prompt rows, rows a
        previous eviction already dropped (``kv_valid`` false; their paged
        backing may have been reclaimed), and unmapped virtual pages (whose
        gathered K rows are garbage-page content) — are masked out of the
        probe softmax and ranked below everything, so they neither soak up
        attention mass nor win retention slots.  The caller ANDs the result
        with the carried ``kv_valid`` (sticky eviction), and the scheduler
        turns fully-dead pages into free-list returns via
        ``dead_page_report``."""
        gen, cfg = self.gen, self.cfg
        b, t_total = tokens.shape
        lb = gen.block_length
        stage_seg = next(s for s in self.segments if s.keep_k is not None)
        g = stage_seg.group_hi                     # layer right after the stage
        g = min(g, self.model.n_groups - 1)
        lp = jax.tree_util.tree_map(lambda a: a[g], params["layers"]["0"])
        from repro.models.common import apply_rope, rms_norm

        h_blk = hidden[stage_seg.stage_idx].astype(jnp.float32)
        xq = rms_norm(h_blk, lp["ln1"], cfg.rms_eps) @ lp["attn"]["wq"]
        if "bq" in lp["attn"]:
            xq = xq + lp["attn"]["bq"]
        q = xq.reshape(b, lb, cfg.n_heads, cfg.head_dim)
        q_pos = self._block_cols(bs)
        q = apply_rope(q, q_pos, theta=cfg.rope_theta, fraction=cfg.rope_fraction)

        kcache = caches["kv"]["0"].k[g]            # [B, T, Hkv, Dh] (dense)
        col = jnp.arange(t_total, dtype=jnp.int32)[None]
        attendable = jnp.ones((b, t_total), bool)
        if prompt_start is not None:
            attendable &= col >= prompt_start[:, None]
        if kv_valid is not None:
            attendable &= kv_valid
        if block_tables is not None:               # paged: pool -> dense view
            kcache = ops.gather_pages(kcache, block_tables)
            attendable &= jnp.repeat(block_tables >= 0, self.page_size, axis=1)
        wl = self._window_limit(bs)
        if wl is not None:
            # the probe must rank only window-visible rows: beyond-horizon
            # K rows are garbage in lazy serving (unmapped) but real in the
            # offline identity layout — clamping both keeps them bit-equal
            attendable &= col < wl[:, None]
        group = cfg.n_heads // cfg.n_kv_heads
        kk = jnp.repeat(jnp.swapaxes(kcache, 1, 2), group, axis=1)   # [B, Hq, T, Dh]
        scores = jnp.einsum(
            "bhqd,bhtd->bhqt",
            jnp.swapaxes(q, 1, 2).astype(jnp.float32),
            kk.astype(jnp.float32),
        ) / (cfg.head_dim ** 0.5)
        scores = jnp.where(attendable[:, None, None, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)            # [B, H, Lb, T]
        recv = jnp.mean(probs, axis=(1, 2))                # [B, T]
        # kernel-size mean pooling over neighbours
        ks = gen.sparse_kernel_size
        pooled = recv
        if ks > 1:
            pad = ks // 2
            padded = jnp.pad(recv, ((0, 0), (pad, pad)), mode="edge")
            pooled = jnp.mean(
                jnp.stack([padded[:, i:i + t_total] for i in range(ks)], -1), -1
            )
        in_block = (col >= bs[:, None]) & (col < (bs + lb)[:, None])
        cand = jnp.where(in_block, jnp.inf,
                         jnp.where(attendable, pooled, -jnp.inf))
        n_keep = int(gen.sparse_retention * (t_total - lb)) + lb
        kth = jnp.sort(cand, axis=-1)[:, -n_keep][:, None]
        return (cand >= kth) | in_block


def make_engine(model: Model, gen: GenerationConfig, **kw) -> DiffusionEngine:
    return DiffusionEngine(model, gen, **kw)
