"""Analytic FLOP / HBM-byte / collective-byte model for §Roofline.

Why analytic: XLA's HLO cost analysis counts each ``while`` body ONCE, not
x trip-count — our layer stack, KV-chunk attention, and CE loss are all
scans, so ``compiled.cost_analysis()`` under-counts by ~the loop lengths
(verified: llama-class train under-counts ~17x).  The roofline therefore
uses this exact matmul-level accounting; the compiled dry-run still supplies
the memory proof, the sharding/collective *structure*, and a lower-bound
cross-check on collective bytes.

All numbers are GLOBAL (whole step across the mesh); divide by chip count
for per-chip roofline terms.  Dtype: bf16 (2 bytes) for params/activations,
f32 (4) for optimizer moments.
"""
from __future__ import annotations

import dataclasses

from repro.configs.base import GenerationConfig, InputShape, ModelConfig
from repro.core.schedule import resolve_segments
from repro.models.common import padded_vocab
from repro.models.mamba import mamba_dims

BF16 = 2
F32 = 4


@dataclasses.dataclass
class StepCost:
    flops: float = 0.0              # matmul-dominated compute
    hbm_bytes: float = 0.0          # param + cache + boundary-activation traffic
    coll_bytes: float = 0.0         # inter-chip traffic (TP + FSDP + MoE + pod)
    model_flops: float = 0.0        # 6*N_active*D reference
    notes: tuple = ()

    def add(self, flops=0.0, hbm=0.0, coll=0.0):
        self.flops += flops
        self.hbm_bytes += hbm
        self.coll_bytes += coll


# ---------------------------------------------------------------------------
# per-layer primitives (per active token unless stated)
# ---------------------------------------------------------------------------


def _attn_proj_flops(cfg: ModelConfig) -> float:
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    return 2 * d * (h + 2 * hkv) * dh + 2 * h * dh * d


def _attn_score_flops(cfg: ModelConfig, kv_len: int) -> float:
    # qk^T + pv per query token
    return 2 * 2 * cfg.n_heads * cfg.head_dim * kv_len


def _mlp_flops(cfg: ModelConfig) -> float:
    return 2 * cfg.d_model * cfg.d_ff * 3


def _moe_flops(cfg: ModelConfig) -> float:
    """Per-token MoE cost: router + GShard one-hot dispatch/combine einsums
    (per token: 2*G_s*k*cf*d each) + expert FFN over capacity slots."""
    m = cfg.moe
    d = cfg.d_model
    router = 2 * d * m.n_experts
    dispatch = 2 * m.router_group_size * m.experts_per_token * m.capacity_factor * d
    expert = m.experts_per_token * m.capacity_factor * 2 * d * m.d_ff_expert * 3
    return router + 2 * dispatch + expert


def _mamba_flops(cfg: ModelConfig) -> float:
    s = cfg.ssm
    dims = mamba_dims(cfg)
    d_in, h, conv_ch = dims["d_inner"], dims["n_heads"], dims["conv_ch"]
    n, p, q = s.d_state, s.headdim, s.chunk
    proj = 2 * cfg.d_model * (2 * d_in + 2 * s.n_groups * n + h) + 2 * d_in * cfg.d_model
    conv = 2 * s.conv_width * conv_ch
    # SSD per token: scores row (Q*N + Q*P per head) + state update (N*P)
    ssd = 2 * h * (q * n + q * p + 2 * n * p)
    return proj + conv + ssd


def _cross_flops(cfg: ModelConfig, *, with_kv: bool) -> float:
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    f = 2 * d * h * dh + 2 * h * dh * d           # q + out proj per token
    f += _attn_score_flops(cfg, cfg.n_enc_tokens)
    if with_kv:   # K/V over enc tokens, amortized once per call — handled by caller
        pass
    return f


def _layer_flops(cfg: ModelConfig, l: int, kv_len: int) -> float:
    kind = cfg.layer_kind(l)
    f = 0.0
    if kind in ("attn", "selfcross"):
        f += _attn_proj_flops(cfg) + _attn_score_flops(cfg, kv_len)
    if kind in ("cross", "selfcross"):
        f += _cross_flops(cfg, with_kv=False)
    if kind == "ssm":
        f += _mamba_flops(cfg)
    if kind != "ssm" or cfg.family == "hybrid":
        f += _moe_flops(cfg) if cfg.layer_is_moe(l) else _mlp_flops(cfg)
    return f


def param_count(cfg: ModelConfig) -> float:
    """Analytic parameter count (matches init within ~1%)."""
    vp = padded_vocab(cfg)
    n = vp * cfg.d_model * (1 if cfg.tie_embeddings else 2)
    for l in range(cfg.n_layers):
        kind = cfg.layer_kind(l)
        d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        if kind in ("attn", "selfcross"):
            n += d * (h + 2 * hkv) * dh + h * dh * d
        if kind in ("cross", "selfcross"):
            n += d * h * dh + h * dh * d + 2 * (cfg.d_enc or d) * hkv * dh
        if kind == "ssm":
            dims = mamba_dims(cfg)
            s = cfg.ssm
            n += d * (2 * dims["d_inner"] + 2 * s.n_groups * s.d_state + dims["n_heads"])
            n += dims["d_inner"] * d + s.conv_width * dims["conv_ch"]
        if kind != "ssm" or cfg.family == "hybrid":
            if cfg.layer_is_moe(l):
                m = cfg.moe
                n += d * m.n_experts + m.n_experts * d * m.d_ff_expert * 3
            else:
                n += d * cfg.d_ff * 3
    if cfg.n_encoder_layers:
        de = cfg.d_enc or cfg.d_model
        n += cfg.n_encoder_layers * (4 * de * de + 3 * de * cfg.d_ff)
    return float(n)


def active_param_count(cfg: ModelConfig) -> float:
    """MoE-aware active params (experts_per_token of n_experts)."""
    n = param_count(cfg)
    if cfg.moe is None:
        return n
    m = cfg.moe
    moe_layers = sum(1 for l in range(cfg.n_layers) if cfg.layer_is_moe(l))
    total_exp = moe_layers * m.n_experts * cfg.d_model * m.d_ff_expert * 3
    active_exp = total_exp * m.experts_per_token / m.n_experts
    return n - total_exp + active_exp


def kv_cache_bytes(cfg: ModelConfig, batch: int, seq: int) -> float:
    n_attn = sum(1 for l in range(cfg.n_layers)
                 if cfg.layer_kind(l) in ("attn", "selfcross"))
    return 2 * n_attn * batch * seq * cfg.n_kv_heads * cfg.head_dim * BF16


# ---------------------------------------------------------------------------
# step costs
# ---------------------------------------------------------------------------


def decode_step_cost(
    cfg: ModelConfig,
    shape: InputShape,
    gen: GenerationConfig,
    mesh_axes: dict,
    *,
    skip: bool = True,
    window_override: int = 0,
) -> StepCost:
    """ONE diffusion decode iteration (paper Alg. 1) on the current block."""
    c = StepCost()
    b, s, lb = shape.global_batch, shape.seq_len, gen.block_length
    kv_len = min(s, 2 * window_override + 1024) if window_override else s
    if gen.mode == "es" and skip:
        segments, sizes = resolve_segments(cfg, gen, lb)
    else:
        from repro.core.schedule import Segment
        segments = [Segment(0, cfg.n_layers // cfg.pattern_period, None, None)]
        sizes = [lb]

    period = cfg.pattern_period
    hybrid_full = cfg.family in ("ssm", "hybrid")
    for seg, size in zip(segments, sizes):
        for g in range(seg.group_lo, seg.group_hi):
            for j in range(period):
                l = g * period + j
                kind = cfg.layer_kind(l)
                tokens = b * (lb if (kind == "ssm" and hybrid_full) else size)
                c.add(flops=tokens * _layer_flops(cfg, l, kv_len))
    # head on the final active set
    c.add(flops=b * sizes[-1] * 2 * cfg.d_model * padded_vocab(cfg))

    # HBM: weights once, full KV cache read, active rows written
    pbytes = active_param_count(cfg) * BF16
    kvb = kv_cache_bytes(cfg, b, kv_len)
    c.add(hbm=pbytes + kvb + b * lb * cfg.d_model * BF16 * cfg.n_layers)

    # collectives: TP all-reduce of activations 2x per layer on active rows
    tp = mesh_axes.get("model", 1)
    if tp > 1:
        act = sum(b * sz * cfg.d_model * BF16 * (seg.group_hi - seg.group_lo) * period
                  for seg, sz in zip(segments, sizes))
        c.add(coll=2 * act * 2 * (tp - 1) / tp)
        if cfg.moe is not None:
            # expert-parallel dispatch+combine all-to-alls
            c.add(coll=2 * b * lb * cfg.moe.experts_per_token * cfg.d_model * BF16)
    # reference: the no-skip (DualCache) block compute, 2*N_active*D_block —
    # ratio > 1 means ES is *below* full-block compute (the paper's saving)
    c.model_flops = 2 * active_param_count(cfg) * b * lb
    return c


def prefill_cost(cfg: ModelConfig, shape: InputShape, gen: GenerationConfig,
                 mesh_axes: dict) -> StepCost:
    """Full forward building all caches (cache init / prompt refresh)."""
    c = StepCost()
    b, s = shape.global_batch, shape.seq_len
    for l in range(cfg.n_layers):
        c.add(flops=b * s * _layer_flops(cfg, l, s))
    c.add(flops=b * gen.block_length * 2 * cfg.d_model * padded_vocab(cfg))
    pbytes = active_param_count(cfg) * BF16
    c.add(hbm=pbytes + kv_cache_bytes(cfg, b, s) + 2 * b * s * cfg.d_model * BF16 * cfg.n_layers)
    tp = mesh_axes.get("model", 1)
    if tp > 1:
        c.add(coll=2 * 2 * b * s * cfg.d_model * BF16 * cfg.n_layers * (tp - 1) / tp)
        if cfg.moe is not None:
            c.add(coll=2 * b * s * cfg.moe.experts_per_token * cfg.d_model * BF16)
    c.model_flops = 2 * active_param_count(cfg) * b * s
    return c


def train_step_cost(cfg: ModelConfig, shape: InputShape, mesh_axes: dict) -> StepCost:
    """fwd + bwd (+ remat ~1 extra fwd) + AdamW update."""
    c = StepCost()
    b, s = shape.global_batch, shape.seq_len
    fwd = sum(b * s * _layer_flops(cfg, l, s) for l in range(cfg.n_layers))
    head = b * s * 2 * cfg.d_model * padded_vocab(cfg)
    c.add(flops=4 * fwd + 3 * head)        # 1 fwd + 2 bwd + 1 remat-fwd of trunk
    n = param_count(cfg)
    pbytes = n * BF16
    c.add(hbm=3 * pbytes + 2 * n * 2 * F32 + n * BF16   # p/g/opt traffic
          + 2 * b * s * cfg.d_model * BF16 * cfg.n_layers)
    tp = mesh_axes.get("model", 1)
    dp = mesh_axes.get("data", 1) * mesh_axes.get("pod", 1)
    if tp > 1:
        c.add(coll=4 * b * s * cfg.d_model * BF16 * cfg.n_layers * (tp - 1) / tp)
    if dp > 1:
        # FSDP: all-gather params (fwd+bwd) + reduce-scatter grads (+pod AR)
        c.add(coll=3 * pbytes * (dp - 1) / dp)
        if mesh_axes.get("pod", 1) > 1:
            c.add(coll=pbytes)
    if cfg.moe is not None and tp > 1:
        c.add(coll=3 * 2 * b * s * cfg.moe.experts_per_token * cfg.d_model * BF16)
    c.model_flops = 6 * active_param_count(cfg) * b * s
    return c
