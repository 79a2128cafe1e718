"""The chunked XLA attention read with grouped-query heads.

``ops._attention_xla_chunked`` (the served read: ``paged_attention``'s XLA
lowering gathers pages and calls it) folds the ``group`` query heads of
each KV head into the query axis, so a K/V chunk is read once per KV head
and never repeated per query head.  Here:

* it equals the repeat-then-read lowering it replaced (kept below as
  ``_repeat_then_read``) and the naive oracle ``kernels/ref.py``, for
  group 1, 4 and 7, dense and paged, bidirectional, block-causal and
  windowed, over several KV chunks;
* at group 1 (MHA: the LLaDA program) it is bit-equal to the
  repeat-then-read;
* compiled in a GQA model's served step at group 7, no instruction under
  ``es.attention`` holds a K/V chunk widened to the query-head count.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs import GenerationConfig, SkipStage
from repro.kernels import ops, ref
from repro.models import build_model
from repro.runtime import Request, StreamScheduler

HKV, D, PS, KV_CHUNK = 2, 16, 8, 16
T = 40                                # 5 pages; 3 KV chunks of 16
BC_START, BC_BLOCK = 16, 8
MASKS = {
    "bidirectional": {},
    "block_causal": dict(bc_start=BC_START, bc_block=BC_BLOCK),
    "window": dict(window=8, anchor=4),
}


def _repeat_then_read(q, k, v, q_pos, kv_pos, *, window=0, anchor=0,
                      bc_start=0, bc_block=0, kv_chunk=KV_CHUNK):
    """The read as it was before grouping: K/V repeated to the query-head
    count inside the chunk scan, then the MHA online softmax."""
    b, hq, lq, d = q.shape
    hkv, lkv = k.shape[1], k.shape[2]
    group = hq // hkv
    scale = 1.0 / (d ** 0.5)
    ck = min(kv_chunk, lkv)
    lkv_p = -(-lkv // ck) * ck
    n = lkv_p // ck
    k = jnp.pad(k, ((0, 0), (0, 0), (0, lkv_p - lkv), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, 0), (0, lkv_p - lkv), (0, 0)))
    kv_pos = jnp.pad(kv_pos, ((0, 0), (0, lkv_p - lkv)), constant_values=-1)
    ks = jnp.moveaxis(k.reshape(b, hkv, n, ck, d), 2, 0)
    vs = jnp.moveaxis(v.reshape(b, hkv, n, ck, d), 2, 0)
    ps = jnp.moveaxis(kv_pos.reshape(b, n, ck), 1, 0)
    qf = q.astype(jnp.float32)
    qp = q_pos[:, None, :, None]

    def step(carry, inp):
        m_prev, l_prev, acc = carry
        kc, vc, pc = inp
        kc = jnp.repeat(kc, group, axis=1).astype(jnp.float32)
        vc = jnp.repeat(vc, group, axis=1).astype(jnp.float32)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kc) * scale
        kp_ = pc[:, None, None, :]
        mask = kp_ >= 0
        if window:
            win = jnp.abs(qp - kp_) <= window
            if anchor > 0:
                win |= kp_ < anchor
            mask &= win
        if bc_block > 0:
            qb = jnp.where(qp >= bc_start, (qp - bc_start) // bc_block, -1)
            kb = jnp.where(kp_ >= bc_start, (kp_ - bc_start) // bc_block, -1)
            mask &= kb <= qb
        s = jnp.where(mask, s, ops.NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, vc)
        return (m_new, l_new, acc), None

    init = (jnp.full((b, hq, lq), ops.NEG_INF, jnp.float32),
            jnp.zeros((b, hq, lq), jnp.float32),
            jnp.zeros((b, hq, lq, d), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(step), init, (ks, vs, ps))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def _inputs(group, seed):
    """Two rows; queries at positions 12..23 span the prompt and both
    generation blocks; the last three cache rows are unfilled."""
    rng = np.random.default_rng(seed)
    b, lq = 2, 12
    q = jnp.asarray(rng.normal(size=(b, HKV * group, lq, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, HKV, T, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, HKV, T, D)), jnp.float32)
    q_pos = jnp.tile(jnp.arange(12, 12 + lq, dtype=jnp.int32)[None], (b, 1))
    kv_pos = jnp.tile(jnp.arange(T, dtype=jnp.int32)[None], (b, 1))
    return q, k, v, q_pos, kv_pos.at[:, -3:].set(-1)


def _paged(k, v, seed):
    """The same K/V in a shuffled page pool (page 0 is the garbage page)."""
    b, n_vp = k.shape[0], T // PS
    rng = np.random.default_rng(seed)
    bt = 1 + rng.permutation(b * n_vp).reshape(b, n_vp).astype(np.int32)

    def pool(x):
        rows = jnp.swapaxes(x, 1, 2).reshape(b * n_vp, PS, HKV, D)
        return jnp.zeros((1 + b * n_vp, PS, HKV, D), x.dtype).at[
            bt.reshape(-1)].set(rows)
    return pool(k), pool(v), jnp.asarray(bt)


@pytest.mark.parametrize("mask", list(MASKS))
@pytest.mark.parametrize("layout", ["dense", "paged"])
@pytest.mark.parametrize("group", [1, 4, 7])
def test_grouped_read_equals_repeat_and_oracle(group, layout, mask):
    kw = MASKS[mask]
    q, k, v, q_pos, kv_pos = _inputs(group, seed=group)
    if layout == "dense":
        got = ops.attention(q, k, v, q_pos, kv_pos, impl="xla",
                            kv_chunk=KV_CHUNK, **kw)
    else:
        kp, vp, bt = _paged(k, v, seed=group)
        np.testing.assert_array_equal(
            jnp.swapaxes(ops.gather_pages(kp, bt), 1, 2), k)
        got = ops.paged_attention(q, kp, vp, q_pos, kv_pos, bt, page_size=PS,
                                  impl="xla", kv_chunk=KV_CHUNK, **kw)
    old = _repeat_then_read(q, k, v, q_pos, kv_pos, **kw)
    want = ref.attention_reference(q, k, v, q_pos, kv_pos, **kw)
    if group == 1:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(old))
    else:
        # same products and the same online softmax; only the dot's shape
        # (group * Lq rows per KV head) changes, so the sums may round apart
        np.testing.assert_allclose(np.asarray(got), np.asarray(old),
                                   atol=1e-6, rtol=1e-6)
    # the chunked lowering against the materialised oracle, as in
    # test_kernels_attention.py
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_grouped_read_keeps_int8_dequant():
    """The int8 KV path dequantizes per KV chunk, group folded in too."""
    group = 7
    q, k, v, q_pos, kv_pos = _inputs(group, seed=11)
    ks = jnp.max(jnp.abs(k), axis=-1) / 127.0
    vs = jnp.max(jnp.abs(v), axis=-1) / 127.0
    k8 = jnp.round(k / ks[..., None]).astype(jnp.int8)
    v8 = jnp.round(v / vs[..., None]).astype(jnp.int8)
    got = ops.attention(q, k8, v8, q_pos, kv_pos, impl="xla",
                        kv_chunk=KV_CHUNK, k_scale=ks, v_scale=vs)
    want = ref.attention_reference(q, k8.astype(jnp.float32) * ks[..., None],
                                   v8.astype(jnp.float32) * vs[..., None],
                                   q_pos, kv_pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_served_step_reads_no_widened_kv_at_group_7():
    """A paged GQA engine at 14 query heads over 2 KV heads, served at
    1,024 + 32 positions: the read's chunk is 1,024 positions, so a K/V
    chunk repeated to the query heads would show as ``[.., 14, 1024, 16]``
    (or ``[.., 2, 7, 1024, 16]`` before its reshape) under
    ``es.attention``.  The K/V chunk itself, ``[.., 2, 1024, 16]``, is
    there."""
    cfg = dataclasses.replace(configs.reduced(configs.get_config("dream-7b")),
                              n_layers=2, n_heads=14, n_kv_heads=2,
                              head_dim=D)
    assert cfg.qkv_bias
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    gen = GenerationConfig(mode="es", skip_stages=(SkipStage(1, 0.5),),
                           gen_length=32, block_length=8,
                           prompt_refresh_period=2, block_refresh_period=4)
    sched = StreamScheduler(model, params, gen, max_slots=2, prompt_len=1024,
                            paged=True, page_size=PS, early_advance=True)
    rng = np.random.default_rng(0)
    for _ in range(2):
        sched.submit(Request(prompt=rng.integers(3, cfg.vocab_size, 40)
                             .astype(np.int32)))
    hlo = sched.engine.compiled_step_text(sched.params, sched.state,
                                          sched._enc_out)
    read = [ln for ln in hlo.splitlines()
            if re.search(r'op_name="[^"]*/es\.attention/', ln)]
    assert read
    chunk = re.compile(r"\[(?:\d+,)*2,1024,16\]")
    widened = re.compile(r"\[(?:\d+,)*(?:14|2,7),1024,16\]")
    assert any(chunk.search(ln) for ln in read)
    assert not [ln for ln in read if widened.search(ln)]
