"""Plain float32 reference of ES-dLLM serving, one request at a time.

It imports nothing of the program.  Its weights are the benchmark's own
(``bench.weights.logical``, drawn again from the seed); its semantics are
the paper's decoding loop as the configuration's ``es`` section states it:

* blocks of ``block_length`` positions are denoised left to right; the
  request's sequence is its real prompt and its output extent, rounded up
  to whole KV pages as the paged server maps it (pad positions and pages
  past the extent are never attended);
* phase 0 of a block is a prompt refresh: a full bidirectional forward
  over the sequence that rebuilds every layer's K/V cache, the hidden
  cache at each skip stage and the block's logits;
* every ``block_refresh_period``-th phase is a block refresh: the block's
  positions through every layer, their K/V written to the cache first,
  attending the whole cache; hidden caches and logits of all of them;
* other phases skip early: after each skip stage only the ``keep`` block
  positions with the highest importance (Eq. 1: ``alpha * confidence +
  (1 - alpha) * |h - h_cached|_1 / (sqrt(d) |h_cached|_2)``, ties to the
  lower index) go on, and only they write K/V, hidden cache and logits.

Decoding is teacher-forced: a request's transcript gives, step by step,
which positions the server committed and to what token.  At each commit
the reference reads its own logits for that position, as last computed,
and the number compared is the gap by which the served token's logit lies
below the best one.  The layers are RMSNorm, half-split RoPE, MHA or GQA
with optional q/k/v bias, and the SwiGLU MLP, in float32 at
``Precision.HIGHEST``.  ``quant`` makes it a control of lower precision:
every weight matrix and its input rows rounded to int8 or to float8 e4m3
(symmetric absmax scales, per output column and per row), attention and
accumulation left in float32.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
NEG = -1e30


@dataclasses.dataclass(frozen=True)
class Semantics:
    """What the reference needs to know about the served configuration."""
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    vocab_size: int
    rope_theta: float
    rms_eps: float
    prompt_len: int               # padded prompt width of the server
    gen_length: int
    block_length: int
    page_size: int
    stage_layers: tuple           # layer index each skip stage follows
    keep: tuple                   # positions kept after each stage
    block_refresh_period: int
    alpha: float

    @property
    def total(self) -> int:
        return self.prompt_len + self.gen_length


def semantics(cfg: dict, serve: dict) -> Semantics:
    """From a config file: its ``model`` sizes, its ``es`` section and the
    served widths (``serve``: prompt_len, gen_length, block_length,
    page_size)."""
    m, es = cfg["model"], cfg["es"]
    return Semantics(
        m["n_layers"], m["d_model"], m["n_heads"], m["n_kv_heads"],
        m["head_dim"], m["vocab_size"], m["rope_theta"], m["rms_eps"],
        serve["prompt_len"], serve["gen_length"], serve["block_length"],
        serve["page_size"], tuple(es["stage_layers"]), tuple(es["keep"]),
        es["block_refresh_period"], es["alpha"])


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------
_FP8_MAX = 448.0    # largest float8_e4m3fn


def _split(x, axis, quant: str):
    """``x`` in the control's precision: (values in int8 or float8 e4m3,
    one float32 scale per slice along ``axis``, symmetric absmax)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    if quant == "int8":
        s = jnp.maximum(amax / 127.0, 1e-12)
        return jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int8), s
    if quant == "fp8":
        s = jnp.maximum(amax / _FP8_MAX, 1e-12)
        return (x / s).astype(jnp.float8_e4m3fn), s
    raise ValueError(f"unknown control precision {quant!r}")


def _lowp(x, axis, quant: str):
    v, s = _split(x, axis, quant)
    return v.astype(jnp.float32) * s


def quantize_weights(w: dict, quant: str) -> dict:
    """Every matrix in the control's precision, one scale per output
    column: (values, scales)."""
    return {k: _split(w[k].astype(jnp.float32), -2, quant)
            for k in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
                      "head")}


def _mm(x, w, qw, quant):
    if quant:
        v, s = qw
        return jnp.dot(_lowp(x, -1, quant), v.astype(jnp.float32) * s,
                       precision=HI)
    return jnp.dot(x, w.astype(jnp.float32), precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * inv            # [n, half]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(sem: Semantics, lw, lq, x, pos, kc, vc, rows, valid, quant):
    """One transformer layer for the rows ``x`` at positions ``pos``: their
    K/V go into the caches at ``rows`` first, then they attend every
    ``valid`` cache position.  Returns (x, kc, vc)."""
    h, hkv, dh = sem.n_heads, sem.n_kv_heads, sem.head_dim
    n = x.shape[0]
    xn = _rms(x, lw["ln1"], sem.rms_eps)
    q = _mm(xn, lw["wq"], lq.get("wq"), quant)
    k = _mm(xn, lw["wk"], lq.get("wk"), quant)
    v = _mm(xn, lw["wv"], lq.get("wv"), quant)
    if "bq" in lw:
        q = q + lw["bq"].astype(jnp.float32)
        k = k + lw["bk"].astype(jnp.float32)
        v = v + lw["bv"].astype(jnp.float32)
    q = _rope(q.reshape(n, h, dh), pos, sem.rope_theta)
    k = _rope(k.reshape(n, hkv, dh), pos, sem.rope_theta)
    kc = kc.at[rows].set(k)
    vc = vc.at[rows].set(v.reshape(n, hkv, dh))
    qg = q.reshape(n, hkv, h // hkv, dh)
    s = jnp.einsum("nkgd,tkd->kgnt", qg, kc, precision=HI) / math.sqrt(dh)
    s = jnp.where(valid[None, None, None, :], s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgnt,tkd->nkgd", p, vc, precision=HI).reshape(n, h * dh)
    x = x + _mm(o, lw["wo"], lq.get("wo"), quant)
    xn = _rms(x, lw["ln2"], sem.rms_eps)
    g = _mm(xn, lw["w_gate"], lq.get("w_gate"), quant)
    u = _mm(xn, lw["w_up"], lq.get("w_up"), quant)
    x = x + _mm(jax.nn.silu(g) * u, lw["w_down"], lq.get("w_down"), quant)
    return x, kc, vc


_LAYER_KEYS = ("ln1", "ln2", "wq", "wk", "wv", "wo", "w_gate", "w_up",
               "w_down", "bq", "bk", "bv")


def _layers(sem, w, wq, lo, hi, x, pos, kc, vc, rows, valid, quant,
            keep_rows=None):
    """Layers ``lo..hi-1`` in a scan.  ``keep_rows``: also return the
    hidden state of those rows after each layer."""
    lw = {k: w[k][lo:hi] for k in _LAYER_KEYS if k in w}
    lq = {k: (v[lo:hi], sc[lo:hi]) for k, (v, sc) in wq.items()
          if k != "head"}

    def body(x, xs):
        lwi, lqi, kci, vci = xs
        x, kci, vci = _layer(sem, lwi, lqi, x, pos, kci, vci, rows, valid,
                             quant)
        out = (kci, vci, None if keep_rows is None else x[keep_rows])
        return x, out

    x, (kc2, vc2, hs) = jax.lax.scan(body, x, (lw, lq, kc[lo:hi], vc[lo:hi]))
    return x, kc.at[lo:hi].set(kc2), vc.at[lo:hi].set(vc2), hs


def _head(sem, w, wq, x, quant):
    x = _rms(x, w["final_norm"], sem.rms_eps)
    return _mm(x, w["head"], wq.get("head"), quant)


def _conf(logits):
    return jnp.max(jax.nn.softmax(logits, axis=-1), axis=-1)


# ---------------------------------------------------------------------------
# the three pass kinds
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class RowState:
    kc: jax.Array        # [L, T, Hkv, Dh]
    vc: jax.Array
    hid: tuple           # per stage [lb, d]
    conf: jax.Array      # [lb]
    logits: jax.Array    # [lb, V] last computed logits per block position


def _prefill(sem, w, wq, tokens, valid, bs, quant):
    t, lb = sem.total, sem.block_length
    pos = jnp.arange(t, dtype=jnp.int32)
    x = w["embed"][tokens].astype(jnp.float32)
    cols = bs + jnp.arange(lb, dtype=jnp.int32)
    kc = jnp.zeros((sem.n_layers, t, sem.n_kv_heads, sem.head_dim), jnp.float32)
    x, kc, vc, hs = _layers(sem, w, wq, 0, sem.n_layers, x, pos, kc,
                            jnp.zeros_like(kc), pos, valid, quant,
                            keep_rows=cols)
    hid = tuple(hs[l] for l in sem.stage_layers)
    logits = _head(sem, w, wq, x[cols], quant)
    return kc, vc, hid, _conf(logits), logits


def _decode(sem, w, wq, kc, vc, hid, conf, logits_c, tok_blk, valid, bs,
            skip, quant):
    lb = sem.block_length
    rows = jnp.arange(lb, dtype=jnp.int32)
    x = w["embed"][tok_blk].astype(jnp.float32)
    hid = list(hid)
    lo = 0
    bounds = list(zip(sem.stage_layers, sem.keep)) + [(sem.n_layers - 1, None)]
    for i, (last, keep) in enumerate(bounds):
        x, kc, vc, _ = _layers(sem, w, wq, lo, last + 1, x, bs + rows, kc, vc,
                               bs + rows, valid, quant)
        lo = last + 1
        if keep is None:
            break
        h_old = hid[i][rows]
        var = jnp.sum(jnp.abs(x - h_old), -1) / (
            math.sqrt(sem.d_model) * jnp.sqrt(jnp.sum(h_old * h_old, -1)) + 1e-8)
        score = sem.alpha * conf[rows] + (1.0 - sem.alpha) * var
        hid[i] = hid[i].at[rows].set(x)
        if skip:
            _, sel = jax.lax.top_k(score, keep)
            rows, x = rows[sel], x[sel]
    logits = _head(sem, w, wq, x, quant)
    return (kc, vc, tuple(hid), conf.at[rows].set(_conf(logits)),
            logits_c.at[rows].set(logits))


def pass_kind(phase: int, block_refresh_period: int) -> str:
    """The pass a row runs at ``phase`` of its block (the served configs
    refresh the prompt only at a block's start)."""
    if phase == 0:
        return "prompt_refresh"
    bp = block_refresh_period
    return "block_refresh" if bp > 0 and phase % bp == 0 else "skip_decode"


class Reference:
    """Teacher-forced replay of served requests against the weights ``w``
    (``bench.weights.logical``).  ``quant`` ("int8" or "fp8") makes it the
    lower-precision control."""

    def __init__(self, sem: Semantics, w: dict, quant: Optional[str] = None):
        self.sem, self.w, self.quant = sem, w, quant
        self.wq = jax.jit(functools.partial(quantize_weights, quant=quant))(w) \
            if quant else {}
        self._prefill = jax.jit(functools.partial(_prefill, sem, quant=quant))
        self._decode = jax.jit(functools.partial(_decode, sem, quant=quant),
                               static_argnames=("skip",))

    def layout(self, prompt: np.ndarray, n_blocks: int):
        """(tokens [T] with the gen region masked, valid [T]) as served."""
        sem = self.sem
        start = sem.prompt_len - len(prompt)
        tokens = np.zeros(sem.total, np.int32)
        tokens[start:sem.prompt_len] = prompt
        tokens[sem.prompt_len:] = sem.vocab_size           # the mask id
        ps = sem.page_size
        end = min(sem.total, -(-(sem.prompt_len + n_blocks * sem.block_length)
                               // ps) * ps)
        valid = np.zeros(sem.total, bool)
        valid[start:end] = True
        return tokens, valid

    def pass_kind(self, phase: int) -> str:
        return pass_kind(phase, self.sem.block_refresh_period)

    def replay(self, prompt, n_blocks, blocks, others=()):
        """Replay one request.  ``blocks[b][j]`` lists the ``(offset,
        token)`` pairs committed at phase ``j`` of block ``b``.  Yields, per
        step, ``(kind, offsets, tokens, logits of this reference at those
        offsets, [same for each of others])`` so that a caller can compare
        several references step by step."""
        sem, lb = self.sem, self.sem.block_length
        tokens, valid = self.layout(np.asarray(prompt, np.int32), n_blocks)
        refs = (self,) + tuple(others)
        tok = jnp.asarray(tokens)
        valid = jnp.asarray(valid)
        for b, steps in enumerate(blocks):
            bs = sem.prompt_len + b * lb
            states = [None] * len(refs)
            for j, commits in enumerate(steps):
                kind = self.pass_kind(j)
                for r, ref in enumerate(refs):
                    if kind == "prompt_refresh":
                        states[r] = RowState(*ref._prefill(
                            ref.w, ref.wq, tok, valid, bs))
                    else:
                        s = states[r]
                        out = ref._decode(
                            ref.w, ref.wq, s.kc, s.vc, s.hid, s.conf,
                            s.logits, tok[bs:bs + lb], valid, bs,
                            skip=kind == "skip_decode")
                        states[r] = RowState(*out)
                offs = np.asarray([o for o, _ in commits], np.int32)
                toks = np.asarray([t for _, t in commits], np.int32)
                yield (kind, offs, toks,
                       [s.logits[offs] for s in states])
                tok = tok.at[bs + offs].set(toks)


def gaps(logits: jax.Array, toks: np.ndarray) -> np.ndarray:
    """Best logit minus the served token's logit, per committed position."""
    lg = np.asarray(logits, np.float64)
    return lg.max(axis=-1) - lg[np.arange(len(toks)), toks]


def control_gaps(ref_logits: jax.Array, low_logits: jax.Array) -> np.ndarray:
    """The gap, under the reference, of the token the lower precision puts
    first."""
    return gaps(ref_logits, np.asarray(jnp.argmax(low_logits, axis=-1)))
