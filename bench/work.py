"""The work ES-dLLM needs, in floating-point operations, from shapes.

Counted per resident row by its own pass kind, whatever implements it:

* prompt refresh: the row's real prompt plus its output extent, through
  every layer, attending that many positions; the head on the block;
* block refresh: the block's positions through every layer; the head on
  the block;
* skip decode: the block's positions through the layers up to the first
  skip stage, then only the kept positions after each stage; the head on
  the last kept set.

Attention is counted at the row's real context.  Padding, rows swept up
by a pass of another kind, and empty slots count as no work.  A multiply
and an add are two operations.
"""
from __future__ import annotations


def layer_flops_per_token(m: dict) -> int:
    d, h, hkv, dh, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                        m["head_dim"], m["d_ff"])
    return 2 * (d * h * dh + 2 * d * hkv * dh + h * dh * d + 3 * d * f)


def attention_flops_per_token(m: dict, context: int) -> int:
    return 2 * 2 * context * m["n_heads"] * m["head_dim"]


def head_flops_per_row(m: dict) -> int:
    return 2 * m["d_model"] * m["vocab_size"]


def rows_per_layer(m: dict, es: dict, block: int) -> list[int]:
    """Block positions each layer computes in a skip-decode step."""
    rows, n, bounds = [], block, dict(zip(es["stage_layers"], es["keep"]))
    for layer in range(m["n_layers"]):
        rows.append(n)
        n = bounds.get(layer, n)
    return rows


def step_flops(m: dict, es: dict, kind: str, prompt_tokens: int,
               n_blocks: int, block: int) -> int:
    """Operations one row of ``kind`` needs in one step."""
    ctx = prompt_tokens + n_blocks * block
    per_tok = layer_flops_per_token(m) + attention_flops_per_token(m, ctx)
    if kind == "prompt_refresh":
        return m["n_layers"] * ctx * per_tok + block * head_flops_per_row(m)
    if kind == "block_refresh":
        return m["n_layers"] * block * per_tok + block * head_flops_per_row(m)
    if kind == "skip_decode":
        rows = rows_per_layer(m, es, block)
        return sum(rows) * per_tok + es["keep"][-1] * head_flops_per_row(m)
    raise ValueError(f"unknown pass kind {kind!r}")
