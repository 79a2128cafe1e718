"""bench/reference.py against the program at reduced sizes on the CPU:
the full forward's logits directly, for both architectures."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference as refmod
from bench import weights as wmod


def reduced(arch):
    from repro import configs
    cfg = configs.reduced(configs.get_config(arch))
    m = {k: getattr(cfg, k) for k in
         ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
          "vocab_size", "qkv_bias", "rope_theta", "rms_eps", "param_dtype")}
    return cfg, m


@pytest.mark.parametrize("arch", ["llada-8b", "dream-7b"])
def test_full_forward_logits_match_the_program(arch):
    from repro.models import build_model
    cfg, m = reduced(arch)
    model = build_model(cfg)
    seed = 2**40 + 3
    w = wmod.make_logical(m, seed)
    params = wmod.make_program_params(m, seed, model)
    es = {"stage_layers": [0, 1], "keep": [8, 4], "block_refresh_period": 4,
          "alpha": 0.5}
    sem = refmod.semantics({"model": m, "es": es},
                           {"prompt_len": 24, "gen_length": 32,
                            "block_length": 16, "page_size": 8})
    rng = np.random.default_rng(0)
    prompt = rng.integers(3, m["vocab_size"], 20).astype(np.int32)
    ref = refmod.Reference(sem, w)
    tokens, valid = ref.layout(prompt, 2)
    # a few generated tokens already committed in the first block
    tokens[24:28] = rng.integers(3, m["vocab_size"], 4)
    bs = 24
    *_, logits = ref._prefill(w, {}, jnp.asarray(tokens), jnp.asarray(valid), bs)
    real = tokens[valid]                     # the program sees no padding
    got, _ = model.forward(params, jnp.asarray(real)[None])
    start = int(np.argmax(valid))
    got = np.asarray(got[0, bs - start: bs - start + 16, : m["vocab_size"]])
    # RoPE is relative, so positions shifted by the pad agree to rounding
    np.testing.assert_allclose(np.asarray(logits), got, rtol=0, atol=2e-4)
    assert float(np.abs(got).max()) > 0.05


def test_layout_follows_page_rounding():
    m = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 1,
         "head_dim": 4, "d_ff": 8, "vocab_size": 11, "qkv_bias": False,
         "rope_theta": 1e4, "rms_eps": 1e-5, "param_dtype": "float32"}
    sem = refmod.semantics(
        {"model": m, "es": {"stage_layers": [0], "keep": [2],
                            "block_refresh_period": 4, "alpha": 0.5}},
        {"prompt_len": 16, "gen_length": 32, "block_length": 8,
         "page_size": 16})
    ref = refmod.Reference(sem, wmod.make_logical(m, 1))
    tokens, valid = ref.layout(np.arange(3, 8, dtype=np.int32), 1)
    # prompt at 11..15; one block ends at 24, the page at 32
    assert list(np.nonzero(valid)[0]) == list(range(11, 32))
    assert (tokens[16:] == 11).all() and list(tokens[11:16]) == [3, 4, 5, 6, 7]
    assert [ref.pass_kind(j) for j in range(6)] == [
        "prompt_refresh", "skip_decode", "skip_decode", "skip_decode",
        "block_refresh", "skip_decode"]


def test_gap_helpers():
    lg = jnp.asarray([[0.0, 2.0, 1.0], [3.0, 0.5, 0.0]])
    np.testing.assert_allclose(refmod.gaps(lg, np.array([2, 0])), [1.0, 0.0])
    low = jnp.asarray([[0.0, 0.0, 5.0], [0.0, 9.0, 0.0]])
    np.testing.assert_allclose(refmod.control_gaps(lg, low), [1.0, 2.5])


@pytest.mark.parametrize("quant", ["int8", "fp8"])
def test_control_rounds_weights(quant):
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 32)) * 0.02
    v, s = refmod._split(x, -2, quant)
    back = np.asarray(v.astype(jnp.float32) * s)
    err = np.abs(back - np.asarray(x)).max() / float(jnp.abs(x).max())
    assert 0 < err < (1 / 127 if quant == "int8" else 1 / 8)
