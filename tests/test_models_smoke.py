"""Per-architecture smoke tests (deliverable f): for each assigned arch, a
REDUCED family-preserving variant runs one forward and one train step on CPU
with shape + finiteness assertions."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import build_model
from repro.train import OptimizerConfig, init_train_state, make_train_step

ALL_ARCHS = configs.ASSIGNED_ARCHS + configs.PAPER_ARCHS


def _enc(cfg, key, b):
    if cfg.family in ("audio", "vlm"):
        return jax.random.normal(key, (b, cfg.n_enc_tokens, cfg.d_enc or cfg.d_model))
    return None


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_forward_shapes_and_finite(arch, rng):
    cfg = configs.reduced(configs.get_config(arch))
    model = build_model(cfg)
    params = model.init(rng)
    b, l = 2, 24
    tokens = jax.random.randint(rng, (b, l), 0, cfg.vocab_size)
    logits, aux = model.forward(params, tokens, enc_embeds=_enc(cfg, rng, b))
    from repro.models.common import padded_vocab
    assert logits.shape == (b, l, padded_vocab(cfg))
    assert bool(jnp.all(jnp.isfinite(logits)))
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_mask_token_has_an_embedding_row(arch):
    """The engines' mask id is ``vocab_size``: the padded table must hold
    it at published widths too (an out-of-range id gathers NaN)."""
    from repro.configs import GenerationConfig
    from repro.core import make_engine
    from repro.models.common import padded_vocab
    cfg = configs.get_config(arch)
    engine = make_engine(build_model(cfg), GenerationConfig(
        mode="dualcache", gen_length=32, block_length=32))
    assert cfg.vocab_size <= engine.mask_id < padded_vocab(cfg)
    assert padded_vocab(cfg) % 256 == 0


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_one_train_step(arch, rng):
    cfg = configs.reduced(configs.get_config(arch))
    model = build_model(cfg)
    state = init_train_state(model, rng)
    step = jax.jit(make_train_step(model, OptimizerConfig(total_steps=10,
                                                          warmup_steps=1),
                                   ce_chunk=8))
    b, l = 2, 16
    batch = {
        "tokens": jax.random.randint(rng, (b, l), 0, cfg.vocab_size),
        "loss_region": jnp.ones((b, l), bool).at[:, :4].set(False),
    }
    enc = _enc(cfg, rng, b)
    if enc is not None:
        batch["enc_embeds"] = enc
    new_state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    # params actually changed
    before = jax.tree_util.tree_leaves(state.params)[3]
    after = jax.tree_util.tree_leaves(new_state.params)[3]
    assert not np.allclose(np.asarray(before), np.asarray(after))


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "gemma3-1b", "jamba-v0.1-52b"])
def test_full_config_validates(arch):
    cfg = configs.get_config(arch)
    cfg.validate()
    model = build_model(cfg)
    assert model.n_groups * model.period == cfg.n_layers


def test_all_full_configs_construct():
    for arch in ALL_ARCHS:
        cfg = configs.get_config(arch)
        model = build_model(cfg)
        # param struct materializes without allocation
        struct = jax.eval_shape(lambda m=model: m.init(jax.random.PRNGKey(0)))
        n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(struct))
        assert n > 1e6
