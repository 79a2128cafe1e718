"""End-to-end system behaviour: the serving runtime's offline mode + checkpoint
round-trip through generation (deliverable b/c integration)."""
import dataclasses

import jax
import numpy as np
import pytest

from repro import configs
from repro.configs import GenerationConfig, SkipStage
from repro.models import build_model
from repro.launch import serve
from repro.runtime import Request, ShardedStreamScheduler, StreamScheduler
from repro.train import restore_checkpoint, save_checkpoint


@pytest.fixture(scope="module")
def served():
    cfg = configs.reduced(configs.get_config("llada-8b"))
    cfg = dataclasses.replace(cfg, n_layers=4)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    gen = GenerationConfig(gen_length=16, block_length=8, mode="es",
                           skip_stages=(SkipStage(1, 0.5),),
                           prompt_refresh_period=8, block_refresh_period=4)
    server = StreamScheduler(model, params, gen, max_slots=4, prompt_len=16)
    return cfg, model, params, server


def test_server_serves_batches(served):
    cfg, model, params, server = served
    rng = np.random.default_rng(0)
    for _ in range(6):   # more requests than slots -> slots recycle
        plen = int(rng.integers(4, 17))
        server.submit(Request(prompt=rng.integers(3, cfg.vocab_size, plen).astype(np.int32)))
    done = server.drain()
    assert len(done) == 6
    for r in done:
        assert r.output is not None and r.output.shape == (16,)
        assert (r.output < cfg.vocab_size).all()
        assert r.latency_s > 0
    assert server.stats.requests == 6
    assert server.stats.tokens_generated == 96
    assert server.stats.tps > 0


@pytest.mark.parametrize("shards", [1, 2])
def test_build_server_follows_shards(served, shards):
    """``serve.build_server`` gives one ``StreamScheduler`` for one shard
    and a ``ShardedStreamScheduler`` of that many lanes otherwise; either
    serves a queue to completion."""
    cfg, model, params, _ = served
    args = serve.build_parser().parse_args(
        ["--paged", "--early-advance", "--shards", str(shards), "--batch",
         "4", "--prompt-len", "16", "--gen-length", "16", "--block-length",
         "8", "--page-size", "8"])
    serve.validate(args)
    server = serve.build_server(args, model, params,
                                serve.generation_config(args, cfg))
    if shards == 1:
        assert type(server) is StreamScheduler
    else:
        assert isinstance(server, ShardedStreamScheduler)
        assert len(server.lanes) == shards
    rng = np.random.default_rng(1)
    for _ in range(3):
        server.submit(Request(prompt=rng.integers(3, cfg.vocab_size, 12)
                              .astype(np.int32)))
    done = server.drain()
    assert len(done) == 3 and all(r.output.shape == (16,) for r in done)

def test_generation_stable_through_checkpoint(served, tmp_path):
    cfg, model, params, server = served
    path = str(tmp_path / "ck.npz")
    save_checkpoint(path, params, step=1)
    params2, _ = restore_checkpoint(path, params)

    gen = GenerationConfig(gen_length=8, block_length=8, mode="dualcache",
                           prompt_refresh_period=0, block_refresh_period=1)
    from repro.core import make_engine
    eng = make_engine(model, gen)
    prompt = jax.numpy.asarray(np.arange(3, 15, dtype=np.int32)[None])
    a = np.asarray(eng.generate(params, prompt, jax.random.PRNGKey(5)))
    b = np.asarray(eng.generate(params2, prompt, jax.random.PRNGKey(5)))
    np.testing.assert_array_equal(a, b)
