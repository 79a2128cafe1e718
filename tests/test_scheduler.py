"""Continuous-batching scheduler invariants.

Key invariants:
  * greedy (temp-0) streaming output is TOKEN-FOR-TOKEN equal to the offline
    ``engine.generate`` for the same prompts — rows are computation-
    independent, so co-resident traffic must not perturb a request;
  * slots recycle under staggered arrivals, the jitted ``engine.step``
    traces exactly once across mixed prefill/decode/idle slot phases;
  * streaming callbacks deliver each request's blocks in order, exactly once;
  * stats count only real requests when slots outnumber traffic (padded
    tail), and only requested tokens for short (max_new_tokens) requests.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro import configs
from repro.configs import GenerationConfig, SkipStage
from repro.core import make_engine
from repro.models import build_model
from repro.runtime import Request, StreamScheduler
from repro.runtime.request import pad_and_stack

PROMPT_LEN = 16
GEN = dict(gen_length=16, block_length=8)


@pytest.fixture(scope="module")
def served():
    cfg = configs.reduced(configs.get_config("llada-8b"))
    cfg = dataclasses.replace(cfg, n_layers=4)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    gen = GenerationConfig(mode="es", skip_stages=(SkipStage(1, 0.5),),
                           prompt_refresh_period=8, block_refresh_period=4, **GEN)
    return cfg, model, params, gen


def _requests(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(3, cfg.vocab_size,
                                        int(rng.integers(4, PROMPT_LEN + 1))
                                        ).astype(np.int32))
            for _ in range(n)]


def _offline_reference(model, params, gen, reqs):
    eng = make_engine(model, gen)
    prompts = pad_and_stack(reqs, 0, PROMPT_LEN)
    return np.asarray(eng.generate(params, jax.numpy.asarray(prompts),
                                   jax.random.PRNGKey(1)))


def test_stream_equals_offline_generate(served):
    """Continuous batching must not change what any request decodes to."""
    cfg, model, params, gen = served
    reqs = _requests(cfg, 5)
    sched = StreamScheduler(model, params, gen, max_slots=4,
                            prompt_len=PROMPT_LEN)
    for r in reqs:
        sched.submit(r)
    done = sched.drain()
    assert len(done) == 5
    ref = _offline_reference(model, params, gen, reqs)
    by_id = {r.request_id: r.output for r in done}
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(by_id[r.request_id], ref[i, PROMPT_LEN:])


def test_slot_recycling_staggered_arrivals(served):
    """Arrivals trickle in mid-flight: slots recycle, outputs still match
    the offline reference, and the jitted step compiled exactly once."""
    cfg, model, params, gen = served
    reqs = _requests(cfg, 6, seed=3)
    sched = StreamScheduler(model, params, gen, max_slots=2,
                            prompt_len=PROMPT_LEN)
    it = iter(reqs)
    for r in (next(it), next(it)):
        sched.submit(r)
    max_seen = 0
    while sched.has_work():
        sched.step()
        # stagger: trickle one new request per engine iteration
        nxt = next(it, None)
        if nxt is not None:
            sched.submit(nxt)
        max_seen = max(max_seen, sum(r is not None for r in sched.slot_req))
    done = sched.drain()
    assert len(done) == 6
    assert max_seen == 2, "both slots must have been resident at once"
    assert sched.engine.step_trace_count == 1, \
        "mixed slot phases must reuse ONE compiled step program"
    ref = _offline_reference(model, params, gen, reqs)
    by_id = {r.request_id: r.output for r in done}
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(by_id[r.request_id], ref[i, PROMPT_LEN:])


def test_streaming_callback_ordering(served):
    """Every request streams block 0, 1, ... exactly once, in order, and the
    streamed blocks concatenate to the final output."""
    cfg, model, params, gen = served
    reqs = _requests(cfg, 3, seed=5)
    events: dict[int, list] = {r.request_id: [] for r in reqs}

    def cb(req, bi, blk):
        events[req.request_id].append((bi, blk))

    sched = StreamScheduler(model, params, gen, max_slots=2,
                            prompt_len=PROMPT_LEN, stream_cb=cb)
    for r in reqs:
        sched.submit(r)
    done = sched.drain()
    n_blocks = gen.gen_length // gen.block_length
    for r in done:
        evs = events[r.request_id]
        assert [bi for bi, _ in evs] == list(range(n_blocks))
        streamed = np.concatenate([blk for _, blk in evs])
        np.testing.assert_array_equal(streamed, r.output)
        assert (r.output < cfg.vocab_size).all(), "mask leaked into stream"


def test_stats_with_padded_tail(served):
    """Fewer requests than slots: idle slots must not inflate stats."""
    cfg, model, params, gen = served
    reqs = _requests(cfg, 3, seed=9)
    sched = StreamScheduler(model, params, gen, max_slots=4,
                            prompt_len=PROMPT_LEN)
    for r in reqs:
        sched.submit(r)
    done = sched.drain()
    assert len(done) == 3
    s = sched.stats
    assert s.submitted == 3 and s.completed == 3
    assert s.tokens_out == 3 * gen.gen_length
    assert len(s.latencies_s) == 3
    assert s.goodput > 0 and s.wall_s > 0
    assert s.latency_pct(50) <= s.latency_pct(95)
    for r in done:
        assert r.latency_s >= r.service_s > 0
        assert r.tps() > 0


def test_short_request_prefix_and_accounting(served):
    """max_new_tokens requests finish early, free their slot, count only the
    requested tokens, and equal the offline generation's block prefix."""
    cfg, model, params, gen = served
    reqs = _requests(cfg, 2, seed=11)
    reqs[0].max_new_tokens = gen.block_length          # 1 of 2 blocks
    sched = StreamScheduler(model, params, gen, max_slots=2,
                            prompt_len=PROMPT_LEN)
    for r in reqs:
        sched.submit(r)
    done = sched.drain()
    by_id = {r.request_id: r for r in done}
    short = by_id[reqs[0].request_id]
    assert short.output.shape == (gen.block_length,)
    ref = _offline_reference(model, params, gen, reqs)
    np.testing.assert_array_equal(
        short.output, ref[0, PROMPT_LEN:PROMPT_LEN + gen.block_length])
    assert sched.stats.tokens_out == gen.block_length + gen.gen_length


def test_modality_mismatch_rejected_at_submit(served):
    cfg, model, params, gen = served
    sched = StreamScheduler(model, params, gen, max_slots=2,
                            prompt_len=PROMPT_LEN)
    bad = Request(prompt=np.arange(3, 9, dtype=np.int32),
                  enc_embeds=np.zeros((4, cfg.d_model), np.float32))
    with pytest.raises(ValueError, match="modality"):
        sched.submit(bad)


def test_encoder_family_streams(served):
    """Encoder-conditioned arch: enc_embeds are encoded once at admission
    into the device-resident slot buffer and the step still traces once."""
    cfg = configs.reduced(configs.get_config("seamless-m4t-large-v2"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    gen = GenerationConfig(gen_length=8, block_length=8, mode="dualcache",
                           prompt_refresh_period=0, block_refresh_period=1)
    sched = StreamScheduler(model, params, gen, max_slots=2, prompt_len=8)
    rng = np.random.default_rng(0)
    for _ in range(3):
        sched.submit(Request(
            prompt=rng.integers(3, cfg.vocab_size, 6).astype(np.int32),
            enc_embeds=rng.normal(size=(cfg.n_enc_tokens, cfg.d_enc)
                                  ).astype(np.float32)))
    done = sched.drain()
    assert len(done) == 3
    assert all((r.output < cfg.vocab_size).all() for r in done)
    assert sched.engine.step_trace_count == 1
    with pytest.raises(ValueError, match="modality"):
        sched.submit(Request(prompt=np.arange(3, 9, dtype=np.int32)))
