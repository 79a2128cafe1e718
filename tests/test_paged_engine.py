"""Paged-KV engine + scheduler invariants, and per-slot RNG replay.

Key invariants:
  * greedy ``generate()`` is BIT-IDENTICAL dense-vs-paged (the XLA paged
    lowering gathers mapped pages and reuses the dense chunked attention);
  * the paged scheduler admits on page availability (actual prompt length,
    not the padded worst case), recycles pages the moment a request
    retires, and still traces ``engine.step`` exactly once;
  * a pool HALF the dense-equivalent size still completes all traffic —
    slot count is decoupled from worst-case sequence length;
  * sampled (temperature > 0) generation under continuous batching is
    bit-equal to its offline replay: draws use a per-row
    ``fold_in(base_key, slot_iters)`` chain, independent of co-resident
    traffic (ROADMAP open item).
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro import configs
from repro.configs import GenerationConfig, SkipStage
from repro.core import make_engine
from repro.core.engine import DiffusionEngine
from repro.models import build_model
from repro.runtime import Request, StreamScheduler
from repro.runtime.request import pad_and_stack

PROMPT_LEN = 16
GEN = dict(gen_length=16, block_length=8)
PS = 8                              # page size; t_total = 32 -> 4 vpages
N_VP = (PROMPT_LEN + GEN["gen_length"]) // PS


@pytest.fixture(scope="module")
def small_model():
    cfg = configs.reduced(configs.get_config("llada-8b"))
    cfg = dataclasses.replace(cfg, n_layers=4)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _es_cfg(**kw):
    base = dict(mode="es", skip_stages=(SkipStage(1, 0.5),),
                prompt_refresh_period=8, block_refresh_period=4, **GEN)
    base.update(kw)
    return GenerationConfig(**base)


def _requests(cfg, n, seed=0, full=False):
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(
        3, cfg.vocab_size,
        PROMPT_LEN if full else int(rng.integers(4, PROMPT_LEN + 1))
    ).astype(np.int32)) for _ in range(n)]


# ---------------------------------------------------------------------------
# offline: dense vs paged bit-identity
# ---------------------------------------------------------------------------


def test_paged_generate_bit_identical_to_dense(small_model):
    cfg, model, params = small_model
    prompt = jax.random.randint(jax.random.PRNGKey(7), (2, PROMPT_LEN),
                                0, cfg.vocab_size)
    g = _es_cfg(skip_stages=(SkipStage(1, .5), SkipStage(2, .5)))
    dense = np.asarray(DiffusionEngine(model, g)
                       .generate(params, prompt, jax.random.PRNGKey(1)))
    paged = np.asarray(DiffusionEngine(model, g, paged=True, page_size=PS)
                       .generate(params, prompt, jax.random.PRNGKey(1)))
    np.testing.assert_array_equal(dense, paged)


def test_paged_int8_generate_matches_dense_int8(small_model):
    cfg, model, params = small_model
    prompt = jax.random.randint(jax.random.PRNGKey(7), (2, PROMPT_LEN),
                                0, cfg.vocab_size)
    g = _es_cfg()
    dense = np.asarray(DiffusionEngine(model, g, kv_cache_dtype="int8")
                       .generate(params, prompt, jax.random.PRNGKey(1)))
    paged = np.asarray(
        DiffusionEngine(model, g, paged=True, page_size=PS,
                        kv_cache_dtype="int8")
        .generate(params, prompt, jax.random.PRNGKey(1)))
    np.testing.assert_array_equal(dense, paged)


def test_paged_pallas_engine_agrees(small_model):
    """The paged Pallas kernel (interpret mode) drives a full generation and
    matches the paged XLA path token-for-token (f32 tolerances permitting)."""
    cfg, model, params = small_model
    prompt = jax.random.randint(jax.random.PRNGKey(7), (2, PROMPT_LEN),
                                0, cfg.vocab_size)
    g = _es_cfg()
    a = np.asarray(DiffusionEngine(model, g, paged=True, page_size=PS)
                   .generate(params, prompt, jax.random.PRNGKey(1)))
    b = np.asarray(
        DiffusionEngine(model, g, paged=True, page_size=PS,
                        attn_impl="pallas")
        .generate(params, prompt, jax.random.PRNGKey(1)))
    agreement = (a == b).mean()
    assert agreement > 0.95, f"paged pallas diverged: {agreement}"


def test_paged_sparse_attention_runs(small_model):
    """Sparse-dLLM eviction probes the KV cache directly — the paged path
    must gather the pool through the block table for the probe."""
    cfg, model, params = small_model
    prompt = jax.random.randint(jax.random.PRNGKey(7), (2, PROMPT_LEN),
                                0, cfg.vocab_size)
    g = _es_cfg(sparse_attention=True, sparse_retention=0.5)
    dense = np.asarray(DiffusionEngine(model, g)
                       .generate(params, prompt, jax.random.PRNGKey(1)))
    paged = np.asarray(DiffusionEngine(model, g, paged=True, page_size=PS)
                       .generate(params, prompt, jax.random.PRNGKey(1)))
    np.testing.assert_array_equal(dense, paged)


# ---------------------------------------------------------------------------
# serving: page-gated admission + recycling
# ---------------------------------------------------------------------------


def test_paged_stream_equals_offline_and_recycles_pages(small_model):
    cfg, model, params = small_model
    gen = _es_cfg()
    reqs = _requests(cfg, 5, seed=3, full=True)
    sched = StreamScheduler(model, params, gen, max_slots=2,
                            prompt_len=PROMPT_LEN, paged=True, page_size=PS)
    for r in reqs:
        sched.submit(r)
    done = sched.drain()
    assert len(done) == 5
    assert sched.engine.step_trace_count == 1, \
        "paged serving must reuse ONE compiled step program"
    assert sched.stats.pages_in_use == 0, "retired slots must return pages"
    assert sched.stats.gauges()["pages_total"] == 2 * N_VP
    eng = make_engine(model, gen)
    ref = np.asarray(eng.generate(
        params, jax.numpy.asarray(pad_and_stack(reqs, 0, PROMPT_LEN)),
        jax.random.PRNGKey(0)))
    by_id = {r.request_id: r.output for r in done}
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(by_id[r.request_id], ref[i, PROMPT_LEN:])


def test_page_gated_admission_half_pool(small_model):
    """A pool HALF the dense-equivalent size (4 slots but pages for ~2 full
    requests) still completes all traffic: admission waits for pages, FIFO
    order is preserved, and the peak gauge respects the pool bound."""
    cfg, model, params = small_model
    gen = _es_cfg()
    reqs = _requests(cfg, 6, seed=5)
    pool_pages = 2 * N_VP + 1
    sched = StreamScheduler(model, params, gen, max_slots=4,
                            prompt_len=PROMPT_LEN, paged=True, page_size=PS,
                            kv_pages=pool_pages)
    for r in reqs:
        sched.submit(r)
    done = sched.drain()
    assert len(done) == 6
    assert sched.stats.peak_pages_in_use <= pool_pages - 1
    assert sched.stats.pages_in_use == 0
    order = [r.request_id for r in done]
    assert order == sorted(order), "page gating must not reorder FIFO traffic"
    for r in done:
        assert (r.output < cfg.vocab_size).all()


def test_paged_short_request_equals_truncated_offline(small_model):
    """The paged replay contract for max_new_tokens requests: unmapped
    trailing pages mean the request never attends the mask region beyond its
    last block, so it decodes exactly like an offline run with
    gen_length = requested blocks (dense serving attends the padded tail and
    legitimately differs — see StreamScheduler._pages_needed)."""
    cfg, model, params = small_model
    gen = _es_cfg()
    rng = np.random.default_rng(17)
    req = Request(prompt=rng.integers(3, cfg.vocab_size, 12).astype(np.int32),
                  max_new_tokens=GEN["block_length"])       # 1 of 2 blocks
    sched = StreamScheduler(model, params, gen, max_slots=1,
                            prompt_len=PROMPT_LEN, paged=True, page_size=PS)
    sched.submit(req)
    done = sched.drain()
    assert len(done) == 1
    short_gen = _es_cfg(gen_length=GEN["block_length"])
    eng = DiffusionEngine(model, short_gen, paged=True, page_size=PS)
    prompts = jax.numpy.asarray(pad_and_stack([req], 0, PROMPT_LEN))
    ref = np.asarray(eng.generate(
        params, prompts, jax.random.PRNGKey(0),
        prompt_start=jax.numpy.asarray([PROMPT_LEN - 12])))
    np.testing.assert_array_equal(done[0].output, ref[0, PROMPT_LEN:])


def test_short_prompts_map_fewer_pages(small_model):
    """Admission accounting uses the request's ACTUAL prompt length: a
    short-prompt short-output request must map fewer pages than the padded
    worst case (that headroom is the paged capacity win)."""
    cfg, model, params = small_model
    gen = _es_cfg()
    rng = np.random.default_rng(11)
    short = Request(prompt=rng.integers(3, cfg.vocab_size, 4).astype(np.int32),
                    max_new_tokens=GEN["block_length"])
    sched = StreamScheduler(model, params, gen, max_slots=1,
                            prompt_len=PROMPT_LEN, paged=True, page_size=PS)
    sched.submit(short)
    done = sched.drain()
    assert len(done) == 1
    # prompt_start = 12 -> first vpage 1; 1 block -> last vpage 3: 2 pages
    assert sched.stats.peak_pages_in_use == 2 < N_VP
    assert done[0].output.shape == (GEN["block_length"],)
    assert (done[0].output < cfg.vocab_size).all()


def test_paged_sparse_serving_matches_offline_ragged_prompts(small_model):
    """Sparse eviction + paged pool + RAGGED prompts: unmapped pages and pad
    rows must stay out of the eviction probe's softmax and retention ranking
    (their gathered K rows are garbage-page content), so paged serving equals
    the offline paged generation with matching prompt_start."""
    cfg, model, params = small_model
    gen = _es_cfg(sparse_attention=True, sparse_retention=0.5)
    reqs = _requests(cfg, 3, seed=21)             # ragged prompt lengths
    sched = StreamScheduler(model, params, gen, max_slots=3,
                            prompt_len=PROMPT_LEN, paged=True, page_size=PS)
    for r in reqs:
        sched.submit(r)
    done = sched.drain()
    assert len(done) == 3
    starts = jax.numpy.asarray(
        [PROMPT_LEN - min(len(r.prompt), PROMPT_LEN) for r in reqs])
    eng = DiffusionEngine(model, gen, paged=True, page_size=PS)
    ref = np.asarray(eng.generate(
        params, jax.numpy.asarray(pad_and_stack(reqs, 0, PROMPT_LEN)),
        jax.random.PRNGKey(0), prompt_start=starts))
    by_id = {r.request_id: r.output for r in done}
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(by_id[r.request_id], ref[i, PROMPT_LEN:])


# ---------------------------------------------------------------------------
# per-slot RNG: sampled continuous batching == offline replay
# ---------------------------------------------------------------------------


def test_sampled_stream_equals_offline_replay(small_model):
    """temperature > 0 under continuous batching with STAGGERED arrivals:
    per-row fold_in(fold_in(base_key, seed), slot_iters) key chains make
    every request's sampling stream depend only on its own seed and
    progress, so outputs are bit-equal to the offline generate() of the
    same prompts with the same per-request seeds."""
    cfg, model, params = small_model
    gen = GenerationConfig(mode="dualcache", temperature=0.8,
                           prompt_refresh_period=0, block_refresh_period=1,
                           **GEN)
    reqs = _requests(cfg, 5, seed=9)
    for i, r in enumerate(reqs):
        r.sample_seed = 100 + i
    sched = StreamScheduler(model, params, gen, max_slots=2,
                            prompt_len=PROMPT_LEN, seed=0)
    it = iter(reqs)
    for r in (next(it), next(it)):
        sched.submit(r)
    while sched.has_work():
        sched.step()
        nxt = next(it, None)
        if nxt is not None:
            sched.submit(nxt)          # trickle: slots sit on different iters
    done = sched.drain()
    assert len(done) == 5
    eng = make_engine(model, gen)
    ref = np.asarray(eng.generate(
        params, jax.numpy.asarray(pad_and_stack(reqs, 0, PROMPT_LEN)),
        jax.random.PRNGKey(0),
        sample_seeds=jax.numpy.asarray([r.sample_seed for r in reqs])))
    by_id = {r.request_id: r.output for r in done}
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(
            by_id[r.request_id], ref[i, PROMPT_LEN:],
            err_msg=f"sampled replay diverged for request {i}")


def test_early_advance_greedy_equals_offline_replay(small_model):
    """Per-row cadence + early block advance (parallel decoding finishes
    blocks in ~1 iteration): every request's greedy output must be
    BIT-IDENTICAL to its offline generate() — early advance only removes
    the dead iterations after blk_done, which never touched tokens or
    kv_valid — and the mixed-mode step still traces exactly once."""
    cfg, model, params = small_model
    gen = _es_cfg(parallel_decoding=True, pd_threshold=0.0)
    reqs = _requests(cfg, 5, seed=13, full=True)
    sched = StreamScheduler(model, params, gen, max_slots=2,
                            prompt_len=PROMPT_LEN, paged=True, page_size=PS,
                            early_advance=True)
    it = iter(reqs)
    for r in (next(it), next(it)):
        sched.submit(r)
    while sched.has_work():
        sched.step()
        nxt = next(it, None)
        if nxt is not None:
            sched.submit(nxt)          # mid-cycle admissions at any phase
    done = sched.drain()
    assert len(done) == 5
    assert sched.engine.step_trace_count == 1, \
        "mixed-mode rows must reuse ONE compiled step program"
    assert sched.stats.early_advances > 0, \
        "1-iteration blocks must advance before the aligned boundary"
    assert sched.stats.pages_in_use == 0
    eng = DiffusionEngine(model, gen, paged=True, page_size=PS)
    ref = np.asarray(eng.generate(
        params, jax.numpy.asarray(pad_and_stack(reqs, 0, PROMPT_LEN)),
        jax.random.PRNGKey(0)))
    by_id = {r.request_id: r.output for r in done}
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(
            by_id[r.request_id], ref[i, PROMPT_LEN:],
            err_msg=f"early advance changed greedy output of request {i}")


def test_early_advance_sampled_equals_offline_replay(small_model):
    """Sampled (temperature > 0) + early advance: the lifetime iteration
    counter JUMPS to blocks_done * steps_per_block at each advance, exactly
    the offline numbering, so per-seed draw chains replay bit-identically
    no matter how many dead iterations were skipped."""
    cfg, model, params = small_model
    gen = GenerationConfig(mode="dualcache", temperature=0.8,
                           parallel_decoding=True, pd_threshold=0.0,
                           prompt_refresh_period=0, block_refresh_period=1,
                           **GEN)
    reqs = _requests(cfg, 5, seed=15)
    for i, r in enumerate(reqs):
        r.sample_seed = 300 + i
    sched = StreamScheduler(model, params, gen, max_slots=2,
                            prompt_len=PROMPT_LEN, seed=0, early_advance=True)
    it = iter(reqs)
    for r in (next(it), next(it)):
        sched.submit(r)
    while sched.has_work():
        sched.step()
        nxt = next(it, None)
        if nxt is not None:
            sched.submit(nxt)
    done = sched.drain()
    assert len(done) == 5
    assert sched.stats.early_advances > 0
    eng = make_engine(model, gen)
    ref = np.asarray(eng.generate(
        params, jax.numpy.asarray(pad_and_stack(reqs, 0, PROMPT_LEN)),
        jax.random.PRNGKey(0),
        sample_seeds=jax.numpy.asarray([r.sample_seed for r in reqs])))
    by_id = {r.request_id: r.output for r in done}
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(
            by_id[r.request_id], ref[i, PROMPT_LEN:],
            err_msg=f"early-advance sampled replay diverged for request {i}")



@pytest.mark.parametrize("temperature", [0.0, 0.7])
def test_early_advance_serves_in_fewer_steps(small_model, temperature):
    """One arrival schedule in engine steps (a request every 3 steps,
    lengths of 1, 2 or 4 blocks), parallel decoding at threshold 0 so a
    block unmasks in one iteration, 4 slots at equal pool pages: early
    advance serves it in strictly fewer ``step()`` calls than the aligned
    cadence, which idles every slot out to the block boundary, and every
    request's output is the same under both."""
    cfg, model, params = small_model
    plen, gen_length = 24, 32           # t_total = 56 -> 7 vpages a slot
    gen = _es_cfg(gen_length=gen_length, parallel_decoding=True,
                  pd_threshold=0.0, temperature=temperature,
                  skip_stages=(SkipStage(1, 0.5), SkipStage(2, 0.5)))
    served = {}
    for early in (False, True):
        rng = np.random.default_rng(0)
        reqs = [Request(prompt=rng.integers(
                    3, cfg.vocab_size, int(rng.integers(8, plen + 1))
                ).astype(np.int32), max_new_tokens=(1, 2, 4, 1, 2)[i % 5] * 8,
                    sample_seed=i) for i in range(8)]
        sched = StreamScheduler(model, params, gen, max_slots=4,
                                prompt_len=plen, paged=True, page_size=PS,
                                kv_pages=4 * (plen + gen_length) // PS + 1,
                                early_advance=early)
        pending, tick, steps = list(reqs), 0, 0
        while pending or sched.has_work():
            while pending and 3 * (len(reqs) - len(pending)) <= tick:
                sched.submit(pending.pop(0))
            steps += sched.step()
            tick += 1
        assert sched.stats.completed == len(reqs)
        served[early] = steps, [r.output.tolist() for r in reqs]
    assert served[True][0] < served[False][0]
    assert served[True][1] == served[False][1]

def test_mid_cycle_admission_bit_identity(small_model):
    """Any-iteration admission WITHOUT parallel decoding: full-length blocks
    mean admitted rows prefill (phase 0) while residents sit mid-block in
    skip/refresh modes — the mixed-mode masks must keep every row's
    trajectory exactly its offline one."""
    cfg, model, params = small_model
    gen = _es_cfg()                     # es mode: skip + block/prompt refresh
    reqs = _requests(cfg, 6, seed=19, full=True)
    sched = StreamScheduler(model, params, gen, max_slots=3,
                            prompt_len=PROMPT_LEN, paged=True, page_size=PS,
                            early_advance=True)
    it = iter(reqs)
    sched.submit(next(it))
    phases_seen = set()
    while sched.has_work():
        phases_seen.update(np.asarray(sched.state.phase)[
            np.asarray(sched.state.active)].tolist())
        sched.step()
        nxt = next(it, None)
        if nxt is not None:
            sched.submit(nxt)          # one admission per iteration
    done = sched.drain()
    assert len(done) == 6
    assert len(phases_seen) > 1, "admissions never landed mid-cycle"
    assert sched.engine.step_trace_count == 1
    eng = DiffusionEngine(model, gen, paged=True, page_size=PS)
    ref = np.asarray(eng.generate(
        params, jax.numpy.asarray(pad_and_stack(reqs, 0, PROMPT_LEN)),
        jax.random.PRNGKey(0)))
    by_id = {r.request_id: r.output for r in done}
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(
            by_id[r.request_id], ref[i, PROMPT_LEN:],
            err_msg=f"mid-cycle admission perturbed request {i}")


def test_page_lane_guard_for_real_tpu_compiles():
    """page_size < 128 lanes must be rejected when compiling the paged
    kernels for real TPU, with interpret mode (CPU tests) exempt."""
    from repro.kernels import ops
    ops.validate_page_lanes(8, interpret=True)          # interpret: exempt
    ops.validate_page_lanes(256, interpret=False)       # rounded pool: fine
    with pytest.raises(ValueError, match="128"):
        ops.validate_page_lanes(8, interpret=False)
    with pytest.raises(ValueError, match="128"):
        ops.validate_page_lanes(192, interpret=False)   # not a multiple
    # the op wrappers guard before any Mosaic lowering can be attempted
    pool = jax.numpy.zeros((4, 8, 2, 4))
    new = jax.numpy.zeros((1, 2, 2, 4))
    idx = jax.numpy.zeros((1, 2), jax.numpy.int32)
    bt = jax.numpy.zeros((1, 2), jax.numpy.int32)
    with pytest.raises(ValueError, match="128"):
        ops.scatter_rows_paged(pool, new, idx, bt, page_size=8,
                               impl="pallas", interpret=False)


def test_duplicate_prompts_sample_distinct_completions(small_model):
    """The per-row key chain must decorrelate ROWS, not just iterations:
    a batch of identical prompts at temperature > 0 is the canonical
    draw-N-samples use case and must not collapse to one completion."""
    cfg, model, params = small_model
    gen = GenerationConfig(mode="dualcache", temperature=1.0,
                           prompt_refresh_period=0, block_refresh_period=1,
                           **GEN)
    prompt = jax.numpy.tile(
        jax.random.randint(jax.random.PRNGKey(2), (1, PROMPT_LEN),
                           3, cfg.vocab_size), (4, 1))
    out = np.asarray(make_engine(model, gen)
                     .generate(params, prompt, jax.random.PRNGKey(5)))
    gen_region = out[:, PROMPT_LEN:]
    assert (gen_region < cfg.vocab_size).all()
    assert len({row.tobytes() for row in gen_region}) > 1, \
        "identical prompts produced identical samples (rows share a key)"
