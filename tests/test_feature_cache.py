"""Adaptive cross-iteration feature cache (dLLM-Cache integration).

Contract under test (docs/ARCHITECTURE.md "Adaptive feature-cache
contract"):
  * ``cache_prompt_interval <= 1`` disables the cache and the engine is
    BIT-IDENTICAL to the uncached one (greedy and sampled, dense and
    paged) — branch 3 does not even exist in the compiled program;
  * with the cache enabled but every scheduled refresh FULL (the
    prompt-refresh period at or above the block step count makes every
    refresh block-initial), the machinery is live — feat/conf planes,
    lifetime-indexed branch split, stats counters — yet outputs stay
    bit-identical to the uncached engine;
  * cached generation is dense-vs-paged bit-identical and
    serving-vs-offline replay bit-identical, including mid-cycle
    (early-advance) admission and the prompt refresh that runs one row at
    a time over the refreshing rows (0, 1, 2 or every slot in one step,
    and a block-causal prefix-sharing cohort refreshing together);
  * the variation kernel matches its XLA reference bit-for-bit in
    interpret mode;
  * the cadence: the k-th scheduled refresh is FULL iff
    ``k % cache_prompt_interval == 0``, and a block's first iteration is
    always FULL;
  * quality: on long prompts refreshed every iteration, greedy serving
    with partial refreshes agrees with the uncached engine on at least
    ``AGREEMENT_FLOOR`` of the tokens.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs import GenerationConfig, SkipStage
from repro.core.engine import BlockState, DiffusionEngine
from repro.core.schedule import branch_index, full_refresh_pred
from repro.kernels import ops
from repro.models import build_model
from repro.runtime import Request, StreamScheduler
from repro.runtime.request import pad_and_stack

PROMPT_LEN = 16
PS = 8
GEN = dict(gen_length=16, block_length=8)


@pytest.fixture(scope="module")
def small_model():
    cfg = configs.reduced(configs.get_config("llada-8b"))
    cfg = dataclasses.replace(cfg, n_layers=4)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _cfg(**kw):
    base = dict(mode="es", skip_stages=(SkipStage(1, 0.5),),
                prompt_refresh_period=2, block_refresh_period=4, **GEN)
    base.update(kw)
    return GenerationConfig(**base)


def _gen(model, params, gcfg, prompt, **ekw):
    return np.asarray(DiffusionEngine(model, gcfg, **ekw)
                      .generate(params, prompt, jax.random.PRNGKey(1)))


# ---------------------------------------------------------------------------
# bit-identity when disabled / all-full
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("temperature", [0.0, 0.7])
@pytest.mark.parametrize("paged", [False, True])
def test_interval_one_bit_identical_to_uncached(small_model, temperature,
                                                paged):
    """cache_prompt_interval <= 1 must be the uncached engine, bit for bit,
    greedy and sampled, dense and paged."""
    cfg, model, params = small_model
    prompt = jax.random.randint(jax.random.PRNGKey(7), (2, PROMPT_LEN),
                                0, cfg.vocab_size)
    ekw = dict(paged=True, page_size=PS) if paged else {}
    ref = _gen(model, params, _cfg(temperature=temperature), prompt, **ekw)
    one = _gen(model, params,
               _cfg(temperature=temperature, cache_prompt_interval=1),
               prompt, **ekw)
    np.testing.assert_array_equal(ref, one)


def test_all_full_refreshes_bit_identical_to_uncached(small_model):
    """With the cache ON but prompt_refresh_period >= steps-per-block every
    scheduled refresh is block-initial, hence FULL: the live machinery
    (feature planes, lifetime branch split, stats) must not perturb a
    single token."""
    cfg, model, params = small_model
    prompt = jax.random.randint(jax.random.PRNGKey(7), (2, PROMPT_LEN),
                                0, cfg.vocab_size)
    ref = _gen(model, params, _cfg(prompt_refresh_period=8), prompt)
    on = _gen(model, params,
              _cfg(prompt_refresh_period=8, cache_prompt_interval=4), prompt)
    np.testing.assert_array_equal(ref, on)


def test_cached_generate_dense_equals_paged(small_model):
    cfg, model, params = small_model
    prompt = jax.random.randint(jax.random.PRNGKey(7), (2, PROMPT_LEN),
                                0, cfg.vocab_size)
    g = _cfg(cache_prompt_interval=2)
    dense = _gen(model, params, g, prompt)
    paged = _gen(model, params, g, prompt, paged=True, page_size=PS)
    np.testing.assert_array_equal(dense, paged)


# ---------------------------------------------------------------------------
# variation kernel parity
# ---------------------------------------------------------------------------


def test_variation_score_xla_matches_pallas_interpret():
    k = jax.random.PRNGKey(3)
    h_new = jax.random.normal(k, (3, 24, 16), jnp.float32)
    h_old = h_new + 0.1 * jax.random.normal(jax.random.fold_in(k, 1),
                                            (3, 24, 16), jnp.float32)
    h_old = h_old.at[:, 0].set(0.0)       # cold row: cos := 0, max variation
    conf = jax.random.uniform(jax.random.fold_in(k, 2), (3, 24), jnp.float32)
    x = ops.variation_score(h_new, h_old, conf, alpha=0.5, impl="xla")
    p = ops.variation_score(h_new, h_old, conf, alpha=0.5, impl="pallas",
                            interpret=True)
    np.testing.assert_allclose(np.asarray(x), np.asarray(p), atol=1e-6)
    # zeroed cached feature => cosine term contributes its maximum
    assert np.all(np.asarray(x)[:, 0] >= 0.5 * np.asarray(conf)[:, 0])


# ---------------------------------------------------------------------------
# serving: mid-cycle admission + the one-row prompt refresh
# ---------------------------------------------------------------------------


def _serve(model, params, gcfg, reqs, **skw):
    sched = StreamScheduler(model, params, gcfg, max_slots=2,
                            prompt_len=PROMPT_LEN, paged=True, page_size=PS,
                            early_advance=True, **skw)
    for r in reqs:
        sched.submit(r)
    done = sched.drain()
    by_id = {r.request_id: r.output for r in done}
    return [by_id[r.request_id] for r in reqs], sched


def test_cached_serving_equals_offline_replay(small_model):
    """Early-advance serving (staggered, mid-cycle admissions over 2 slots
    for 5 requests) with the adaptive cache ON replays each request
    bit-identically offline — the cache planes are per-row state carried
    exactly like kv_valid."""
    cfg, model, params = small_model
    g = _cfg(cache_prompt_interval=2)
    rng = np.random.default_rng(3)
    reqs = [Request(prompt=rng.integers(3, cfg.vocab_size, PROMPT_LEN)
                    .astype(np.int32)) for _ in range(5)]
    outs, sched = _serve(model, params, g, reqs)
    assert sched.engine.step_trace_count == 1, \
        "cached serving must still reuse ONE compiled step program"
    eng = DiffusionEngine(model, g, paged=True, page_size=PS)
    ref = np.asarray(eng.generate(
        params, jnp.asarray(pad_and_stack(reqs, 0, PROMPT_LEN)),
        jax.random.PRNGKey(0)))
    for i in range(len(reqs)):
        np.testing.assert_array_equal(outs[i], ref[i, PROMPT_LEN:])
    # the refresh gauges saw traffic: partial refreshes skipped some
    # eligible rows (hit > 0) and full ones counted everything
    assert sched.stats.cache_eligible_total > 0
    assert 0.0 < sched.stats.cache_hit_fraction < 1.0
    assert sched.stats.tokens_refreshed_p50 > 0


SLOTS = 4
ROW_CACHES = {"off": {}, "adaptive": dict(cache_prompt_interval=2),
              "sparse": dict(sparse_attention=True, sparse_retention=0.5)}


@pytest.fixture(scope="module")
def row_engines(small_model):
    """Per cache variant: its sampled config, a SLOTS-slot serving engine
    and an offline engine, built once and shared by the tests below so
    each program compiles once."""
    cfg, model, params = small_model
    built = {}

    def get(cache):
        if cache not in built:
            g = _cfg(temperature=0.7, **ROW_CACHES[cache])
            n_vp = (PROMPT_LEN + GEN["gen_length"]) // PS
            built[cache] = (
                g,
                DiffusionEngine(model, g, paged=True, page_size=PS,
                                kv_pages=SLOTS * n_vp + 1,
                                early_advance=True),
                DiffusionEngine(model, g, paged=True, page_size=PS))
        return built[cache]
    return get


def _serve_staggered(model, params, gcfg, reqs, together, **skw):
    """Serve ``reqs`` on SLOTS paged slots: the first ``together`` in one
    step, so their rows refresh together at every block start, then one
    more after each step, so each of those refreshes a step apart."""
    sched = StreamScheduler(model, params, gcfg, max_slots=SLOTS,
                            prompt_len=PROMPT_LEN, paged=True, page_size=PS,
                            early_advance=True, seed=0, **skw)
    it = iter(reqs)
    for r in [next(it) for _ in range(max(together, 1))]:
        sched.submit(r)
    while sched.has_work():
        sched.step()
        nxt = next(it, None)
        if nxt is not None:
            sched.submit(nxt)
    done = {r.request_id: r.output for r in sched.drain()}
    return [done[r.request_id] for r in reqs], sched


def _offline(eng, params, reqs):
    return np.asarray(eng.generate(
        params, jnp.asarray(pad_and_stack(reqs, 0, PROMPT_LEN)),
        jax.random.PRNGKey(0),
        prompt_start=jnp.asarray([PROMPT_LEN - len(r.prompt) for r in reqs]),
        sample_seeds=jnp.asarray([r.sample_seed for r in reqs])))


def _varied_requests(cfg, n, seed):
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(3, cfg.vocab_size,
                                        int(rng.integers(4, PROMPT_LEN + 1)))
                    .astype(np.int32), sample_seed=100 + i)
            for i in range(n)]


@pytest.mark.parametrize("cache", list(ROW_CACHES))
@pytest.mark.parametrize("together", [0, 1, 2, SLOTS])
def test_row_refresh_equals_offline_replay(small_model, row_engines,
                                           together, cache):
    """The prompt refresh of a paged attention-only engine runs one row at
    a time over the rows that refresh: with 0, 1, 2 or all SLOTS rows
    refreshing in one step, sampled, with variable-length prompts, with
    the adaptive cache off and on, and under sparse eviction (whose
    ``kv_valid`` the loop writes back), every request replays its offline
    ``generate()`` bit for bit.  With none refreshing the loop is the
    identity."""
    cfg, model, params = small_model
    g, eng, offline = row_engines(cache)
    reqs = _varied_requests(cfg, SLOTS, 5 + together)
    outs, sched = _serve_staggered(model, params, g, reqs, together,
                                   engine=eng)
    assert eng.refresh_per_row and eng.step_trace_count == 1
    ref = _offline(offline, params, reqs)
    for i in range(SLOTS):
        np.testing.assert_array_equal(outs[i], ref[i, PROMPT_LEN:],
                                      err_msg=f"request {i}")
    if together == SLOTS:
        # rows admitted in one step stay in step: every pass takes them all
        assert sched.stats.refresh_rows_per_pass == SLOTS
    if together == 0:
        state = sched.state
        st = BlockState(state.tokens, state.caches, state.conf, state.pred,
                        state.hidden, state.kv_valid, state.phase, state.key,
                        state.feat, state.conf_full)
        carry = (st.caches, st.conf, st.pred, st.hidden, st.kv_valid,
                 st.feat, jnp.ones((SLOTS, 2), jnp.int32))
        none = jnp.zeros((SLOTS,), bool)
        out = jax.jit(lambda c: eng._refresh_rows(params, state, st, none,
                                                  c))(carry)
        for a, b in zip(jax.tree_util.tree_leaves(carry),
                        jax.tree_util.tree_leaves(out)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_row_refresh_gqa_bias_equals_offline_replay():
    """The one-row refresh on a grouped-query stack with q/k/v bias
    (reduced Dream-7B): two rows refreshing together and one a step
    behind replay their offline ``generate()`` bit for bit."""
    cfg = dataclasses.replace(configs.reduced(configs.get_config("dream-7b")),
                              n_layers=4)
    assert cfg.n_kv_heads < cfg.n_heads and cfg.qkv_bias
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    g = _cfg(temperature=0.7)
    reqs = _varied_requests(cfg, 3, 21)
    outs, sched = _serve_staggered(model, params, g, reqs, 2)
    assert sched.engine.refresh_per_row
    ref = _offline(DiffusionEngine(model, g, paged=True, page_size=PS),
                   params, reqs)
    for i in range(len(reqs)):
        np.testing.assert_array_equal(outs[i], ref[i, PROMPT_LEN:],
                                      err_msg=f"request {i}")


def test_row_refresh_prefix_cohort_block_causal(small_model):
    """A block-causal prefix-sharing cohort (one prompt, shared prompt
    pages) whose rows refresh together in the one-row loop decodes exactly
    as the same requests served without sharing, in the same mode: the
    shared pages are exempt from rewrite, so no row of the loop sees
    another's writes."""
    cfg, model, params = small_model
    g = _cfg(temperature=0.7, block_causal=True)
    rng = np.random.default_rng(11)
    prompt = rng.integers(3, cfg.vocab_size, PROMPT_LEN).astype(np.int32)

    def mk():
        return [Request(prompt=prompt.copy(), sample_seed=100 + i)
                for i in range(3)]
    reqs = mk()
    sched = StreamScheduler(model, params, g, max_slots=SLOTS,
                            prompt_len=PROMPT_LEN, paged=True, page_size=PS,
                            early_advance=True, seed=0, prefix_sharing=True)
    for r in reqs:
        sched.submit(r)
    sched.step()
    assert sched.stats.shared_mappings > 0, "the cohort shares its prompt"
    sched.drain()
    shared = [r.output for r in reqs]
    assert sched.stats.refresh_rows_per_pass == 3.0
    plain, _ = _serve_staggered(model, params, g, mk(), 3,
                                engine=sched.engine)
    for a, b in zip(shared, plain):
        np.testing.assert_array_equal(a, b)
    assert not all(np.array_equal(shared[0], o) for o in shared[1:]), \
        "sampled rows with distinct seeds should differ"


@pytest.mark.parametrize("cache", ["off", "adaptive"])
def test_refresh_counters_follow_the_phases(small_model, row_engines, cache):
    """``refresh_passes`` counts steps with a row at FULL prompt refresh and
    ``refresh_rows`` those rows.  ``_cfg`` refreshes at phases 0, 2, 4, 6
    of each 8-step block; with the adaptive cache, those at 2 and 6 are
    partial and run their own pass.  Two blocks per request."""
    cfg, model, params = small_model
    g, eng, _ = row_engines(cache)
    per_block = 2 if cache == "adaptive" else 4
    rng = np.random.default_rng(9)
    prompts = [rng.integers(3, cfg.vocab_size, PROMPT_LEN).astype(np.int32)
               for _ in range(3)]

    def mk():
        return [Request(prompt=p.copy(), sample_seed=i)
                for i, p in enumerate(prompts)]
    # one slot: the requests run one after another, a row per pass
    one = StreamScheduler(model, params, g, max_slots=1,
                          prompt_len=PROMPT_LEN, paged=True, page_size=PS,
                          early_advance=True)
    for r in mk():
        one.submit(r)
    one.drain()
    assert one.stats.refresh_passes == 3 * 2 * per_block
    assert one.stats.refresh_rows == 3 * 2 * per_block
    assert one.stats.gauges()["refresh_rows_per_pass"] == 1.0
    # SLOTS slots: two rows in step, the third a step behind them
    _, three = _serve_staggered(model, params, g, mk(), 2, engine=eng)
    assert three.stats.refresh_passes == 2 * 2 * per_block
    assert three.stats.refresh_rows == 3 * 2 * per_block
    assert three.stats.gauges()["refresh_rows_per_pass"] == 1.5


# ---------------------------------------------------------------------------
# cadence truth
# ---------------------------------------------------------------------------


def test_full_refresh_cadence():
    g = _cfg(prompt_refresh_period=2, cache_prompt_interval=2)
    spb = g.resolved_steps()            # 8 -> refreshes at t = 0, 2, 4, 6
    iters = np.arange(2 * spb)
    full = np.asarray(full_refresh_pred(g, iters))
    # 4 refreshes per block, every 2nd FULL; block-initial always FULL
    assert full[[0, 4, 8, 12]].all()
    assert not full[[2, 6, 10, 14]].any()
    br = np.asarray(branch_index(g, iters % spb, iters))
    assert br.tolist()[:8] == [2, 0, 3, 0, 2, 0, 3, 0]
    # disabled: every refresh full, branch 3 never emitted
    g0 = _cfg(prompt_refresh_period=2)
    assert np.asarray(full_refresh_pred(g0, iters)).all()
    assert set(np.asarray(branch_index(g0, iters % spb, iters)).tolist()) \
        <= {0, 1, 2}


def test_adaptive_cache_gating(small_model):
    """The cache requires es mode on an attention-only period-1 stack with
    at least one skip stage (the probe boundary)."""
    cfg, model, params = small_model
    with pytest.raises(AssertionError):
        DiffusionEngine(model, _cfg(mode="vanilla", skip_stages=(),
                                    cache_prompt_interval=2))
    with pytest.raises(AssertionError):
        DiffusionEngine(model, _cfg(skip_stages=(),
                                    cache_prompt_interval=2))


# ---------------------------------------------------------------------------
# quality: partial refreshes against the uncached engine
# ---------------------------------------------------------------------------

LONG_PROMPT_LEN = 600               # the refresh-dominated regime the cache
LONG_GEN = 16                       # targets: 2 blocks of 8 per request
CACHE_PROMPT_INTERVAL = 8           # 1 FULL + 7 PARTIAL refreshes per block
AGREEMENT_FLOOR = 0.80


def _long_cfg(**kw):
    # a refresh every iteration (the recompute-everything regime) on an
    # 8-layer stack whose probe is its first layer
    return GenerationConfig(mode="es", gen_length=LONG_GEN, block_length=8,
                            skip_stages=(SkipStage(1, 0.5),
                                         SkipStage(2, 0.5)),
                            prompt_refresh_period=1, block_refresh_period=4,
                            **kw)


def _serve_long(model, params, gcfg):
    """Eight greedy full-length long prompts on SLOTS paged slots."""
    rng = np.random.default_rng(9)
    reqs = [Request(prompt=rng.integers(3, model.cfg.vocab_size,
                                        LONG_PROMPT_LEN).astype(np.int32),
                    max_new_tokens=LONG_GEN, sample_seed=i)
            for i in range(8)]
    n_vp = (LONG_PROMPT_LEN + LONG_GEN) // PS
    sched = StreamScheduler(model, params, gcfg, max_slots=SLOTS,
                            prompt_len=LONG_PROMPT_LEN, paged=True,
                            page_size=PS, kv_pages=SLOTS * n_vp + 1,
                            early_advance=True)
    for r in reqs:
        sched.submit(r)
    done = {r.request_id: r.output for r in sched.drain()}
    return np.stack([done[r.request_id] for r in reqs]), sched


@pytest.fixture(scope="module")
def long_prompt_uncached():
    cfg = dataclasses.replace(configs.reduced(configs.get_config("llada-8b")),
                              n_layers=8)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params, _serve_long(model, params, _long_cfg())[0]


@pytest.mark.parametrize("fraction", [0.03125, 0.125])
def test_partial_refresh_greedy_agreement(long_prompt_uncached, fraction):
    """With 7 of every 8 refreshes PARTIAL (recomputing only the top
    ``fraction`` most-varied past tokens), greedy serving of long prompts
    agrees with the uncached engine on at least AGREEMENT_FLOOR of the
    tokens, and the partial refreshes did run and skip work."""
    model, params, uncached = long_prompt_uncached
    out, sched = _serve_long(model, params, _long_cfg(
        cache_prompt_interval=CACHE_PROMPT_INTERVAL,
        cache_refresh_fraction=fraction))
    st = sched.stats
    # one event per refreshing row and step; the FULL ones are refresh_rows
    partial = len(st.refresh_event_tokens) - st.refresh_rows
    assert st.refresh_rows > 0
    assert partial == (CACHE_PROMPT_INTERVAL - 1) * st.refresh_rows
    assert 0.0 < st.cache_hit_fraction < 1.0
    agreement = float((out == uncached).mean())
    assert agreement >= AGREEMENT_FLOOR, agreement
