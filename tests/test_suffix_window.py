"""Sliding active-window attention + lazy page reservation (Streaming-dLLM).

Contract under test (docs/ARCHITECTURE.md §1c, dynamic-window contract):
  * ``window_blocks == 0`` disables windowing — the clamp is compiled out
    and generation is BIT-IDENTICAL to the unwindowed engine; a window wide
    enough to cover the whole sequence is likewise bit-identical (the mask
    never fires);
  * windowed generation is dense-vs-paged bit-identical: the dense clamp
    (``window_kv_clamp``) and the paged windowed block-table walk
    (``window_block_tables``) express the SAME read set;
  * windowed lazy-reserve serving replays bit-identically offline (greedy
    and sampled, mid-cycle admission included) even though serving leaves
    far-suffix pages unmapped while offline maps everything — the window
    mask makes the unmapped region unobservable;
  * lazy admission reserves prompt + one active window only, defers the
    far suffix (``pages_deferred``), grows the mapping just-in-time as
    ``bs`` advances, and returns everything at retirement (no leak);
  * under pool pressure a row whose growth is denied STALLS and resumes —
    it is never killed and still produces the exact offline tokens;
  * ``Request.max_blocks`` hard-caps the generated extent in every mode;
  * at equal pool pages lazy windowed admission holds at least 1.5x the
    residents of eager full reservation, and windowed greedy serving
    agrees with the unwindowed engine on at least ``AGREEMENT_FLOOR`` of
    the tokens.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs import GenerationConfig, SkipStage
from repro.core.engine import DiffusionEngine
from repro.core.schedule import window_limit
from repro.models import build_model
from repro.runtime import Request, StreamScheduler
from repro.runtime.request import pad_and_stack

PROMPT_LEN = 16
PS = 8
GEN = dict(gen_length=32, block_length=8)       # 4 blocks; t_total = 48
N_VP = (PROMPT_LEN + GEN["gen_length"]) // PS   # 6 virtual pages


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


def _requests(cfg, n, seed=3):
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(3, cfg.vocab_size, PROMPT_LEN)
                    .astype(np.int32), sample_seed=i) for i in range(n)]


def _serve(model, params, gcfg, reqs, **skw):
    sched = StreamScheduler(model, params, gcfg, max_slots=2,
                            prompt_len=PROMPT_LEN, paged=True, page_size=PS,
                            early_advance=True, **skw)
    for r in reqs:
        sched.submit(r)
    done = sched.drain()
    by_id = {r.request_id: r.output for r in done}
    return [by_id[r.request_id] for r in reqs], sched


def _offline_ref(model, params, gcfg, reqs):
    eng = DiffusionEngine(model, gcfg, paged=True, page_size=PS)
    return np.asarray(eng.generate(
        params, jnp.asarray(pad_and_stack(reqs, 0, PROMPT_LEN)),
        jax.random.PRNGKey(0),
        sample_seeds=jnp.asarray([r.sample_seed for r in reqs])))


# ---------------------------------------------------------------------------
# window_blocks = ∞: the clamp compiles out / never fires
# ---------------------------------------------------------------------------


def test_window_limit_compiles_out_when_disabled():
    """window_blocks == 0 is the unbounded sentinel: the shared helper
    returns None so every consumer skips the clamp at trace time."""
    bs = np.array([16, 24])
    assert window_limit(_cfg(), bs) is None
    assert not _cfg().windowed
    g = _cfg(window_blocks=1)
    assert g.windowed
    np.testing.assert_array_equal(window_limit(g, bs), bs + 2 * 8)


@pytest.mark.parametrize("temperature", [0.0, 0.7])
@pytest.mark.parametrize("paged", [False, True])
def test_wide_window_bit_identical_to_unwindowed(small_model, temperature,
                                                 paged):
    """A window covering the whole sequence (limit = bs + 5*lb >= t_total
    for every reachable bs) must reproduce the unwindowed engine bit for
    bit — greedy and sampled, dense and paged."""
    cfg, model, params = small_model
    prompt = jax.random.randint(jax.random.PRNGKey(7), (2, PROMPT_LEN),
                                0, cfg.vocab_size)
    ekw = dict(paged=True, page_size=PS) if paged else {}
    ref = _gen(model, params, _cfg(temperature=temperature), prompt, **ekw)
    wide = _gen(model, params,
                _cfg(temperature=temperature, window_blocks=4), prompt, **ekw)
    np.testing.assert_array_equal(ref, wide)


# ---------------------------------------------------------------------------
# windowed: dense vs paged vs pallas read-set agreement
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("temperature", [0.0, 0.7])
def test_windowed_dense_equals_paged(small_model, temperature):
    """The dense kv_pos clamp and the paged windowed block-table walk must
    express the SAME read set: bit-identical outputs."""
    cfg, model, params = small_model
    prompt = jax.random.randint(jax.random.PRNGKey(7), (2, PROMPT_LEN),
                                0, cfg.vocab_size)
    g = _cfg(window_blocks=1, temperature=temperature)
    dense = _gen(model, params, g, prompt)
    paged = _gen(model, params, g, prompt, paged=True, page_size=PS)
    np.testing.assert_array_equal(dense, paged)


def test_windowed_changes_far_suffix_reads(small_model):
    """Sanity that the window is live: a 1-block window must actually mask
    far-suffix reads, so some token somewhere may differ from unwindowed —
    and if every token happens to agree the mask must at least alter the
    horizon (checked via the helper, not the tokens)."""
    g = _cfg(window_blocks=1)
    lim = window_limit(g, np.array([PROMPT_LEN]))
    # first block: horizon ends 2 blocks past the prompt, before t_total
    assert int(lim[0]) == PROMPT_LEN + 2 * 8 < PROMPT_LEN + GEN["gen_length"]


def test_windowed_pallas_interpret_agrees(small_model):
    """The Pallas kernel walking a windowed (−1-punched) block table must
    agree with the windowed XLA gather path."""
    cfg, model, params = small_model
    prompt = jax.random.randint(jax.random.PRNGKey(7), (2, PROMPT_LEN),
                                0, cfg.vocab_size)
    g = _cfg(window_blocks=1)
    a = _gen(model, params, g, prompt, paged=True, page_size=PS)
    b = _gen(model, params, g, prompt, paged=True, page_size=PS,
             attn_impl="pallas")
    agreement = (a == b).mean()
    assert agreement > 0.95, f"windowed pallas diverged: {agreement}"


# ---------------------------------------------------------------------------
# lazy reservation: serving == offline, growth accounting, stall-not-kill
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("temperature", [0.0, 0.7])
def test_windowed_lazy_serving_equals_offline_replay(small_model,
                                                     temperature):
    """Lazy-reserve serving (mid-cycle admission, 5 requests over 2 slots)
    leaves far-suffix pages unmapped, yet every request replays its offline
    windowed generation bit for bit — the window mask makes the unmapped
    region unobservable.  Still ONE compiled step program."""
    cfg, model, params = small_model
    g = _cfg(window_blocks=1, temperature=temperature)
    reqs = _requests(cfg, 5)
    outs, sched = _serve(model, params, g, reqs, lazy_reserve=True)
    assert sched.engine.step_trace_count == 1, \
        "windowed serving must still reuse ONE compiled step program"
    assert sched.stats.pages_deferred > 0, \
        "lazy admission should have deferred far-suffix pages"
    ref = _offline_ref(model, params, g, reqs)
    for i in range(len(reqs)):
        np.testing.assert_array_equal(
            outs[i], ref[i, PROMPT_LEN:],
            err_msg=f"lazy windowed replay diverged for request {i}")


def test_lazy_growth_accounting(small_model):
    """With an ample pool: admission maps prompt + one window (2 of 6
    vpages deferred per full-prompt request), the frontier reaches the full
    extent only as bs advances, nothing stalls, and retirement returns
    every page (pages_in_use -> 0, free list back to full)."""
    cfg, model, params = small_model
    g = _cfg(window_blocks=1)
    reqs = _requests(cfg, 2)
    sched = StreamScheduler(model, params, g, max_slots=2,
                            prompt_len=PROMPT_LEN, paged=True, page_size=PS,
                            early_advance=True, lazy_reserve=True)
    for r in reqs:
        sched.submit(r)
    sched.step()                        # admission + first prefill
    # full extent is 6 vpages; init maps prompt(2) + 2 window blocks(2) = 4
    assert sched.slot_frontier[0] == 4 and sched.slot_extent[0] == (0, 6)
    assert sched.stats.pages_deferred == 2 * len(reqs)
    assert sched.stats.pages_in_use == 4 * len(reqs)
    frontiers = {sched.slot_frontier[0]}
    while sched.has_work():
        sched.step()
        frontiers.add(sched.slot_frontier[0])
    # the frontier walked forward page by page as bs advanced
    assert frontiers == {4, 5, 6}
    assert sched.stats.window_stalls == 0
    assert sched.stats.pages_in_use == 0, "pages leaked at retirement"
    assert sched.allocator.free_pages == sched.allocator.num_pages - 1
    # and the outputs are the offline windowed tokens
    ref = _offline_ref(model, params, g, reqs)
    done = {r.request_id: r.output for r in sched.drain()}
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(done[r.request_id],
                                      ref[i, PROMPT_LEN:])


def test_stall_not_kill_under_pool_pressure(small_model):
    """A 10-page pool holds two lazily-admitted full-prompt requests (4
    mapped + 2 deferred each) but cannot grow both windows at once: the
    younger row must STALL (never be killed) while the no-deadlock policy
    keeps the older one growing, then resume off the freed pages and still
    produce the exact offline tokens."""
    cfg, model, params = small_model
    g = _cfg(window_blocks=1)
    reqs = _requests(cfg, 2)
    outs, sched = _serve(model, params, g, reqs, lazy_reserve=True,
                         kv_pages=11)
    assert sched.stats.window_stalls >= 1, \
        "the pressured pool should have stalled the younger row"
    assert sched.stats.completed == len(reqs)
    assert sched.stats.pages_in_use == 0
    ref = _offline_ref(model, params, g, reqs)
    for i in range(len(reqs)):
        np.testing.assert_array_equal(
            outs[i], ref[i, PROMPT_LEN:],
            err_msg=f"stalled-row replay diverged for request {i}")


def test_max_blocks_hard_cap(small_model):
    """Request.max_blocks bounds the generated extent regardless of
    gen_length — the retired output holds exactly that many blocks."""
    cfg, model, params = small_model
    g = _cfg(window_blocks=1)
    reqs = _requests(cfg, 1)
    reqs[0].max_blocks = 2
    outs, sched = _serve(model, params, g, reqs, lazy_reserve=True)
    assert outs[0].shape[0] == 2 * GEN["block_length"]
    assert sched.stats.pages_in_use == 0


def test_on_demand_extent_growth(small_model):
    """On-demand gen_length growth (ROADMAP item 5): a request admitted
    with a 1-block soft hint but ``max_blocks=3`` grows block-by-block at
    each final-block entry up to the hard cap, and the grown output is
    bit-identical to an offline run generating exactly 3 blocks — growth
    lands only at block entry, so the window never re-maps pages it
    already attended as masked."""
    cfg, model, params = small_model
    g = _cfg(window_blocks=1)
    reqs = _requests(cfg, 1)
    reqs[0].max_new_tokens = 8          # soft hint: 1 block
    reqs[0].max_blocks = 3              # hard cap: may grow to 3
    outs, sched = _serve(model, params, g, reqs, lazy_reserve=True)
    assert outs[0].shape[0] == 3 * GEN["block_length"]
    assert sched.stats.blocks_grown >= 1, \
        "the extent should have grown past the admitted horizon"
    assert sched.stats.pages_in_use == 0, "pages leaked at retirement"
    assert sched.allocator.free_pages == sched.allocator.num_pages - 1
    ref = _offline_ref(model, params, _cfg(window_blocks=1, gen_length=24),
                       reqs)
    np.testing.assert_array_equal(
        outs[0], ref[0, PROMPT_LEN:],
        err_msg="grown output diverged from the offline 3-block replay")


def test_growth_denied_is_sticky_under_pressure(small_model):
    """When the pool cannot back a growth grant at final-block entry the
    denial is STICKY: both rows finish at their admitted 2-block extent
    (16 tokens), never grow, and never stall waiting for pages they
    already refused — a later mid-block grant would re-map pages the
    window had attended as masked and break replay."""
    cfg, model, params = small_model
    g = _cfg(window_blocks=1)
    reqs = _requests(cfg, 2)
    for r in reqs:
        r.max_new_tokens = 16           # 2 blocks: fills the 1+wb horizon
        r.max_blocks = 4                # would grow, pool permitting
    # each 2-block extent maps ceil((16+16)/8)=4 pages up-front; an
    # 8-page pool holds both with ZERO slack, so the first final-block
    # entry's growth ask (1 page) is denied for both rows
    outs, sched = _serve(model, params, g, reqs, lazy_reserve=True,
                         kv_pages=9)
    for o in outs:
        assert o.shape[0] == 2 * GEN["block_length"]
    assert sched.stats.blocks_grown == 0
    assert sched.stats.window_stalls == 0, \
        "a sticky denial must not leave rows stalling for growth"
    assert sched.stats.pages_in_use == 0
    ref = _offline_ref(model, params, _cfg(window_blocks=1, gen_length=16),
                       reqs)
    for i in range(len(reqs)):
        np.testing.assert_array_equal(outs[i], ref[i, PROMPT_LEN:])


def test_lazy_reserve_gating(small_model):
    """lazy_reserve requires paged + a finite window.  The historical third
    exclusion — prefix_sharing — is LIFTED: deficit accounting is
    private-pages-only, so shared prompt pages subtract from the up-front
    need while growth deficits (all-private far suffix) are untouched, and
    the combination now constructs cleanly."""
    cfg, model, params = small_model
    with pytest.raises(AssertionError):
        StreamScheduler(model, params, _cfg(window_blocks=1),
                        prompt_len=PROMPT_LEN, lazy_reserve=True)
    with pytest.raises(AssertionError):
        StreamScheduler(model, params, _cfg(), prompt_len=PROMPT_LEN,
                        paged=True, page_size=PS, lazy_reserve=True)
    sched = StreamScheduler(model, params, _cfg(window_blocks=1),
                            prompt_len=PROMPT_LEN, paged=True, page_size=PS,
                            lazy_reserve=True, prefix_sharing=True)
    assert sched.lazy_reserve and sched.prefix_sharing


@pytest.mark.parametrize("temperature", [0.0, 0.7])
def test_lazy_reserve_with_prefix_sharing(small_model, temperature):
    """Regression for the lifted lazy_reserve × prefix_sharing exclusion:
    duplicate prompts admitted together under a finite window must (a)
    actually share prompt pages, (b) still defer far-suffix pages, and (c)
    replay bit-identically offline — greedy and sampled."""
    cfg, model, params = small_model
    g = _cfg(window_blocks=1, temperature=temperature)
    reqs = _requests(cfg, 2)
    reqs[1] = Request(prompt=reqs[0].prompt.copy(),
                      sample_seed=reqs[1].sample_seed)
    outs, sched = _serve(model, params, g, reqs,
                         lazy_reserve=True, prefix_sharing=True)
    assert sched.stats.pages_deferred > 0, "lazy deferral must stay active"
    n_prompt_vp = PROMPT_LEN // PS
    if temperature > 0:
        # sampled: CoW reserves offset the sharing win page-for-page, so
        # the proof of sharing is the fork the divergence forced
        assert sched.stats.cow_forks == n_prompt_vp
    else:
        assert sched.stats.cow_forks == 0
        assert sched.stats.peak_pages_in_use < 2 * N_VP, \
            "duplicate prompts should have shared prompt pages"
    assert sched.stats.pages_in_use == 0
    ref = _offline_ref(model, params, g, reqs)
    for i in range(len(reqs)):
        np.testing.assert_array_equal(
            outs[i], ref[i, PROMPT_LEN:],
            err_msg=f"lazy+sharing replay diverged for request {i}")


# ---------------------------------------------------------------------------
# long generation at equal pool pages: quality and admitted concurrency
# ---------------------------------------------------------------------------

LONG_GEN = 64                       # 8 blocks; t_total = 80 -> 10 vpages
POOL_PAGES = 29                     # one page short of three full extents
AGREEMENT_FLOOR = 0.80


def _long_cfg(**kw):
    return GenerationConfig(mode="es", gen_length=LONG_GEN, block_length=8,
                            skip_stages=(SkipStage(1, 0.5),
                                         SkipStage(2, 0.5)),
                            prompt_refresh_period=8, block_refresh_period=4,
                            **kw)


def _serve_long(model, params, gcfg, lazy):
    """Eight greedy full-prompt, full-length requests on 8 paged slots
    over a POOL_PAGES pool (+ the garbage page)."""
    reqs = _requests(model.cfg, 8, seed=11)
    sched = StreamScheduler(model, params, gcfg, max_slots=8,
                            prompt_len=PROMPT_LEN, paged=True, page_size=PS,
                            kv_pages=POOL_PAGES + 1, early_advance=True,
                            lazy_reserve=lazy)
    for r in reqs:
        sched.submit(r)
    done = {r.request_id: r.output for r in sched.drain()}
    return np.stack([done[r.request_id] for r in reqs]), sched


@pytest.fixture(scope="module")
def long_served(small_model):
    """The unwindowed eager run, and windowed lazy runs built on demand."""
    cfg, model, params = small_model
    runs = {0: _serve_long(model, params, _long_cfg(), lazy=False)}

    def get(window_blocks):
        if window_blocks not in runs:
            runs[window_blocks] = _serve_long(
                model, params, _long_cfg(window_blocks=window_blocks),
                lazy=True)
        return runs[window_blocks]
    return get


@pytest.mark.parametrize("window_blocks", [5, 4])
def test_windowed_greedy_agreement(long_served, window_blocks):
    """Windowed lazy serving of long generations agrees with the
    unwindowed eager replay on at least AGREEMENT_FLOOR of the greedy
    tokens."""
    full, _ = long_served(0)
    out, sched = long_served(window_blocks)
    assert sched.stats.completed == len(out)
    agreement = float((out == full).mean())
    assert agreement >= AGREEMENT_FLOOR, agreement


def test_lazy_admission_concurrency_at_equal_pool(long_served):
    """At the same POOL_PAGES, eager full reservation is page-gated at 2
    residents while lazy windowed admission (prompt + window mapped, far
    suffix deferred) holds at least 1.5x as many, and every request
    defers exactly its far-suffix pages."""
    _, eager = long_served(0)
    _, lazy = long_served(5)
    pages_full = -(-(PROMPT_LEN + LONG_GEN) // PS)
    pages_admit = -(-(PROMPT_LEN + min(1 + 5, LONG_GEN // 8) * 8) // PS)
    assert lazy.stats.resident_peak >= 1.5 * eager.stats.resident_peak
    assert lazy.stats.pages_deferred == \
        lazy.stats.completed * (pages_full - pages_admit)
    assert eager.stats.pages_deferred == 0
    assert eager.stats.window_stalls == 0
