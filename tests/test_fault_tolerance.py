"""Fault-tolerant serving under pressure (docs/ARCHITECTURE.md §5).

The failure-handling contract this file pins down:

  * **priority preemption with host spill/resume**: a page-starved higher
    class spills the lowest-priority resident at its block boundary; the
    victim's resumed output is BIT-IDENTICAL to an uninterrupted offline
    run — greedy and sampled alike (the draw-key numbering survives the
    round trip) — and under mixed classes no interactive request finishes
    later than it would without preemption;
  * **SLO-aware admission**: higher classes admit first; a request whose
    wait + estimated service exceeds its ``deadline_s`` is rejected with a
    typed ``DeadlineUnmeetable``, never silently queued;
  * **poison-slot quarantine**: a row going non-finite is retired with a
    typed ``PoisonedRequest``, its slot reset and private pages scrubbed,
    without perturbing co-resident outputs;
  * **drain watchdog**: zero forward progress raises a typed
    ``DrainStalled`` naming the stuck slots instead of hanging;
  * the new failure gauges flow through ``SchedulerStats.gauges()``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs import GenerationConfig, SkipStage
from repro.core import make_engine
from repro.runtime import (
    ConfigError,
    DeadlineUnmeetable,
    DrainStalled,
    PoisonedRequest,
    Request,
    SchedulerStats,
    StreamScheduler,
)

PROMPT_LEN = 16
GEN = dict(gen_length=16, block_length=8)
PS = 8                              # t_total = 32 -> 4 vpages per slot
N_VP = (PROMPT_LEN + GEN["gen_length"]) // PS


@pytest.fixture(scope="module")
def small_model():
    cfg = configs.reduced(configs.get_config("llada-8b"))
    cfg = dataclasses.replace(cfg, n_layers=4)
    from repro.models import build_model
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _es_cfg(**kw):
    base = dict(mode="es", skip_stages=(SkipStage(1, 0.5),),
                prompt_refresh_period=8, block_refresh_period=4, **GEN)
    base.update(kw)
    return GenerationConfig(**base)


def _mk_req(cfg, seed=0, **kw):
    rng = np.random.default_rng(seed)
    prompt = rng.integers(3, cfg.vocab_size, PROMPT_LEN).astype(np.int32)
    return Request(prompt=prompt, **kw)


def _offline(model, params, gen, reqs):
    """Uninterrupted paged replay of ``reqs`` (full-length prompts)."""
    from repro.runtime.request import pad_and_stack
    eng = make_engine(model, gen, paged=True, page_size=PS)
    return np.asarray(eng.generate(
        params, jnp.asarray(pad_and_stack(reqs, 0, PROMPT_LEN)),
        jax.random.PRNGKey(0),
        sample_seeds=jnp.asarray([r.sample_seed if r.sample_seed is not None
                                  else r.request_id for r in reqs])))


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------


def test_preemption_config_validation(small_model):
    cfg, model, params = small_model
    gen = _es_cfg()
    with pytest.raises(ConfigError, match="requires paged"):
        StreamScheduler(model, params, gen, preemption=True)
    with pytest.raises(ConfigError, match="prefix_sharing"):
        StreamScheduler(model, params, gen, paged=True, page_size=PS,
                        prefix_sharing=True, preemption=True)
    with pytest.raises(ConfigError, match="lazy_reserve"):
        StreamScheduler(model, params, _es_cfg(window_blocks=1), paged=True,
                        page_size=PS, lazy_reserve=True, preemption=True)


# ---------------------------------------------------------------------------
# preemption: spill to host, resume bit-identical
# ---------------------------------------------------------------------------


def _preempt_roundtrip(small_model, gen):
    cfg, model, params = small_model
    low = _mk_req(cfg, seed=0, priority=0, sample_seed=11)
    high = _mk_req(cfg, seed=1, priority=1, sample_seed=22)
    # pool fits exactly ONE full request: the high class can only enter by
    # spilling the low one
    sched = StreamScheduler(model, params, gen, max_slots=2,
                            prompt_len=PROMPT_LEN, paged=True, page_size=PS,
                            kv_pages=N_VP + 1, preemption=True)
    sched.submit(low)
    sched.step()                       # low admitted, prefill runs
    assert sched.slot_req[0] is low
    sched.submit(high)
    done = sched.drain()
    assert {r.request_id for r in done} == {low.request_id, high.request_id}
    assert all(r.error is None for r in done)
    assert sched.stats.preemptions >= 1, "high class never preempted"
    assert sched.stats.pages_spilled >= N_VP
    assert len(sched.stats.resume_waits) == sched.stats.preemptions
    assert sched.stats.pages_in_use == 0
    g = sched.stats.gauges()
    assert g["preemptions"] == sched.stats.preemptions
    assert g["resume_p50"] >= 0.0
    ref = _offline(model, params, gen, [low, high])
    for i, r in enumerate([low, high]):
        np.testing.assert_array_equal(
            r.output, ref[i, PROMPT_LEN:],
            err_msg=f"spill/resume changed request {i}'s output")


def test_preempt_spill_resume_bit_identical_greedy(small_model):
    _preempt_roundtrip(small_model, _es_cfg())


def test_preempt_spill_resume_bit_identical_sampled(small_model):
    """The draw-key numbering (per-request seed + lifetime iteration) must
    survive the spill round trip — sampled resumes replay bit-exactly."""
    _preempt_roundtrip(small_model, _es_cfg(temperature=0.8))


def test_preemption_needs_priority_gap(small_model):
    """Equal classes never preempt each other: the second request simply
    waits for pages, FIFO — the pre-preemption contract is unchanged."""
    cfg, model, params = small_model
    gen = _es_cfg()
    a = _mk_req(cfg, seed=0, priority=1)
    b = _mk_req(cfg, seed=1, priority=1)
    sched = StreamScheduler(model, params, gen, max_slots=2,
                            prompt_len=PROMPT_LEN, paged=True, page_size=PS,
                            kv_pages=N_VP + 1, preemption=True)
    sched.submit(a)
    sched.step()
    sched.submit(b)
    done = sched.drain()
    assert len(done) == 2
    assert sched.stats.preemptions == 0
    # FIFO held: a finished before b was admitted
    assert a.finish_s <= b.admit_s



@pytest.mark.parametrize("temperature", [0.0, 0.7])
def test_preemption_finishes_interactive_no_later(small_model, temperature):
    """Mixed classes at a pool of exactly two full extents: three
    full-length class-0 jobs at step 0, then a one-block class-1 request
    every 5 steps.  Without preemption the interactive class waits for a
    batch extent to retire; with it a batch resident spills to host.  Each
    interactive request finishes at an engine step no later than without
    preemption, at least one spill happens, and every output is the same
    under both."""
    cfg, model, params = small_model
    plen, gen_length = 24, 32           # t_total = 56 -> 7 vpages a slot
    gen = _es_cfg(gen_length=gen_length, temperature=temperature,
                  skip_stages=(SkipStage(1, 0.5), SkipStage(2, 0.5)))
    served = {}
    for preempt in (False, True):
        rng = np.random.default_rng(21)
        mk = lambda: rng.integers(3, cfg.vocab_size, plen).astype(np.int32)
        batch = [Request(prompt=mk(), priority=0, sample_seed=i)
                 for i in range(3)]
        inter = [Request(prompt=mk(), priority=1, max_new_tokens=8,
                         sample_seed=10 + i) for i in range(4)]
        sched = StreamScheduler(model, params, gen, max_slots=4,
                                prompt_len=plen, paged=True, page_size=PS,
                                kv_pages=2 * (plen + gen_length) // PS + 1,
                                preemption=preempt)
        for r in batch:
            sched.submit(r)
        arrivals = {5 * (1 + i): r for i, r in enumerate(inter)}
        steps, finished = 0, {}
        while arrivals or sched.has_work():
            if steps in arrivals:
                sched.submit(arrivals.pop(steps))
            assert sched.step(), "the batch jobs keep a step running"
            steps += 1
            for r in inter:
                if r.output is not None:
                    finished.setdefault(r.request_id, steps)
        assert sched.stats.completed == len(batch) + len(inter)
        assert all(r.error is None for r in batch + inter)
        served[preempt] = ([finished[r.request_id] for r in inter],
                           [r.output.tolist() for r in batch + inter],
                           sched.stats.preemptions)
    (off, off_out, _), (on, on_out, preemptions) = served[False], served[True]
    assert preemptions >= 1
    assert all(a <= b for a, b in zip(on, off)), (on, off)
    assert on_out == off_out

# ---------------------------------------------------------------------------
# SLO admission: priority classes + typed deadline verdicts
# ---------------------------------------------------------------------------


def test_priority_class_admits_first(small_model):
    cfg, model, params = small_model
    gen = _es_cfg()
    filler = _mk_req(cfg, seed=0)
    low = _mk_req(cfg, seed=1, priority=0)
    high = _mk_req(cfg, seed=2, priority=5)
    sched = StreamScheduler(model, params, gen, max_slots=1,
                            prompt_len=PROMPT_LEN)
    for r in (filler, low, high):      # high submitted LAST
        sched.submit(r)
    # admission happens at step(), so all three compete for the single
    # slot at once: the high class wins it, then FIFO within class 0
    done = sched.drain()
    assert [r.request_id for r in done] == \
        [high.request_id, filler.request_id, low.request_id], \
        "the higher class must overtake the earlier-submitted lower class"


def test_deadline_rejected_at_submit_when_nonpositive(small_model):
    cfg, model, params = small_model
    sched = StreamScheduler(model, params, _es_cfg(), max_slots=1,
                            prompt_len=PROMPT_LEN)
    r = _mk_req(cfg, seed=0, deadline_s=0.0)
    sched.submit(r)
    assert isinstance(r.error, DeadlineUnmeetable)
    assert r.error.request_id == r.request_id
    assert sched.stats.deadline_rejects == 1
    assert not sched.queue
    assert sched.drain() == [r]        # the verdict flows out through drain


def test_deadline_rejected_at_admission_after_waiting(small_model):
    cfg, model, params = small_model
    clk = [0.0]
    sched = StreamScheduler(model, params, _es_cfg(), max_slots=1,
                            prompt_len=PROMPT_LEN, clock=lambda: clk[0])
    r = _mk_req(cfg, seed=0, deadline_s=5.0)
    sched.submit(r)
    clk[0] += 10.0                     # queue wait alone blows the budget
    sched.step()
    assert isinstance(r.error, DeadlineUnmeetable)
    assert r.error.waited_s == pytest.approx(10.0)
    assert r.output is None
    assert sched.stats.deadline_rejects == 1
    assert sched.stats.completed == 0


def test_generous_deadline_admits_and_completes(small_model):
    cfg, model, params = small_model
    sched = StreamScheduler(model, params, _es_cfg(), max_slots=1,
                            prompt_len=PROMPT_LEN)
    r = _mk_req(cfg, seed=0, deadline_s=3600.0)
    sched.submit(r)
    done = sched.drain()
    assert done == [r] and r.error is None and r.output is not None
    assert sched.stats.deadline_rejects == 0


# ---------------------------------------------------------------------------
# poison-slot quarantine
# ---------------------------------------------------------------------------


def _poison_slot(sched, slot):
    """Write NaN into the slot's private current-block KV page in place."""
    st = sched.state
    bs = int(np.asarray(st.bs)[slot])
    pg = int(np.asarray(st.block_tables)[slot, bs // PS])
    assert pg > 0 and sched.allocator.refcount(pg) == 1

    def poison(pool):
        if not jnp.issubdtype(pool.dtype, jnp.floating):
            return pool
        return pool.at[:, pg].set(jnp.nan)

    caches = dict(st.caches)
    caches["kv"] = jax.tree_util.tree_map(poison, caches["kv"])
    sched.state = st._replace(caches=caches)


def test_quarantine_isolates_poisoned_row(small_model):
    cfg, model, params = small_model
    gen = _es_cfg()
    victim = _mk_req(cfg, seed=0)
    bystander = _mk_req(cfg, seed=1)
    sched = StreamScheduler(model, params, gen, max_slots=2,
                            prompt_len=PROMPT_LEN, paged=True, page_size=PS)
    sched.submit(victim)
    sched.submit(bystander)
    sched.step()                       # both admitted + prefilled
    for _ in range(60):                # re-inject until a decode reads it
        if sched.stats.poisoned_requests:
            break
        _poison_slot(sched, 0)
        sched.step()
    assert sched.stats.poisoned_requests == 1, "detector never fired"
    assert isinstance(victim.error, PoisonedRequest)
    assert victim.error.slot == 0 and victim.output is None
    assert sched.slot_req[0] is None, "poisoned slot must be recycled"
    done = sched.drain()
    assert bystander in done and bystander.error is None
    assert sched.stats.completed == 1, \
        "completed must count only clean finishes"
    assert sched.stats.pages_in_use == 0
    # the co-resident decoded exactly what a solo offline run decodes —
    # the poisoned row perturbed nothing it didn't own
    ref = _offline(model, params, gen, [bystander])
    np.testing.assert_array_equal(bystander.output, ref[0, PROMPT_LEN:])
    # the quarantined pages were scrubbed before re-entering the free
    # list: nothing non-finite survives anywhere in the pool
    for leaf in jax.tree_util.tree_leaves(sched.state.caches["kv"]):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            assert bool(jnp.all(jnp.isfinite(leaf))), \
                "NaN bytes leaked past quarantine scrubbing"


def test_quarantine_recycles_slot_for_new_work(small_model):
    """A fresh request admitted into a previously-poisoned slot decodes
    normally — quarantine's reset leaves no residue."""
    cfg, model, params = small_model
    gen = _es_cfg()
    victim = _mk_req(cfg, seed=3)
    sched = StreamScheduler(model, params, gen, max_slots=1,
                            prompt_len=PROMPT_LEN, paged=True, page_size=PS)
    sched.submit(victim)
    sched.step()
    for _ in range(60):
        if sched.stats.poisoned_requests:
            break
        _poison_slot(sched, 0)
        sched.step()
    assert isinstance(victim.error, PoisonedRequest)
    fresh = _mk_req(cfg, seed=4)
    sched.submit(fresh)
    done = sched.drain()
    assert fresh in done and fresh.error is None
    ref = _offline(model, params, gen, [fresh])
    np.testing.assert_array_equal(fresh.output, ref[0, PROMPT_LEN:])


# ---------------------------------------------------------------------------
# drain watchdog
# ---------------------------------------------------------------------------


def test_drain_watchdog_names_stuck_slots(small_model):
    cfg, model, params = small_model
    sched = StreamScheduler(model, params, _es_cfg(), max_slots=1,
                            prompt_len=PROMPT_LEN)
    sched.submit(_mk_req(cfg, seed=0))
    sched.engine.step = lambda p, s, e: s          # wedge the engine
    with pytest.raises(DrainStalled, match=r"max_steps=40.*slot 0"):
        sched.drain(max_steps=40)


def test_drain_watchdog_zero_progress_trips_without_budget(small_model):
    cfg, model, params = small_model
    sched = StreamScheduler(model, params, _es_cfg(), max_slots=1,
                            prompt_len=PROMPT_LEN)
    sched.submit(_mk_req(cfg, seed=1))
    sched.engine.step = lambda p, s, e: s
    sched._drain_patience = 10                     # don't wait for the bound
    with pytest.raises(DrainStalled, match="no forward progress"):
        sched.drain()


def test_drain_watchdog_silent_on_healthy_runs(small_model):
    cfg, model, params = small_model
    sched = StreamScheduler(model, params, _es_cfg(), max_slots=1,
                            prompt_len=PROMPT_LEN)
    r = _mk_req(cfg, seed=2)
    sched.submit(r)
    done = sched.drain(max_steps=5000, max_wall_s=600.0)
    assert done == [r] and r.error is None


# ---------------------------------------------------------------------------
# gauges
# ---------------------------------------------------------------------------


def test_failure_gauges_flow_through_stats():
    s = SchedulerStats()
    g = s.gauges()
    for key in ("preemptions", "pages_spilled", "resume_p50",
                "deadline_rejects", "poisoned_requests"):
        assert key in g and g[key] == 0
    s.preemptions = 2
    s.pages_spilled = 8
    s.resume_waits.extend([0.1, 0.3, 0.2])
    s.deadline_rejects = 1
    s.poisoned_requests = 3
    g = s.gauges()
    assert g["preemptions"] == 2 and g["pages_spilled"] == 8
    assert g["resume_p50"] == pytest.approx(0.2)
    assert g["deadline_rejects"] == 1 and g["poisoned_requests"] == 3
