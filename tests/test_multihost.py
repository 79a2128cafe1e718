"""Multi-host disaggregated serving (ShardedStreamScheduler).

Contract under test (docs/ARCHITECTURE.md §6a):
  * H shard-local lanes behind one submit queue complete every request;
    each lane's full allocator-ledger invariants hold, plus the one
    cross-shard law: Σ shard (used + free) == Σ shard capacity;
  * placement is final (no migration): each shard's outputs are
    BIT-IDENTICAL to a fresh single-shard scheduler replaying that
    shard's requests with the lane's seed;
  * homogeneous lanes share ONE compiled step program (the scheduler's
    ``engine=`` kwarg) — sharding must not multiply traces;
  * ``least_loaded`` balances, ``prefix_affinity`` routes a prompt to
    the shard whose persistent store holds its pages, ``disagg`` sends
    long prompts to refresh shards and short ones to decode shards;
  * bad topologies raise ``ConfigError`` upfront — before any params
    init or engine trace;
  * the simulated multi-host path (``--xla_force_host_platform_device_count``,
    the dry-run trick) pins one lane per fake device and supports the
    jit-with-shardings step (``bind_state_shardings`` over
    ``make_host_mesh``) — exercised in a subprocess so the XLA flag is
    set before jax initialises;
  * one ``step()`` dispatches every lane before it waits on any, and that
    interleaving moves nothing: placements, outputs, step counts and
    ledgers equal those of the lanes stepped one at a time.
"""
import dataclasses
import importlib.util
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro import configs
from repro.configs import GenerationConfig, SkipStage
from repro.core.engine import DiffusionEngine
from repro.models import build_model
from repro.runtime import (
    ConfigError,
    Request,
    ShardedStreamScheduler,
    StreamScheduler,
)
from repro.runtime.request import pad_and_stack

_spec = importlib.util.spec_from_file_location(
    "fuzz_serving",
    os.path.join(os.path.dirname(__file__), "..", "tools", "fuzz_serving.py"))
fuzz = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(fuzz)

PROMPT_LEN = 16
PS = 8
GEN = dict(gen_length=32, block_length=8)       # 4 blocks; t_total = 48


def _small_model():
    cfg = configs.reduced(configs.get_config("llada-8b"))
    cfg = dataclasses.replace(cfg, n_layers=4)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def small_model():
    return _small_model()


def _cfg(**kw):
    base = dict(mode="es", skip_stages=(SkipStage(1, 0.5),),
                prompt_refresh_period=2, block_refresh_period=4, **GEN)
    base.update(kw)
    return GenerationConfig(**base)


def _requests(cfg, n, plen=PROMPT_LEN, seed=3, base_id=0):
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(3, cfg.vocab_size, plen)
                    .astype(np.int32), request_id=base_id + i,
                    sample_seed=base_id + i) for i in range(n)]


def _sharded(model, params, gen, **kw):
    base = dict(shards=2, max_slots=4, prompt_len=PROMPT_LEN, paged=True,
                page_size=PS, early_advance=True, devices=None)
    base.update(kw)
    return ShardedStreamScheduler(model, params, gen, **base)


def _offline_ref(model, params, gcfg, reqs, plen=PROMPT_LEN):
    eng = DiffusionEngine(model, gcfg, paged=True, page_size=PS)
    import jax.numpy as jnp
    return np.asarray(eng.generate(
        params, jnp.asarray(pad_and_stack(reqs, 0, plen)),
        jax.random.PRNGKey(0),
        sample_seeds=jnp.asarray([r.sample_seed for r in reqs])))


# ---------------------------------------------------------------------------
# completion, ledgers, single shared trace
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("temperature", [0.0, 0.7])
def test_sharded_serving_completes_with_ledger_invariants(small_model,
                                                          temperature):
    """6 requests over 2 shards all complete; every per-shard ledger
    invariant holds; cross-shard conservation holds; homogeneous lanes
    reuse ONE compiled step program."""
    cfg, model, params = small_model
    g = _cfg(temperature=temperature)
    sched = _sharded(model, params, g)
    reqs = _requests(cfg, 6)
    for r in reqs:
        sched.submit(r)
    done = sched.drain()
    assert len(done) == len(reqs)
    assert all(r.error is None and r.output is not None for r in done)
    assert sum(sched.placed) == len(reqs)
    assert set(sched.placements) == {r.request_id for r in reqs}
    assert sched.engine.step_trace_count == 1, \
        "homogeneous lanes must share ONE compiled step program"
    for lane in sched.lanes:
        fuzz.check_allocator_invariants(lane)
    sched.allocator.check_conservation()
    assert sched.allocator.used_pages == 0
    assert sched.stats.completed == len(reqs)


@pytest.mark.parametrize("temperature", [0.0, 0.7])
def test_per_shard_outputs_bit_identical_to_single_shard_replay(small_model,
                                                                temperature):
    """Placement is final: replaying each shard's requests through a fresh
    single-shard scheduler (same lane seed) reproduces the sharded outputs
    bit for bit."""
    cfg, model, params = small_model
    g = _cfg(temperature=temperature)
    sched = _sharded(model, params, g)
    reqs = _requests(cfg, 6)
    for r in reqs:
        sched.submit(r)
    done = {r.request_id: r.output for r in sched.drain()}
    for s in range(sched.shards):
        lane_reqs = [r for r in reqs if sched.placements[r.request_id] == s]
        assert lane_reqs, f"shard {s} received no requests"
        replay = StreamScheduler(
            model, params, g, max_slots=2, prompt_len=PROMPT_LEN,
            paged=True, page_size=PS, early_advance=True, seed=s)
        for r in lane_reqs:
            replay.submit(Request(prompt=r.prompt.copy(),
                                  request_id=r.request_id,
                                  sample_seed=r.sample_seed))
        ref = {r.request_id: r.output for r in replay.drain()}
        for r in lane_reqs:
            np.testing.assert_array_equal(
                done[r.request_id], ref[r.request_id],
                err_msg=f"shard {s} request {r.request_id} diverged from "
                        f"its single-shard replay")


# ---------------------------------------------------------------------------
# placement policies
# ---------------------------------------------------------------------------


def test_least_loaded_balances(small_model):
    """Identical requests submitted back-to-back spread evenly: the load
    key counts queued page estimates, so the queue never piles onto one
    shard."""
    cfg, model, params = small_model
    sched = _sharded(model, params, _cfg())
    for r in _requests(cfg, 6):
        sched.submit(r)
    assert sched.placed == [3, 3], sched.placed
    sched.drain()


def test_prefix_affinity_routes_to_owning_shard(small_model):
    """A prompt whose pages live in shard 0's persistent prefix store is
    routed back to shard 0 even when shard 1 is emptier."""
    cfg, model, params = small_model
    g = _cfg(block_causal=True)
    sched = _sharded(model, params, g, placement="prefix_affinity",
                     prefix_sharing=True)
    first = _requests(cfg, 1)[0]
    sched.submit(first)
    owner = sched.placements[first.request_id]
    sched.drain()
    # store hit beats load: resubmit the same prompt alongside fillers
    again = Request(prompt=first.prompt.copy(), request_id=101,
                    sample_seed=first.sample_seed)
    sched.submit(again)
    assert sched.placements[101] == owner, \
        "prefix_affinity must route a stored prompt to its owning shard"
    out = {r.request_id: r.output for r in sched.drain()}
    np.testing.assert_array_equal(out[101], first.output)


def test_disagg_routes_by_prompt_length(small_model):
    """disagg: long prompts land on the refresh shard (full prompt_len),
    short prompts on the decode shard (decode_prompt_len); all complete
    and the short rows match their own offline replay at the SHORT
    padded width."""
    cfg, model, params = small_model
    g = _cfg()
    long_plen, short_plen = 32, 16
    sched = ShardedStreamScheduler(
        model, params, g, shards=2, max_slots=4, prompt_len=long_plen,
        decode_prompt_len=short_plen, placement="disagg", refresh_shards=1,
        paged=True, page_size=PS, early_advance=True, devices=None)
    longs = _requests(cfg, 2, plen=long_plen, seed=5, base_id=0)
    shorts = _requests(cfg, 3, plen=short_plen, seed=6, base_id=10)
    for r in longs + shorts:
        sched.submit(r)
    assert all(sched.placements[r.request_id] == 0 for r in longs)
    assert all(sched.placements[r.request_id] == 1 for r in shorts)
    done = {r.request_id: r.output for r in sched.drain()}
    assert len(done) == 5
    # decode lane runs the SHORT schedule: bit-identical to offline at
    # prompt_len=16 (lane seed = base seed + 1 only affects engine state
    # init, not per-request sampling, which chains off sample_seed)
    ref = _offline_ref(model, params, g, shorts, plen=short_plen)
    for i, r in enumerate(shorts):
        np.testing.assert_array_equal(
            done[r.request_id], ref[i, short_plen:],
            err_msg=f"decode-shard request {r.request_id} diverged")


# ---------------------------------------------------------------------------
# validation + stats surface
# ---------------------------------------------------------------------------


def test_topology_validation_raises_upfront(small_model):
    cfg, model, params = small_model
    g = _cfg()
    kw = dict(paged=True, page_size=PS, devices=None)
    with pytest.raises(ConfigError, match="divide max_slots"):
        ShardedStreamScheduler(model, params, g, shards=3, max_slots=4, **kw)
    with pytest.raises(ConfigError, match="requires paged"):
        ShardedStreamScheduler(model, params, g, shards=2, max_slots=4,
                               devices=None)
    with pytest.raises(ConfigError, match="divide evenly"):
        ShardedStreamScheduler(model, params, g, shards=2, max_slots=4,
                               kv_pages=31, **kw)
    with pytest.raises(ConfigError, match="unknown placement"):
        ShardedStreamScheduler(model, params, g, shards=2, max_slots=4,
                               placement="round_robin", **kw)
    with pytest.raises(ConfigError, match="prefix store"):
        ShardedStreamScheduler(model, params, g, shards=2, max_slots=4,
                               placement="prefix_affinity", **kw)
    with pytest.raises(ConfigError, match="disagg knob"):
        ShardedStreamScheduler(model, params, g, shards=2, max_slots=4,
                               decode_prompt_len=8, **kw)
    with pytest.raises(ConfigError, match="refresh_shards"):
        ShardedStreamScheduler(model, params, g, shards=2, max_slots=4,
                               placement="disagg", refresh_shards=2, **kw)
    with pytest.raises(ConfigError, match="pool too small"):
        ShardedStreamScheduler(model, params, g, shards=2, max_slots=4,
                               kv_pages=12, **kw)


def test_stats_rollup_and_shard_gauges(small_model):
    cfg, model, params = small_model
    sched = _sharded(model, params, _cfg())
    reqs = _requests(cfg, 4)
    for r in reqs:
        sched.submit(r)
    sched.drain()
    agg = sched.stats
    assert agg.completed == len(reqs)
    assert agg.completed == sum(l.stats.completed for l in sched.lanes)
    gauges = sched.shard_gauges()
    assert [g["shard"] for g in gauges] == [0, 1]
    assert sum(g["placed"] for g in gauges) == len(reqs)
    for g in gauges:
        for key in ("placed", "resident", "queued", "blocks_grown",
                    "pages_in_use"):
            assert key in g, key
    sched.reset_stats()
    assert sched.stats.completed == 0
    assert sched.stats.pages_total == sched.allocator.num_pages - len(
        sched.lanes)


def test_stats_wall_is_the_rounds_wall_and_counts_lanes_in_flight(
        small_model):
    """The rollup's ``wall_s`` is the router's own wall, not the sum of the
    overlapping lane walls: it fits inside the drain's real wall.  Two
    equal lanes are both in flight in every round."""
    cfg, model, params = small_model
    sched = _sharded(model, params, _cfg())
    for r in _requests(cfg, 4):
        sched.submit(r)
    assert sched.placed == [2, 2]
    t0 = sched.clock()
    sched.drain()
    wall = sched.clock() - t0
    agg = sched.stats
    assert 0.0 < agg.wall_s <= wall
    assert agg.rounds == sched.lanes[0]._step_count > 0
    assert agg.lanes_in_flight == 2.0
    assert agg.gauges()["lanes_in_flight"] == 2.0
    assert [g["lanes_in_flight"] for g in sched.shard_gauges()] == [2.0, 2.0]
    sched.reset_stats()
    assert sched.stats.wall_s == 0.0 and sched.stats.lanes_in_flight == 0.0


# ---------------------------------------------------------------------------
# the round: every lane dispatched before the first wait
# ---------------------------------------------------------------------------


def test_step_dispatches_every_lane_before_the_first_wait(small_model,
                                                          monkeypatch):
    """One ``step()`` enqueues lane 0's and lane 1's engine steps before it
    blocks on either device, then waits on them in lane order."""
    cfg, model, params = small_model
    sched = _sharded(model, params, _cfg())
    for r in _requests(cfg, 4):
        sched.submit(r)
    calls = []
    engine_step = sched.engine.step

    def lane_of(pick, x):
        return [i for i, l in enumerate(sched.lanes) if pick(l) is x]

    def step(params, state, enc_out):
        calls.append(("dispatch", *lane_of(lambda l: l.state, state)))
        return engine_step(params, state, enc_out)

    block = jax.block_until_ready

    def wait(x):
        calls.append(("wait", *lane_of(lambda l: l.state.tokens, x)))
        return block(x)

    monkeypatch.setattr(sched.engine, "step", step)
    monkeypatch.setattr(jax, "block_until_ready", wait)
    for _ in range(2):
        calls.clear()
        assert sched.step()
        assert calls == [("dispatch", 0), ("dispatch", 1),
                         ("wait", 0), ("wait", 1)], calls


def _lanes_one_at_a_time(sched):
    """The lanes stepped in turn, each to its own wait (no overlap)."""
    while sched.has_work():
        for lane in sched.lanes:
            if lane.has_work():
                lane.step()
    return sched.completed


def check_overlap_equivalence(cfg, model, params, temperature, devices):
    """The same requests through overlapped rounds and through the lanes
    stepped one at a time: equal placements, outputs and step counts, and
    both ledgers conserved and empty."""
    g = _cfg(temperature=temperature)
    runs = []
    for drive in (lambda s: s.drain(), _lanes_one_at_a_time):
        sched = _sharded(model, params, g, devices=devices)
        if devices is not None:
            assert len(set(sched.devices)) == sched.shards
        reqs = _requests(cfg, 6)
        for r in reqs:
            sched.submit(r)
        done = drive(sched)
        assert len(done) == len(reqs)
        assert all(r.error is None for r in done)
        for lane in sched.lanes:
            fuzz.check_allocator_invariants(lane)
        sched.allocator.check_conservation()
        assert sched.allocator.used_pages == 0
        runs.append((dict(sched.placements),
                     {r.request_id: r.output for r in done},
                     [lane._step_count for lane in sched.lanes]))
    (pl_a, out_a, n_a), (pl_b, out_b, n_b) = runs
    assert pl_a == pl_b
    assert n_a == n_b
    assert out_a.keys() == out_b.keys()
    for rid in out_a:
        np.testing.assert_array_equal(out_a[rid], out_b[rid],
                                      err_msg=f"request {rid}")


_EQUIV_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import sys
import jax
assert len(jax.devices()) == 2, jax.devices()
import test_multihost as t
cfg, model, params = t._small_model()
t.check_overlap_equivalence(cfg, model, params, float(sys.argv[1]),
                            jax.devices())
print("EQUIV_OK")
"""


def _subprocess_env() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         os.path.dirname(os.path.abspath(__file__)),
         env.get("PYTHONPATH", "")])
    return env


@pytest.mark.parametrize("devices", ["shared", "forced2"])
@pytest.mark.parametrize("temperature", [0.0, 0.7])
def test_overlapped_rounds_equal_lanes_stepped_in_turn(small_model,
                                                       temperature, devices):
    """Overlapping the lanes moves no output, on lanes that share a device
    and on lanes pinned to two forced CPU devices (a subprocess, so the
    XLA flag is set before jax initialises)."""
    if devices == "shared":
        check_overlap_equivalence(*small_model, temperature, None)
        return
    proc = subprocess.run(
        [sys.executable, "-c", _EQUIV_SUBPROC, str(temperature)],
        env=_subprocess_env(), capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "EQUIV_OK" in proc.stdout


# ---------------------------------------------------------------------------
# simulated multi-host: forced fake devices + jit-with-shardings
# ---------------------------------------------------------------------------

_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import dataclasses
import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.configs import GenerationConfig, SkipStage
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.runtime import Request, ShardedStreamScheduler, StreamScheduler
from repro.sharding.specs import engine_state_pspecs, shardings_of

assert len(jax.devices()) == 2, jax.devices()

cfg = dataclasses.replace(
    configs.reduced(configs.get_config("llada-8b")), n_layers=2)
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
gen = GenerationConfig(mode="es", skip_stages=(SkipStage(1, 0.5),),
                       gen_length=16, block_length=8,
                       prompt_refresh_period=2, block_refresh_period=4)

# (a) lane-per-device: devices="auto" pins each lane's state to its shard
sched = ShardedStreamScheduler(
    model, params, gen, shards=2, max_slots=2, prompt_len=16,
    paged=True, page_size=8, early_advance=True)
assert sched.devices is not None and len(set(sched.devices)) == 2
for s, lane in enumerate(sched.lanes):
    dev, = lane.state.tokens.devices()
    assert dev == sched.devices[s], (s, dev)
rng = np.random.default_rng(0)
reqs = [Request(prompt=rng.integers(3, cfg.vocab_size, 16).astype(np.int32),
                request_id=i, sample_seed=i) for i in range(3)]
for r in reqs:
    sched.submit(r)
done = {r.request_id: r.output for r in sched.drain()}
assert len(done) == 3
sched.allocator.check_conservation()

# (b) jit-with-shardings: one scheduler whose step is re-jitted with
# explicit EngineState shardings over the 1-D host mesh — outputs must
# be bit-identical to the unsharded run above for the same per-lane trace
mesh = make_host_mesh(2)
flat = StreamScheduler(model, params, gen, max_slots=2, prompt_len=16,
                       paged=True, page_size=8, early_advance=True, seed=0)
specs = engine_state_pspecs(flat.state, mesh, paged=True)
flat.engine.bind_state_shardings(shardings_of(specs, mesh))
lane0 = [r for r in reqs if sched.placements[r.request_id] == 0]
for r in lane0:
    flat.submit(Request(prompt=r.prompt.copy(), request_id=r.request_id,
                        sample_seed=r.sample_seed))
ref = {r.request_id: r.output for r in flat.drain()}
for r in lane0:
    np.testing.assert_array_equal(done[r.request_id], ref[r.request_id])
print("MULTIHOST_OK")
"""


def test_simulated_multihost_subprocess():
    """End-to-end on 2 forced fake host devices (the dry-run trick): lanes
    pin to distinct devices, the sharded scheduler completes and conserves
    pages, and the jit-with-shardings step over ``make_host_mesh`` replays
    shard 0 bit-identically."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    proc = subprocess.run([sys.executable, "-c", _SUBPROC], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "MULTIHOST_OK" in proc.stdout
