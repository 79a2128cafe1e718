"""Multi-host disaggregated serving (ROADMAP open item 4).

``ShardedStreamScheduler`` scales the streaming scheduler past one host:
H independent *lanes* (one full ``StreamScheduler`` per shard, each with
its own page ledger, slot planes, and drain watchdog) behind ONE global
submit queue with a pluggable placement policy.  CI simulates the
multi-host topology with ``--xla_force_host_platform_device_count`` (the
same trick ``launch/dryrun.py`` uses); on real hardware each lane pins to
one host's accelerator set.

Design contract (docs/ARCHITECTURE.md §6a):

* **Shard-local ledgers.**  The paged pool is partitioned, never pooled:
  each lane owns a private ``PageAllocator`` whose refcounts, CoW
  cohorts, and persistent prefix store reference only lane-local pages.
  Every single-scheduler ledger invariant therefore holds PER SHARD
  unchanged, plus one new cross-shard conservation law:
  Σ_shard (used + free) == Σ_shard capacity  (checked by
  ``ShardedPageAllocator.check_conservation``).

* **Placement, not migration.**  A request is routed to exactly one
  shard at submit time and lives there for its whole life — preemption
  spill/resume, poison quarantine, and deadline verdicts all stay
  lane-local, so the per-shard serving outputs are bit-identical to a
  single-shard replay of the same per-shard trace (lane ``s`` seeds its
  engine state with ``seed + s``; replay with the same seed).

* **Prefix-affinity soundness.**  The persistent prefix store is
  shard-local, so a store hit can only ever be claimed by the owning
  shard; the ``prefix_affinity`` policy routes a request to the shard
  whose store holds its prompt bytes (falling back to least-loaded on a
  miss) — affinity is an optimization, never a correctness requirement.

* **Iteration smoothing (dInfer).**  Because every dLLM iteration
  reprocesses context, one long-prompt refresh inflates the step wall
  for EVERY co-resident row: the jitted step's width is the scheduler's
  padded ``prompt_len + gen_length``.  The ``disagg`` policy dedicates
  ``refresh_shards`` lanes to long prompts (full ``prompt_len``) and
  gives the remaining decode lanes a short ``decode_prompt_len``, so a
  long prefill can no longer inflate decode p95.

* **Lanes step together.**  One ``step()`` is a round: every lane with
  work dispatches its engine step (``StreamScheduler._dispatch``), and
  only then does the round wait on each lane and book its step
  (``StreamScheduler._finish``), so the lanes' devices compute at once
  and the host books lane k while the others compute.  Each lane's own
  sequence (admit, dispatch, wait, book) is a lone scheduler's, so the
  interleaving moves no output.

All lanes share ONE ``DiffusionEngine`` (the scheduler's ``engine=``
kwarg): homogeneous lanes reuse a single compiled step program, and
disagg lanes retrace once per distinct state width — never per shard.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import numpy as np

from repro.runtime.errors import ConfigError, DrainStalled, LedgerError
from repro.runtime.request import Request, StreamCallback
from repro.runtime.scheduler import PageAllocator, SchedulerStats, \
    StreamScheduler, _span

PLACEMENTS = ("least_loaded", "prefix_affinity", "disagg")


class ShardedPageAllocator:
    """Aggregate, read-mostly view over H shard-local page ledgers.

    Allocation always happens through a lane's own ``PageAllocator`` —
    this wrapper only sums the gauges and enforces the one law that
    spans shards: page conservation."""

    def __init__(self, lanes: list[PageAllocator]):
        self._lanes = list(lanes)

    def shard(self, s: int) -> PageAllocator:
        return self._lanes[s]

    def __len__(self) -> int:
        return len(self._lanes)

    @property
    def num_pages(self) -> int:
        return sum(a.num_pages for a in self._lanes)

    @property
    def capacity(self) -> int:
        """Allocatable pages (each lane excludes its own garbage page)."""
        return sum(a.num_pages - 1 for a in self._lanes)

    @property
    def free_pages(self) -> int:
        return sum(a.free_pages for a in self._lanes)

    @property
    def used_pages(self) -> int:
        return sum(a.used_pages for a in self._lanes)

    @property
    def reclaimable_pages(self) -> int:
        return sum(a.reclaimable_pages for a in self._lanes)

    @property
    def shared_mappings(self) -> int:
        return sum(a.shared_mappings for a in self._lanes)

    @property
    def prefix_evictions(self) -> int:
        return sum(a.prefix_evictions for a in self._lanes)

    def check_conservation(self) -> None:
        """Σ shard (used + free) == Σ shard capacity, and per shard too —
        a page can neither migrate between shards nor vanish."""
        for s, a in enumerate(self._lanes):
            if a.used_pages + a.free_pages != a.num_pages - 1:
                raise LedgerError(
                    f"shard {s}: used {a.used_pages} + free {a.free_pages} "
                    f"!= capacity {a.num_pages - 1}")
        if self.used_pages + self.free_pages != self.capacity:
            raise LedgerError(
                f"cross-shard conservation violated: used {self.used_pages} "
                f"+ free {self.free_pages} != capacity {self.capacity}")


class ShardedStreamScheduler:
    """H shard-local ``StreamScheduler`` lanes behind one submit queue.

    Mirrors the single-scheduler surface (``submit`` / ``step`` /
    ``drain`` / ``has_work`` / ``stats``) so servers and benches swap it
    in unchanged; adds ``shard_gauges()`` (per-shard breakdown),
    ``placements`` (request_id -> shard), and an aggregate
    ``allocator``."""

    def __init__(
        self,
        model,
        params,
        gen,
        *,
        shards: int = 2,
        placement: str = "least_loaded",
        max_slots: int = 8,
        prompt_len: int = 64,
        decode_prompt_len: Optional[int] = None,
        refresh_shards: int = 1,
        pad_id: int = 0,
        seed: int = 0,
        stream_cb: Optional[StreamCallback] = None,
        clock=time.monotonic,
        paged: bool = False,
        page_size: int = 16,
        kv_pages: Optional[int] = None,     # TOTAL pool across all shards
        devices="auto",                     # "auto": one jax device per shard
                                            # when jax.devices() holds enough
                                            # (the simulated multi-host mesh),
                                            # else shared; None: never pin;
                                            # or an explicit per-shard list
        **lane_kw,
    ):
        # -- upfront typed validation: a bad topology must not cost a
        # params init or an engine trace (same contract as launch/serve.py)
        if not isinstance(shards, int) or shards < 1:
            raise ConfigError(f"shards must be a positive int, got {shards!r}")
        if shards > 1 and not paged:
            raise ConfigError(
                "shards > 1 requires paged=True: the multi-host design "
                "shards the PAGED pool (dense KV has no per-shard ledger)")
        if max_slots % shards:
            raise ConfigError(
                f"shards ({shards}) must divide max_slots ({max_slots}): "
                "slot planes split evenly across the data axis")
        if placement not in PLACEMENTS:
            raise ConfigError(
                f"unknown placement {placement!r}; choose from {PLACEMENTS}")
        if placement == "prefix_affinity" and not lane_kw.get("prefix_sharing"):
            raise ConfigError(
                "placement='prefix_affinity' routes on the persistent "
                "prefix store — it requires prefix_sharing=True")
        if placement == "disagg":
            if shards < 2:
                raise ConfigError(
                    "placement='disagg' needs >= 2 shards (refresh + decode)")
            if not (1 <= refresh_shards < shards):
                raise ConfigError(
                    f"refresh_shards ({refresh_shards}) must satisfy "
                    f"1 <= refresh_shards < shards ({shards})")
            if decode_prompt_len is None:
                decode_prompt_len = prompt_len
            if decode_prompt_len > prompt_len:
                raise ConfigError(
                    "decode_prompt_len must not exceed prompt_len: decode "
                    "shards take the SHORT prompts")
        else:
            if decode_prompt_len is not None:
                raise ConfigError(
                    "decode_prompt_len is a disagg knob; it is ignored by "
                    f"placement={placement!r} — refusing to drop it silently")
            decode_prompt_len = prompt_len
        slots_per = max_slots // shards
        lane_prompt = [
            prompt_len if (placement != "disagg" or s < refresh_shards)
            else decode_prompt_len
            for s in range(shards)
        ]
        lane_pages: list[Optional[int]] = [None] * shards
        if paged:
            for s in range(shards):
                t_total = lane_prompt[s] + gen.gen_length
                if t_total % page_size:
                    raise ConfigError(
                        f"page_size {page_size} must divide shard {s}'s "
                        f"prompt+gen total {t_total}")
            if kv_pages is not None:
                if kv_pages % shards:
                    raise ConfigError(
                        f"kv_pages ({kv_pages}) must divide evenly across "
                        f"{shards} shards (per-shard ledgers are equal-size)")
                per = kv_pages // shards
                for s in range(shards):
                    n_vp = (lane_prompt[s] + gen.gen_length) // page_size
                    if per <= n_vp:
                        raise ConfigError(
                            f"shard pool too small: {per} pages/shard cannot "
                            f"admit shard {s}'s full-length request "
                            f"({n_vp} pages + garbage page)")
                lane_pages = [per] * shards
            else:
                # equal-size ledgers even under disagg (decode lanes would
                # default smaller): one pool shape => one shared engine
                per = max(
                    slots_per * ((lane_prompt[s] + gen.gen_length)
                                 // page_size) + 1
                    for s in range(shards))
                lane_pages = [per] * shards
        # preemption / lazy_reserve / prefix_sharing compose lane-locally:
        # the lane ctor itself validates the unsound combinations (typed),
        # and spill/resume, deficit accounting, and the prefix store never
        # cross a shard boundary — nothing is silently ignored here.
        self.shards = shards
        self.placement = placement
        self.refresh_shards = refresh_shards if placement == "disagg" else 0
        self.decode_prompt_len = decode_prompt_len
        self.prompt_len = prompt_len
        self.paged = paged
        self.page_size = page_size
        self.gen = gen
        self.clock = clock
        if isinstance(devices, str) and devices == "auto":
            devs = jax.devices()
            devices = devs[:shards] if len(devs) >= shards else None
        elif devices is not None and len(devices) != shards:
            raise ConfigError(
                f"devices must hold one device per shard "
                f"({len(devices)} != {shards})")
        self.devices = devices
        self.lanes: list[StreamScheduler] = []
        shared_engine = None
        for s in range(shards):
            lane_params = params if devices is None \
                else jax.device_put(params, devices[s])
            # build the lane's device state (tokens, pools, block tables,
            # slot planes) on its shard's device directly, not on device 0
            with jax.default_device(None if devices is None else devices[s]):
                lane = StreamScheduler(
                    model, lane_params, gen,
                    max_slots=slots_per,
                    prompt_len=lane_prompt[s],
                    pad_id=pad_id,
                    seed=seed + s,
                    stream_cb=stream_cb,
                    clock=clock,
                    paged=paged,
                    page_size=page_size,
                    kv_pages=lane_pages[s],
                    engine=shared_engine,
                    **lane_kw,
                )
            if devices is not None:
                # commit the state to the shard's device (no copy: it is
                # already there); the shared engine's jitted step follows
                # the committed inputs
                lane.state = jax.device_put(lane.state, devices[s])
            if shared_engine is None:
                shared_engine = lane.engine
            lane.lane_index = s
            self.lanes.append(lane)
        self.engine = shared_engine
        self.allocator = ShardedPageAllocator(
            [l.allocator for l in self.lanes]) if paged else None
        self.placements: dict[int, int] = {}    # request_id -> shard
        self.placed = [0] * shards              # per-shard admission counter
        # rounds that dispatched a lane, the lanes they dispatched, and the
        # wall of those rounds (the lanes' own walls overlap)
        self.rounds = 0
        self.lanes_dispatched = 0
        self.wall_s = 0.0

    # ------------------------------------------------------------------
    # placement
    # ------------------------------------------------------------------
    def _lane_load(self, s: int) -> tuple:
        """Deterministic load key: committed pages (resident + queued
        estimate) then queue depth then shard index (total order)."""
        lane = self.lanes[s]
        pages = lane.allocator.used_pages if lane.allocator else 0
        for r in lane.queue:
            p = np.asarray(r.prompt, np.int32)[-lane.prompt_len:]
            pages += lane._pages_needed(len(p), lane._req_blocks(r))[2]
        return (pages, len(lane.queue), s)

    def _place(self, req: Request) -> int:
        if self.placement == "disagg":
            if len(req.prompt) > self.decode_prompt_len:
                pool = range(self.refresh_shards)
            else:
                pool = range(self.refresh_shards, self.shards)
            return min(pool, key=self._lane_load)
        if self.placement == "prefix_affinity":
            for s, lane in enumerate(self.lanes):
                if not lane.persistent_prefix:
                    continue
                p = np.asarray(req.prompt, np.int32)[-lane.prompt_len:]
                if lane.allocator.lookup_prefix((p.tobytes(), len(p))) \
                        is not None:
                    return s        # the owning shard holds the pages
        return min(range(self.shards), key=self._lane_load)

    # ------------------------------------------------------------------
    # the single-scheduler surface
    # ------------------------------------------------------------------
    def submit(self, req: Request) -> None:
        s = self._place(req)
        self.placements[req.request_id] = s
        self.placed[s] += 1
        self.lanes[s].submit(req)

    def step(self) -> bool:
        """One round: dispatch every lane with work, then wait on and book
        each lane that dispatched.  False when no lane ran a step."""
        t0 = self.clock()
        inflight = []
        for lane in self.lanes:
            if lane.has_work():
                with _span("es.sched.step", lane=lane.lane_index):
                    f = lane._dispatch()
                if f is not None:
                    inflight.append((lane, f))
        if not inflight:
            return False
        self.rounds += 1
        self.lanes_dispatched += len(inflight)
        for lane, f in inflight:
            with _span("es.sched.finish", lane=lane.lane_index):
                lane._finish(f)
        self.wall_s += self.clock() - t0
        return True

    def has_work(self) -> bool:
        return any(lane.has_work() for lane in self.lanes)

    def drain(self, *, max_steps: Optional[int] = None,
              max_wall_s: Optional[float] = None) -> list[Request]:
        """Round-robin pump until every lane is empty; each lane keeps its
        own zero-progress watchdog semantics through the aggregate
        snapshot (a stuck lane can never hide behind a progressing one,
        because residency and completions are part of the snapshot)."""
        t0 = self.clock()
        patience = max(l._drain_patience for l in self.lanes)
        idle = 0
        steps = 0
        snap = tuple(l._progress_snapshot() for l in self.lanes)
        while self.has_work():
            if max_steps is not None and steps >= max_steps:
                raise DrainStalled(
                    f"max_steps={max_steps} exhausted with work remaining",
                    self._stuck_slots())
            if max_wall_s is not None and self.clock() - t0 > max_wall_s:
                raise DrainStalled(
                    f"max_wall_s={max_wall_s} exceeded with work remaining",
                    self._stuck_slots())
            self.step()
            steps += 1
            nxt = tuple(l._progress_snapshot() for l in self.lanes)
            idle = idle + 1 if nxt == snap else 0
            snap = nxt
            if idle >= patience:
                raise DrainStalled(
                    f"no forward progress in {idle} consecutive steps",
                    self._stuck_slots())
        done: list[Request] = []
        for lane in self.lanes:
            done.extend(lane._completed)
            lane._completed = []
        return done

    def _stuck_slots(self) -> list:
        out = []
        for s, lane in enumerate(self.lanes):
            out.extend((s,) + t for t in lane._stuck_slots())
        return out

    @property
    def completed(self) -> list[Request]:
        out = []
        for lane in self.lanes:
            out.extend(lane._completed)
            lane._completed = []
        return out

    # ------------------------------------------------------------------
    # stats rollup
    # ------------------------------------------------------------------
    @property
    def stats(self) -> SchedulerStats:
        """Per-shard gauges rolled up additively (peak gauges sum per-shard
        maxima — an upper bound, since lane peaks need not co-occur).
        ``wall_s``, ``rounds`` and ``lanes_dispatched`` are the router's
        own: the lanes' walls overlap, so their sum would count a round's
        seconds once per lane."""
        agg = SchedulerStats()
        for lane in self.lanes:
            for f in dataclasses.fields(SchedulerStats):
                v = getattr(lane.stats, f.name)
                if isinstance(v, list):
                    getattr(agg, f.name).extend(v)
                else:
                    setattr(agg, f.name, getattr(agg, f.name) + v)
        agg.wall_s = self.wall_s
        agg.rounds = self.rounds
        agg.lanes_dispatched = self.lanes_dispatched
        return agg

    def shard_gauges(self) -> list[dict]:
        """Per-shard monitoring surface (the stats-line breakdown).
        ``lanes_in_flight`` is the router's, the same on every row."""
        lanes_in_flight = self.stats.lanes_in_flight
        out = []
        for s, lane in enumerate(self.lanes):
            g = lane.stats.gauges()
            g["lanes_in_flight"] = lanes_in_flight
            g["shard"] = s
            g["placed"] = self.placed[s]
            g["resident"] = sum(r is not None for r in lane.slot_req)
            g["queued"] = len(lane.queue)
            g["completed"] = lane.stats.completed
            out.append(g)
        return out

    def reset_stats(self) -> None:
        """Bench idiom: zero every lane's counters after warmup, keeping
        the static pool gauge."""
        self.rounds = self.lanes_dispatched = 0
        self.wall_s = 0.0
        for lane in self.lanes:
            lane.stats.__init__()
            if lane.allocator is not None:
                lane.stats.pages_total = lane.allocator.num_pages - 1
