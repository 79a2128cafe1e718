"""Continuous-batching scheduler over the slot-based engine state.

Requests enter and leave slots one at a time: the scheduler drives
``DiffusionEngine.step`` — ONE compiled program advancing every resident
slot by one denoising iteration — and does all control flow host-side:

* **slot admission** from a FIFO queue.  The engine's cadence is per-row
  (``EngineState.phase [B]``, mixed-mode step), so with
  ``early_advance=True`` admission happens on ANY iteration — a fresh slot
  enters at phase 0 and its next step prefills it while resident slots keep
  decoding.  ``early_advance=False`` keeps the block-aligned contract
  (admission only when every slot sits at phase 0, block advance only at
  the shared boundary) — bit-identical serving either way, the aligned mode
  just inserts dead iterations;
* **slot recycling** the moment a request's last block completes — with
  ``early_advance=True`` that is the very iteration the block unmasks, not
  the end of a cycle — so a long request never stalls short ones behind it;
* **per-request streaming** of completed (fully unmasked) blocks through
  ``Request.stream_cb`` / a scheduler-wide callback;
* **stats**: per-request latency/TPS and aggregate goodput — completed
  tokens per wall second, the metric arrival-process serving is judged on;

* **paged KV admission** (``paged=True``): the engine's KV caches are ONE
  page pool shared by all slots; a free-page allocator gates admission on
  page availability computed from each request's *actual* prompt length and
  requested blocks (not the padded worst case), maps the pages into the
  slot's block-table row, and returns them the moment the request retires.
  Slot count is thereby decoupled from worst-case sequence length: a pool
  sized for N dense slots can serve 2N+ mixed-length slots.

* **prefix page sharing** (``prefix_sharing=True``, paged only): admission
  hashes each request's full prompt pages; requests admitted in the SAME
  cycle with an identical prompt (and identical shape: prompt length and
  requested blocks) map the same physical pages read-only, with a refcount
  per page.  dLLM attention is bidirectional — prompt K/V depend on the
  whole sequence state — so pages are shareable exactly while every
  sharer's full sequence state is identical at every write: greedy
  (temperature-0) cohorts stay identical for life and share until
  retirement; sampled cohorts diverge at their first draw, so the
  scheduler copy-on-writes (``engine.fork_pages``) every shared page onto
  reserve pages right before the first refresh that would scatter diverged
  prompt K/V.  Reserves are allocated at admission, so a fork can never
  deadlock on an empty free list.

* **page-aligned sparse eviction**: sparse-attention eviction is sticky
  (see core.engine), so once every row of a mapped page behind the
  current block is dead (``kv_pos < 0``) nothing will ever read or
  validly write it again — after each refresh the scheduler unmaps such
  pages (``engine.dead_page_report``) and returns them to the free list,
  where they are immediately re-admittable, instead of leaving them
  masked-but-resident.

* **fault tolerance under pressure** (docs/ARCHITECTURE.md §5a): admission
  is SLO-aware — higher ``Request.priority`` classes admit first (FIFO
  within a class) and a request that cannot meet its ``deadline_s`` given
  the measured per-step cost is rejected with a typed
  ``DeadlineUnmeetable`` verdict instead of silently queueing.  With
  ``preemption=True`` (paged only) a page-starved higher class may *spill*
  the lowest-priority resident: its mapped page BYTES are gathered to host
  memory, its per-row counters parked, its pages freed — and it later
  re-admits by scattering the pages back, resuming at its block boundary
  bit-identically to an uninterrupted run.  A per-row non-finite detector
  quarantines poisoned rows (typed ``PoisonedRequest``, slot reset, private
  pages scrubbed) so one bad request can never corrupt co-resident,
  cohort-shared, or persistent-store pages.  ``drain()`` carries a
  watchdog: zero forward progress (or a blown step/wall budget) raises a
  typed ``DrainStalled`` naming the stuck slots instead of hanging CI.

``drain()`` is the offline mode (submit everything, call drain, read
``Request.output``).  docs/ARCHITECTURE.md documents the full
memory-manager contract.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import GenerationConfig
from repro.core.engine import DiffusionEngine
from repro.core.schedule import full_refresh_pred, invariant_limit
from repro.models.model import Model
from repro.runtime.errors import (
    ConfigError,
    DeadlineUnmeetable,
    DrainStalled,
    LedgerError,
    PoisonedRequest,
)
from repro.runtime.request import Request, StreamCallback

# a host span on the profiler's trace; costs one object when none runs
_span = jax.profiler.TraceAnnotation


@dataclasses.dataclass
class SchedulerStats:
    submitted: int = 0
    completed: int = 0
    tokens_out: int = 0
    wall_s: float = 0.0                  # serving-loop wall: admission + engine.step
    latencies_s: list = dataclasses.field(default_factory=list)
    # paged-KV gauges (0 / static in dense mode).  pages_in_use counts
    # PHYSICAL pages: a page mapped by several slots through prefix sharing
    # counts once (refcount-aware), so the gauge is comparable to pool bytes.
    pages_in_use: int = 0                # physical pool pages with >=1 claim
    pages_total: int = 0                 # allocatable pages (excl. garbage page)
    peak_pages_in_use: int = 0
    shared_mappings: int = 0             # extra block-table claims on shared pages
    cow_forks: int = 0                   # pages copied by copy-on-write forks
    pages_reclaimed: int = 0             # pages returned early by page-aligned eviction
    resident_peak: int = 0               # max concurrently admitted requests
    early_advances: int = 0              # block advances before the aligned boundary
    pages_deferred: int = 0              # far-suffix pages lazy admission did
                                         # NOT reserve up front (each deferred
                                         # page is pool capacity other slots
                                         # can use until the window reaches it)
    window_stalls: int = 0               # stall events: a row whose window
                                         # could not map its next pages this
                                         # step paused (never killed) until
                                         # growth is granted
    blocks_grown: int = 0                # extent blocks granted past the
                                         # admission-time request (on-demand
                                         # gen_length growth up to max_blocks,
                                         # lazy_reserve mode only)
    admission_waits: list = dataclasses.field(default_factory=list)
                                         # per-request queue wait (arrival -> admit)
    # adaptive feature cache (0 / empty with the cache disabled).  A FULL
    # refresh counts refreshed == eligible; a PARTIAL refresh counts only the
    # variation-selected tokens — so the hit fraction is the share of
    # eligible past-token K/V recomputations the cache avoided.
    cache_refreshed_total: int = 0       # past-token K/V rows recomputed
    cache_eligible_total: int = 0        # past-token K/V rows a refresh saw
    refresh_event_tokens: list = dataclasses.field(default_factory=list)
                                         # tokens refreshed per refresh event
    # the engine's prompt-refresh pass (FULL refreshes: one full-sequence
    # row each on a paged attention-only engine, so the pass's device time
    # grows with the rows it refreshes)
    refresh_passes: int = 0              # steps with >=1 row at full refresh
    refresh_rows: int = 0                # rows those steps refreshed
    # persistent cross-request prefix cache (block-causal mode only; all 0
    # otherwise).  A *hit* admits a request whose full prompt pages were
    # already resident — zero prompt-page allocations; an *eviction* drops
    # an LRU store entry under pool pressure (its pages free only once the
    # last slot claim dies).  invariant_tokens_skipped counts positions a
    # FULL refresh left in place because block-causal masking makes their
    # K/V iteration-invariant (core.schedule.invariant_limit).
    prefix_hits: int = 0                 # admissions served from the store
    prefix_evictions: int = 0            # LRU store entries dropped
    invariant_tokens_skipped: int = 0    # refresh rewrites skipped as invariant
    # failure handling (ARCHITECTURE §5a; all 0 / empty when the pressure
    # features are off).  A preemption spills ONE victim request (all its
    # mapped pages); resume_waits measures spill -> re-admission.
    preemptions: int = 0                 # victim requests spilled to host
    pages_spilled: int = 0               # pages gathered to host by spills
    resume_waits: list = dataclasses.field(default_factory=list)
                                         # per-resume parked time (spill->resume)
    deadline_rejects: int = 0            # typed DeadlineUnmeetable verdicts
    poisoned_requests: int = 0           # rows quarantined by the NaN detector
    # ShardedStreamScheduler's rounds (0 on a lone scheduler): rounds that
    # dispatched a lane, and the lanes in flight when each began its waits
    rounds: int = 0
    lanes_dispatched: int = 0

    @property
    def goodput(self) -> float:
        """Completed tokens per wall second (aggregate serving metric)."""
        return self.tokens_out / self.wall_s if self.wall_s else 0.0

    @property
    def admission_wait_p50(self) -> float:
        if not self.admission_waits:
            return 0.0
        return float(np.percentile(np.asarray(self.admission_waits), 50))

    @property
    def cache_hit_fraction(self) -> float:
        """Fraction of eligible past-token K/V recomputations the adaptive
        feature cache skipped (0.0 when disabled or before any refresh)."""
        if not self.cache_eligible_total:
            return 0.0
        return 1.0 - self.cache_refreshed_total / self.cache_eligible_total

    @property
    def tokens_refreshed_p50(self) -> float:
        if not self.refresh_event_tokens:
            return 0.0
        return float(np.percentile(np.asarray(self.refresh_event_tokens), 50))

    @property
    def refresh_rows_per_pass(self) -> float:
        """Mean rows a prompt-refresh pass refreshed (0.0 before any)."""
        if not self.refresh_passes:
            return 0.0
        return self.refresh_rows / self.refresh_passes

    @property
    def lanes_in_flight(self) -> float:
        """Mean lanes whose steps ran at once in a round (0.0 before any)."""
        if not self.rounds:
            return 0.0
        return self.lanes_dispatched / self.rounds

    @property
    def resume_p50(self) -> float:
        """Median seconds a preempted request spent parked on the host."""
        if not self.resume_waits:
            return 0.0
        return float(np.percentile(np.asarray(self.resume_waits), 50))

    def gauges(self) -> dict:
        """Point-in-time gauge snapshot (the monitoring-surface dict)."""
        return {
            "pages_in_use": self.pages_in_use,
            "pages_total": self.pages_total,
            "peak_pages_in_use": self.peak_pages_in_use,
            "shared_mappings": self.shared_mappings,
            "cow_forks": self.cow_forks,
            "pages_reclaimed": self.pages_reclaimed,
            "resident_peak": self.resident_peak,
            "early_advances": self.early_advances,
            "pages_deferred": self.pages_deferred,
            "window_stalls": self.window_stalls,
            "blocks_grown": self.blocks_grown,
            "admission_wait_p50": self.admission_wait_p50,
            "cache_hit_fraction": self.cache_hit_fraction,
            "tokens_refreshed_p50": self.tokens_refreshed_p50,
            "refresh_rows_per_pass": self.refresh_rows_per_pass,
            "prefix_hits": self.prefix_hits,
            "prefix_evictions": self.prefix_evictions,
            "invariant_tokens_skipped": self.invariant_tokens_skipped,
            "preemptions": self.preemptions,
            "pages_spilled": self.pages_spilled,
            "resume_p50": self.resume_p50,
            "deadline_rejects": self.deadline_rejects,
            "poisoned_requests": self.poisoned_requests,
            "lanes_in_flight": self.lanes_in_flight,
        }

    # aliases of goodput / completed / tokens_out under the names the
    # serve.py summary line and offline callers read
    @property
    def tps(self) -> float:
        return self.goodput

    @property
    def requests(self) -> int:
        return self.completed

    @property
    def tokens_generated(self) -> int:
        return self.tokens_out

    def latency_pct(self, pct: float) -> float:
        if not self.latencies_s:
            return 0.0
        return float(np.percentile(np.asarray(self.latencies_s), pct))


class PageAllocator:
    """Host-side refcounted free-list over the shared KV pool.

    Page 0 is the reserved garbage page (unmapped block-table entries clamp
    to it) and is never handed out; pages 1..num_pages-1 are allocatable.

    v2 (memory manager): every allocated page carries a refcount.
    ``alloc`` hands pages out at refcount 1; ``share`` adds a claim — the
    prefix-sharing path, where refcount > 1 means the page is READ-ONLY and
    a scatter of diverged content must fork it first (``engine.fork_pages``);
    ``release`` drops one claim and returns the page to the free list when
    the last claim dies.  ``used_pages`` counts *physical* pages — a page
    shared by N slots counts once — which is what makes the scheduler's
    ``pages_in_use`` gauge comparable to pool bytes.

    The allocator also keeps the **prefix page hash**: full prompt pages
    registered under a content key at admission, so duplicate prompts
    admitted in the same cycle can map the same physical pages.  The
    scheduler clears the hash at the end of every admission cycle, because
    bidirectional dLLM attention makes prompt K/V depend on the whole
    sequence state: pages written by slots admitted in different cycles are
    never content-equal (docs/ARCHITECTURE.md, sharing contract).

    **Persistent mode** (``persistent=True``, block-causal attention only):
    the index becomes a cross-request prefix STORE.  ``register_prefix``
    takes one store-owned ``share`` claim per page, so registered prompt
    pages stay resident — content intact — after every slot claim dies;
    ``lookup_prefix`` is an LRU touch; and ``alloc`` under pool pressure
    evicts least-recently-used store entries (dropping only the store's
    claims — an entry whose pages are still mapped by live slots frees
    nothing until those slots retire) before reporting the pool full.  The
    scheduler never cycle-clears a persistent index: block-causal prompt
    K/V depend only on the prompt bytes, so residency is sound across
    admission cycles and requests (docs/ARCHITECTURE.md §4).
    """

    def __init__(self, num_pages: int, persistent: bool = False):
        assert num_pages >= 2, "pool needs the garbage page + >=1 real page"
        self.num_pages = num_pages
        self.persistent = persistent
        self._free = list(range(num_pages - 1, 0, -1))   # pop() -> low ids first
        self._refcount = [0] * num_pages
        # content key -> payload.  Same-cycle mode: opaque admission payload,
        # cleared every cycle.  Persistent mode: (slot, [(vp, page)]) whose
        # pages the store holds claims on; dict order is the LRU order
        # (lookup reinserts, eviction pops from the front).
        self._prefix: dict = {}
        self.prefix_evictions = 0        # LRU store entries evicted (persistent)
        self.pages_allocated = 0         # lifetime pages handed out by alloc()

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return (self.num_pages - 1) - len(self._free)

    @property
    def shared_mappings(self) -> int:
        """Extra claims created by sharing (sum of refcount-1 over pages)."""
        return sum(rc - 1 for rc in self._refcount if rc > 1)

    @property
    def reclaimable_pages(self) -> int:
        """Pages an LRU eviction sweep could free RIGHT NOW: store-claimed
        pages with no other live claim.  Admission and window-growth gates
        must count these next to ``free_pages`` — a persistent store is a
        cache, not a reservation, and treating its idle pages as unavailable
        deadlocks a tight pool (the gate never passes, eviction never runs)."""
        if not self.persistent:
            return 0
        return sum(1 for _, page_map in self._prefix.values()
                   for _, pg in page_map if self._refcount[pg] == 1)

    def refcount(self, page: int) -> int:
        return self._refcount[page]

    def alloc(self, n: int) -> Optional[list[int]]:
        if n > len(self._free) and self.persistent:
            # pool pressure: evict LRU store entries until the request fits
            # or no evictable entry remains.  Dropping an entry releases the
            # STORE's claims only, so an entry whose every page is still
            # mapped by a live slot would free nothing — it is hot by
            # definition and is skipped, not churned (evicting it could
            # never satisfy THIS alloc, and would force the next admission
            # of the same prompt to re-allocate the whole prefix).
            for key in list(self._prefix):
                if n <= len(self._free):
                    break
                _, page_map = self._prefix[key]
                if all(self._refcount[pg] > 1 for _, pg in page_map):
                    continue
                del self._prefix[key]
                self.release([pg for _, pg in page_map])
                self.prefix_evictions += 1
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._refcount[p] = 1
        self.pages_allocated += n
        return pages

    def _check_live(self, page: int, op: str) -> None:
        """Typed ledger guards (ARCHITECTURE invariant 13): operating on a
        page with no live claim is always bookkeeping corruption, never a
        load condition, so it raises ``LedgerError`` instead of asserting —
        the guard survives ``python -O`` and callers can pattern-match."""
        rc = self._refcount[page]
        if rc < 0:
            raise LedgerError(
                f"negative refcount {rc} on page {page} (ledger corrupted)")
        if rc == 0:
            verb = ("double release of" if op == "release"
                    else "share-after-free on")
            raise LedgerError(f"{verb} page {page}: no live claim")

    def share(self, pages: list[int]) -> None:
        """Add one read-only claim per page (prefix sharing)."""
        for p in pages:
            self._check_live(p, "share")
            self._refcount[p] += 1

    def release(self, pages: list[int]) -> int:
        """Drop one claim per page; the last claim frees the page.  Returns
        the number of pages PHYSICALLY freed (refcount hit 0) — the unit
        gauges must report, since a shared page's other claims keep it
        resident."""
        freed = 0
        for p in pages:
            self._check_live(p, "release")
            self._refcount[p] -= 1
            if self._refcount[p] == 0:
                self._free.append(p)
                freed += 1
        return freed

    # -- prefix page hash ---------------------------------------------------
    # Same-cycle mode: valid within ONE admission cycle (scheduler clears).
    # Persistent mode: a cross-request store with LRU residency (see class
    # docstring); payload must be (slot, [(vp, page)]).
    def register_prefix(self, key, payload) -> None:
        if self.persistent:
            assert key not in self._prefix, "re-registering a resident prefix"
            _, page_map = payload
            self.share([pg for _, pg in page_map])   # the store's own claims
        self._prefix[key] = payload

    def lookup_prefix(self, key):
        hit = self._prefix.get(key)
        if hit is not None and self.persistent:
            # LRU touch: reinsertion moves the key to the back of the
            # eviction order
            self._prefix.pop(key)
            self._prefix[key] = hit
        return hit

    def clear_prefix_index(self) -> None:
        if self.persistent:
            # full flush (not part of the serving loop in persistent mode):
            # drop every store claim so the pages can actually free
            for _, page_map in self._prefix.values():
                self.release([pg for _, pg in page_map])
        self._prefix.clear()

    def drop_prefix_entries(self, pages: set) -> int:
        """Persistent mode: drop every store entry mapping any of ``pages``
        (quarantine hygiene — a poisoned row's pages must not stay reachable
        through the cross-request store).  Returns entries dropped."""
        if not self.persistent:
            return 0
        dropped = 0
        for key in list(self._prefix):
            _, page_map = self._prefix[key]
            if any(pg in pages for _, pg in page_map):
                del self._prefix[key]
                self.release([pg for _, pg in page_map])
                dropped += 1
        return dropped


@dataclasses.dataclass(eq=False)            # identity equality (ndarray fields)
class _SpilledRequest:
    """A preempted request parked on the host (ARCHITECTURE §5a).

    Captured at the victim's block boundary (``phase == 0``): the next step
    of both the parked and an uninterrupted run would be a FULL refresh,
    which rebuilds conf/pred/hidden/feat from tokens + KV without reading
    their carried values — so only the fields below need to survive.  The
    KV page BYTES must restore exactly (block-causal invariant-refresh
    exemption never rewrites settled positions), hence ``kv_data``.
    A spilled request holds ZERO allocator claims while parked.
    """
    req: Request
    seq: int                 # original submission order (class-FIFO resume)
    n_blocks: int            # admission-time block budget
    vps: list                # mapped virtual pages at spill time, in order
    kv_data: object          # engine.spill_pages host tree (one axis-1 slice
                             # per entry of vps, same order)
    row: dict                # per-row counters + token/kv_valid/feat planes
    streamed: int            # blocks already streamed before the spill
    spill_s: float           # clock at spill (resume_waits gauge)


@dataclasses.dataclass
class _InFlight:
    """A dispatched engine step: what its finish needs from its dispatch
    (the step's start on the clock, the host copies read before the engine
    ran)."""
    t0: float
    phases: np.ndarray
    resident: np.ndarray
    refresh_rows: np.ndarray
    pre_blocks_left: np.ndarray
    pre_r: Optional[np.ndarray] = None   # feature-cache counters (cache on)
    pre_e: Optional[np.ndarray] = None


class StreamScheduler:
    """Slot-recycling streaming scheduler (continuous batching)."""

    def __init__(
        self,
        model: Model,
        params: dict,
        gen: GenerationConfig,
        *,
        max_slots: int = 8,
        prompt_len: int = 64,
        pad_id: int = 0,
        seed: int = 0,
        stream_cb: Optional[StreamCallback] = None,
        clock=time.monotonic,
        paged: bool = False,
        page_size: int = 16,
        kv_pages: Optional[int] = None,     # None => dense-equivalent pool
        prefix_sharing: bool = False,       # CoW prompt-page dedup (paged only)
        early_advance: bool = False,        # per-row cadence: any-iteration
                                            # admission + immediate block advance
        lazy_reserve: bool = False,         # windowed paged mode: admit with
                                            # prompt + active-window pages only
                                            # and grow the mapping just-in-time
                                            # as each row's bs advances
        preemption: bool = False,           # page pressure may spill the
                                            # lowest-priority resident to host
                                            # memory (paged only; resumes
                                            # bit-identically at its block
                                            # boundary)
        **engine_kw,
    ):
        assert gen.gen_length % gen.block_length == 0
        self.model = model
        self.params = params
        self.gen = gen
        self.max_slots = max_slots
        self.prompt_len = prompt_len
        self.pad_id = pad_id
        self.stream_cb = stream_cb
        self.clock = clock
        self.paged = paged
        self.page_size = page_size
        assert not (prefix_sharing and not paged), \
            "prefix_sharing shares pool pages — it requires paged=True"
        self.prefix_sharing = prefix_sharing
        assert not (lazy_reserve and not paged), \
            "lazy_reserve defers pool pages — it requires paged=True"
        assert not (lazy_reserve and not gen.windowed), \
            "lazy_reserve needs a finite window (window_blocks > 0): unmapped " \
            "far-suffix pages are sound only when the window masks them"
        # lazy_reserve composes with prefix_sharing: deficit accounting is
        # private-pages-only, and shared prompt vpages always sit inside the
        # initially-mapped extent, so admission subtracts them from the
        # up-front need while growth deficits (all-private far suffix) are
        # untouched (ARCHITECTURE §1c).
        self.lazy_reserve = lazy_reserve
        # preemption spill/resume needs every victim page to be private
        # (refcount 1, fully owned by the victim): a spilled page is
        # RELEASED, which under sharing would yank pages out from under
        # co-resident sharers, and under lazy reservation would break the
        # max-deficit liveness accounting.  Typed, upfront rejection.
        if preemption:
            if not paged:
                raise ConfigError(
                    "preemption=True requires paged=True: spilling moves "
                    "pool pages, dense KV rows cannot be released")
            if prefix_sharing:
                raise ConfigError(
                    "preemption=True is incompatible with prefix_sharing: "
                    "a spill releases the victim's pages, which sharing "
                    "may have mapped into co-resident slots")
            if lazy_reserve:
                raise ConfigError(
                    "preemption=True is incompatible with lazy_reserve: "
                    "spills would invalidate the max-deficit window-growth "
                    "liveness accounting")
        self.preemption = preemption
        self.early_advance = early_advance
        engine_kw.setdefault("early_advance", early_advance)
        # persistent cross-request prefix cache: sound exactly when the mask
        # is block-causal (prompt K/V depend only on prompt bytes), so it
        # auto-enables with the flag pair and silently stays off otherwise —
        # bidirectional sharing keeps its same-cycle-only contract.
        self.persistent_prefix = bool(
            prefix_sharing and paged and gen.block_causal)
        t_total = prompt_len + gen.gen_length
        self.allocator: Optional[PageAllocator] = None
        if paged:
            assert t_total % page_size == 0, (
                f"page_size {page_size} must divide prompt+gen {t_total}")
            n_vp = t_total // page_size
            if kv_pages is None:
                kv_pages = max_slots * n_vp + 1
            assert kv_pages > n_vp, (
                "pool too small: a full-length request could never be admitted")
            engine_kw.update(paged=True, page_size=page_size, kv_pages=kv_pages)
            self.allocator = PageAllocator(
                kv_pages, persistent=self.persistent_prefix)
        shared_engine = engine_kw.pop("engine", None)
        if shared_engine is not None:
            # multi-host lanes hand every scheduler the SAME engine so
            # homogeneous shards share one compiled step program; everything
            # that changes the traced program must agree, typed and upfront
            if (shared_engine.gen is not gen
                    or shared_engine.paged != paged
                    or (paged and shared_engine.page_size != page_size)
                    or (paged and shared_engine.kv_pages != kv_pages)
                    or shared_engine.early_advance
                    != engine_kw["early_advance"]):
                raise ConfigError(
                    "shared engine mismatch: a scheduler can only reuse an "
                    "engine built with the same gen config and identical "
                    "paged/page_size/kv_pages/early_advance settings")
            self.engine = shared_engine
        else:
            self.engine = DiffusionEngine(model, gen, **engine_kw)
        self.n_blocks = gen.gen_length // gen.block_length
        self.state = self.engine.init_engine_state(
            max_slots, prompt_len, jax.random.PRNGKey(seed))
        self.queue: deque[Request] = deque()
        self.slot_req: list[Optional[Request]] = [None] * max_slots
        self.slot_streamed: list[int] = [0] * max_slots
        self.slot_blocks: list[int] = [0] * max_slots   # blocks this request asked for
        # one entry per page CLAIM this slot holds (shared pages included —
        # releasing a claim only frees the page when its refcount hits 0)
        self.slot_pages: list[list[int]] = [[] for _ in range(max_slots)]
        # lazy reservation (window growth) bookkeeping, paged mode only:
        # extent = the (first_vp, last_vp) the request will EVER map, frontier
        # = first still-unmapped vp (== last_vp once fully grown), order = the
        # admission sequence number the no-deadlock growth policy ranks by.
        self.slot_extent: list[tuple[int, int]] = [(0, 0)] * max_slots
        self.slot_frontier: list[int] = [0] * max_slots
        self.slot_order: list[int] = [0] * max_slots
        # on-demand extent growth (ROADMAP item 5): True freezes a row's
        # extent for life — set at admission for rows without max_blocks
        # headroom, and STICKY on a denied growth decision (a later grant
        # would remap the row's read set mid-block and break replay)
        self.slot_no_grow: list[bool] = [True] * max_slots
        self._admit_seq = 0
        # slots paused by a denied window growth: inactive on device but NOT
        # retired — _finish_cycle skips them, _grow_windows resumes them
        self.stalled: set[int] = set()
        # preempted requests parked on the host (zero allocator claims);
        # re-admission competes with the queue by (priority, submission seq)
        self._spilled: list[_SpilledRequest] = []
        self._submit_seq = 0
        self._seq: dict[int, int] = {}      # request_id -> submission seq
        # measured per-engine-step wall cost (EWMA) — the analytic term of
        # the deadline-admission estimate; None until the first step
        self._step_ewma: Optional[float] = None
        # zero-progress watchdog bound for drain(): generous — several full
        # offline passes' worth of iterations — so it can only ever trip on
        # a real livelock, never on a slow-but-progressing pool
        self._drain_patience = max(
            64, 8 * gen.resolved_steps() * (self.n_blocks + 2))
        # sharing cohorts: {"owner": slot, "slots": {slot: [(vp, page)]},
        # "reserve": {slot: [pages]}, "born": step} — see _admit/_cow_fork
        self.cohorts: list[dict] = []
        self._step_count = 0
        # set by ShardedStreamScheduler: the lane's index, an argument of
        # its ``es.sched.step`` profiler span
        self.lane_index: Optional[int] = None
        self.stats = SchedulerStats()
        if self.allocator is not None:
            self.stats.pages_total = self.allocator.num_pages - 1
        self._completed: list[Request] = []
        # modality contract: encoder-conditioned archs need enc_embeds on
        # every request, others on none — validated at submit() so a mixed
        # batch can never reach the compute path.
        self.expects_enc = bool(model.cfg.n_encoder_layers) or \
            model.cfg.family in ("audio", "vlm")
        self._enc_out = None
        if self.expects_enc:
            d_enc = model.cfg.d_enc or model.cfg.d_model
            # encoder outputs are projected to d_model for VLM cross-attn;
            # device-resident so steady-state steps pay no host->device copy
            d_out = model.cfg.d_model if model.cfg.family == "vlm" else d_enc
            self._enc_out = jax.numpy.zeros(
                (max_slots, model.cfg.n_enc_tokens, d_out), np.float32)

    # ------------------------------------------------------------------
    # submission / admission
    # ------------------------------------------------------------------
    def submit(self, req: Request) -> None:
        has_enc = req.enc_embeds is not None
        if has_enc != self.expects_enc:
            raise ValueError(
                f"modality mismatch: model "
                f"{'requires' if self.expects_enc else 'does not accept'} "
                f"enc_embeds but request {req.request_id} "
                f"{'omitted' if self.expects_enc else 'supplied'} them"
            )
        req.arrival_s = self.clock()
        self.stats.submitted += 1
        self._seq[req.request_id] = self._submit_seq
        self._submit_seq += 1
        if req.deadline_s is not None:
            # submit-time triage: a nonpositive budget, or an estimated
            # service time that already exceeds it, can only ever miss
            est = self._estimate_service_s(self._req_blocks(req))
            if req.deadline_s <= 0 or est > req.deadline_s:
                self._reject_deadline(req, 0.0, est)
                return
        self.queue.append(req)

    def _req_blocks(self, req: Request) -> int:
        """Admission-time block budget (the soft hint capped by the hard
        ``max_blocks``) — the quantity the page and deadline math size by."""
        n_blocks = self.n_blocks
        if req.max_new_tokens is not None:
            # whole blocks only: the block loop is the progress quantum
            n_blocks = min(
                max(-(-req.max_new_tokens // self.gen.block_length), 1),
                self.n_blocks)
        if req.max_blocks is not None:
            # HARD cap, honoured in every mode: under lazy reservation it
            # bounds the extent the window may ever grow to
            n_blocks = min(n_blocks, max(req.max_blocks, 1))
        return n_blocks

    def _estimate_service_s(self, n_blocks: int) -> float:
        """Analytic service estimate: blocks x steps-per-block x the
        measured per-step wall EWMA.  0.0 until the first step has been
        timed — cold admission never rejects on a guess."""
        if self._step_ewma is None:
            return 0.0
        return n_blocks * self.gen.resolved_steps() * self._step_ewma

    def _reject_deadline(self, req: Request, waited: float,
                         est: float) -> None:
        now = self.clock()
        req.error = DeadlineUnmeetable(
            req.request_id, req.deadline_s, waited, est)
        req.finish_s = now
        req.latency_s = now - req.arrival_s
        self.stats.deadline_rejects += 1
        self._completed.append(req)

    def _free_slots(self) -> list[int]:
        return [i for i, r in enumerate(self.slot_req) if r is None]

    def _pages_needed(self, prompt_tokens: int, n_blocks: int) -> tuple[int, int, int]:
        """(first_vp, last_vp, count) of virtual pages a request must map.

        Accounting uses the request's ACTUAL prompt length: pad rows below
        ``prompt_start`` are attention-masked, so whole pad-only pages are
        simply never mapped — short prompts and short (max_new_tokens)
        requests both cost fewer pool pages than the padded worst case.

        Note the semantics this buys: a paged ``max_new_tokens`` request
        never maps (so never attends) the mask-token region beyond its last
        block — it decodes exactly like an offline run with
        ``gen_length = n_blocks * block_length``.  Dense serving instead
        attends the full padded tail, so short-request outputs differ
        between the two layouts by design (full-length requests are
        bit-identical).  Offline replay of a short paged request therefore
        uses the truncated ``gen_length``, not the scheduler's."""
        ps = self.page_size
        start = self.prompt_len - prompt_tokens          # prompt_start
        first_vp = start // ps
        last_vp = -(-(self.prompt_len + n_blocks * self.gen.block_length) // ps)
        return first_vp, last_vp, last_vp - first_vp

    def _admit(self) -> None:
        """Fill free slots from the queue.  An admitted slot's phase is set
        to 0, so the next step prefills its caches — under per-row cadence
        that works on ANY iteration (``early_advance=True`` calls this every
        step); block-aligned mode calls it only when every slot sits at
        phase 0, preserving the shared cadence.

        In paged mode admission is additionally page-availability-gated:
        the queue head waits (FIFO, no overtaking) until retirements return
        enough pages.

        With ``prefix_sharing`` the request's full prompt pages are hashed
        into the allocator's prefix index; a same-cycle duplicate (identical
        prompt bytes, prompt length, and requested blocks) maps the owner's
        physical pages read-only (refcount + 1) and allocates only its
        private pages — plus, when sampling, an equal number of CoW
        *reserve* pages so the pre-refresh fork can never fail on an empty
        free list.  The index is cleared at the end of the cycle: slots
        admitted in different cycles have different sequence states, so
        their prompt K/V are never content-equal (bidirectional attention).
        """
        free = self._free_slots()
        if not (self.queue or self._spilled):
            return
        if not free and not self.preemption:
            return
        st = self.state
        t_total = self.prompt_len + self.gen.gen_length
        now = self.clock()
        lb = self.gen.block_length
        sampled = self.gen.temperature > 0
        cycle_cohorts: dict = {}        # share key -> cohort (this cycle only)
        while self.queue or self._spilled:
            # merged candidate order: highest priority class first, FIFO
            # (submission order) within a class.  Spilled requests compete
            # under the same key, so a parked victim regains its original
            # place the moment capacity returns; with every priority at the
            # default 0 this degenerates to the plain FIFO queue.
            cands = [(-r.priority, self._seq[r.request_id], r)
                     for r in self.queue]
            cands += [(-rec.req.priority, rec.seq, rec)
                      for rec in self._spilled]
            cands.sort(key=lambda c: (c[0], c[1]))
            top = cands[0][2]
            if not free:
                # slot-starved: spill one lower-class victim to free its
                # slot (its pages return with it) — preemption covers the
                # slot dimension, not just the page pool
                st, ok = self._try_preempt(st, 0, -cands[0][0], free)
                if not ok or not free:
                    break
            if isinstance(top, _SpilledRequest):
                rec = top
                got = self.allocator.alloc(len(rec.vps))
                if got is None:
                    st, ok = self._try_preempt(
                        st, len(rec.vps), rec.req.priority, free)
                    if ok:
                        got = self.allocator.alloc(len(rec.vps))
                if got is None:
                    break               # page-gated: retry next cycle
                self._spilled.remove(rec)
                slot = free.pop(0)
                st = self._resume_into(st, slot, rec, got, now)
                continue
            req = top
            if req.deadline_s is not None:
                # SLO admission: once wait + estimated service exceeds the
                # budget the request can only miss — reject NOW with a
                # typed verdict instead of burning a slot and pool pages
                waited = now - req.arrival_s
                est = self._estimate_service_s(self._req_blocks(req))
                if waited + est > req.deadline_s:
                    self.queue.remove(req)
                    self._reject_deadline(req, waited, est)
                    continue
            n_blocks = self._req_blocks(req)
            p = np.asarray(req.prompt, np.int32)[-self.prompt_len:]
            no_grow = req.max_blocks is None
            if self.lazy_reserve and req.max_blocks is not None:
                # On-demand extent growth (ROADMAP item 5): the initial
                # active window already attends 1 + window_blocks blocks,
                # so the existence of every block inside that horizon must
                # be decided HERE, once — mapping them later would change
                # this row's read set mid-block and break bit-identical
                # replay.  Blocks past the horizon are decided one at a
                # time at their block entry by _grow_windows.  The grow
                # predicate mirrors the lazy admission gate (whole enlarged
                # need coverable now, on top of every resident deficit);
                # a denial admits the soft-hint extent and freezes it.
                cap = min(max(req.max_blocks, 1), self.n_blocks)
                horizon = 1 + self.gen.window_blocks
                if n_blocks < min(horizon, cap):
                    want_nb = min(horizon, cap)
                    resident_deficit = max(
                        (self.slot_extent[s][1] - self.slot_frontier[s]
                         for s, r in enumerate(self.slot_req)
                         if r is not None), default=0)
                    avail = (self.allocator.free_pages
                             + self.allocator.reclaimable_pages)
                    w_need = self._pages_needed(len(p), want_nb)[2]
                    if avail - w_need >= resident_deficit:
                        n_blocks = want_nb
                    else:
                        no_grow = True
            pages: list[int] = []
            shared_map: list[tuple[int, int]] = []   # [(vp, physical page)]
            reserve: list[int] = []
            share_key = None
            share_hit = None
            first_vp = last_vp = map_last = 0
            deficit_new = 0
            if self.allocator is not None:
                first_vp, last_vp, need = self._pages_needed(len(p), n_blocks)
                map_last = last_vp
                vp0 = -(-(self.prompt_len - len(p)) // self.page_size)
                vp1 = self.prompt_len // self.page_size
                if (self.prefix_sharing and not self.expects_enc
                        and vp1 > vp0):
                    # persistent (block-causal) keys drop n_blocks: prompt
                    # K/V depend only on the prompt bytes, so requests with
                    # different generation budgets share the same pages
                    share_key = (p.tobytes(), len(p)) if \
                        self.persistent_prefix else (p.tobytes(), len(p),
                                                     n_blocks)
                    share_hit = self.allocator.lookup_prefix(share_key)
                if self.lazy_reserve:
                    # map prompt + the first active-window's worth of
                    # blocks only; the rest is a recorded DEFICIT the
                    # window grows into just-in-time.  No-deadlock gate:
                    # after this admission the free list must still cover
                    # the largest single deficit (this request's, or any
                    # resident row's) so the oldest row can always finish
                    # growing — the liveness invariant of ARCHITECTURE
                    # §1c.  A failed gate waits FIFO, like page-gating.
                    # Deficits are private-pages-only by construction:
                    # shared prompt vpages sit inside the initial extent,
                    # so sharing only ever shrinks the up-front need.
                    init_blocks = min(1 + self.gen.window_blocks, n_blocks)
                    init_last = -(-(self.prompt_len + init_blocks * lb)
                                  // self.page_size)
                    deficit_new = last_vp - init_last
                    map_last = init_last
                    need = init_last - first_vp
                if share_hit is not None:
                    owner_slot, owner_map = share_hit
                    shared_map = list(owner_map)
                    # CoW reserves protect sampled cohorts from diverged
                    # prompt rewrites — a bidirectional-mode hazard only.
                    # Block-causal prompt K/V are trajectory-independent,
                    # so persistent hits reserve nothing.
                    n_res = len(shared_map) if (
                        sampled and not self.persistent_prefix) else 0
                    n_priv = need - len(shared_map)
                    # claim the shared pages BEFORE alloc: under pool
                    # pressure alloc may evict this very store entry, and
                    # these claims keep the pages resident through it
                    self.allocator.share([pg for _, pg in shared_map])
                    if self.lazy_reserve:
                        resident_deficit = max(
                            (self.slot_extent[s][1] - self.slot_frontier[s]
                             for s, r in enumerate(self.slot_req)
                             if r is not None), default=0)
                        avail = (self.allocator.free_pages
                                 + self.allocator.reclaimable_pages)
                        if avail - (n_priv + n_res) < \
                                max(deficit_new, resident_deficit):
                            self.allocator.release(
                                [pg for _, pg in shared_map])
                            break               # reserve-gated: retry later
                    got = self.allocator.alloc(n_priv + n_res)
                    if got is None:
                        self.allocator.release([pg for _, pg in shared_map])
                        break                   # page-gated: retry next cycle
                    pages = got[:n_priv]
                    reserve = got[n_priv:]
                    if self.persistent_prefix:
                        self.stats.prefix_hits += 1
                else:
                    if self.lazy_reserve:
                        resident_deficit = max(
                            (self.slot_extent[s][1] - self.slot_frontier[s]
                             for s, r in enumerate(self.slot_req)
                             if r is not None), default=0)
                        avail = (self.allocator.free_pages
                                 + self.allocator.reclaimable_pages)
                        if avail - need < max(
                                deficit_new, resident_deficit):
                            break               # reserve-gated: retry later
                    got = self.allocator.alloc(need)
                    if got is None and self.preemption:
                        # page-starved: spill lower classes at their block
                        # boundaries until the pool covers this request
                        st, ok = self._try_preempt(st, need, req.priority, free)
                        if ok:
                            got = self.allocator.alloc(need)
                    if got is None:
                        break                   # page-gated: retry next cycle
                    pages = got
            slot = free.pop(0)
            self.queue.remove(req)
            row = np.full((t_total,), self.engine.mask_id, np.int32)
            row[: self.prompt_len] = self.pad_id
            row[self.prompt_len - len(p): self.prompt_len] = p
            st = st._replace(
                tokens=st.tokens.at[slot].set(row),
                bs=st.bs.at[slot].set(self.prompt_len),
                blocks_left=st.blocks_left.at[slot].set(n_blocks),
                phase=st.phase.at[slot].set(0),
                iters=st.iters.at[slot].set(0),
                kv_valid=st.kv_valid.at[slot].set(True),
                active=st.active.at[slot].set(True),
                prompt_start=st.prompt_start.at[slot].set(
                    self.prompt_len - len(p) if self.paged else 0),
                sample_seeds=st.sample_seeds.at[slot].set(
                    req.sample_seed if req.sample_seed is not None
                    else req.request_id),
            )
            if st.feat is not None:
                # adaptive feature cache: a recycled slot must not inherit the
                # previous request's probe features / confidences or inflate
                # its refresh counters
                st = st._replace(
                    feat=st.feat.at[slot].set(0.0),
                    conf_full=st.conf_full.at[slot].set(0.0),
                    cache_refreshed=st.cache_refreshed.at[slot].set(0),
                    cache_eligible=st.cache_eligible.at[slot].set(0),
                )
            if self.allocator is not None:
                bt_row = np.full((t_total // self.page_size,), -1, np.int32)
                shared_vps = {vp for vp, _ in shared_map}
                priv = iter(pages)
                # map_last == last_vp except under lazy_reserve, where the
                # far-suffix [map_last, last_vp) stays unmapped for now
                for vp in range(first_vp, map_last):
                    if vp not in shared_vps:
                        bt_row[vp] = next(priv)
                for vp, pg in shared_map:
                    bt_row[vp] = pg
                st = st._replace(
                    block_tables=st.block_tables.at[slot].set(bt_row))
                # one claim per mapped page; CoW reserves are claims too but
                # live in the cohort until consumed by a fork or retirement
                self.slot_pages[slot] = pages + [pg for _, pg in shared_map]
                if share_key is not None:
                    if share_hit is not None and not self.persistent_prefix:
                        # bidirectional sharing: hits join a CoW cohort so a
                        # sampled divergence can fork before any refresh
                        cohort = cycle_cohorts.get(share_key)
                        if cohort is None:
                            cohort = {"owner": owner_slot,
                                      "slots": {owner_slot: list(owner_map)},
                                      "reserve": {},
                                      "born": self._step_count}
                            self.cohorts.append(cohort)
                            cycle_cohorts[share_key] = cohort
                        cohort["slots"][slot] = list(shared_map)
                        if reserve:
                            cohort["reserve"][slot] = reserve
                    elif share_hit is None:
                        # persistent mode: registration hands the STORE its
                        # own claims, so the pages outlive this slot
                        my_map = [(vp, int(bt_row[vp]))
                                  for vp in range(vp0, vp1)]
                        self.allocator.register_prefix(share_key, (slot, my_map))
                self.slot_extent[slot] = (first_vp, last_vp)
                self.slot_frontier[slot] = map_last
                self.slot_order[slot] = self._admit_seq
                self._admit_seq += 1
                self.stats.pages_deferred += deficit_new
                self.stats.pages_in_use = self.allocator.used_pages
                self.stats.peak_pages_in_use = max(
                    self.stats.peak_pages_in_use, self.stats.pages_in_use)
            self.slot_blocks[slot] = n_blocks
            self.slot_no_grow[slot] = no_grow
            if self.expects_enc:
                enc = self.model.encode(
                    self.params, jax.numpy.asarray(req.enc_embeds)[None],
                    self.engine.attn_impl)
                self._enc_out = self._enc_out.at[slot].set(enc[0])
            req.admit_s = now
            self.stats.admission_waits.append(now - req.arrival_s)
            self.slot_req[slot] = req
            self.slot_streamed[slot] = 0
        self.state = st
        if self.allocator is not None:
            if not self.persistent_prefix:
                # cross-cycle sharing is unsound under bidirectional
                # attention: the prefix index only ever describes THIS
                # cycle's admissions.  Block-causal mode keeps the store —
                # prompt K/V depend on prompt bytes alone, so residency
                # stays sound across cycles and requests.
                self.allocator.clear_prefix_index()
            self.stats.shared_mappings = self.allocator.shared_mappings
            self.stats.prefix_evictions = self.allocator.prefix_evictions
        self.stats.resident_peak = max(
            self.stats.resident_peak,
            sum(r is not None for r in self.slot_req))

    # ------------------------------------------------------------------
    # priority preemption: host-memory spill / resume (ARCHITECTURE §5a)
    # ------------------------------------------------------------------
    def _try_preempt(self, st, need: int, priority: int,
                     free: list) -> tuple:
        """Spill lowest-priority residents until the free list covers
        ``need`` pages (``need == 0``: free exactly one SLOT).  Returns
        ``(st, ok)``.

        Victim policy: only residents of a STRICTLY lower class, taken
        lowest class first and youngest first within a class — the oldest
        resident of any class is spilled last, preserving the no-starvation
        shape of the lazy-reserve liveness argument.  A victim is eligible
        only at its block boundary (``phase == 0``): the immediately
        following step of an uninterrupted run would be the block-entry
        refresh, which rebuilds conf/pred/hidden from tokens + KV — so the
        snapshot below is exactly sufficient for a bit-identical resume.
        Mid-block residents are simply not eligible this cycle; the caller
        retries once they wrap."""
        if not self.preemption or self.allocator is None:
            return st, False
        phases = np.asarray(st.phase)
        victims = [s for s, r in enumerate(self.slot_req)
                   if r is not None and r.priority < priority
                   and s not in self.stalled and int(phases[s]) == 0]
        if not victims:
            return st, False
        victims.sort(key=lambda s: (self.slot_req[s].priority,
                                    -self.slot_order[s]))
        if need > 0:
            reachable = self.allocator.free_pages + sum(
                len(self.slot_pages[s]) for s in victims)
            if reachable < need:
                return st, False        # even spilling every victim won't fit
        now = self.clock()
        spilled_any = False
        for s in victims:
            if need > 0 and self.allocator.free_pages >= need:
                break
            if need == 0 and spilled_any:
                break
            st = self._spill_slot(st, s, now)
            free.append(s)
            spilled_any = True
        ok = self.allocator.free_pages >= need if need > 0 else spilled_any
        return st, ok

    def _spill_slot(self, st, slot: int, now: float):
        """Park a resident on the host: gather its mapped page BYTES, copy
        its per-row planes/counters, release every allocator claim, and
        deactivate the row.  A parked request holds ZERO pool pages — the
        ledger invariant needs no new term for it."""
        req = self.slot_req[slot]
        bt = np.asarray(st.block_tables)
        vps = [int(v) for v in np.nonzero(bt[slot] >= 0)[0]]
        pages = [int(bt[slot, vp]) for vp in vps]
        kv_data = self.engine.spill_pages(st, pages)
        row = {
            "tokens": np.asarray(st.tokens[slot]).copy(),
            "kv_valid": np.asarray(st.kv_valid[slot]).copy(),
            "bs": int(st.bs[slot]),
            "blocks_left": int(st.blocks_left[slot]),
            "iters": int(st.iters[slot]),
            "prompt_start": int(st.prompt_start[slot]),
            "sample_seed": int(st.sample_seeds[slot]),
            "extent": self.slot_extent[slot],
            "frontier": self.slot_frontier[slot],
        }
        if st.feat is not None:
            # the adaptive cache's probe plane and full-confidence plane are
            # carried ACROSS refreshes (a refresh scatters only its block's
            # columns), so unlike conf/pred/hidden they must round-trip
            row["feat"] = np.asarray(st.feat[slot]).copy()
            row["conf_full"] = np.asarray(st.conf_full[slot]).copy()
            row["cache_refreshed"] = int(st.cache_refreshed[slot])
            row["cache_eligible"] = int(st.cache_eligible[slot])
        self._spilled.append(_SpilledRequest(
            req=req, seq=self._seq[req.request_id],
            n_blocks=self.slot_blocks[slot], vps=vps, kv_data=kv_data,
            row=row, streamed=self.slot_streamed[slot], spill_s=now))
        self.allocator.release(self.slot_pages[slot])
        self.slot_pages[slot] = []
        st = st._replace(
            active=st.active.at[slot].set(False),
            block_tables=st.block_tables.at[slot].set(-1))
        self.slot_req[slot] = None
        self.stats.preemptions += 1
        self.stats.pages_spilled += len(pages)
        self.stats.pages_in_use = self.allocator.used_pages
        return st

    def _resume_into(self, st, slot: int, rec: _SpilledRequest,
                     got: list, now: float):
        """Re-admit a parked request: scatter its page bytes onto freshly
        allocated pool pages, rebuild its block-table row at the SAME
        virtual pages (physical ids may differ — the row only ever reads
        pages through its own table), and restore every per-row field the
        block-entry refresh reads.  ``phase`` pins to 0 and ``iters``
        restores exactly, so the draw-key numbering
        (fold_in(seed) + lifetime iteration) continues precisely where the
        uninterrupted run would be — greedy AND sampled resumes are
        bit-identical."""
        st = self.engine.restore_pages(st, got, rec.kv_data)
        row = rec.row
        bt_row = np.full(
            ((self.prompt_len + self.gen.gen_length) // self.page_size,),
            -1, np.int32)
        bt_row[rec.vps] = got
        st = st._replace(
            tokens=st.tokens.at[slot].set(jnp.asarray(row["tokens"])),
            kv_valid=st.kv_valid.at[slot].set(jnp.asarray(row["kv_valid"])),
            bs=st.bs.at[slot].set(row["bs"]),
            blocks_left=st.blocks_left.at[slot].set(row["blocks_left"]),
            phase=st.phase.at[slot].set(0),
            iters=st.iters.at[slot].set(row["iters"]),
            active=st.active.at[slot].set(True),
            prompt_start=st.prompt_start.at[slot].set(row["prompt_start"]),
            sample_seeds=st.sample_seeds.at[slot].set(row["sample_seed"]),
            block_tables=st.block_tables.at[slot].set(jnp.asarray(bt_row)),
        )
        if st.poisoned is not None:
            st = st._replace(poisoned=st.poisoned.at[slot].set(False))
        if st.feat is not None:
            st = st._replace(
                feat=st.feat.at[slot].set(jnp.asarray(row["feat"])),
                conf_full=st.conf_full.at[slot].set(
                    jnp.asarray(row["conf_full"])),
                cache_refreshed=st.cache_refreshed.at[slot].set(
                    row["cache_refreshed"]),
                cache_eligible=st.cache_eligible.at[slot].set(
                    row["cache_eligible"]),
            )
        self.slot_req[slot] = rec.req
        self.slot_blocks[slot] = rec.n_blocks
        self.slot_streamed[slot] = rec.streamed
        self.slot_pages[slot] = list(got)
        self.slot_extent[slot] = row["extent"]
        self.slot_frontier[slot] = row["frontier"]
        self.slot_order[slot] = self._admit_seq
        self._admit_seq += 1
        if self.expects_enc:
            # cross/ssm caches are rebuilt wholesale by the refresh, but it
            # reads the encoder plane — re-encode into the resumed slot
            enc = self.model.encode(
                self.params, jax.numpy.asarray(rec.req.enc_embeds)[None],
                self.engine.attn_impl)
            self._enc_out = self._enc_out.at[slot].set(enc[0])
        self.stats.resume_waits.append(now - rec.spill_s)
        self.stats.pages_in_use = self.allocator.used_pages
        self.stats.peak_pages_in_use = max(
            self.stats.peak_pages_in_use, self.stats.pages_in_use)
        return st

    # ------------------------------------------------------------------
    # the serving loop
    # ------------------------------------------------------------------
    def has_work(self) -> bool:
        return bool(self.queue) or bool(self._spilled) \
            or any(r is not None for r in self.slot_req)

    def step(self) -> bool:
        """One engine iteration (+ bookkeeping).  Returns False and does
        nothing when there is neither queued nor resident work.

        Per-row cadence: admission, the CoW-fork / reclaim hooks, and
        completion bookkeeping all key on the per-slot phase vector.  With
        ``early_advance=False`` the phases stay mutually aligned (admission
        and advancement only happen when every slot wraps together), so the
        behavior reduces exactly to the old block-aligned scheduler.

        The iteration is two halves run back to back: ``_dispatch`` (admit,
        prepare, enqueue the engine step) and ``_finish`` (wait on the
        device, then book the step).  ``ShardedStreamScheduler`` runs every
        lane's first half before any lane's second, so the lanes' devices
        compute at once.

        Each phase runs inside a profiler span (``es.sched.step`` around
        the call; ``es.sched.admit``, ``es.sched.prepare``,
        ``es.engine.dispatch``, ``es.engine.wait``, ``es.sched.after``,
        ``es.sched.retire``, ``es.sched.grow`` inside it), which records
        nothing unless a ``jax.profiler`` trace is running.  A lane of
        ``ShardedStreamScheduler`` gives its index as the ``lane``
        argument of ``es.sched.step`` and of ``es.engine.wait``: which
        lane's device the host is waiting on.  There ``es.sched.step``
        covers the dispatch half and ``es.sched.finish`` the finish half."""
        with _span("es.sched.step", **self._lane_arg()):
            inflight = self._dispatch()
            if inflight is None:
                return False
            self._finish(inflight)
            return True

    def _lane_arg(self) -> dict:
        return {} if self.lane_index is None else {"lane": self.lane_index}

    def _dispatch(self) -> Optional[_InFlight]:
        """Admit, prepare and enqueue one engine step without waiting on
        the device; None when nothing is resident (no step runs)."""
        t0 = self.clock()           # admission work (incl. encode) is wall time
        with _span("es.sched.admit"):
            phases = np.asarray(self.state.phase)
            if (self.queue or self._spilled) and bool(phases.any()) \
                    and not any(r is not None for r in self.slot_req):
                # quarantine (unlike normal retirement) can retire the LAST
                # resident mid-block, freezing every phase counter off the
                # boundary — with nobody resident the counters are
                # meaningless, but the aligned admission gate reads them, so
                # re-zero or the gate never reopens and queued work starves
                # a free pool
                self.state = self.state._replace(
                    phase=jnp.zeros_like(self.state.phase))
                phases = np.asarray(self.state.phase)
            if self.early_advance or bool((phases == 0).all()):
                self._admit()
                phases = np.asarray(self.state.phase)
        resident = np.asarray([r is not None for r in self.slot_req])
        if not resident.any():
            return None
        with _span("es.sched.prepare"):
            # rows whose upcoming step is a prompt refresh — the only branch
            # that scatters into THAT row's prompt pages — per the engine's
            # own per-row cadence
            refresh_rows = self.engine.prompt_refresh_rows(phases) & resident
            if self.stalled:
                # a stalled row is frozen (inactive on device, phase
                # drifting): its phase vector entry no longer describes an
                # upcoming refresh, so keep it out of the CoW-fork / reclaim
                # hooks until resume
                stalled_mask = np.zeros(self.max_slots, bool)
                stalled_mask[list(self.stalled)] = True
                refresh_rows &= ~stalled_mask
            if self.paged and refresh_rows.any():
                self._cow_fork_before_refresh(refresh_rows)
            if refresh_rows.any():
                # the rows the prompt-refresh pass runs: FULL refreshes (the
                # adaptive cache's partial ones run a pass of their own)
                it_h = np.asarray(self.state.iters)
                full_r = refresh_rows & np.asarray(
                    full_refresh_pred(self.gen, it_h), bool)
                if full_r.any():
                    self.stats.refresh_passes += 1
                    self.stats.refresh_rows += int(full_r.sum())
                if self.gen.block_causal:
                    # gauge: positions the upcoming FULL refreshes will leave
                    # in place (same elementwise horizon the engine's refresh
                    # token mask uses, so the two can never drift apart)
                    inv = np.asarray(invariant_limit(
                        self.gen, np.asarray(self.state.bs), it_h,
                        self.prompt_len))
                    skipped = np.maximum(
                        inv - np.asarray(self.state.prompt_start), 0)
                    self.stats.invariant_tokens_skipped += int(
                        skipped[full_r].sum())
            inflight = _InFlight(t0, phases, resident, refresh_rows,
                                 np.asarray(self.state.blocks_left))
            if self.state.feat is not None:
                # cumulative per-slot counters (reset on admission): the step
                # delta is this iteration's refresh activity
                inflight.pre_r = np.asarray(self.state.cache_refreshed)
                inflight.pre_e = np.asarray(self.state.cache_eligible)
        with _span("es.engine.dispatch"):
            self.state = self.engine.step(self.params, self.state,
                                          self._enc_out)
        return inflight

    def _finish(self, inflight: _InFlight) -> None:
        """Wait on the step ``_dispatch`` enqueued, then book it: the wall
        and its EWMA, cache counters, quarantine, reclaim, retirement and
        window growth."""
        phases, resident = inflight.phases, inflight.resident
        refresh_rows = inflight.refresh_rows
        with _span("es.engine.wait", **self._lane_arg()):
            jax.block_until_ready(self.state.tokens)
        self._step_count += 1
        dt = self.clock() - inflight.t0
        self.stats.wall_s += dt
        # per-step wall EWMA: the measured-cost term of deadline admission
        self._step_ewma = dt if self._step_ewma is None \
            else 0.8 * self._step_ewma + 0.2 * dt
        with _span("es.sched.after"):
            if inflight.pre_r is not None:
                d_r = np.asarray(self.state.cache_refreshed) - inflight.pre_r
                d_e = np.asarray(self.state.cache_eligible) - inflight.pre_e
                self.stats.cache_refreshed_total += int(d_r.sum())
                self.stats.cache_eligible_total += int(d_e.sum())
                self.stats.refresh_event_tokens.extend(d_r[d_e > 0].tolist())
            if self.state.poisoned is not None:
                # quarantine BEFORE reclaim/retirement bookkeeping: a
                # poisoned row must never reach the streaming or
                # page-eviction paths
                pois = np.asarray(self.state.poisoned)
                if pois.any():
                    self._quarantine([int(s) for s in np.nonzero(pois)[0]])
            if self.paged and self.gen.sparse_attention and refresh_rows.any():
                self._reclaim_dead_pages(refresh_rows)
        with _span("es.sched.retire"):
            if self.early_advance:
                adv = (np.asarray(self.state.blocks_left)
                       < inflight.pre_blocks_left) & resident
                steps_pb = self.gen.resolved_steps()
                self.stats.early_advances += int(
                    (adv & ((phases + 1) % steps_pb != 0)).sum())
                # streams / retires per iteration: a finished row's slot is
                # free for the very next admission, not for the end of a
                # cycle
                self._finish_cycle()
            elif bool((np.asarray(self.state.phase) == 0).all()):
                self._finish_cycle()
        if self.lazy_reserve:
            # AFTER retirement so pages freed this step are grantable this
            # step; runs every iteration because aligned mode advances bs at
            # the phase wrap, not through the early_advance bookkeeping
            with _span("es.sched.grow"):
                self._grow_windows()

    # ------------------------------------------------------------------
    # lazy reservation: just-in-time window growth
    # ------------------------------------------------------------------
    def _grow_windows(self) -> None:
        """Map the next window's pages for every lazily-reserved row whose
        ``bs`` advanced past its mapped frontier.

        Growth target per row: the pages covering its current attention
        horizon (``bs + block_length * (1 + window_blocks)``), capped at the
        row's extent — rows nearing their last block ask for nothing, so
        they can never stall near the finish line.

        **On-demand extent growth (ROADMAP item 5):** a row whose request
        set ``max_blocks`` above its admitted block budget may RAISE the
        extent itself, one block at a time.  The decision point is a block
        ENTRY: right after the advance into what is currently the row's
        final block, its window horizon first exceeds the extent
        (``want > extent_last``) and the very next step would attend the
        candidate block's region — so the raise (or its denial) lands
        between the advance step and that first read, and the row's read
        set matches the offline run of whichever final length wins.  A
        raise is granted only when the whole enlarged remaining need
        (``new_last - frontier``) is coverable right now while still
        covering every strictly-older row's deficit — growth never
        increases any deficit the liveness induction relies on.  A denial
        is STICKY (``slot_no_grow``): granting later, mid-block, would
        remap pages the row already attended as masked and break replay —
        the row simply finishes at its current extent (no new stall path).
        The decision for blocks inside the ADMISSION horizon is made by
        ``_admit`` under the same predicate.  The device ``blocks_left``
        bump lands at the entry of the old final block — one whole block
        before the advance it postpones.

        **No-deadlock policy (max-deficit reserve, ARCHITECTURE §1c):**
        residents are ranked by admission order; row r is granted g pages iff
        the free list would still cover every STRICTLY OLDER row's remaining
        deficit afterwards (for the oldest row that bound is vacuous).
        Together with the admission gate this keeps the invariant "the free
        list covers the oldest resident's deficit" — so the oldest row always
        grows, always finishes, and returns its pages; induction gives every
        row liveness.  A denied row STALLS (``active=False``, host-side
        ``stalled`` set, ``window_stalls`` gauge) and is NEVER killed; it
        resumes — at phase 0, since stalls only ever trigger right after a
        block advance — the step its grant lands.
        """
        residents = [s for s, r in enumerate(self.slot_req) if r is not None]
        if not residents:
            return
        bs = np.asarray(self.state.bs)
        bl = np.asarray(self.state.blocks_left)
        lb = self.gen.block_length
        wb = self.gen.window_blocks
        ps = self.page_size
        order = sorted(residents, key=lambda s: self.slot_order[s])
        deficit = {s: self.slot_extent[s][1] - self.slot_frontier[s]
                   for s in order}
        bt = None
        resumed: list[int] = []
        stalled_now: list[int] = []
        grown: list[int] = []
        for i, slot in enumerate(order):
            frontier = self.slot_frontier[slot]
            first_vp, extent_last = self.slot_extent[slot]
            limit = int(bs[slot]) + lb * (1 + wb)
            want = -(-limit // ps)
            req = self.slot_req[slot]
            if (want > extent_last and not self.slot_no_grow[slot]
                    and int(bl[slot]) > 0
                    and req is not None and req.max_blocks is not None
                    and self.slot_blocks[slot]
                    < min(max(req.max_blocks, 1), self.n_blocks)):
                nb = self.slot_blocks[slot] + 1
                new_last = -(-(self.prompt_len + nb * lb) // ps)
                older = max((deficit[s] for s in order[:i]), default=0)
                if (self.allocator.free_pages
                        + self.allocator.reclaimable_pages) \
                        - (new_last - frontier) >= older:
                    self.stats.pages_deferred += new_last - extent_last
                    self.stats.blocks_grown += 1
                    self.slot_extent[slot] = (first_vp, new_last)
                    self.slot_blocks[slot] = nb
                    deficit[slot] = new_last - frontier
                    extent_last = new_last
                    grown.append(slot)
                else:
                    # sticky: a later, mid-block grant would change pages
                    # this row already attended as masked
                    self.slot_no_grow[slot] = True
            target = min(want, extent_last)
            g = target - frontier
            if g <= 0:
                continue
            older = max((deficit[s] for s in order[:i]), default=0)
            if (self.allocator.free_pages
                    + self.allocator.reclaimable_pages) - g >= older:
                got = self.allocator.alloc(g)       # gate implies it succeeds
                if bt is None:
                    bt = np.array(self.state.block_tables)
                bt[slot, frontier:target] = got
                self.slot_pages[slot].extend(got)
                self.slot_frontier[slot] = target
                deficit[slot] -= g
                if slot in self.stalled:
                    self.stalled.discard(slot)
                    resumed.append(slot)
            elif slot not in self.stalled:
                self.stalled.add(slot)
                self.stats.window_stalls += 1
                stalled_now.append(slot)
        st = self.state
        if bt is not None:
            st = st._replace(block_tables=jnp.asarray(bt))
        for slot in grown:
            # one more block of budget on device — granted while the row is
            # still >= one whole block away from its final advance
            st = st._replace(blocks_left=st.blocks_left.at[slot].add(1))
        for slot in resumed:
            # the engine's phase counter kept ticking while the row was
            # frozen; the stall hit right after a block advance, where the
            # phase had wrapped to 0 — pin it back to the prefill entry so
            # the resumed trajectory is the one an unstalled run would take
            st = st._replace(active=st.active.at[slot].set(True),
                             phase=st.phase.at[slot].set(0))
        for slot in stalled_now:
            st = st._replace(active=st.active.at[slot].set(False))
        self.state = st
        if bt is not None or resumed or stalled_now:
            self.stats.pages_in_use = self.allocator.used_pages
            self.stats.peak_pages_in_use = max(
                self.stats.peak_pages_in_use, self.stats.pages_in_use)

    # ------------------------------------------------------------------
    # memory manager v2: CoW fork + page-aligned eviction
    # ------------------------------------------------------------------
    def _dissolve_cohort(self, cohort: dict) -> None:
        """Drop a cohort whose membership fell to <= 1.  A sole survivor's
        shared pages are exclusively its own now (the other claims died with
        their slots), so it will never fork — release any CoW reserve it is
        still holding, or those pages leak for the pool's lifetime."""
        for reserve in cohort["reserve"].values():
            self.allocator.release(reserve)
        cohort["reserve"] = {}
        self.cohorts.remove(cohort)

    def _cow_fork_before_refresh(self, refresh_rows) -> None:
        """Copy-on-write: an upcoming refresh scatters recomputed prompt
        K/V into the refreshing row's mapped pages.  Greedy cohorts stay
        bit-identical (identical trajectories ⇒ identical per-row phases ⇒
        identical bytes), so sharing persists; sampled cohorts diverged at
        their first draw, so the shared pages must be forked BEFORE any
        diverged content reaches a refcount>1 page.

        ``refresh_rows`` [B] is the per-row refresh predicate for THIS step
        (``engine.prompt_refresh_rows``) — the re-keyed successor of the
        old global ``is_prompt_refresh(phase)``.  Under per-row cadence a
        cohort's members can refresh on different iterations, and the
        OWNER's refresh corrupts followers' reads exactly like a follower's
        own write would — so the first post-divergence step on which ANY
        member is about to refresh forks ALL followers onto their
        admission-time reserves and repoints their block tables.

        Under this fork-before-refresh policy the fork's data copy is
        belt-and-suspenders: the refresh about to run rewrites every row of
        a (fully-prompt) shared page anyway, so only the repoint and the
        refcount hand-off are load-bearing.  The copy is kept because it
        makes the CoW invariant policy-independent — a forked page is a
        faithful replica no matter when a future policy chooses to fork
        (e.g. mid-block, where the content IS live)."""
        if not self.cohorts or self.gen.temperature <= 0:
            return
        bt = np.array(self.state.block_tables)
        all_src: list[int] = []
        all_dst: list[int] = []
        for cohort in list(self.cohorts):
            if self._step_count <= cohort["born"]:
                continue            # the admission prefill itself: no draws yet
            if not any(refresh_rows[s] for s in cohort["slots"]):
                continue            # nobody in this cohort refreshes this step
            for slot in [s for s in cohort["slots"] if s != cohort["owner"]]:
                mapping = [(vp, pg) for vp, pg in cohort["slots"][slot]
                           if bt[slot, vp] == pg]    # eviction may have unmapped
                reserve = cohort["reserve"].pop(slot, [])
                src = [pg for _, pg in mapping]
                dst = reserve[: len(src)]
                assert len(dst) == len(src), "CoW reserve exhausted"
                if src:
                    all_src += src
                    all_dst += dst
                    for (vp, _), pg in zip(mapping, dst):
                        bt[slot, vp] = pg
                    sp = self.slot_pages[slot]
                    for s_pg, d_pg in zip(src, dst):
                        sp[sp.index(s_pg)] = d_pg
                    self.allocator.release(src)      # drop read-only claims
                    self.stats.cow_forks += len(src)
                if reserve[len(src):]:               # eviction shrank the need
                    self.allocator.release(reserve[len(src):])
                del cohort["slots"][slot]
            if len(cohort["slots"]) <= 1:
                self._dissolve_cohort(cohort)
        if all_src:
            # one jitted fork over every (src, dst) pair of every cohort and
            # one block-table upload — followers and cohorts don't serialize
            self.state = self.engine.fork_pages(self.state, all_src, all_dst)
            self.state = self.state._replace(block_tables=jnp.asarray(bt))
        self.stats.shared_mappings = self.allocator.shared_mappings
        self.stats.pages_in_use = self.allocator.used_pages

    def _reclaim_dead_pages(self, refresh_rows) -> None:
        """Page-aligned sparse eviction: after a refresh re-scored the
        retention sets, unmap every fully-dead page behind each slot's
        current block and return it to the free list — freed capacity is
        immediately admittable instead of masked-but-resident.

        Scans only ``refresh_rows``: a row's dead set can change only at
        its own refresh (that is also when its ``bs`` has just advanced and
        settled new pages), so under per-row cadence the other slots'
        host-side bookkeeping is skipped — in aligned mode every resident
        row refreshes together and this reduces to the full scan."""
        dead = self.engine.dead_page_report(self.state) \
            & np.asarray(refresh_rows, bool)[:, None]
        if not dead.any():
            return
        bt = np.array(self.state.block_tables)
        for slot, req in enumerate(self.slot_req):
            if req is None:
                continue
            vps = np.nonzero(dead[slot])[0]
            if vps.size == 0:
                continue
            pages = [int(bt[slot, vp]) for vp in vps]
            bt[slot, vps] = -1
            # count PHYSICAL frees: a shared page reclaims once, when its
            # last sharer's claim dies (every sharer evicts it identically)
            self.stats.pages_reclaimed += self.allocator.release(pages)
            for pg in pages:
                self.slot_pages[slot].remove(pg)
            for cohort in self.cohorts:          # shed evicted shared claims
                if slot in cohort["slots"]:
                    cohort["slots"][slot] = [
                        (vp, pg) for vp, pg in cohort["slots"][slot]
                        if bt[slot, vp] == pg]
        self.state = self.state._replace(block_tables=jnp.asarray(bt))
        self.stats.pages_in_use = self.allocator.used_pages
        self.stats.shared_mappings = self.allocator.shared_mappings

    def _finish_cycle(self) -> None:
        """Post-step bookkeeping: stream newly completed blocks, retire
        finished requests, recycle their slots.  Runs after every iteration
        under ``early_advance`` (a block can complete on any step) and only
        at the shared boundary in block-aligned mode."""
        tokens = np.asarray(self.state.tokens)
        blocks_left = np.asarray(self.state.blocks_left)
        active = np.asarray(self.state.active)
        lb = self.gen.block_length
        now = self.clock()
        for slot, req in enumerate(self.slot_req):
            if req is None:
                continue
            done_blocks = self.slot_blocks[slot] - int(blocks_left[slot])
            for bi in range(self.slot_streamed[slot], done_blocks):
                blk = tokens[slot, self.prompt_len + bi * lb:
                             self.prompt_len + (bi + 1) * lb].copy()
                for cb in (req.stream_cb, self.stream_cb):
                    if cb is not None:
                        cb(req, bi, blk)
            self.slot_streamed[slot] = done_blocks
            if not active[slot] and slot in self.stalled:
                continue            # paused by _grow_windows, not finished
            if not active[slot]:
                n_tok = self.slot_blocks[slot] * lb
                req.output = tokens[slot, self.prompt_len:
                                    self.prompt_len + n_tok].copy()
                req.finish_s = now
                req.latency_s = now - req.arrival_s
                self.stats.completed += 1
                self.stats.tokens_out += n_tok
                self.stats.latencies_s.append(req.latency_s)
                self._completed.append(req)
                self.slot_req[slot] = None
                if self.allocator is not None:
                    # return page claims immediately and unmap the slot's
                    # row — a freed page may be re-issued next cycle, and a
                    # stale mapping would let the idle slot scribble on it.
                    # A SHARED page only truly frees when its last sharer
                    # retires (refcount), but this slot's claims always die
                    # here, including any unconsumed CoW reserve.
                    if self.slot_pages[slot]:
                        self.allocator.release(self.slot_pages[slot])
                        self.slot_pages[slot] = []
                        self.state = self.state._replace(
                            block_tables=self.state.block_tables.at[slot].set(-1))
                    for cohort in list(self.cohorts):
                        if slot in cohort["slots"]:
                            del cohort["slots"][slot]
                            reserve = cohort["reserve"].pop(slot, [])
                            if reserve:
                                self.allocator.release(reserve)
                            if len(cohort["slots"]) <= 1:
                                self._dissolve_cohort(cohort)
                    self.stats.pages_in_use = self.allocator.used_pages
                    self.stats.shared_mappings = self.allocator.shared_mappings

    # ------------------------------------------------------------------
    # poison-slot quarantine (ARCHITECTURE §5b)
    # ------------------------------------------------------------------
    def _quarantine(self, slots: list) -> None:
        """Retire rows the engine's non-finite detector flagged: typed
        ``PoisonedRequest`` verdict, slot reset, pages freed.  One bad
        request never corrupts anyone else:

        * co-resident slots never read the row (dense attention never
          crosses rows; paged attention reads only pages in the reader's
          own block table);
        * pages this slot owned EXCLUSIVELY (refcount 1) are zero-scrubbed
          on device before returning to the free list, so a later occupant
          can never observe the non-finite bytes;
        * a refcount>1 page is left intact — it is shared read-only with a
          live cohort.  Greedy cohorts compute identical bytes, so they go
          non-finite in lock-step and this same sweep quarantines every
          member (dropping all claims); sampled cohorts CoW-forked before
          any post-divergence write, so a shared page a survivor still maps
          was never written by the poisoned trajectory;
        * any persistent prefix-store entry touching the row's pages is
          dropped, so the cross-request cache cannot re-serve them.
        """
        st = self.state
        now = self.clock()
        mask_id = self.engine.mask_id
        for slot in slots:
            req = self.slot_req[slot]
            if req is not None:
                req.error = PoisonedRequest(
                    req.request_id, slot, self._step_count)
                req.finish_s = now
                req.latency_s = now - req.arrival_s
                self.stats.poisoned_requests += 1
                self._completed.append(req)
                self.slot_req[slot] = None
                self.stalled.discard(slot)
            if self.allocator is not None and self.slot_pages[slot]:
                pages = self.slot_pages[slot]
                priv = [pg for pg in pages
                        if self.allocator.refcount(pg) == 1]
                if priv:
                    st = self.engine.scrub_pages(st, priv)
                self.allocator.drop_prefix_entries(set(pages))
                self.allocator.release(pages)
                self.slot_pages[slot] = []
                st = st._replace(
                    block_tables=st.block_tables.at[slot].set(-1))
                for cohort in list(self.cohorts):
                    if slot in cohort["slots"]:
                        del cohort["slots"][slot]
                        reserve = cohort["reserve"].pop(slot, [])
                        if reserve:
                            self.allocator.release(reserve)
                        if len(cohort["slots"]) <= 1:
                            self._dissolve_cohort(cohort)
            # reset the device row: admission's fresh prefill rewrites
            # everything anyway (iters==0 exempts nothing), so this is
            # belt-and-suspenders — but it guarantees no non-finite value
            # survives in any plane a future policy might carry over
            st = st._replace(
                tokens=st.tokens.at[slot].set(mask_id),
                conf=st.conf.at[slot].set(0.0),
                pred=st.pred.at[slot].set(0),
                hidden=tuple(h.at[slot].set(0.0) for h in st.hidden),
                kv_valid=st.kv_valid.at[slot].set(True),
                active=st.active.at[slot].set(False),
                poisoned=st.poisoned.at[slot].set(False),
            )
            if st.feat is not None:
                st = st._replace(
                    feat=st.feat.at[slot].set(0.0),
                    conf_full=st.conf_full.at[slot].set(0.0))
            self.slot_streamed[slot] = 0
        self.state = st
        if self.allocator is not None:
            self.stats.pages_in_use = self.allocator.used_pages
            self.stats.shared_mappings = self.allocator.shared_mappings

    def drain(self, *, max_steps: Optional[int] = None,
              max_wall_s: Optional[float] = None) -> list[Request]:
        """Offline mode: run until queue, spill list, and slots are empty
        (submit everything, drain, read ``Request.output`` /
        ``Request.error``).

        Watchdog (ARCHITECTURE §5c): liveness bugs fail typed instead of
        hanging.  Three tripwires raise ``DrainStalled`` naming the stuck
        slots: an explicit ``max_steps`` / ``max_wall_s`` budget blowing
        while work remains, and — always on — a zero-progress monitor that
        trips after ``_drain_patience`` consecutive steps with no
        observable change (completions, tokens, streamed blocks,
        queue/spill depth, or any failure-handling gauge)."""
        t_start = self.clock()
        steps = 0
        idle = 0
        snap = self._progress_snapshot()
        while self.has_work():
            if max_steps is not None and steps >= max_steps:
                raise DrainStalled(
                    f"max_steps={max_steps} exhausted with work remaining",
                    self._stuck_slots())
            if max_wall_s is not None \
                    and self.clock() - t_start > max_wall_s:
                raise DrainStalled(
                    f"max_wall_s={max_wall_s} exceeded with work remaining",
                    self._stuck_slots())
            self.step()
            steps += 1
            nxt = self._progress_snapshot()
            idle = idle + 1 if nxt == snap else 0
            snap = nxt
            if idle >= self._drain_patience:
                raise DrainStalled(
                    f"no forward progress in {idle} consecutive steps",
                    self._stuck_slots())
        done, self._completed = self._completed, []
        return done

    def _progress_snapshot(self) -> tuple:
        """Everything the watchdog accepts as forward progress."""
        s = self.stats
        return (s.completed, s.tokens_out, tuple(self.slot_streamed),
                sum(r is not None for r in self.slot_req),
                len(self.queue), len(self._spilled), s.deadline_rejects,
                s.poisoned_requests, s.preemptions, s.window_stalls)

    def _stuck_slots(self) -> list:
        phases = np.asarray(self.state.phase)
        bl = np.asarray(self.state.blocks_left)
        return [(s, r.request_id, int(phases[s]), int(bl[s]))
                for s, r in enumerate(self.slot_req) if r is not None]
