"""One run of one cell: set-up, the measured window, the check.

The served path is the program's own launcher (``repro.launch.serve``:
``build_parser`` / ``validate`` / ``generation_config`` /
``build_server``) with the configuration's flags and no engine override,
fed the benchmark's seeded weights.  The window drives
``StreamScheduler.step`` (``ShardedStreamScheduler.step`` for several
lanes) and records, from outside the program:

* per request: due, submit and admit times, and each block's commit time
  (``Request.stream_cb``, called after the step's ``block_until_ready``);
* per lane step: its host interval and each resident row's pass kind;
* per request, step by step, which positions were committed to which
  token, read from the token plane the scheduler has just copied to the
  host.  That transcript is what the reference replays.

After the window: the peak device memory, then the program's state is
freed and ``bench.reference`` replays a sample of the finished requests
drawn from the seed.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import os
import time
from collections import deque
from typing import Optional

import numpy as np

from bench import reference as refmod
from bench import spec as specmod
from bench import traffic as trafficmod
from bench import weights as wmod

# how long past the close a request due in the window may take to its first
# block: an overloaded server first works off the backlog the window left
FOLLOW_UP_S = 120.0
TRACE_S = 3.0               # traced tail of the window (--trace 1)
CACHE = specmod.BENCH / ".cache"


class CompileClock:
    """Backend compiles (and the seconds they take) from JAX's monitoring
    events; a persistent-cache hit counts its load instead."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


_CLOCK: Optional[CompileClock] = None


def compile_clock() -> CompileClock:
    """The process's one CompileClock (JAX's listeners cannot be removed,
    so several runs in one process share it)."""
    global _CLOCK
    if _CLOCK is None:
        _CLOCK = CompileClock()
    return _CLOCK


def configure_compile_cache() -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` if set, else a
    fixed directory in the checkout; every program is kept."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CACHE / "jax")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ---------------------------------------------------------------------------
# building the served path
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Served:
    cfg: object                 # repro ModelConfig
    args: object                # parsed launcher flags
    model: object
    gen: object
    sched: object
    lanes: list                 # StreamScheduler lanes (one if unsharded)


def serve_widths(args) -> dict:
    return {"prompt_len": args.prompt_len, "gen_length": args.gen_length,
            "block_length": args.block_length, "page_size": args.page_size}


def build(config: dict, params_seed: int) -> tuple[Served, dict]:
    """The program's server for ``config`` with the seed's weights."""
    from repro import configs
    from repro.launch import serve
    from repro.models import build_model

    args = serve.build_parser().parse_args(config["serve_argv"])
    serve.validate(args)
    cfg = dataclasses.replace(configs.get_config(config["arch"]),
                              **config["model"])
    model = build_model(cfg)
    params = wmod.make_program_params(config["model"], params_seed, model)
    gen = serve.generation_config(args, cfg)
    sched = serve.build_server(args, model, params, gen)
    lanes = list(getattr(sched, "lanes", [sched]))
    served = Served(cfg, args, model, gen, sched, lanes)
    check_semantics(served, config)
    return served, params


def check_semantics(served: Served, config: dict) -> None:
    """The reference follows the configuration's ``es`` section; refuse to
    run when the served engine does something else."""
    es, gen, eng = config["es"], served.gen, served.sched.engine
    got = {
        "stage_layers": [s.group_hi - 1 for s in eng.segments
                         if s.keep_k is not None],
        "keep": [s.keep_k for s in eng.segments if s.keep_k is not None],
        "block_refresh_period": gen.block_refresh_period,
        "prompt_refresh_period": gen.prompt_refresh_period,
        "alpha": gen.alpha, "temperature": gen.temperature,
    }
    want = {k: es[k] for k in got}
    problems = [k for k in got if got[k] != want[k]]
    if eng.model.period != 1 or gen.resolved_steps() != gen.block_length \
            or gen.mode != "es" or gen.adaptive_cache or gen.windowed \
            or gen.block_causal or gen.parallel_decoding:
        problems.append("cadence")
    if gen.prompt_refresh_period and \
            gen.prompt_refresh_period < gen.block_length:
        problems.append("prompt refresh inside a block")
    if problems:
        raise SystemExit(f"bench: the served engine departs from the "
                         f"configuration's es section in {problems}: "
                         f"served {got}, stated {want}")


# ---------------------------------------------------------------------------
# the load loop and what it records
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Tracked:
    index: int
    prompt: np.ndarray
    n_blocks: int
    due: Optional[float]
    submit: float = math.nan
    lane: int = -1
    block_t: list = dataclasses.field(default_factory=list)
    steps: list = dataclasses.field(default_factory=list)   # per block
    known: Optional[np.ndarray] = None
    request: object = None


class Loop:
    """Offers a mix to the served path and records what comes back."""

    def __init__(self, served: Served, mix: dict, clock=time.monotonic,
                 spans: bool = False):
        self.served, self.mix, self.clock = served, mix, clock
        self.sched, self.lanes = served.sched, served.lanes
        self.lb = served.gen.block_length
        self.mask_id = served.sched.engine.mask_id
        self.tracked: dict[int, Tracked] = {}
        self.steps: list[dict] = []
        self.rounds: list[tuple[float, float]] = []   # sched.step() calls
        self.round_commits = 0      # tokens committed by the last round
        self.sched_steps_in_window = 0
        self.stalled_row_steps = 0
        self.t0 = 0.0
        self._span = self._annotate if spans else \
            (lambda name: contextlib.nullcontext())

    @staticmethod
    def _annotate(name):
        import jax
        return jax.profiler.TraceAnnotation(name)

    # -- submission ------------------------------------------------------
    def submit(self, p: trafficmod.Planned, request_id: int) -> Tracked:
        from repro.runtime import Request
        tr = Tracked(request_id, p.prompt, p.n_blocks, p.due_s)
        tr.known = np.full(p.n_blocks * self.lb, self.mask_id, np.int32)
        tr.steps = [[] for _ in range(p.n_blocks)]

        def on_block(req, bi, blk, tr=tr):
            with self._span("bench.stream_cb"):
                tr.block_t.append(self.clock() - self.t0)

        req = Request(prompt=p.prompt.copy(), request_id=request_id,
                      stream_cb=on_block,
                      max_new_tokens=p.n_blocks * self.lb)
        tr.request = req
        self.tracked[request_id] = tr
        with self._span("bench.submit"):
            tr.submit = self.clock() - self.t0
            self.sched.submit(req)
        tr.lane = self.sched.placements[request_id] if len(self.lanes) > 1 \
            else 0
        return tr

    # -- one step of the served path -------------------------------------
    def step(self) -> None:
        pre = [(lane._step_count, list(lane.slot_req)) for lane in self.lanes]
        t0 = self.clock() - self.t0
        with self._span("bench.sched_step"):
            self.sched.step()
        t1 = self.clock() - self.t0
        self.rounds.append((t0, t1))
        self.round_commits = 0
        for li, lane in enumerate(self.lanes):
            count, before = pre[li]
            if lane._step_count == count:
                continue
            self._track_lane(li, lane, before, t0, t1)

    def _track_lane(self, li, lane, before, t0, t1) -> None:
        tokens = np.asarray(lane.state.tokens)   # the host copy the step made
        p_len = lane.prompt_len
        rows = []
        for slot in range(lane.max_slots):
            req = lane.slot_req[slot] or before[slot]
            tr = None if req is None else self.tracked.get(req.request_id)
            if tr is None or tr.request is not req:
                continue
            gen = tokens[slot, p_len:p_len + tr.n_blocks * self.lb]
            new = np.nonzero((tr.known == self.mask_id)
                             & (gen != self.mask_id))[0]
            cur = _current_block(tr, self.lb, self.mask_id)
            phase = len(tr.steps[cur])
            tr.steps[cur].append([(int(i - cur * self.lb), int(gen[i]))
                                  for i in new])
            tr.known[new] = gen[new]
            self.round_commits += len(new)
            if not len(new):
                self.stalled_row_steps += 1
            rows.append((len(tr.prompt), tr.n_blocks, refmod.pass_kind(
                phase, self.served.gen.block_refresh_period)))
        self.steps.append({"t0": t0, "t1": t1, "lane": li, "rows": rows})

    # -- the window --------------------------------------------------------
    def run(self, planned: list, seconds: float, lead_in: float = 0.0,
            on_tick=None) -> None:
        """Offer ``planned`` from ``-lead_in`` to ``seconds`` on this loop's
        clock, whose 0 is the window's start.  ``on_tick(now)`` runs between
        steps."""
        self.t0 = self.clock() + lead_in
        pending = deque(planned)
        open_loop = self.mix["loop"] == "open"
        backlog = int(self.mix.get("backlog", 0))
        while True:
            now = self.clock() - self.t0
            if now >= seconds:
                break
            if on_tick is not None:
                on_tick(now)
            if open_loop:
                while pending and pending[0].due_s <= now:
                    p = pending.popleft()
                    self.submit(p, p.index)
            else:
                while pending and self._queued() < backlog + self._free():
                    p = pending.popleft()
                    self.submit(p, p.index)
            if self.sched.has_work():
                self.step()
            elif open_loop and pending:
                time.sleep(max(0.0, min(pending[0].due_s, seconds) - now))
            elif not pending:
                break
        self.sched_steps_in_window = sum(1 for t0, _ in self.rounds if t0 >= 0)

    def follow_up(self, seconds: float, limit_s: float = FOLLOW_UP_S) -> None:
        """After the close: no new arrivals; step until every request due in
        the window has its first block, for at most ``limit_s``, or until a
        step commits nothing at all (the served path is stuck)."""
        end = self.clock() + limit_s
        stuck = 0
        while self.clock() < end and self.sched.has_work() and stuck < 3:
            waiting = [t for t in self.tracked.values()
                       if t.due is not None and t.due < seconds
                       and not t.block_t and t.request.error is None]
            if not waiting:
                break
            self.step()
            stuck = stuck + 1 if self.round_commits == 0 else 0

    def _queued(self) -> int:
        return sum(len(lane.queue) for lane in self.lanes)

    def _free(self) -> int:
        return sum(r is None for lane in self.lanes for r in lane.slot_req)


def _current_block(tr: Tracked, lb: int, mask_id: int) -> int:
    """The first block of the request that still holds a masked position."""
    for b in range(tr.n_blocks):
        if (tr.known[b * lb:(b + 1) * lb] == mask_id).any():
            return b
    return tr.n_blocks - 1


# ---------------------------------------------------------------------------
# warm-up
# ---------------------------------------------------------------------------
def warm_up(served: Served, mix: dict, seed: int, vocab: int) -> None:
    """Every program the window runs, compiled before it: the step, and
    admission and retirement on every slot of every lane.  All slots are
    filled with one-block requests of the mix's lengths, half of them a few
    steps late so that idle rows drift off phase 0; once they drain, each
    lane takes one more request while empty, which is when the scheduler
    re-zeroes its phase plane (``StreamScheduler.step``)."""
    from repro.runtime import Request
    n = sum(lane.max_slots for lane in served.lanes)
    warm_mix = {k: v for k, v in mix.items() if k != "output_tokens"}
    warm_mix.update(loop="closed", requests=n + len(served.lanes),
                    output_blocks={"dist": "choice", "values": [1],
                                   "weights": [1]})
    planned = trafficmod.plan(warm_mix, seed ^ 0x5EED, 0.0, vocab,
                              served.gen.block_length)
    reqs = [Request(prompt=p.prompt, request_id=-1 - p.index,
                    max_new_tokens=served.gen.block_length) for p in planned]
    for r in reqs[: n // 2]:
        served.sched.submit(r)
    for _ in range(3):
        served.sched.step()
    for r in reqs[n // 2: n]:
        served.sched.submit(r)
    served.sched.drain()
    for lane, r in zip(served.lanes, reqs[n:]):
        lane.submit(r)
    served.sched.drain()


# ---------------------------------------------------------------------------
# the record the metric readers read
# ---------------------------------------------------------------------------
def make_record(loop: Loop, seconds: float, config: dict, chips: int,
                setup_s: float, peaks: dict, trace_summary: Optional[dict]) -> dict:
    reqs = []
    for tr in loop.tracked.values():
        req = tr.request
        admit = (req.admit_s - loop.t0) if req.admit_s else None
        reqs.append({
            "index": tr.index, "due": tr.due, "submit": tr.submit,
            "admit": admit, "blocks": list(tr.block_t),
            "n_blocks": tr.n_blocks, "prompt_tokens": int(len(tr.prompt)),
            "lane": tr.lane, "error": req.error is not None,
        })
    lanes = [0] * len(loop.lanes)
    for r in reqs:
        lanes[r["lane"]] += sum(1 for t in r["blocks"]
                                if 0.0 <= t <= seconds) * loop.lb
    return {
        "seconds": seconds, "chips": chips, "block_length": loop.lb,
        "model": config["model"], "es": config["es"],
        "requests": reqs, "steps": loop.steps,
        "rounds": [list(r) for r in loop.rounds],
        "lane_tokens": lanes, "setup_s": setup_s, "peaks": peaks,
        "trace": trace_summary,
    }


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------
def sample(loop: Loop, seed: int, target_tokens: int) -> list[Tracked]:
    """Finished requests to compare: the longest, then others drawn from
    the seed until ``target_tokens`` served tokens."""
    done = [t for t in loop.tracked.values()
            if t.request.error is None and t.request.output is not None
            and len(t.block_t) == t.n_blocks]
    if not done:
        return []
    done.sort(key=lambda t: (t.n_blocks, len(t.prompt), -t.index))
    out = [done.pop()]
    rng = np.random.default_rng(seed)
    for i in rng.permutation(len(done)):
        if sum(t.n_blocks for t in out) * loop.lb >= target_tokens:
            break
        out.append(done[i])
    return out


def transcript_mismatches(tr: Tracked, lb: int) -> int:
    """Positions where the recorded commits disagree with the output."""
    out = np.asarray(tr.request.output)
    got = np.full(tr.n_blocks * lb, -1, np.int64)
    for b, steps in enumerate(tr.steps):
        for commits in steps:
            for off, tok in commits:
                got[b * lb + off] = tok
    return int((got != out[: len(got)]).sum())


def _stats(acc: dict) -> dict:
    """Per pass kind and over ``all`` tokens: the widest gap, the mean gap
    and the share of committed tokens that were not the reference's best."""
    acc = dict(acc)
    if acc:
        acc["all"] = [g for v in acc.values() for g in v]
    return {k: {"widest": float(g.max()), "mean": float(g.mean()),
                "off_best": float((g > 0).mean()), "tokens": int(g.size)}
            for k, g in ((k, np.concatenate(v)) for k, v in acc.items())}


def check(sem: refmod.Semantics, m: dict, seed: int, picked: list,
          lb: int, control: Optional[str] = None) -> dict:
    """Replays ``picked`` through the reference.  Returns, under
    ``"served"``, the statistics of the gaps of the served tokens
    (``_stats``), and under ``"control"`` (with ``control``, "int8" or
    "fp8") those of the tokens that the reference in that precision puts
    first at the same positions, read under the float32 reference."""
    w = wmod.make_logical(m, seed)
    ref = refmod.Reference(sem, w)
    others = (refmod.Reference(sem, w, quant=control),) if control else ()
    acc: dict[str, list] = {}
    acc_low: dict[str, list] = {}
    for tr in picked:
        for kind, offs, toks, lg in ref.replay(tr.prompt, tr.n_blocks,
                                               tr.steps, others):
            if not len(offs):
                continue
            acc.setdefault(kind, []).append(refmod.gaps(lg[0], toks))
            if control:
                acc_low.setdefault(kind, []).append(
                    refmod.control_gaps(lg[0], lg[1]))
    return {"served": _stats(acc),
            "control": _stats(acc_low) if control else None}
