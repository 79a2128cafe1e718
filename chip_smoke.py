#!/usr/bin/env python3
"""Bring-up smoke of the ES-dLLM serving path on TPU.

    python chip_smoke.py             # one chip: serve (XLA), kernels, serve (Pallas)
    python chip_smoke.py --chips 4   # four chips: sharded lanes + per-shard replay

LLaDA-8B at its published widths (MHA 32x128, d_ff 12,288, vocabulary
126,464) in bf16 with random weights, cut in depth only, is built and
served through the same functions as ``repro.launch.serve``: a paged
``StreamScheduler`` with ES skip stages and early block advance, 8 slots,
prompt 512, gen 256, block 32, page 128.

The script checks what comes out and fails on any miss: every request
completes, no mask token is left, no row is quarantined, the serving step
traces once, each Pallas kernel agrees with its XLA lowering within a
stated tolerance, and the Pallas program really holds Mosaic kernels.  It
exits non-zero and prints no result line when JAX finds no TPU.  The times
it prints are those of one smoke run, not a benchmark.  The last line of
stdout is ``{"ok": true, "device": {"platform", "kind", "count"}}``.

Everything runs in this one process: a chip belongs to the process that
first touches it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

from repro import configs  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.runtime import Request  # noqa: E402

# 8 of LLaDA-8B's 32 layers: one of four pipeline stages of a four-chip
# deployment, plus the embedding and the head.  The chip's compiler refuses
# 16 layers (17.93 GB of 15.75 GB HBM for the served step at these shapes).
DEPTH = 8
N_LAYERS_PUBLISHED = 32
SERVE_ARGV = ["--arch", "llada-8b", "--mode", "es", "--paged",
              "--early-advance", "--prompt-len", "512", "--gen-length", "256",
              "--block-length", "32", "--page-size", "128"]
SLOTS_PER_CHIP = 8
N_REQUESTS = 12
REQUESTS_PER_SHARD = 6
SEED = 0

# Pallas vs XLA tolerances.  Attention outputs are bf16 (one rounding step
# near 1.0 is 2**-7) and XLA feeds the f32 softmax weights to the MXU as
# bf16 by default while the kernel keeps them f32, so the two may differ by
# a few bf16 steps.  The score kernels reduce 4096 f32 terms per row in a
# different order than XLA's fused reductions: f32 rounding, ~1e-6 relative.
# Scatter and fork move bytes and must be exact.
TOL = {"flash_attention": 2e-2, "paged_flash_attention": 2e-2,
       "paged_scatter_kv": 0.0, "fork_pages": 0.0,
       "importance": 1e-4, "variation": 1e-4}


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class CompileClock:
    """Seconds JAX spends in backend compiles (a persistent-cache hit counts
    its load time instead) and the number of cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
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

    def snapshot(self) -> tuple:
        return self.seconds, self.compiles, self.cache_hits

    def since(self, snap) -> str:
        s, c, h = snap
        return (f"compile_s={self.seconds - s:.2f} backend_compiles="
                f"{self.compiles - c} persistent_cache_hits="
                f"{self.cache_hits - h}")


def model_config():
    return dataclasses.replace(configs.get_config("llada-8b"), n_layers=DEPTH,
                               param_dtype="bfloat16",
                               compute_dtype="bfloat16")


def make_prompts(vocab_size: int, prompt_len: int, n: int, seed: int):
    """Seeded random prompts of mixed lengths (16 .. prompt_len tokens)."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(16, prompt_len + 1, n)
    return [rng.integers(3, vocab_size, int(k)).astype(np.int32) for k in lens]


def submit_all(sched, prompts):
    for i, p in enumerate(prompts):
        sched.submit(Request(prompt=p.copy(), request_id=i, sample_seed=i))


def check_outputs(outs: dict, ids, mask_id: int) -> list[str]:
    """Misses: requests without an output, or outputs holding a mask id."""
    missing = [i for i in ids if outs.get(i) is None]
    masked = [i for i in ids
              if outs.get(i) is not None and (outs[i] == mask_id).any()]
    errs = []
    if missing:
        errs.append(f"requests without output: {missing}")
    if masked:
        errs.append(f"mask ids left in requests: {masked}")
    return errs


def mosaic_calls(lowered) -> int:
    return lowered.as_text().count("tpu_custom_call")


def serve_phase(args, model, params, gen, prompts, clock, label: str,
                require_mosaic: bool = True, **engine_kw):
    """Serve ``prompts`` once; returns (outputs by request id, misses)."""
    sched = serve.build_server(args, model, params, gen, **engine_kw)
    submit_all(sched, prompts)
    snap = clock.snapshot()
    t0 = time.perf_counter()
    done = sched.drain()
    wall = time.perf_counter() - t0
    outs = {r.request_id: r.output for r in done}
    errs = check_outputs(outs, range(len(prompts)), sched.engine.mask_id)
    if sched.stats.poisoned_requests:
        errs.append(f"poisoned_requests={sched.stats.poisoned_requests}")
    traces = sched.engine.step_trace_count
    if traces != 1:
        errs.append(f"step_trace_count={traces} (want 1)")
    log(f"serve[{label}]: completed={sched.stats.completed}/{len(prompts)} "
        f"steps={sched._step_count} wall_s={wall:.2f} "
        f"tokens_out={sched.stats.tokens_out} step_trace_count={traces} "
        f"poisoned_requests={sched.stats.poisoned_requests} "
        f"{clock.since(snap)} (one smoke run, not a benchmark)")
    if require_mosaic and engine_kw.get("attn_impl") == "pallas":
        # interpret mode would lower the kernels to plain XLA ops
        n = mosaic_calls(sched.engine._jit_step.lower(
            sched.params, sched.state, None))
        log(f"serve[{label}]: Mosaic kernel calls in the step program: {n}")
        if n == 0:
            errs.append("the Pallas step holds no Mosaic kernel")
    return outs, errs


def kernel_phase(cfg, args, interpret: bool = False) -> list[str]:
    """Each Pallas kernel of the serving path against its XLA lowering, at
    the serving phase's shapes."""
    b, lb, ps = args.batch, args.block_length, args.page_size
    t = args.prompt_len + args.gen_length
    h, hkv, dh, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    n_vp = t // ps
    pages = b * n_vp + 1
    bf16, f32 = jnp.bfloat16, jnp.float32
    ks = iter(jax.random.split(jax.random.PRNGKey(SEED), 16))

    def normal(shape, dtype=bf16):
        return jax.random.normal(next(ks), shape, f32).astype(dtype)

    # slot b: its prompt starts at a random pad offset, queries sit in the
    # block at bs; every slot maps n_vp distinct pool pages (page 0 = garbage)
    prompt_start = jax.random.randint(next(ks), (b,), 0, args.prompt_len)
    kv_pos = jnp.where(jnp.arange(t)[None] >= prompt_start[:, None],
                       jnp.arange(t)[None], -1).astype(jnp.int32)
    bs = args.prompt_len + lb * jax.random.randint(
        next(ks), (b,), 0, args.gen_length // lb)
    q_pos = (bs[:, None] + jnp.arange(lb)[None]).astype(jnp.int32)
    bt = (1 + jax.random.permutation(next(ks), b * n_vp)).reshape(
        b, n_vp).astype(jnp.int32)
    q = normal((b, h, lb, dh))
    k, v = normal((b, hkv, t, dh)), normal((b, hkv, t, dh))
    k_pool, v_pool = normal((pages, ps, hkv, dh)), normal((pages, ps, hkv, dh))
    pool_g = normal((cfg.n_layers, pages, ps, hkv, dh))
    fork_src = jnp.asarray([3, 9, 17, 25, 0, 0, 0, 0], jnp.int32)
    fork_dst = jnp.asarray([40, 41, 42, 43, 0, 0, 0, 0], jnp.int32)
    new_rows = normal((b, lb, hkv, dh))
    h_blk = normal((b, lb, d)), normal((b, lb, d))
    conf_blk = jax.random.uniform(next(ks), (b, lb))
    feat = normal((b, t, d), f32), normal((b, t, d), f32)
    conf_t = jax.random.uniform(next(ks), (b, t))

    def kw(impl):
        return {"interpret": interpret} if impl == "pallas" else {}

    cases = {
        "flash_attention": (
            lambda impl, *a: ops.attention(*a, impl=impl, **kw(impl)),
            (q, k, v, q_pos, kv_pos)),
        "paged_flash_attention": (
            lambda impl, *a: ops.paged_attention(*a, page_size=ps, impl=impl,
                                                 **kw(impl)),
            (q, k_pool, v_pool, q_pos, kv_pos, bt)),
        "paged_scatter_kv": (
            lambda impl, *a: ops.scatter_rows_paged(*a, page_size=ps,
                                                    impl=impl, **kw(impl)),
            (k_pool, new_rows, q_pos, bt)),
        "fork_pages": (
            lambda impl, *a: ops.fork_pages(*a, impl=impl, **kw(impl)),
            (pool_g, fork_src, fork_dst)),
        "importance": (
            lambda impl, *a: ops.importance_score(*a, alpha=0.5, impl=impl,
                                                  **kw(impl)),
            (*h_blk, conf_blk)),
        "variation": (
            lambda impl, *a: ops.variation_score(*a, alpha=0.5, impl=impl,
                                                 **kw(impl)),
            (*feat, conf_t)),
    }

    errs = []
    for name, (fn, inputs) in cases.items():
        pallas = jax.jit(lambda *a, fn=fn: fn("pallas", *a))
        xla = jax.jit(lambda *a, fn=fn: fn("xla", *a))
        if not interpret and mosaic_calls(pallas.lower(*inputs)) == 0:
            # interpret mode would lower the kernel to plain XLA ops
            errs.append(f"{name}: no Mosaic kernel in the Pallas program")
        got, want = pallas(*inputs), xla(*inputs)
        err = float(jnp.max(jnp.abs(got.astype(f32) - want.astype(f32))))
        ok = err <= TOL[name] and bool(jnp.all(jnp.isfinite(got)))
        log(f"kernel {name}: shape={tuple(got.shape)} max_abs_err={err:.3e} "
            f"tol={TOL[name]:.0e} {'ok' if ok else 'FAIL'}")
        if not ok:
            errs.append(f"{name}: max_abs_err {err:.3e} > tol {TOL[name]:.0e}")
    return errs


def one_chip(clock, interpret: bool = False) -> list[str]:
    """Serve with the XLA lowerings, check each kernel, serve with the
    Pallas kernels.  ``interpret`` runs the kernels in interpret mode, for
    a rehearsal on the CPU at a small size."""
    args = serve.build_parser().parse_args(
        SERVE_ARGV + ["--batch", str(SLOTS_PER_CHIP)])
    serve.validate(args)
    cfg = model_config()
    log(f"depth kept: {cfg.n_layers} of {N_LAYERS_PUBLISHED} layers (one of four "
        f"pipeline stages, plus embedding and head; 16 layers exceed HBM)")
    snap = clock.snapshot()
    t0 = time.perf_counter()
    model, params = serve.init_model(cfg, SEED)
    jax.block_until_ready(params)
    gen = serve.generation_config(args, cfg)
    log(f"init: d_model={cfg.d_model} heads={cfg.n_heads}x{cfg.head_dim} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} dtype={cfg.param_dtype} "
        f"skip_stages={gen.skip_stages} wall_s={time.perf_counter() - t0:.2f} "
        f"{clock.since(snap)}")
    prompts = make_prompts(cfg.vocab_size, args.prompt_len, N_REQUESTS, SEED)

    errs = []
    ref, e = serve_phase(args, model, params, gen, prompts, clock, "xla")
    errs += [f"serve[xla]: {m}" for m in e]
    errs += kernel_phase(cfg, args, interpret)
    got, e = serve_phase(args, model, params, gen, prompts, clock, "pallas",
                         require_mosaic=not interpret,
                         attn_impl="pallas", importance_impl="pallas")
    errs += [f"serve[pallas]: {m}" for m in e]
    both = [i for i in ref if ref[i] is not None and got.get(i) is not None]
    if both:
        agree = np.mean([np.mean(ref[i] == got[i]) for i in both])
        # random weights make argmax near-ties common, so a bf16-level
        # difference can flip a token: reported, not gated
        log(f"token agreement pallas vs xla: {agree:.4f} over {len(both)} "
            f"requests")
    return errs


def four_chips(clock, n: int) -> list[str]:
    """Sharded lanes, one per chip, and each shard replayed on one lane."""
    args = serve.build_parser().parse_args(
        SERVE_ARGV + ["--batch", str(SLOTS_PER_CHIP * n), "--shards", str(n)])
    serve.validate(args)
    lane_args = serve.build_parser().parse_args(
        SERVE_ARGV + ["--batch", str(SLOTS_PER_CHIP)])
    cfg = model_config()
    log(f"depth kept: {cfg.n_layers} of {N_LAYERS_PUBLISHED} layers per replica")
    model, params = serve.init_model(cfg, SEED)
    gen = serve.generation_config(args, cfg)
    prompts = make_prompts(cfg.vocab_size, args.prompt_len,
                           REQUESTS_PER_SHARD * n, SEED)

    errs = []
    sched = serve.build_server(args, model, params, gen)
    lane_devs = [lane.state.tokens.devices().pop() for lane in sched.lanes]
    log(f"lanes pinned to devices: {[d.id for d in lane_devs]}")
    if len(set(lane_devs)) != n:
        errs.append(f"lanes share devices: {lane_devs}")
    submit_all(sched, prompts)
    snap = clock.snapshot()
    t0 = time.perf_counter()
    done = sched.drain()
    wall = time.perf_counter() - t0
    outs = {r.request_id: r.output for r in done}
    errs += check_outputs(outs, range(len(prompts)), sched.engine.mask_id)
    if sched.stats.poisoned_requests:
        errs.append(f"poisoned_requests={sched.stats.poisoned_requests}")
    sched.allocator.check_conservation()     # raises LedgerError on a leak
    log(f"sharded serve: completed={len(done)}/{len(prompts)} "
        f"placed={sched.placed} wall_s={wall:.2f} "
        f"step_trace_count={sched.engine.step_trace_count} "
        f"{clock.since(snap)} conservation=ok (one smoke run, not a benchmark)")

    for s in range(n):
        ids = [i for i in range(len(prompts)) if sched.placements[i] == s]
        replay = serve.build_server(lane_args, model, params, gen, seed=s,
                                    engine=sched.engine)
        for i in ids:
            replay.submit(Request(prompt=prompts[i].copy(), request_id=i,
                                  sample_seed=i))
        ref = {r.request_id: r.output for r in replay.drain()}
        same = all(ref.get(i) is not None and outs.get(i) is not None
                   and np.array_equal(ref[i], outs[i]) for i in ids)
        log(f"shard {s}: {len(ids)} requests, replay bit-identical={same}")
        if not same:
            errs.append(f"shard {s} diverged from its single-lane replay")
    return errs


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: serving, kernels and Pallas serving on one "
                         "chip; 4: sharded lanes on four chips with "
                         "per-shard replay, and nothing else")
    opts = ap.parse_args()

    devs = jax.devices()
    dev = devs[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; this smoke never runs on the "
              f"{dev.platform}", file=sys.stderr)
        return 1
    if len(devs) < opts.chips:
        print(f"chip_smoke: --chips {opts.chips} but JAX sees {len(devs)}",
              file=sys.stderr)
        return 1

    log(f"compile cache: {serve.configure_compile_cache()}")
    clock = CompileClock()
    t0 = time.perf_counter()
    errs = four_chips(clock, opts.chips) if opts.chips == 4 \
        else one_chip(clock)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", "not reported")
             for d in devs[:opts.chips]]
    log(f"total wall_s={time.perf_counter() - t0:.2f} "
        f"{clock.since((0.0, 0, 0))} peak_bytes_in_use per device={peaks}")
    if errs:
        for e in errs:
            print(f"chip_smoke: FAIL {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
