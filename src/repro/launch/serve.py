"""Serving launcher: run the ES-dLLM serving runtime on a reduced model
(CPU-runnable end-to-end driver, deliverable b).

The runtime is the continuous-batching ``StreamScheduler`` (one per lane
of a ``ShardedStreamScheduler`` with ``--shards > 1``): slot admission at
block boundaries, slot recycling on completion, per-request block
streaming.  ``--paged`` turns the KV caches
into one shared page pool; add ``--prefix-sharing`` (and e.g.
``--dup-prompts``) for copy-on-write prompt-page dedup across duplicate
requests (docs/ARCHITECTURE.md).

  PYTHONPATH=src python -m repro.launch.serve --arch llada-8b --requests 16
  PYTHONPATH=src python -m repro.launch.serve --paged --prefix-sharing \
      --dup-prompts --requests 8
  PYTHONPATH=src python -m repro.launch.serve --paged --window-blocks 2 \
      --lazy-reserve --gen-length 64 --requests 8
  PYTHONPATH=src python -m repro.launch.serve --paged --shards 2 \
      --placement disagg --decode-prompt-len 16 --requests 8
"""
from __future__ import annotations

import argparse
import os
from pathlib import Path

import jax
import numpy as np

from repro import configs
from repro.configs import GenerationConfig, ModelConfig, default_skip_stages
from repro.models import build_model
from repro.runtime import (ConfigError, Request, ShardedStreamScheduler,
                           StreamScheduler)

REPO_ROOT = Path(__file__).resolve().parents[3]


def configure_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; when it is unset the
    cache goes to ``<repo>/.jax_cache``.  The path is fixed because it is
    part of the cache key: a directory that moves never hits."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llada-8b")
    ap.add_argument("--full", action="store_true",
                    help="full config (default: reduced, CPU-runnable)")
    ap.add_argument("--mode", default="es", choices=["vanilla", "dualcache", "es"])
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--batch", type=int, default=8,
                    help="slot count (split evenly across --shards)")
    ap.add_argument("--gen-length", type=int, default=32)
    ap.add_argument("--block-length", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--parallel-decoding", action="store_true")
    ap.add_argument("--early-advance", action="store_true",
                    help="per-row cadence: a slot advances its block the "
                         "moment it fully unmasks and admission happens on "
                         "any iteration (pairs with --parallel-decoding, "
                         "which makes block completion time variable)")
    ap.add_argument("--stream-print", action="store_true",
                    help="print each request's blocks as they unmask")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV pool + block tables")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--kv-pages", type=int, default=None,
                    help="pool pages incl. garbage page (default: dense-equivalent)")
    ap.add_argument("--prefix-sharing", action="store_true",
                    help="CoW prefix page sharing: same-cycle duplicate "
                         "prompts map the same physical prompt pages "
                         "(requires --paged; see docs/ARCHITECTURE.md)")
    ap.add_argument("--dup-prompts", action="store_true",
                    help="submit one prompt duplicated --requests times "
                         "(the prefix-sharing showcase workload)")
    ap.add_argument("--prompt-refresh-period", type=int, default=64,
                    help="iterations between scheduled prompt refreshes "
                         "(partial refreshes only exist when this is "
                         "smaller than the steps per block)")
    ap.add_argument("--cache-prompt-interval", type=int, default=0,
                    help="adaptive feature cache: every k-th scheduled "
                         "prompt refresh is FULL, the ones between are "
                         "variation-gated PARTIAL refreshes (<=1 disables; "
                         "es mode only)")
    ap.add_argument("--cache-response-interval", type=int, default=4,
                    help="short-interval response refresh: the block-refresh "
                         "period (sets block_refresh_period)")
    ap.add_argument("--cache-variation-threshold", type=float, default=0.0,
                    help="minimum variation score a candidate token needs "
                         "for its K/V to be recomputed in a partial refresh")
    ap.add_argument("--window-blocks", type=int, default=0,
                    help="sliding active window: attention reads at most "
                         "this many generation blocks past the current one "
                         "(0 = unbounded, windowing compiled out)")
    ap.add_argument("--lazy-reserve", action="store_true",
                    help="defer far-suffix page reservation: admission maps "
                         "prompt + one active window, the rest grows "
                         "just-in-time as the window slides (requires "
                         "--paged and --window-blocks > 0)")
    ap.add_argument("--priority-classes", type=int, default=1,
                    help="spread requests round-robin over this many "
                         "admission classes (class k = priority k; higher "
                         "admits first)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request SLO budget from arrival; admission "
                         "rejects a request with a typed DeadlineUnmeetable "
                         "once wait + estimated service exceeds it")
    ap.add_argument("--preemption", action="store_true",
                    help="priority preemption with host page spill/resume: "
                         "a higher-class arrival may spill a lower-class "
                         "resident's pages to host at its block boundary "
                         "and resume it bit-identically later (requires "
                         "--paged; docs/ARCHITECTURE.md §5a)")
    ap.add_argument("--block-causal", action="store_true",
                    help="causal-block attention mask: prompt K/V becomes a "
                         "pure function of the prompt, enabling the "
                         "persistent cross-request prefix store (with "
                         "--paged --prefix-sharing) and invariant-position "
                         "refresh skipping (docs/ARCHITECTURE.md §4b/4c)")
    ap.add_argument("--shards", type=int, default=1,
                    help="data-parallel serving shards: each shard owns a "
                         "private slot plane, page ledger, and admission "
                         "queue; a global placement policy routes each "
                         "request to exactly one shard (requires --paged; "
                         "docs/ARCHITECTURE.md §6a)")
    ap.add_argument("--placement", default="least_loaded",
                    choices=["least_loaded", "prefix_affinity", "disagg"],
                    help="per-request shard placement policy: least_loaded "
                         "(committed pages + queue depth), prefix_affinity "
                         "(route to the shard whose persistent store owns "
                         "the prompt; needs --prefix-sharing), or disagg "
                         "(prefill/decode disaggregation by prompt length)")
    ap.add_argument("--refresh-shards", type=int, default=1,
                    help="disagg only: how many leading shards take the "
                         "LONG-prompt (refresh) class")
    ap.add_argument("--decode-prompt-len", type=int, default=None,
                    help="disagg only: decode shards pad prompts to this "
                         "shorter width (the iteration-smoothing win); "
                         "requests with longer prompts route to the "
                         "refresh shards")
    return ap


def validate(args: argparse.Namespace) -> None:
    """Typed upfront checks of a parsed command line."""
    # fail fast on SLO/preemption misconfiguration, before any model build
    # (the scheduler re-validates --preemption, but a bad flag should not
    # cost a params init)
    if args.priority_classes < 1:
        raise ConfigError(
            f"--priority-classes must be >= 1, got {args.priority_classes}")
    if args.deadline_s is not None and args.deadline_s <= 0:
        raise ConfigError(
            f"--deadline-s must be positive, got {args.deadline_s} "
            "(a non-positive budget rejects every request at submit)")
    if args.preemption and not args.paged:
        raise ConfigError("--preemption requires --paged: spilling moves "
                          "pool pages, dense KV rows cannot be released")
    if args.preemption and args.prefix_sharing:
        raise ConfigError("--preemption is incompatible with "
                          "--prefix-sharing: a spill releases pages other "
                          "requests may still map")
    if args.preemption and args.lazy_reserve:
        raise ConfigError("--preemption is incompatible with "
                          "--lazy-reserve: spill breaks the max-deficit "
                          "liveness accounting")
    # multi-host topology misconfiguration also fails before the model
    # build (the ShardedStreamScheduler ctor re-validates all of these)
    if args.shards < 1:
        raise ConfigError(f"--shards must be >= 1, got {args.shards}")
    if args.shards > 1:
        if not args.paged:
            raise ConfigError("--shards > 1 requires --paged: shards own "
                              "per-shard page ledgers")
        if args.batch % args.shards:
            raise ConfigError(
                f"--shards ({args.shards}) must divide the slot count "
                f"--batch ({args.batch})")
        if args.kv_pages is not None and args.kv_pages % args.shards:
            raise ConfigError(
                f"--kv-pages ({args.kv_pages}) must divide evenly across "
                f"{args.shards} shards")
    if args.placement == "prefix_affinity" and not args.prefix_sharing:
        raise ConfigError("--placement prefix_affinity routes on the "
                          "persistent prefix store: it requires "
                          "--prefix-sharing (and --block-causal for the "
                          "store to exist)")
    if args.placement == "disagg":
        if args.shards < 2:
            raise ConfigError("--placement disagg needs --shards >= 2 "
                              "(refresh + decode classes)")
        if not (1 <= args.refresh_shards < args.shards):
            raise ConfigError(
                f"--refresh-shards ({args.refresh_shards}) must satisfy "
                f"1 <= refresh_shards < shards ({args.shards})")
        if (args.decode_prompt_len is not None
                and args.decode_prompt_len > args.prompt_len):
            raise ConfigError(
                f"--decode-prompt-len ({args.decode_prompt_len}) must not "
                f"exceed --prompt-len ({args.prompt_len})")
    elif args.decode_prompt_len is not None:
        raise ConfigError("--decode-prompt-len is a disagg knob; it does "
                          "nothing under --placement "
                          f"{args.placement} — refusing to drop it silently")
    if args.placement != "least_loaded" and args.shards < 2:
        raise ConfigError(f"--placement {args.placement} needs --shards "
                          ">= 2 (a single shard has nothing to route)")


def init_model(cfg: ModelConfig, seed: int = 0):
    """Build the model and its random parameters."""
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(seed))


def generation_config(args: argparse.Namespace,
                      cfg: ModelConfig) -> GenerationConfig:
    return GenerationConfig(
        gen_length=args.gen_length,
        block_length=args.block_length,
        mode=args.mode,
        skip_stages=default_skip_stages(cfg.n_layers) if args.mode == "es" else (),
        prompt_refresh_period=args.prompt_refresh_period,
        block_refresh_period=args.cache_response_interval,
        parallel_decoding=args.parallel_decoding,
        cache_prompt_interval=args.cache_prompt_interval,
        cache_variation_threshold=args.cache_variation_threshold,
        window_blocks=args.window_blocks,
        block_causal=args.block_causal,
    )


def build_server(args: argparse.Namespace, model, params,
                 gen: GenerationConfig, stream_cb=None, **engine_kw):
    """The server the command line describes: a ``ShardedStreamScheduler``
    with ``--shards > 1``, else a ``StreamScheduler``.  ``engine_kw``
    reaches the ``DiffusionEngine`` (e.g. ``attn_impl``)."""
    if args.shards > 1:
        return ShardedStreamScheduler(
            model, params, gen, shards=args.shards,
            placement=args.placement, refresh_shards=args.refresh_shards,
            decode_prompt_len=args.decode_prompt_len,
            max_slots=args.batch, prompt_len=args.prompt_len,
            stream_cb=stream_cb, paged=args.paged,
            page_size=args.page_size, kv_pages=args.kv_pages,
            prefix_sharing=args.prefix_sharing,
            early_advance=args.early_advance,
            lazy_reserve=args.lazy_reserve, preemption=args.preemption,
            **engine_kw)
    return StreamScheduler(model, params, gen, max_slots=args.batch,
                           prompt_len=args.prompt_len, stream_cb=stream_cb,
                           paged=args.paged, page_size=args.page_size,
                           kv_pages=args.kv_pages,
                           prefix_sharing=args.prefix_sharing,
                           early_advance=args.early_advance,
                           lazy_reserve=args.lazy_reserve,
                           preemption=args.preemption, **engine_kw)


def main() -> None:
    args = build_parser().parse_args()
    validate(args)
    configure_compile_cache()
    cfg = configs.get_config(args.arch)
    if not args.full:
        cfg = configs.reduced(cfg)
    model, params = init_model(cfg)
    gen = generation_config(args, cfg)

    stream_cb = None
    if args.stream_print:
        def stream_cb(req, bi, blk):
            print(f"  [stream] req={req.request_id} block={bi}: {blk.tolist()}")
    server = build_server(args, model, params, gen, stream_cb=stream_cb)

    rng = np.random.default_rng(0)
    if args.dup_prompts:
        dup_prompt = rng.integers(3, cfg.vocab_size,
                                  args.prompt_len).astype(np.int32)
    for i in range(args.requests):
        slo = dict(priority=i % args.priority_classes,
                   deadline_s=args.deadline_s)
        if args.dup_prompts:
            server.submit(Request(prompt=dup_prompt.copy(), **slo))
            continue
        plen = int(rng.integers(8, args.prompt_len + 1))
        server.submit(Request(
            prompt=rng.integers(3, cfg.vocab_size, plen).astype(np.int32),
            **slo))

    done = server.drain()
    line = (f"served {len(done)} requests  "
            f"mode={args.mode}  TPS={server.stats.tps:.2f}  "
            f"wall={server.stats.wall_s:.2f}s"
            f"  p50={server.stats.latency_pct(50):.2f}s"
            f"  p95={server.stats.latency_pct(95):.2f}s"
            f"  admission_p50={server.stats.admission_wait_p50:.3f}s")
    if args.early_advance:
        line += f"  early_advances={server.stats.early_advances}"
    if gen.adaptive_cache:
        line += (f"  cache_hit={server.stats.cache_hit_fraction:.3f}"
                 f"  refresh_p50={server.stats.tokens_refreshed_p50:.0f}")
    if args.paged:
        line += (f"  peak_pages={server.stats.peak_pages_in_use}"
                 f"/{server.stats.pages_total}"
                 f"  concurrency_peak={server.stats.resident_peak}")
        if args.prefix_sharing:
            line += f"  cow_forks={server.stats.cow_forks}"
        persistent = (any(l.persistent_prefix for l in server.lanes)
                      if args.shards > 1 else server.persistent_prefix)
        if persistent:
            line += (f"  prefix_hits={server.stats.prefix_hits}"
                     f"  prefix_evictions={server.stats.prefix_evictions}")
        if gen.sparse_attention:
            line += f"  pages_reclaimed={server.stats.pages_reclaimed}"
        if args.lazy_reserve:
            line += (f"  pages_deferred={server.stats.pages_deferred}"
                     f"  window_stalls={server.stats.window_stalls}")
    if args.preemption:
        line += (f"  preemptions={server.stats.preemptions}"
                 f"  pages_spilled={server.stats.pages_spilled}"
                 f"  resume_p50={server.stats.resume_p50:.3f}s")
    if args.deadline_s is not None:
        line += f"  deadline_rejects={server.stats.deadline_rejects}"
    if server.stats.poisoned_requests:
        line += f"  poisoned_requests={server.stats.poisoned_requests}"
    if args.shards > 1:
        line += f"  lanes_in_flight={server.stats.lanes_in_flight:.2f}"
    print(line)
    if args.shards > 1:
        # per-shard gauge breakdown: placement + residency + pool usage of
        # each shard-local ledger (the multi-host monitoring surface)
        for g in server.shard_gauges():
            print(f"  shard {g['shard']}: placed={g['placed']}  "
                  f"resident={g['resident']}  queued={g['queued']}  "
                  f"completed={g['completed']}  "
                  f"pages={g['pages_in_use']}/{g['pages_total']}  "
                  f"peak={g['peak_pages_in_use']}  "
                  f"blocks_grown={g['blocks_grown']}")
    ok = [r for r in done if r.output is not None]
    if ok:
        print("sample output:", ok[0].output[:24].tolist())


if __name__ == "__main__":
    main()
