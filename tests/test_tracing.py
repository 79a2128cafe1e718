"""Named spans and scopes for the profiler.

* One ``StreamScheduler.step`` under ``jax.profiler`` records the eight
  ``es.`` host spans, nested in ``es.sched.step`` and in order; a lane of
  ``ShardedStreamScheduler`` gives its index as the ``lane`` argument of
  ``es.sched.step`` and of ``es.engine.wait``.
* The compiled engine step carries ``es.skip_decode``, ``es.block_refresh``
  and ``es.prompt_refresh`` (and ``es.partial_refresh`` with the adaptive
  cache) in the ``op_name`` of its pass conditionals, and ``es.attention``
  under each of them.  Asking for that text traces the step no further.
"""
import dataclasses
import glob
import os
import re

import jax
import numpy as np
import pytest

from repro import configs
from repro.configs import GenerationConfig, SkipStage
from repro.models import build_model
from repro.runtime import Request, StreamScheduler
from repro.runtime.multihost import ShardedStreamScheduler

PROMPT_LEN = 16
PS = 8
GEN = dict(gen_length=32, block_length=8)

SCHED_SPANS = ["es.sched.admit", "es.sched.prepare", "es.engine.dispatch",
               "es.engine.wait", "es.sched.after", "es.sched.retire",
               "es.sched.grow"]
PASSES = ["es.skip_decode", "es.block_refresh", "es.prompt_refresh"]


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


def _requests(cfg, n):
    rng = np.random.default_rng(0)
    return [Request(prompt=rng.integers(3, cfg.vocab_size, PROMPT_LEN)
                    .astype(np.int32)) for _ in range(n)]


def _host_spans(path):
    """The ``es.`` events of the newest trace under ``path``:
    (name, start_ns, end_ns, args)."""
    from jax.profiler import ProfileData
    f = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)[-1]
    out = []
    for plane in ProfileData.from_file(f).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("es."):
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                dict(e.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def test_step_records_host_spans_nested_in_order(small_model, tmp_path):
    cfg, model, params = small_model
    sched = StreamScheduler(model, params, _cfg(window_blocks=1), max_slots=2,
                            prompt_len=PROMPT_LEN, paged=True, page_size=PS,
                            early_advance=True, lazy_reserve=True)
    for r in _requests(cfg, 2):
        sched.submit(r)
    sched.step()                            # compiles outside the trace
    with jax.profiler.trace(str(tmp_path)):
        assert sched.step()
    spans = _host_spans(str(tmp_path))
    assert [s[0] for s in spans] == ["es.sched.step"] + SCHED_SPANS
    step, children = spans[0], spans[1:]
    assert "lane" not in step[3]
    wait, = [s for s in children if s[0] == "es.engine.wait"]
    assert "lane" not in wait[3]
    for name, s, e, _ in children:
        assert step[1] <= s <= e <= step[2], name
    # siblings follow one another without overlap
    for a, b in zip(children, children[1:]):
        assert a[2] <= b[1], (a[0], b[0])


def test_lane_index_is_a_span_argument(small_model, tmp_path):
    cfg, model, params = small_model
    sched = ShardedStreamScheduler(model, params, _cfg(), shards=2,
                                   max_slots=4, prompt_len=PROMPT_LEN,
                                   paged=True, page_size=PS,
                                   early_advance=True, devices=None)
    assert [lane.lane_index for lane in sched.lanes] == [0, 1]
    for r in _requests(cfg, 4):
        sched.submit(r)
    sched.step()
    with jax.profiler.trace(str(tmp_path)):
        sched.step()
    spans = _host_spans(str(tmp_path))
    for name in ("es.sched.step", "es.engine.wait"):
        got = [s[3].get("lane") for s in spans if s[0] == name]
        assert got == [0, 1], name


def _op_names(hlo: str) -> list[str]:
    return re.findall(r'op_name="([^"]*)"', hlo)


@pytest.mark.parametrize("adaptive", [False, True])
def test_compiled_step_names_its_passes(small_model, adaptive):
    cfg, model, params = small_model
    gen = _cfg(prompt_refresh_period=8, cache_prompt_interval=4) if adaptive \
        else _cfg()
    sched = StreamScheduler(model, params, gen, max_slots=2,
                            prompt_len=PROMPT_LEN, paged=True, page_size=PS)
    for r in _requests(cfg, 2):
        sched.submit(r)
    sched.step()
    eng = sched.engine
    traced = eng.step_trace_count
    hlo = eng.compiled_step_text(sched.params, sched.state, sched._enc_out)
    assert eng.step_trace_count == traced
    names = _op_names(hlo)
    passes = PASSES + (["es.partial_refresh"] if adaptive else [])
    conds = {n.split("/")[-2] for n in names
             if n.endswith("/cond") and n.split("/")[-2].startswith("es.")}
    assert conds == set(passes)
    for p in passes:
        assert any(f"/{p}/" in n and "/es.attention/" in n for n in names), p
    # the K/V scatters stay outside the attention read
    assert not any("/es.attention/" in n and "scatter" in n for n in names)


_SHAPE = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]")


@pytest.mark.parametrize("paged", [True, False])
def test_prompt_refresh_runs_on_its_rows(small_model, paged):
    """A paged attention-only engine refreshes prompts one row at a time:
    its one ``es.prompt_refresh`` conditional computes the prefill on
    ``[1, T, d_model]`` activations.  A dense-KV engine keeps the masked
    pass over every slot, ``[B, T, d_model]``."""
    cfg, model, params = small_model
    slots = 3
    kw = dict(paged=True, page_size=PS) if paged else {}
    sched = StreamScheduler(model, params, _cfg(), max_slots=slots,
                            prompt_len=PROMPT_LEN, **kw)
    for r in _requests(cfg, 2):
        sched.submit(r)
    sched.step()
    eng = sched.engine
    assert eng.refresh_per_row == paged
    hlo = eng.compiled_step_text(sched.params, sched.state, sched._enc_out)
    scoped = [ln for ln in hlo.splitlines()
              if re.search(r'op_name="[^"]*/es\.prompt_refresh/', ln)]
    conds = [ln for ln in scoped if " conditional(" in ln]
    assert len(conds) == 1
    assert re.search(r'op_name="[^"]*/es\.prompt_refresh/cond"', conds[0])
    shapes = {m.group(1) for m in map(_SHAPE.match, scoped) if m}
    t = PROMPT_LEN + GEN["gen_length"]
    one, every = f"1,{t},{cfg.d_model}", f"{slots},{t},{cfg.d_model}"
    if paged:
        assert one in shapes and every not in shapes
    else:
        assert every in shapes and one not in shapes
