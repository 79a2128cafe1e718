"""The six Pallas kernels of the serving path, compiled by the TPU compiler
for a described (not attached) v5e chip at LLaDA-8B widths.

Interpret mode runs a kernel body without the TPU's tiling rules, so a
kernel can pass every CPU test and still be refused on the chip (block
shapes not 8/128-aligned, too much VMEM).  These tests compile each kernel
through its ``kernels/ops.py`` wrapper with ``interpret=False`` — the same
lowering the chip runs — and check that a Mosaic custom call is in the
program.  Nothing executes; the topology is described inside a fixture, so
a worker that never runs this file never loads the TPU library.

Shapes are the chip smoke's serving phase: MHA 32x128 (d_model 4096),
8 slots, prompt 512 + gen 256 (T = 768), block 32, page 128, bf16.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

B, H, DH, D_MODEL = 8, 32, 128, 4096
T, LB, PS, G = 768, 32, 128, 8
N_VP = T // PS
PAGES = B * N_VP + 1            # dense-equivalent pool + garbage page
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these compiles out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler / topology support here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_flash_attention_compiles(one_chip):
    def fn(q, k, v, qp, kp):
        return ops.attention(q, k, v, qp, kp, impl="pallas", interpret=False)
    _compile(fn, one_chip, ((B, H, LB, DH), BF16), ((B, H, T, DH), BF16),
             ((B, H, T, DH), BF16), ((B, LB), jnp.int32), ((B, T), jnp.int32))


def test_paged_flash_attention_compiles(one_chip):
    def fn(q, kp, vp, qp, kvp, bt):
        return ops.paged_attention(q, kp, vp, qp, kvp, bt, page_size=PS,
                                   impl="pallas", interpret=False)
    _compile(fn, one_chip, ((B, H, LB, DH), BF16),
             ((PAGES, PS, H, DH), BF16), ((PAGES, PS, H, DH), BF16),
             ((B, LB), jnp.int32), ((B, T), jnp.int32),
             ((B, N_VP), jnp.int32))


def test_paged_scatter_kv_compiles(one_chip):
    def fn(pool, new, idx, bt):
        return ops.scatter_rows_paged(pool, new, idx, bt, page_size=PS,
                                      impl="pallas", interpret=False)
    _compile(fn, one_chip, ((PAGES, PS, H, DH), BF16), ((B, T, H, DH), BF16),
             ((B, T), jnp.int32), ((B, N_VP), jnp.int32))


def test_fork_pages_compiles(one_chip):
    def fn(pool, src, dst):
        return ops.fork_pages(pool, src, dst, impl="pallas", interpret=False)
    _compile(fn, one_chip, ((G, PAGES, PS, H, DH), BF16),
             ((8,), jnp.int32), ((8,), jnp.int32))


def test_importance_compiles(one_chip):
    # ES skip decode scores the block's tokens at a skip stage
    def fn(hn, ho, conf):
        return ops.importance_score(hn, ho, conf, alpha=0.5, impl="pallas",
                                    interpret=False)
    _compile(fn, one_chip, ((B, LB, D_MODEL), BF16), ((B, LB, D_MODEL), BF16),
             ((B, LB), jnp.float32))


def test_variation_compiles(one_chip):
    # adaptive-cache partial refresh scores every position's f32 feature
    def fn(hn, ho, conf):
        return ops.variation_score(hn, ho, conf, alpha=0.5, impl="pallas",
                                   interpret=False)
    _compile(fn, one_chip, ((B, T, D_MODEL), jnp.float32),
             ((B, T, D_MODEL), jnp.float32), ((B, T), jnp.float32))
