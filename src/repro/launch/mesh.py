"""Production mesh construction (TPU v5e pods).

A function, not a module-level constant, so importing this module never
touches jax device state (the dry-run sets XLA_FLAGS *before* first init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the model code places arrays with
    explicit ``NamedSharding``s and lets the compiler propagate the rest,
    which Explicit axes (the default since JAX 0.7) refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many (possibly fake) devices exist."""
    return _auto_mesh((data, model), ("data", "model"))


def make_host_mesh(shards: int = 1):
    """1-D data mesh with one entry per serving shard (multi-host serving).

    CI simulates the multi-host topology on CPU with
    ``--xla_force_host_platform_device_count=N`` — the same trick the
    dry-run uses — so ``shards`` fake host devices back the mesh; on real
    hardware each entry is one host's accelerator set."""
    return _auto_mesh((shards,), ("data",))


# TPU v5e hardware constants for the roofline model (DESIGN §8)
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link
