"""Production mesh builders.

Functions, not module constants, so importing this module never touches
jax device state (device count is locked at first jax init — the dry-run
sets XLA_FLAGS before any import; tests/benches must see 1 device).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod mesh: 16×16 = 256 chips per pod; 2 pods = 512 chips.

    Axes: 'data' (DP / ZeRO / FSDP), 'model' (TP / EP / SP), plus 'pod'
    (outer DP + FSDP for 400B-class models) when ``multi_pod``."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many (possibly fake) devices exist — used by
    CPU integration tests."""
    n = len(jax.devices())
    data = min(data, n // model) or 1
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


# v5e hardware constants for the roofline terms (per chip).
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BW = 819e9                # bytes/s
ICI_BW = 50e9                 # bytes/s per link
