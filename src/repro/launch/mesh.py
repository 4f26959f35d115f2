"""Production mesh definitions (TPU v5e pods).

`make_production_mesh` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — required because
the dry-run forces 512 host devices while tests/benches must see 1.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh() -> jax.sharding.Mesh:
    """Single-device mesh for smoke runs on CPU."""
    return jax.make_mesh((1, 1), ("data", "model"), axis_types=_auto(2))


def _auto(n: int) -> tuple:
    # Sharding in this repo is steered by `with_sharding_constraint` and
    # GSPMD propagation, which need Auto axes (make_mesh defaults to
    # Explicit, where a constraint becomes an assertion).
    return (jax.sharding.AxisType.Auto,) * n


# TPU v5e hardware constants for the roofline model (per chip).
PEAK_BF16_FLOPS = 197e12     # FLOP/s
HBM_BW = 819e9               # bytes/s
ICI_LINK_BW = 50e9           # bytes/s per link
