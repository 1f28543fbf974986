"""Production mesh construction.

Defined as FUNCTIONS (not module-level constants) so importing this
module never touches jax device state — the dry-run sets the placeholder
device count before any jax initialization.  Every mesh here is built
with ``AxisType.Auto`` axes: the model code leaves sharding to GSPMD
propagation (plus ``shard_hint`` annotations), not explicit sharding.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds a 2-pod leading axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 2, data: int = 0):
    """Small mesh over whatever devices exist (tests/examples on CPU)."""
    n = len(jax.devices())
    data = data or max(1, n // model)
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
