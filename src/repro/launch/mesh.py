"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set XLA_FLAGS before the
first jax initialization.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False,
                         devices: Optional[list] = None) -> Mesh:
    """16×16 per pod (256 chips); 2×16×16 across two pods (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    if devices is not None:
        import numpy as np
        return Mesh(np.asarray(devices).reshape(shape), axes)
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """Arbitrary mesh (tests / elastic replans / degraded runs). Axes are
    Auto-typed: the sharding rules place arrays with NamedShardings and
    leave propagation to the compiler."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def required_devices(multi_pod: bool) -> int:
    return 512 if multi_pod else 256
