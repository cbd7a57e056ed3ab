"""Production meshes (assignment-fixed shapes).

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (device count is locked at first jax init, and the
dry-run needs 512 host-platform devices while tests/benches see 1).
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)
