"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first init).

Mesh building goes through ``repro.dist.sharding.make_mesh`` (``Auto``
axes, which the sharding hints require).
"""

from __future__ import annotations

import jax

from repro.dist.sharding import make_mesh as _make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_mesh(shape, axes):
    return _make_mesh(shape, axes)


def make_host_mesh(tp: int = 1):
    """Mesh over whatever devices exist (CPU tests, elastic restarts)."""
    n = len(jax.devices())
    tp = min(tp, n)
    return _make_mesh((n // tp, tp), ("data", "model"))
