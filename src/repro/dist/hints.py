"""Ambient activation-sharding hints.

Model code runs mesh-agnostic; launchers that *do* have a mesh open a
``sharding_hints(mesh=...)`` context, and layers mark their key activations
with ``constrain(x, roles)`` where each role names a *class* of mesh axes
rather than a concrete axis (mesh-axis convention: ``pod``/``data`` are data
parallel, ``model`` is tensor parallel — see ``repro.dist.__init__``):

  * ``"dp"``  — the data-parallel axes of the ambient mesh (``pod``/``data``);
  * ``"tp"``  — the tensor-parallel axis (``model``);
  * ``None``  — replicated;
  * a literal mesh-axis name (or tuple of names) passes through.

Outside a context — or when no mapped axis divides the dimension —
``constrain`` is the identity, so the same layer code serves single-device
tests and 512-chip dry-runs.  The context also carries the resolved
``{"mesh", "dp", "tp"}`` state (``current()``) for layers that need to branch
on topology, e.g. the expert-parallel MoE dispatch.
"""

from __future__ import annotations

from contextlib import contextmanager
from contextvars import ContextVar
from typing import Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.dist import sharding as shd

# Ambient hint state: None, or {"mesh": Mesh, "dp": tuple, "tp": str | None}.
_HINTS: ContextVar = ContextVar("repro_sharding_hints", default=None)


@contextmanager
def sharding_hints(mesh=None, dp=None, tp=None):
    """Install ambient sharding hints for the enclosed region.

    ``dp``/``tp`` default to the conventional axes present on ``mesh``
    (``("pod", "data")`` and ``"model"``); pass them explicitly to override.
    """
    if dp is None:
        dp = tuple(a for a in shd.DP_AXES
                   if mesh is not None and a in mesh.shape)
    elif isinstance(dp, str):
        dp = (dp,)
    if tp is None and mesh is not None:
        tp = shd.tp_axis(mesh)
    token = _HINTS.set({"mesh": mesh, "dp": tuple(dp), "tp": tp})
    try:
        yield _HINTS.get()
    finally:
        _HINTS.reset(token)


def current() -> Optional[dict]:
    """The active hint state, or None outside any ``sharding_hints``."""
    return _HINTS.get()


def resolve(shape, roles) -> Optional[P]:
    """Resolve per-dim roles to a PartitionSpec under the ambient mesh.

    Returns None when there is nothing to constrain (no context, or every
    role resolves to replication).  Divisibility-safe, and never maps one
    mesh axis to two dims of the same tensor.
    """
    state = _HINTS.get()
    if state is None or state.get("mesh") is None:
        return None
    mesh = state["mesh"]
    used: set = set()
    out = []
    for dim, role in zip(shape, roles):
        if role is None:
            out.append(None)
            continue
        if role == "dp":
            axes = state["dp"]
        elif role == "tp":
            axes = (state["tp"],) if state["tp"] is not None else ()
        elif isinstance(role, str):
            axes = (role,)
        else:
            axes = tuple(role)
        axes = shd.fit_axes(dim, tuple(a for a in axes if a not in used),
                            mesh)
        if not axes:
            out.append(None)
            continue
        used.update(axes)
        out.append(axes[0] if len(axes) == 1 else axes)
    while out and out[-1] is None:
        out.pop()
    if not out:
        return None
    return P(*out)


def per_shard(fn, *args):
    """``fn(*args)`` run on each shard of the ambient mesh (``shard_map``):
    every argument and the one output split on their leading (batch, head)
    dims over the ``dp`` and ``tp`` axes as ``resolve`` maps them for
    ``args[0]``, all other dims whole.  For computations independent
    across those dims that the SPMD partitioner cannot split itself, such
    as a Pallas kernel.  A plain call outside a context or when nothing
    resolves."""
    spec = resolve(args[0].shape[:2], ("dp", "tp"))
    if spec is None:
        return fn(*args)
    return jax.shard_map(fn, mesh=_HINTS.get()["mesh"],
                         in_specs=(spec,) * len(args), out_specs=spec,
                         check_vma=False)(*args)


def constrain(x, roles):
    """``with_sharding_constraint`` under the ambient hints; identity when
    no context is active or nothing resolves (divisibility fallback)."""
    spec = resolve(x.shape, roles)
    if spec is None:
        return x
    mesh = _HINTS.get()["mesh"]
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
