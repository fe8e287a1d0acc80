"""Sharding rule sets: logical model axes -> concrete mesh shardings.

Mesh-axis naming convention (see ``repro.dist.__init__``): data parallelism
lives on ``("pod", "data")`` (outer to inner), tensor parallelism on
``"model"``.  Model code only names *logical* axes (``embed``, ``heads``,
``mlp``, ``expert``, ...); a rule set maps each logical axis to mesh axes, and
``repro.nn.module.resolve_spec`` applies the mapping divisibility-safely —
any dimension not divisible by the mapped mesh-axis product is replicated
instead of failing (the GQA kv-heads case: 6 kv heads on an 8-way model axis
simply stay replicated).

Two rule sets:

  * ``"tp"``      — tensor parallelism only: width-like axes (mlp, heads,
                    experts, vocab) shard over ``model``; everything else is
                    replicated.
  * ``"fsdp_tp"`` — ``"tp"`` plus ZeRO/FSDP-style sharding of the ``embed``
                    axis over the data-parallel axes.

Also hosts ``make_mesh``: the program's meshes use ``Auto`` axes, which
sharding constraints (``repro.dist.hints``) require; bare ``jax.make_mesh``
defaults to ``Explicit`` axes.
"""

from __future__ import annotations

from typing import Any, Mapping, Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec

from repro.core import kv_cache as _kvc
from repro.nn.module import (LogicalSpec, init_shapes, logical,  # noqa: F401
                             named_shardings, resolve_spec, resolve_specs)

P = PartitionSpec

# Data-parallel mesh axes, outermost first; tensor-parallel axis name.
DP_AXES = ("pod", "data")
TP_AXIS = "model"

_TP_RULES = {
    "embed": None,
    "vocab": TP_AXIS,
    "mlp": TP_AXIS,
    "heads": TP_AXIS,
    "kv_heads": TP_AXIS,
    "mosa_heads": TP_AXIS,
    "expert": TP_AXIS,
    "expert_mlp": None,
    "batch": DP_AXES,
}

RULE_SETS: Mapping[str, Mapping[str, Any]] = {
    "tp": _TP_RULES,
    "fsdp_tp": {**_TP_RULES, "embed": DP_AXES},
}


# ---------------------------------------------------------------------- mesh
def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """``jax.make_mesh`` with ``Auto`` axes (see the module docstring)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape))


# ------------------------------------------------------------- axis fitting
def fit_axes(dim: int, axes: Sequence[str], mesh: Mesh) -> tuple:
    """Largest prefix of ``axes`` whose mesh-size product divides ``dim``.

    Trims from the *right* (innermost axis first) so the outer data-parallel
    axis survives longest — a batch of 16 on a (pod=2, data=16) mesh shards
    over ``pod`` alone rather than replicating.
    """
    axes = tuple(a for a in axes if a in mesh.shape)
    while axes and dim > 0:
        total = 1
        for a in axes:
            total *= mesh.shape[a]
        if total > 0 and dim % total == 0:
            return axes
        axes = axes[:-1]
    return ()


def dp_axes(mesh: Mesh, rule_set: str = "fsdp_tp",
            batch: Optional[int] = None) -> tuple:
    """Data-parallel axes of ``mesh``, trimmed so they divide ``batch``.

    Driven by the rule set's ``batch`` mapping, restricted to axes present
    on the mesh.
    """
    if rule_set not in RULE_SETS:
        raise KeyError(f"unknown rule set {rule_set!r}; have {list(RULE_SETS)}")
    ruled = RULE_SETS[rule_set].get("batch") or ()
    if isinstance(ruled, str):
        ruled = (ruled,)
    axes = tuple(a for a in ruled if a in mesh.shape)
    if batch is None:
        return axes
    return fit_axes(batch, axes, mesh)


def tp_axis(mesh: Mesh) -> Optional[str]:
    return TP_AXIS if TP_AXIS in mesh.shape else None


def mesh_rules(mesh: Mesh, rule_set: str) -> dict:
    """RULE_SETS entry restricted to axes that exist on ``mesh``."""
    if rule_set not in RULE_SETS:
        raise KeyError(f"unknown rule set {rule_set!r}; have {list(RULE_SETS)}")
    out = {}
    for name, axes in RULE_SETS[rule_set].items():
        if axes is None:
            out[name] = None
            continue
        if isinstance(axes, str):
            axes = (axes,)
        present = tuple(a for a in axes if a in mesh.shape)
        out[name] = present if present else None
    return out


# ------------------------------------------------------------ public makers
def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh, rule_set: str = "fsdp_tp",
                   batch: Optional[int] = None) -> NamedSharding:
    """Sharding for a batch-leading tensor: dim 0 over the dp axes."""
    axes = dp_axes(mesh, rule_set, batch)
    if not axes:
        return NamedSharding(mesh, P())
    return NamedSharding(mesh, P(axes[0] if len(axes) == 1 else axes))


def _axes_product(axes, mesh: Mesh) -> int:
    total = 1
    for a in axes or ():
        total *= mesh.shape[a]
    return total


def param_shardings(model, mesh: Mesh, rule_set: str = "fsdp_tp",
                    shapes=None):
    """NamedSharding tree for ``model``'s parameters (one leaf per param).

    The ``heads``/``kv_heads`` logical axes usually label FUSED
    ``n_heads * d_head`` projection dims, so plain dim-divisibility is not
    enough: 2 GQA kv heads of d_head=16 give a 32-wide dim that a 4-way model
    axis *can* split — but only by splitting ``d_head`` itself, which breaks
    head-local ops (RoPE's rotate-half permutes within d_head).  When the
    model config is visible, those rules are dropped unless the *head count*
    divides the mapped axes (head-granular fallback to replication).
    """
    if shapes is None:
        shapes = init_shapes(model)
    rules = dict(mesh_rules(mesh, rule_set))
    att = getattr(getattr(model, "cfg", None), "attention", None)
    if att is not None:
        for rule, n in (("heads", getattr(att, "n_heads", None)),
                        ("kv_heads", getattr(att, "n_kv_heads", None))):
            if n and rules.get(rule) and n % _axes_product(rules[rule], mesh):
                rules[rule] = None
    return named_shardings(shapes, model.specs(), rules, mesh)


def opt_shardings(param_sh, opt_shapes, mesh: Mesh):
    """NamedSharding tree for an optimizer state built over ``param_sh``.

    Moment-style states (``{"mu": ..., "nu": ...}`` — AdamW, or ``{"mom"}``
    — SGD) carry one fp32 buffer per parameter and shard EXACTLY like the
    parameter they track (so the update is local everywhere the param is);
    anything unrecognized replicates.
    """
    moment_keys = {"mu", "nu", "mom"}
    if isinstance(opt_shapes, dict) and set(opt_shapes) <= moment_keys:
        return {k: param_sh for k in opt_shapes}
    return jax.tree.map(lambda _: replicated(mesh), opt_shapes)


def train_state_shardings(model, mesh: Mesh, rule_set: str, optimizer,
                          shapes=None):
    """(param_sh, opt_sh, scalar_sh) for the donated train step — the one
    call ``repro.train.loop`` needs to place the whole training state."""
    if shapes is None:
        shapes = init_shapes(model)
    param_sh = param_shardings(model, mesh, rule_set, shapes)
    opt_shapes = jax.eval_shape(optimizer.init, shapes)
    return param_sh, opt_shardings(param_sh, opt_shapes, mesh), \
        replicated(mesh)


# ------------------------------------------------------- cache spec table
# Per-cache-type logical axes, one name (or None) per tensor dim, mirroring
# each NamedTuple's field layout in ``repro.core.kv_cache``.  Resolution:
#
#   * ``"batch"``                     -> the rule set's data-parallel axes;
#   * ``"seq"``                       -> ``model``, only under
#                                        ``seq_sharded`` (the batch==1
#                                        long-context layout);
#   * ``"kv_heads"`` / ``"mosa_heads"`` -> whatever the rule set maps them to
#     (``model`` under ``tp``/``fsdp_tp``) — unlike the *parameter* specs,
#     cache head dims hold the literal head count (never fused with d_head),
#     so plain dim-divisibility is the correct guard here.
#
# This is what lets MoSA's (B, H, k, d) cache shard its HEAD dim over the
# tensor-parallel axis at decode time (head-parallel decode, DESIGN §6): the
# positional heuristic this table replaced could only name "the dim after
# batch", which for MoSA is heads but for dense caches is sequence.
CACHE_AXES: Mapping[type, Mapping[str, tuple]] = {
    _kvc.DenseKVCache: {
        "k": ("batch", "seq", "kv_heads", None),
        "v": ("batch", "seq", "kv_heads", None),
        "length": ("batch",),
    },
    _kvc.WindowKVCache: {
        "k": ("batch", "seq", "kv_heads", None),
        "v": ("batch", "seq", "kv_heads", None),
        "positions": ("batch", "seq"),
        "length": ("batch",),
    },
    _kvc.MLAKVCache: {
        "latent": ("batch", "seq", None),
        "k_rope": ("batch", "seq", None),
        "length": ("batch",),
    },
    _kvc.MoSAKVCache: {
        "k": ("batch", "mosa_heads", None, None),
        "v": ("batch", "mosa_heads", None, None),
        "scores": ("batch", "mosa_heads", None),
        "idx": ("batch", "mosa_heads", None),
        "length": ("batch",),
    },
    _kvc.MoSABlockKVCache: {
        "k": ("batch", "mosa_heads", None, None),
        "v": ("batch", "mosa_heads", None, None),
        "pos": ("batch", "mosa_heads", None),
        "bscore": ("batch", "mosa_heads", None),
        "bidx": ("batch", "mosa_heads", None),
        "bsum": ("batch", "mosa_heads"),
        "length": ("batch",),
    },
}
# The paged cache types of ``repro.serve.paged_kv`` register their entries
# here at import time (``register_cache_axes``) — serve depends on dist,
# never the reverse; any code holding a paged cache instance has necessarily
# imported the module that registered it.


def register_cache_axes(cache_type, table) -> None:
    """Add a cache family's logical-axis table (used by serve.paged_kv)."""
    CACHE_AXES[cache_type] = dict(table)


def cache_spec(cache, mesh: Mesh, rule_set: str = "fsdp_tp",
               seq_sharded: bool = False, stacked: bool = False):
    """PartitionSpec for one typed cache from the ``CACHE_AXES`` table.

    ``cache`` is a KV-cache NamedTuple (arrays or ShapeDtypeStructs);
    ``stacked`` marks layer-stacked ``scan`` caches (every dim shifted right
    by the layer axis, which stays replicated).  Returns a same-type
    NamedTuple of PartitionSpecs.  Divisibility-safe: any dim the mapped
    axes do not divide is replicated; a mesh axis is used at most once per
    tensor (``seq`` wins over heads when ``seq_sharded`` requests both).
    """
    rules = mesh_rules(mesh, rule_set)
    dp = dp_axes(mesh, rule_set)
    tp = tp_axis(mesh)
    table = CACHE_AXES[type(cache)]

    def one_field(leaf, names):
        shape = tuple(getattr(leaf, "shape", ()))
        off = 1 if stacked else 0
        spec = [None] * len(shape)
        used: set = set()
        for i, name in enumerate(names):
            d = off + i
            if name is None or d >= len(shape):
                continue
            dim = shape[d]
            if name == "batch":
                axes = fit_axes(dim, tuple(a for a in dp if a not in used),
                                mesh)
            elif name == "seq":
                axes = (tp,) if (seq_sharded and tp and tp not in used
                                 and dim > 0
                                 and dim % mesh.shape[tp] == 0) else ()
            else:
                axes = rules.get(name) or ()
                if isinstance(axes, str):
                    axes = (axes,)
                axes = tuple(a for a in axes if a not in used)
                if _axes_product(axes, mesh) == 0 or dim == 0 \
                        or dim % _axes_product(axes, mesh):
                    axes = ()
            if axes:
                spec[d] = axes[0] if len(axes) == 1 else axes
                used.update(axes)
        while spec and spec[-1] is None:
            spec.pop()
        return P(*spec)

    return type(cache)(*(one_field(getattr(cache, f), table[f])
                         for f in cache._fields))


def cache_shardings(cache_shapes, mesh: Mesh, rule_set: str = "fsdp_tp",
                    seq_sharded: bool = False):
    """NamedSharding tree for serving caches.

    Typed KV caches (Dense/Window/MLA/MoSA) resolve through the
    ``CACHE_AXES`` spec table — each cache family declares the logical axis
    of every dim.  Under the tp rule sets BOTH MoSA and dense/window caches
    head-shard over ``model`` by default; ``seq_sharded`` makes dense
    caches seq-shard instead (a mesh axis is used at most once per tensor,
    and ``seq`` wins).  Remaining leaves (SSM / xLSTM recurrent states,
    which are plain array pytrees) keep the positional fallback:

      * the batch dim (0; 1 for layer-stacked ``scan`` caches) shards over
        the data-parallel axes;
      * with ``seq_sharded`` the following dim (channels for SSM state)
        shards over ``model``.

    All mappings are divisibility-safe (non-dividing dims replicate).
    """
    dp = dp_axes(mesh, rule_set)
    tp = tp_axis(mesh)

    def is_cache(x):
        return type(x) in CACHE_AXES

    def one(path, leaf):
        stacked = any(getattr(entry, "key", None) == "scan" for entry in path)
        if is_cache(leaf):
            specs = cache_spec(leaf, mesh, rule_set, seq_sharded, stacked)
            return type(leaf)(*(NamedSharding(mesh, s) for s in specs))
        shape = tuple(getattr(leaf, "shape", ()))
        b = 1 if stacked else 0
        spec = [None] * len(shape)
        if len(shape) > b:
            axes = fit_axes(shape[b], dp, mesh)
            if axes:
                spec[b] = axes[0] if len(axes) == 1 else axes
        if seq_sharded and tp is not None and len(shape) > b + 1 \
                and shape[b + 1] % mesh.shape[tp] == 0 and shape[b + 1] > 0:
            spec[b + 1] = tp
        while spec and spec[-1] is None:
            spec.pop()
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(one, cache_shapes,
                                            is_leaf=is_cache)
