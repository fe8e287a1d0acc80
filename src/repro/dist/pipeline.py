"""Layer-stacked pipeline parallelism (GPipe schedule).

Runs on a dedicated mesh whose stage axis is named ``pipe`` (the usual
``pod``/``data``/``model`` convention does not apply here — pipeline meshes
are built separately, e.g. ``make_mesh((4,), ("pipe",))``).

``stack_stage_params`` stacks per-stage parameter pytrees along a leading
stage dim; ``pipeline_forward`` shards that dim over the ``pipe`` axis with
``shard_map`` so each device holds exactly one stage, then runs the classic
GPipe fill/steady/drain schedule: ``n_microbatches + n_stages - 1`` ticks,
activations hopping stage-to-stage via ``collective_permute``.  Stage 0 feeds
microbatch ``t`` at tick ``t``; the last stage emits microbatch ``t-(S-1)``
at tick ``t``; a masked ``psum`` replicates the final outputs (only the last
stage contributes non-zeros).  Everything is differentiable — ``ppermute``
and ``psum`` have exact transposes — so the same schedule serves training.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def stack_stage_params(stage_params: list):
    """Stack a list of per-stage pytrees along a new leading stage dim."""
    if not stage_params:
        raise ValueError("need at least one stage")
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *stage_params)


def pipeline_forward(stage_fn, stage_params, x, *, mesh, n_microbatches: int,
                     axis: str = "pipe"):
    """Apply ``n_stages`` copies of ``stage_fn`` as a pipeline over ``axis``.

    stage_fn:      ``(params, activations) -> activations`` (shape-preserving)
    stage_params:  pytree whose leaves have leading dim == mesh.shape[axis]
                   (see ``stack_stage_params``)
    x:             (B, ...) global batch; B % n_microbatches == 0

    Returns the replicated (B, ...) output, equal to applying the stages
    sequentially.
    """
    if axis not in mesh.shape:
        raise ValueError(f"mesh has no {axis!r} axis: {dict(mesh.shape)}")
    n_stages = mesh.shape[axis]
    leading = {leaf.shape[0] for leaf in jax.tree.leaves(stage_params)}
    if leading != {n_stages}:
        raise ValueError(f"stage_params leading dims {leading} != mesh "
                         f"{axis} size {n_stages}")
    B = x.shape[0]
    if n_microbatches < 1 or B % n_microbatches != 0:
        raise ValueError(f"batch {B} not divisible into {n_microbatches} "
                         "microbatches")
    mb = B // n_microbatches

    @partial(shard_map, mesh=mesh, in_specs=(P(axis), P()), out_specs=P(),
             check_vma=False)
    def run(params, xfull):
        local = jax.tree.map(lambda p: p[0], params)   # this device's stage
        stage = jax.lax.axis_index(axis)
        micro = xfull.reshape((n_microbatches, mb) + xfull.shape[1:])
        buf = jnp.zeros_like(micro[0])
        shift = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        outs = []
        for t in range(n_microbatches + n_stages - 1):
            feed = micro[t] if t < n_microbatches else jnp.zeros_like(buf)
            inp = jnp.where(stage == 0, feed, buf)
            out = stage_fn(local, inp)
            if t >= n_stages - 1:
                outs.append(jnp.where(stage == n_stages - 1, out,
                                      jnp.zeros_like(out)))
            buf = jax.lax.ppermute(out, axis, shift)
        y = jax.lax.psum(jnp.stack(outs), axis)        # non-zero on last stage
        return y.reshape((n_microbatches * mb,) + y.shape[2:])

    return run(stage_params, x)
