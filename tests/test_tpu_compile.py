"""Compile the main-path Pallas kernels for a described TPU v5e chip.

The TPU compiler ships with the installed libtpu and compiles for a chip
that is described, not attached, so these tests catch what interpret mode
cannot — block shapes the Mosaic tiling refuses, VMEM overruns — at the
widths the chip runs: the paper's medium model (4 dense + 54 MoSA heads,
d_head 64, k = 128 of T = 1024), batch 8, paged blocks of 16 tokens.

The topology is described inside a module-scoped fixture, never at
import: only one process may load libtpu at a time, and a test worker that
loaded it while collecting would give the workers different test lists.
Each test asserts that the compiled program holds a native Pallas kernel
(``tpu_custom_call``), not an interpreted emulation.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.dist import hints
from repro.kernels import ops
from repro.serve.paged_attention import (paged_attention_decode,
                                         paged_prefill_attention)
from repro.serve.paged_kv import PagedDenseKVCache

B, H_MOSA, K, D = 8, 54, 128, 64          # batch, MoSA heads, k, d_head
H_DENSE, T, BLOCK, CHUNK = 4, 1024, 16, 512
BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip cannot read back a persistent-cache entry
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _mosa_shapes(sd, H=H_MOSA, S=K):
    x = jax.ShapeDtypeStruct((B, H, S, D), BF16, sharding=sd)
    return (x, x, x, jax.ShapeDtypeStruct((B, H, S), I32, sharding=sd),
            jax.ShapeDtypeStruct((B, H, S), F32, sharding=sd))


def test_mosa_forward_compiles(one_chip):
    def fwd(q, k, v, idx, r):
        return ops.mosa_attention(q, k, v, idx, r, interpret=False)
    assert "tpu_custom_call" in _compiled_text(fwd, *_mosa_shapes(one_chip))


@pytest.mark.parametrize("S", [K, 2 * K, 33])
def test_mosa_forward_backward_compiles(one_chip, S):
    """Fused fwd + both backward kernels under ``jax.grad``; S = 256
    streams two key blocks, S = 33 pads to a 64-row tile."""
    def grads(q, k, v, idx, r):
        def loss(q, k, v, r):
            out = ops.mosa_attention(q, k, v, idx, r, interpret=False)
            return out.astype(F32).sum()
        return jax.grad(loss, argnums=(0, 1, 2, 3))(q, k, v, r)
    text = _compiled_text(grads, *_mosa_shapes(one_chip, S=S))
    assert text.count("tpu_custom_call") >= 3


def test_mosa_forward_backward_compiles_on_2x2_mesh(topo):
    """A Mosaic kernel cannot be split by the SPMD partitioner: under a
    (data, model) mesh the MoSA call must run per (batch, head) shard."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    sh = NamedSharding(mesh, PartitionSpec("data", "model"))
    x = jax.ShapeDtypeStruct((B, H_MOSA, K, D), BF16, sharding=sh)
    idx = jax.ShapeDtypeStruct((B, H_MOSA, K), I32, sharding=sh)
    r = jax.ShapeDtypeStruct((B, H_MOSA, K), F32, sharding=sh)

    def grads(q, k, v, idx, r):
        def loss(q, k, v, r):
            out = ops.mosa_attention(q, k, v, idx, r, interpret=False)
            return out.astype(F32).sum()
        with hints.sharding_hints(mesh=mesh):
            return jax.grad(loss, argnums=(0, 1, 2, 3))(q, k, v, r)
    assert "tpu_custom_call" in _compiled_text(grads, x, x, x, idx, r)


def test_mosa_block_choice_compiles(one_chip):
    q, k, v, _, _ = _mosa_shapes(one_chip)
    nb = K // BLOCK
    bidx = jax.ShapeDtypeStruct((B, H_MOSA, nb), I32, sharding=one_chip)
    rblk = jax.ShapeDtypeStruct((B, H_MOSA, nb), F32, sharding=one_chip)

    def grads(q, k, v, bidx, rblk):
        def loss(q, k, v, rblk):
            out = ops.mosa_block_attention(q, k, v, bidx, rblk,
                                           sel_block_size=BLOCK, T=T,
                                           interpret=False)
            return out.astype(F32).sum()
        return jax.grad(loss, argnums=(0, 1, 2, 3))(q, k, v, rblk)
    assert "tpu_custom_call" in _compiled_text(grads, q, k, v, bidx, rblk)


def test_flash_varlen_compiles(one_chip):
    x = jax.ShapeDtypeStruct((CHUNK, H_DENSE, D), BF16, sharding=one_chip)
    cu = jax.ShapeDtypeStruct((5,), I32, sharding=one_chip)

    def fn(q, k, v, cu):
        return ops.flash_attention_varlen(q, k, v, cu, interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, x, x, x, cu)


def test_flash_compiles(one_chip):
    x = jax.ShapeDtypeStruct((B, H_DENSE, T, D), BF16, sharding=one_chip)

    def fn(q, k, v):
        return ops.flash_attention(q, k, v, interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, x, x, x)


def _paged_cache(sd):
    nb = T // BLOCK
    pool = jax.ShapeDtypeStruct((B * nb, BLOCK, H_DENSE, D), BF16,
                                sharding=sd)
    return PagedDenseKVCache(
        pool, pool, jax.ShapeDtypeStruct((B, nb), I32, sharding=sd),
        jax.ShapeDtypeStruct((B,), I32, sharding=sd))


def test_paged_decode_compiles(one_chip):
    q = jax.ShapeDtypeStruct((B, H_DENSE, D), BF16, sharding=one_chip)

    def fn(q, cache):
        return paged_attention_decode(q, cache, scale=D ** -0.5,
                                      impl="kernel", interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, q, _paged_cache(one_chip))


def test_paged_prefill_compiles(one_chip):
    N = 4
    q = jax.ShapeDtypeStruct((CHUNK, H_DENSE, D), BF16, sharding=one_chip)
    seg = jax.ShapeDtypeStruct((N,), I32, sharding=one_chip)
    cu = jax.ShapeDtypeStruct((N + 1,), I32, sharding=one_chip)

    def fn(q, cache, cu, rows, past):
        return paged_prefill_attention(q, cache, cu, rows, past,
                                       scale=D ** -0.5, impl="kernel",
                                       interpret=False)
    assert "tpu_custom_call" in _compiled_text(
        fn, q, _paged_cache(one_chip), cu, seg, seg)
