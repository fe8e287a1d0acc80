"""Pallas kernel package: fused MoSA attention (fwd + custom-VJP bwd) and
flash attention, with pure-jnp oracles in ``ref``.

Exports resolve lazily (PEP 562, the ``repro.serve`` pattern): importing
``repro.core`` — whose MoSA layer only *conditionally* dispatches here under
``impl="pallas"`` — must never pull ``jax.experimental.pallas`` eagerly.
Leaf modules stay importable directly (``repro.kernels.ops`` etc.).
"""

_EXPORTS = {
    "mosa_attention": "ops",
    "flash_attention": "ops",
    "mosa_attention_pallas": "mosa_attention",
    "mosa_attention_fwd_res": "mosa_attention",
    "mosa_attention_bwd_pallas": "mosa_backward",
    "mosa_attention_trainable": "mosa_vjp",
    "mosa_block_attention": "ops",
    "flash_attention_pallas": "flash_attention",
    "mosa_attention_ref": "ref",
    "mosa_block_attention_ref": "ref",
    "flash_attention_ref": "ref",
}

__all__ = list(_EXPORTS) + ["interpret_default"]


def interpret_default() -> bool:
    """The one platform switch for every Pallas kernel in the repo: kernels
    lower natively on a TPU backend and run through the Pallas interpreter
    (or, for the paged serving kernels, the gather reference) elsewhere."""
    import jax
    return jax.default_backend() != "tpu"


def __getattr__(name):
    if name in _EXPORTS:
        import importlib
        mod = importlib.import_module(f"repro.kernels.{_EXPORTS[name]}")
        return getattr(mod, name)
    raise AttributeError(f"module 'repro.kernels' has no attribute {name!r}")
