"""Operations and least bytes of one call of the paged decode attention
kernel (``repro.serve.paged_attention``, kernel ``_paged_kernel``): one
layer, every live row.  A row at position ``t`` reads the keys and values
of its ``ceil((t + 1) / block)`` blocks (whole blocks: the kernel moves one
block per grid step), and reads q and writes o once."""

from __future__ import annotations


def call_bytes(s: dict, lengths, block: int) -> float:
    b, hp, H = s["param_bytes"], s["d_head"], s["n_dense_heads"]
    kv = sum(-(-(t + 1) // block) * block for t in lengths) * H * hp * 2 * b
    qo = len(lengths) * H * hp * 2 * b
    return float(kv + qo)


def call_flops(s: dict, lengths) -> float:
    hp, H = s["d_head"], s["n_dense_heads"]
    return float(sum(4 * H * hp * (t + 1) for t in lengths))


def call_least_s(s: dict, lengths, block: int, peaks: dict) -> float:
    return max(call_flops(s, lengths) / peaks["bf16_flops"],
               call_bytes(s, lengths, block) / peaks["hbm_bytes_per_s"])
