"""Model operations and least bytes of the serving programs, from shapes.

Operations follow the paper's accounting (App. A, as in the repo's
``core/flops.py``; copied so the benchmark owns its yardstick): per dense
head ``8 h h' T + 4 h' T^2``, per MoSA head ``8 h h' k + 4 h' k^2 + 2 h T +
h' k`` with ``k = T / sparsity``, per FFN ``4 h d_ff T``; the LM head adds
``2 h V`` for each token whose logits a program returns.  ``shape`` is the
``shape`` block of a configuration file.
"""

from __future__ import annotations


def _k(T, s):
    return max(min(T // s["sparsity"], T), min(s["min_k"], T))


def sequence_flops(s: dict, T: int) -> float:
    """Forward operations of all layers over a whole sequence of T tokens."""
    if T <= 0:
        return 0.0
    h, hp, L = s["d_model"], s["d_head"], s["n_layers"]
    dense = 8 * h * hp * T + 4 * hp * T * T
    k = _k(T, s)
    mosa = 8 * h * hp * k + 4 * hp * k * k + 2 * h * T + hp * k
    ffn = 4 * h * s["d_ff"] * T
    return float(L * (s["n_dense_heads"] * dense + s["n_mosa_heads"] * mosa
                      + ffn))


def prefill_flops(s: dict, past: int, take: int) -> float:
    """Operations of a prompt segment: the sequence's operations up to
    ``past + take`` less those up to ``past``, plus one token's LM head."""
    return (sequence_flops(s, past + take) - sequence_flops(s, past)
            + 2.0 * s["d_model"] * s["vocab"])


def decode_flops(s: dict, t: int) -> float:
    """One decode token at position ``t`` (it attends ``t + 1`` keys)."""
    h, hp, L = s["d_model"], s["d_head"], s["n_layers"]
    dense = 8 * h * hp + 4 * hp * (t + 1)
    mosa = 8 * h * hp + 4 * hp * min(t + 1, s["mosa_capacity"]) + 2 * h + hp
    ffn = 4 * h * s["d_ff"]
    return float(L * (s["n_dense_heads"] * dense + s["n_mosa_heads"] * mosa
                      + ffn) + 2 * h * s["vocab"])


def row_kv_bytes(s: dict, t: int) -> float:
    """Cache bytes a decode step at position ``t`` must read for its row:
    dense keys and values of ``t + 1`` tokens, and the MoSA heads' stored
    keys, values, scores and positions (at most ``mosa_capacity``)."""
    b, hp, L = s["param_bytes"], s["d_head"], s["n_layers"]
    dense = (t + 1) * s["n_dense_heads"] * hp * 2 * b
    m = min(t, s["mosa_capacity"])
    mosa = s["n_mosa_heads"] * (m * hp * 2 * b + s["mosa_capacity"] * 8)
    return float(L * (dense + mosa))


def weight_bytes(s: dict, rows: int) -> float:
    """Weights a decode step reads: every parameter once, less the
    embedding rows it does not look up."""
    table = s["vocab"] * s["d_model"]
    return float((s["n_params"] - table + rows * s["d_model"])
                 * s["param_bytes"])


def decode_least_s(s: dict, lengths, peaks: dict) -> float:
    """Least time of one decode step over live rows at ``lengths``: the
    larger of operations over peak FLOP/s and bytes over HBM bandwidth."""
    flops = sum(decode_flops(s, t) for t in lengths)
    nbytes = weight_bytes(s, len(lengths)) + sum(row_kv_bytes(s, t)
                                                 for t in lengths)
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
