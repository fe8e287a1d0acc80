"""The cost functions against hand counts and the repo's FLOP accounting."""

from bench import harness

MOSA8 = harness.load_json(harness.ROOT / "bench" / "configs" /
                          "mosa-paper-medium-mosa8.json")["shape"]
DENSE = harness.load_json(harness.ROOT / "bench" / "configs" /
                          "mosa-paper-medium-dense.json")["shape"]
MS = harness.cost("model_step")
PD = harness.cost("paged_decode")


def test_sequence_flops_reproduce_the_papers_table_4():
    from repro.core.flops import PAPER_MODELS
    medium = PAPER_MODELS["medium"]
    assert MS.sequence_flops(DENSE, 1024) == medium.dense_flops(1024)
    # 439.70 GFLOP per 1024 tokens: twice the small model's 219.85 (the
    # paper prints 430.70 for medium; the repo's tests note the misprint)
    assert abs(MS.sequence_flops(DENSE, 1024) / 1e9 - 439.70) < 0.01


def test_mosa_heads_agree_with_core_flops():
    from repro.core.flops import flops_dense_head, flops_ffn, flops_mosa_head
    for T in (64, 300, 1024):
        k = max(T // 8, 2)
        layer = (4 * flops_dense_head(T, 1024, 64)
                 + 54 * flops_mosa_head(T, k, 1024, 64)
                 + flops_ffn(T, 1024, 4096))
        assert MS.sequence_flops(MOSA8, T) == 18 * layer


def test_hybrid_is_flop_matched_to_dense():
    # the paper's IsoFLOP design: the hybrid costs no more than the baseline
    assert MS.sequence_flops(MOSA8, 1024) <= MS.sequence_flops(DENSE, 1024)


def test_prefill_segments_add_up_to_the_prompt():
    whole = MS.prefill_flops(MOSA8, 0, 700)
    parts = MS.prefill_flops(MOSA8, 0, 512) + MS.prefill_flops(MOSA8, 512, 188)
    lm = 2.0 * 1024 * 8000
    assert abs((parts - lm) - whole) < 1e-6 * whole


def test_paged_decode_bytes_are_the_live_blocks():
    s = MOSA8
    per_block = 16 * 4 * 64 * 2 * 2          # tokens x heads x d x (k,v) x 2 B
    qo = 4 * 64 * 2 * 2
    # positions 0, 15, 16, 100: 1, 1, 2 and 7 blocks
    assert PD.call_bytes(s, [0, 15, 16, 100], 16) == 11 * per_block + 4 * qo
    assert PD.call_flops(s, [9]) == 4 * 4 * 64 * 10
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    assert PD.call_least_s(s, [100], 16, peaks) == \
        PD.call_bytes(s, [100], 16) / 819e9


def test_decode_step_reads_weights_and_live_rows():
    s = MOSA8
    w = MS.weight_bytes(s, 64)
    assert w == (s["n_params"] - 8000 * 1024 + 64 * 1024) * 2
    # a row at position 200 holds all 128 stored MoSA tokens per head
    kv = MS.row_kv_bytes(s, 200)
    assert kv == 18 * (201 * 4 * 64 * 4 + 54 * (128 * 64 * 4 + 128 * 8))
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    least = MS.decode_least_s(s, [200] * 64, peaks)
    assert least == (w + 64 * kv) / 819e9          # bound by bytes
