"""Kernels (``repro.serve.paged_attention``, traced as
``paged_attention_kernel``): least time of the paged decode attention calls
in the traced window over their device time, in %.  A call is one layer of
one decode step over every row; its least time is the larger of its
operations over peak FLOP/s and the whole blocks of live keys and values
(plus q and o) over HBM bandwidth (``bench/costs/paged_decode.py``).  The
per-call mean over the decode steps of the window is set against the
per-call mean of the kernel's time."""

from bench import harness, serving

KERNEL = "paged_attention_kernel"


def read(ctx):
    t = ctx.data.get("trace")
    census = serving.decode_census(ctx)
    if not t or not census or t["kernel_calls"].get(KERNEL, 0) == 0:
        return None
    pd = harness.cost("paged_decode", ctx.root)
    s = ctx.data["geometry"]
    block = ctx.data["serve"]["block_size"]
    least, calls = 0.0, 0
    for n, lengths in census:
        for j in range(n):
            least += pd.call_least_s(s, [x + j for x in lengths], block,
                                     ctx.peaks)
            calls += 1
    per_call = t["kernels"][KERNEL] / t["kernel_calls"][KERNEL]
    return 100.0 * (least / calls) / per_call
