"""Open-loop serving: requests arrive at the mix's fixed rate, whether or
not the server keeps up; the run drains after the window (see
``bench/serving.py``)."""

from bench import serving


def run(ctx):
    return serving.run(ctx, closed=False)
