"""Closed-loop serving: the mix's ``clients`` each keep one request
outstanding; the window opens on a full batch (see ``bench/serving.py``)."""

from bench import serving


def run(ctx):
    return serving.run(ctx, closed=True)
