"""The held experts' share of their HBM roofline.

Least bytes of the held experts a call (``bench/work/lm.py``: the gate, up
and down weights of each held expert that the call's tokens are routed to,
in expectation under uniform routing) over the HBM peak, times the calls,
over the device time in scope ``moe.experts``.
"""
from bench.metrics._layers import seconds


def read(ctx):
    s = seconds(ctx, lambda name: name == "moe.experts")
    if not s or ctx.peaks is None or "experts_min_bytes" not in ctx.work:
        return None
    return 100.0 * ctx.calls * ctx.work["experts_min_bytes"] / ctx.peaks["hbm_bytes_per_s"] / s
