"""The gf2_bmvm kernel's share of its HBM roofline.

Least bytes per call (``bench/work/gf2_bmvm.py``: selected LUT rows, index
words, output words) over the HBM peak, times the calls, over the kernel's
summed device time.  Only the bytes bound applies: the peaks table holds no
VPU int32 rate for the XOR work.
"""
from bench.metrics._kernels import is_gf2_bmvm


def read(ctx):
    if ctx.trace is None:
        return None
    t = sum(e.dur for c in ctx.trace.chips for e in ctx.trace.ops(c, is_gf2_bmvm))
    if not t or ctx.peaks is None:
        return None
    return 100.0 * ctx.calls * ctx.work["min_bytes"] / ctx.peaks["hbm_bytes_per_s"] / t
