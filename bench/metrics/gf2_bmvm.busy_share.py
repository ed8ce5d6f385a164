"""The gf2_bmvm kernel's device time over the device-busy time."""
from bench.metrics._kernels import is_gf2_bmvm


def read(ctx):
    if ctx.trace is None:
        return None
    tr = ctx.trace
    t = sum(e.dur for c in tr.chips for e in tr.ops(c, is_gf2_bmvm))
    if not t:
        return None
    return 100.0 * t / (tr.busy_s() * len(tr.chips))
