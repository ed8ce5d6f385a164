"""Device milliseconds of the kernel named ``gf2_bmvm`` per call, mean over
the chips.

The kernel's ``pallas_call`` is named ``gf2_bmvm``, so its op is
``%gf2_bmvm.<n> = ... custom-call(...)``; a program that leaves the kernel
unnamed has no such op, and the reader then finds nothing.
"""
import re

from bench.metrics._kernels import is_gf2_bmvm

_NAMED = re.compile(r"^%gf2_bmvm(\.\d+)? = ")


def read(ctx):
    if ctx.trace is None:
        return None
    tr = ctx.trace
    t = sum(e.dur for c in tr.chips for e in tr.ops(c, is_gf2_bmvm) if _NAMED.match(e.name))
    if not t:
        return None
    return 1e3 * t / len(tr.chips) / ctx.calls
