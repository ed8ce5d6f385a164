"""The whole step's share of the chips' roofline.

The least time of one call (``work.step_min_s``: the larger of its least
HBM bytes and its least interconnect bytes over the chips' peaks) times the
calls made, over the window's seconds.
"""


def read(ctx):
    if ctx.work.get("step_min_s") is None:
        return None
    return 100.0 * ctx.calls * ctx.work["step_min_s"] / ctx.window_s
