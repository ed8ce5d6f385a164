"""Device milliseconds per call in the app's pack and unpack of the vector
words (scopes ``bmvm.pack`` and ``bmvm.unpack``), mean over the chips."""
from bench.metrics._scopes import per_call_ms


def read(ctx):
    return per_call_ms(ctx, lambda s: s in ("bmvm.pack", "bmvm.unpack"))
