"""Device milliseconds per call in the Mamba mixers (scope ``mamba.mixer``),
mean over the chips."""
from bench.metrics._layers import per_call_ms


def read(ctx):
    return per_call_ms(ctx, lambda s: s == "mamba.mixer")
