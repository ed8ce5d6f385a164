"""Device milliseconds per call in the held-experts MoE layers (scopes
``moe.router``, ``moe.experts``, ``moe.combine``), mean over the chips."""
from bench.metrics._layers import per_call_ms


def read(ctx):
    return per_call_ms(ctx, lambda s: s.startswith("moe."))
