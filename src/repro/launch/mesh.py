"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set XLA_FLAGS before any
jax initialization.

Mesh axes are ``AxisType.Auto``: the model code places arrays with sharding
*constraints* and lets the partitioner propagate them.  Under the default
``Explicit`` axes, ops that mix sharded operands (the embedding gather over a
vocab-sharded table) demand an ``out_sharding=`` at every call site.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips/pod (TPU v5e pod slice); 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Whatever this host actually has (smoke tests, examples)."""
    n = jax.device_count()
    assert n % model == 0
    return _auto_mesh((n // model, model), ("data", "model"))
