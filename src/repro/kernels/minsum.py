"""Pallas TPU kernel: LDPC min-sum check-node update (paper §IV, Fig. 7).

The FPGA check node is a compare tree over the incoming bit-node messages.
On TPU the natural unit is a *block of check nodes*: block (BC, deg) of LLRs
in VMEM, two-min trick computed with VPU reductions along the lane axis, all
checks in the block updated in one shot.  Grid = (n_checks / BC,).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(u_ref, out_ref):
    u = u_ref[...]
    mag = jnp.abs(u)
    neg = u < 0
    sgn = jnp.where(neg, -1.0, 1.0).astype(u.dtype)
    # product of the ±1 signs as the parity of the negative count: Mosaic has
    # no reduce_prod lowering, and the result is exact either way
    n_neg = jnp.sum(neg.astype(jnp.int32), axis=-1, keepdims=True)
    total_sign = jnp.where((n_neg & 1) == 1, -1.0, 1.0).astype(u.dtype)
    min1 = jnp.min(mag, axis=-1, keepdims=True)
    amin = jnp.argmin(mag, axis=-1)
    is_min = jax.lax.broadcasted_iota(jnp.int32, mag.shape, 1) == amin[:, None]
    min2 = jnp.min(jnp.where(is_min, jnp.inf, mag), axis=-1, keepdims=True)
    mins = jnp.where(is_min, min2, min1)
    out_ref[...] = (total_sign * sgn) * mins


@functools.partial(jax.jit, static_argnames=("bc", "interpret"))
def minsum_check_pallas(u: jax.Array, *, bc: int = 256, interpret: bool = False) -> jax.Array:
    """u: (n_checks, deg) f32 -> (n_checks, deg) check-to-bit messages."""
    n, deg = u.shape
    bc = min(bc, n)
    pad = (-n) % bc
    if pad:
        u = jnp.concatenate([u, jnp.ones((pad, deg), u.dtype)])
    out = pl.pallas_call(
        _kernel,
        grid=((n + pad) // bc,),
        in_specs=[pl.BlockSpec((bc, deg), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bc, deg), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n + pad, deg), u.dtype),
        interpret=interpret,
    )(u)
    return out[:n]
