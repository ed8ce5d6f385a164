"""Pallas TPU kernel: particle-filter weighted histogram + Bhattacharyya PE
(paper §V, Fig. 11 — the "candidate histogram" + "Bhattacharya distance"
compute element).

FPGA→TPU adaptation: the FPGA PE walks pixels sequentially into BRAM bins.
A serial scatter wastes the VPU/MXU, so the kernel restates binning as a
one-hot matmul: for a pixel block, ``onehot(bins) @ diag(weights)`` summed
over pixels — an (px_block × n_bins) MXU contraction.  Grid =
(particle_blocks, pixel_blocks) with the histogram block revisited across the
pixel axis (reduction), then the Bhattacharyya coefficient reduces the final
histogram against the reference in the same kernel (fused epilogue).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(bins_ref, w_ref, ref_ref, hist_ref, bc_ref, *, n_bins: int, n_px: int, bpx: int):
    p = pl.program_id(1)
    n_px_blocks = pl.num_programs(1)

    @pl.when(p == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    b = bins_ref[...]                                   # (BN, BPX) int32
    w = w_ref[...]                                      # (1, BPX) f32
    onehot = (b[:, :, None] == jax.lax.broadcasted_iota(jnp.int32, (1, 1, n_bins), 2))
    # mask out pixel padding in the last block
    px0 = p * bpx
    valid = (px0 + jax.lax.broadcasted_iota(jnp.int32, (1, b.shape[1], 1), 1)) < n_px
    contrib = jnp.where(onehot & valid, w[0][None, :, None], 0.0)
    hist_ref[...] += jnp.sum(contrib, axis=1)           # (BN, n_bins)

    @pl.when(p == n_px_blocks - 1)
    def _epilogue():
        h = hist_ref[...]
        h = h / jnp.maximum(jnp.sum(h, axis=-1, keepdims=True), 1e-12)
        hist_ref[...] = h
        bc_ref[...] = jnp.sum(jnp.sqrt(h * ref_ref[...]), axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("n_bins", "bn", "bpx", "interpret"))
def particle_histogram_pallas(bins: jax.Array, weights: jax.Array, ref_hist: jax.Array,
                              *, n_bins: int, bn: int = 8, bpx: int = 512,
                              interpret: bool = False):
    """bins: (N, px) int32; weights: (px,); ref_hist: (n_bins,)
    -> (hist (N, n_bins), bc (N,))."""
    N, px = bins.shape
    bn = min(bn, N)
    bpx = min(bpx, px)
    pad_n = (-N) % bn
    pad_p = (-px) % bpx
    bins_p = jnp.pad(bins, ((0, pad_n), (0, pad_p)))
    w_p = jnp.pad(weights, (0, pad_p))[None, :]
    grid = ((N + pad_n) // bn, (px + pad_p) // bpx)
    hist, bc = pl.pallas_call(
        functools.partial(_kernel, n_bins=n_bins, n_px=px, bpx=bpx),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bpx), lambda i, p: (i, p)),
            pl.BlockSpec((1, bpx), lambda i, p: (0, p)),
            pl.BlockSpec((1, n_bins), lambda i, p: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, n_bins), lambda i, p: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, p: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N + pad_n, n_bins), jnp.float32),
            jax.ShapeDtypeStruct((N + pad_n, 1), jnp.float32),
        ],
        interpret=interpret,
    )(bins_p, w_p, ref_hist[None, :].astype(jnp.float32))
    return hist[:N], bc[:N, 0]
