"""Pallas TPU kernel for Williams' sub-quadratic GF(2) BMVM (paper §VI).

FPGA→TPU adaptation: the paper maps the precomputed LUTs to BRAM and
XOR-accumulates incoming k-bit flits at each processing node.  Here each grid
step c streams one column-tile's LUT slab HBM→VMEM, the packed sub-vector
word ``v[m, c]`` (read from SMEM — the "partition index" flit) selects one of
the 2^k LUT rows, and the XOR accumulation happens in the
revisited VMEM output block — the VPU-resident restatement of the BRAM-lookup
+ XOR-tree datapath.

Layout: LUT (C, 2^k, R) uint32, R padded to a multiple of 128 (lane dim);
the 2^k axis is the sublane axis.  Grid = (M_blocks, C); output block
(BM, R) is revisited across the C axis (reduction pattern).  The index words
arrive as one (BM, C) SMEM block per M block, not as a scalar prefetch of the
whole (M, C) array: SMEM holds 1 MiB, which M=1024 vectors at C=512 exceed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(v_ref, lut_ref, out_ref, *, bm: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # lut_ref block: (1, 2^k, R); select the partition row per batch element
    # (the flit "partition index" v[m, c]) and XOR into the accumulator.
    for dm in range(bm):  # bm is small & static; unrolled gather over sublanes
        idx = v_ref[dm, c]
        row = lut_ref[0, idx, :]
        out_ref[dm, :] = jnp.bitwise_xor(out_ref[dm, :], row)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def gf2_bmvm_pallas(lut: jax.Array, v_words: jax.Array, *, bm: int = 8,
                    interpret: bool = False) -> jax.Array:
    """lut: (C, P=2^k, R) uint32;  v_words: (M, C) uint32 -> (M, R) uint32."""
    C, P, R = lut.shape
    M = v_words.shape[0]
    assert v_words.shape == (M, C)
    pad_m = (-M) % bm
    if pad_m:
        v_words = jnp.concatenate([v_words, jnp.zeros((pad_m, C), v_words.dtype)])
    Mp = M + pad_m
    grid = (Mp // bm, C)
    out = pl.pallas_call(
        functools.partial(_kernel, bm=bm),
        grid=grid,
        in_specs=[pl.BlockSpec((bm, C), lambda m, c: (m, 0),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec((1, P, R), lambda m, c: (c, 0, 0))],
        out_specs=pl.BlockSpec((bm, R), lambda m, c: (m, 0)),
        out_shape=jax.ShapeDtypeStruct((Mp, R), jnp.uint32),
        interpret=interpret,
    )(v_words.astype(jnp.int32), lut)
    return out[:M]
