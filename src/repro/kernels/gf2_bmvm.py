"""Pallas TPU kernel for Williams' sub-quadratic GF(2) BMVM (paper §VI).

FPGA→TPU adaptation: the paper maps the precomputed LUTs to BRAM and
XOR-accumulates incoming k-bit flits at each processing node.  Here each grid
step streams a slab of ``ct`` column tiles' LUTs HBM→VMEM, the packed
sub-vector words ``v[m, c]`` (read from SMEM — the "partition index" flits)
select one of the 2^k LUT rows per vector and column tile, and the XOR
accumulation happens in a VMEM-resident output block — the VPU-resident
restatement of the BRAM-lookup + XOR-tree datapath.

Layout: LUT (C, 2^k, R) uint32, R on the lanes (a multiple of 128 at real
sizes); the 2^k axis is the sublane axis.

Loop order: every LUT byte leaves HBM once per call.  The grid is
``(R // rt, C // ct)`` and has no axis over the vectors: the output block
``(Mp, rt)`` — all Mp vectors — stays resident in VMEM across the C axis
(reduction pattern, zeroed at its first step), and each LUT block
``(ct, 2^k, rt)`` is applied to every vector while it sits in VMEM.  Inside a
step, each vector's output row is loaded once, XORed with its ``ct``
selected LUT rows and stored once, so the accumulator's VMEM traffic is
amortised over ``ct`` column tiles.

Index words: the kernel reads ``v[m, c]`` for all Mp vectors at one column
tile, so the words arrive transposed, as a ``(C // ct, ct, Mp)`` int32 array
of which each step takes one ``(1, ct, Mp)`` SMEM block.  An SMEM block's
last two dims must be divisible by (8, 128) or equal to the array's, and the
whole (Mp, C) array can exceed SMEM's 1 MiB (Mp=128 at C=3072 is 1.5 MiB).

Tile plan (:func:`plan`): a pure function of (M, C, 2^k, R) under a fixed
VMEM budget below the 16 MiB scoped default.  ``rt = R`` when the resident
output and the double-buffered LUT block fit, else the largest multiple of
128 dividing R that does; then the largest ``ct`` (at most
:data:`MAX_CT`) dividing C that still fits.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..telemetry.metrics import get_registry

# VMEM the tile plan may fill: the resident output block plus the
# double-buffered LUT block, with room below Mosaic's 16 MiB scoped default
VMEM_BUDGET = 14 * 2 ** 20
# column tiles a grid step XORs into each output row (unrolled in the body)
MAX_CT = 4
_LANE = 128


@dataclasses.dataclass(frozen=True)
class TilePlan:
    mp: int           # vectors padded to a multiple of 8 (sublanes)
    ct: int           # column tiles per grid step
    rt: int           # output words per grid step: R or a multiple of 128
    vmem_bytes: int   # resident output block + double-buffered LUT block
    lut_passes: int   # times the whole LUT is read from HBM per call


def _vmem_bytes(mp: int, ct: int, p: int, rt: int, r: int) -> int:
    # the output block is double-buffered only when the grid moves it
    return 4 * (mp * rt * (1 if rt == r else 2) + 2 * ct * p * rt)


def plan(m: int, c: int, p: int, r: int) -> TilePlan:
    """Tiles of the (M, C) x (C, P, R) product; the LUT is read once."""
    mp = -(-m // 8) * 8
    rts = [r] + ([t for t in range(r - _LANE, 0, -_LANE) if r % t == 0]
                 if r % _LANE == 0 else [])

    def fits(ct: int, rt: int) -> bool:
        return _vmem_bytes(mp, ct, p, rt, r) <= VMEM_BUDGET

    rt = next((t for t in rts if fits(1, t)), None)
    if rt is None:
        raise ValueError(f"{m} vectors of {r} words do not fit in "
                         f"{VMEM_BUDGET} bytes of VMEM")
    ct = max(t for t in range(1, min(c, MAX_CT) + 1) if c % t == 0 and fits(t, rt))
    return TilePlan(mp, ct, rt, _vmem_bytes(mp, ct, p, rt, r), lut_passes=1)


def _publish(tp: TilePlan, c: int, p: int, r: int) -> None:
    """The plan's LUT traffic, into the opt-in registry (at trace time)."""
    reg = get_registry()
    if reg is not None:
        reg.gauge("kernels.gf2_bmvm.lut_passes").set(tp.lut_passes)
        reg.gauge("kernels.gf2_bmvm.lut_bytes").set(tp.lut_passes * c * p * r * 4)


def _kernel(v_ref, lut_ref, out_ref, *, ct: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # v_ref block: (1, ct, Mp) partition indices; lut_ref block: (ct, 2^k, rt).
    # Each vector's row is loaded once, XORed with the ct rows its words
    # select (the flit "partition index" v[m, c]) and stored once.
    def row(dm, carry):
        acc = out_ref[pl.ds(dm, 1), :]
        for t in range(ct):
            acc = jnp.bitwise_xor(acc, lut_ref[t, pl.ds(v_ref[0, t, dm], 1), :])
        out_ref[pl.ds(dm, 1), :] = acc
        return carry

    jax.lax.fori_loop(0, out_ref.shape[0], row, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gf2_bmvm_pallas(lut: jax.Array, v_words: jax.Array, *,
                    interpret: bool = False) -> jax.Array:
    """lut: (C, P=2^k, R) uint32;  v_words: (M, C) uint32 -> (M, R) uint32."""
    C, P, R = lut.shape
    M = v_words.shape[0]
    assert v_words.shape == (M, C)
    tp = plan(M, C, P, R)
    _publish(tp, C, P, R)
    v_t = jnp.pad(v_words.astype(jnp.int32).T, ((0, 0), (0, tp.mp - M)))
    v_t = v_t.reshape(C // tp.ct, tp.ct, tp.mp)
    out = pl.pallas_call(
        functools.partial(_kernel, ct=tp.ct),
        grid=(R // tp.rt, C // tp.ct),
        in_specs=[pl.BlockSpec((1, tp.ct, tp.mp), lambda r, c: (c, 0, 0),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec((tp.ct, P, tp.rt), lambda r, c: (c, 0, r))],
        out_specs=pl.BlockSpec((tp.mp, tp.rt), lambda r, c: (0, r)),
        out_shape=jax.ShapeDtypeStruct((tp.mp, R), jnp.uint32),
        interpret=interpret,
        name="gf2_bmvm",
    )(v_t, lut)
    return out[:M]
