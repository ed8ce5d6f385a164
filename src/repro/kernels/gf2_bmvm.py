"""Pallas TPU kernel for Williams' sub-quadratic GF(2) BMVM (paper §VI).

FPGA→TPU adaptation: the paper maps the precomputed LUTs to BRAM and
XOR-accumulates incoming k-bit flits at each processing node.  Here the
packed sub-vector words ``v[m, c]`` (the "partition index" flits, read from
SMEM) select one of the 2^k LUT rows per vector and column tile, and the
XOR accumulation happens in VMEM — the VPU-resident restatement of the
BRAM-lookup + XOR-tree datapath.

Layout: LUT (C, 2^k, R) uint32, R on the lanes (a multiple of 128 at real
sizes); the 2^k axis is the sublane axis.  In HBM the LUT is tiled (8, 128)
over (2^k, R), so the least unit a DMA can move out of one column tile is an
aligned group of 8 rows: Mosaic refuses ``lut.at[c, pl.ds(row, 1), :]``
("Slice shape along dimension 1 must be aligned to tiling (8)") and accepts
``lut.at[c, pl.ds(row // 8 * 8, 8), :]``, one contiguous 32·R-byte block.

Two paths move the LUT; :func:`plan` picks one from the shapes.

Gather (few vectors): the LUT stays in HBM.  The index words, flattened to
(M·C,) int32, are scalar-prefetched into SMEM; for each vector m and column
tile c the kernel DMAs the group of 8 rows that holds row ``v[m, c]`` into a
VMEM ring of :data:`RING` slots, with ``RING - 1`` DMAs in flight while it
XORs row ``v[m, c] % 8`` of the landed slot into a register accumulator,
stored once per vector into the resident (M, R) output.  It reads M·C
groups, 32·R bytes each: at M=1 eight times the least bytes, one row a
column tile, because of the tiling above.

Stream (many vectors): every LUT byte leaves HBM once per call.  The grid is
``(R // rt, C // ct)`` and has no axis over the vectors: the output block
``(Mp, rt)`` — all Mp vectors — stays resident in VMEM across the C axis
(reduction pattern, zeroed at its first step), and each LUT block
``(ct, 2^k, rt)`` is applied to every vector while it sits in VMEM.  Inside a
step, each vector's output row is loaded once, XORed with its ``ct``
selected LUT rows and stored once.  The index words arrive transposed, as a
``(C // ct, ct, Mp)`` int32 array of which each step takes one
``(1, ct, Mp)`` SMEM block: the whole (Mp, C) array can exceed SMEM's 1 MiB
(Mp=128 at C=3072 is 1.5 MiB).

Plan (:func:`plan`): a pure function of (M, C, 2^k, R).  The gather moves
M·C groups of 8 rows and the stream C·2^k/8; the gather is taken when its
groups are at most :data:`GATHER_SHARE` of the stream's, its index words fit
:data:`SMEM_BUDGET`, 2^k is a multiple of 8 and its ring and output fit
:data:`VMEM_BUDGET`.  On the stream, ``rt = R`` when the resident output and
the double-buffered LUT block fit the budget, else the largest multiple of
128 dividing R that does; then the largest ``ct`` (at most :data:`MAX_CT`)
dividing C that still fits.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..telemetry.metrics import get_registry

# VMEM the tile plan may fill, with room below Mosaic's 16 MiB scoped
# default: on the stream the resident output block plus the double-buffered
# LUT block, on the gather the ring plus the output block
VMEM_BUDGET = 14 * 2 ** 20
# column tiles a stream grid step XORs into each output row (unrolled)
MAX_CT = 4
# the gather is taken while its row groups (M·C) are at most this share of
# the stream's (C·2^k/8).  Chip sweep of the kernel alone at the benchmark's
# (3072, 256, 3072) LUT, TPU v5e: the gather costs 0.130–0.136 µs a group
# from M=1 to M=32 and ties the stream's 12.79 ms at M=32, share 1; 0.75
# keeps a quarter of margin for LUTs whose smaller groups cost more a byte
GATHER_SHARE = 0.75
# row-group slots of the gather's VMEM ring; RING - 1 DMAs are in flight.
# Same sweep: 4 slots are 12% slower than 8 at M=1 and M=8, 16 no faster
RING = 8
# bytes of index words the gather may scalar-prefetch into SMEM
SMEM_BUDGET = 512 * 2 ** 10
_LANE = 128
_GROUP = 8        # LUT rows in one (8, 128) tile: the least DMA along 2^k


@dataclasses.dataclass(frozen=True)
class TilePlan:
    path: str         # "gather" (row groups the words select) or "stream"
    mp: int           # output rows: M on the gather, M padded to 8 on the stream
    ct: int           # column tiles per stream grid step (1 on the gather)
    rt: int           # output words per stream grid step: R or a multiple of 128
    vmem_bytes: int   # the output block plus the LUT blocks or the ring
    lut_passes: int   # times the whole LUT is read from HBM per call
    row_groups: int   # 8-row groups DMA'd per call on the gather (0 on the stream)
    lut_bytes: int    # LUT bytes read from HBM per call


def _vmem_bytes(mp: int, ct: int, p: int, rt: int, r: int) -> int:
    # the output block is double-buffered only when the grid moves it
    return 4 * (mp * rt * (1 if rt == r else 2) + 2 * ct * p * rt)


def plan(m: int, c: int, p: int, r: int) -> TilePlan:
    """The path and tiles of the (M, C) x (C, P, R) product."""
    groups = m * c
    gather_vmem = 4 * r * (RING * _GROUP + m)
    if (p % _GROUP == 0 and groups <= GATHER_SHARE * c * p / _GROUP
            and 4 * groups <= SMEM_BUDGET and gather_vmem <= VMEM_BUDGET):
        return TilePlan("gather", m, 1, r, gather_vmem, lut_passes=0,
                        row_groups=groups, lut_bytes=groups * _GROUP * r * 4)
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
    return TilePlan("stream", mp, ct, rt, _vmem_bytes(mp, ct, p, rt, r),
                    lut_passes=1, row_groups=0, lut_bytes=c * p * r * 4)


def _publish(tp: TilePlan) -> None:
    """The plan's LUT traffic, into the opt-in registry (at trace time)."""
    reg = get_registry()
    if reg is not None:
        reg.gauge("kernels.gf2_bmvm.lut_passes").set(tp.lut_passes)
        reg.gauge("kernels.gf2_bmvm.lut_bytes").set(tp.lut_bytes)
        reg.gauge("kernels.gf2_bmvm.row_groups").set(tp.row_groups)


def _stream_kernel(v_ref, lut_ref, out_ref, *, ct: int):
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


def _gather_kernel(w_ref, lut_hbm, out_ref, ring, sems, *, m: int, c: int):
    # w_ref: (M·C,) words in SMEM, v[m, c] at i = m·C + c; lut_hbm: the LUT
    # in HBM; ring: (RING, 8, R) VMEM slots, group i lands in slot i % RING
    n = m * c

    def copy(i):
        w = w_ref[i]
        slot = jax.lax.rem(i, RING)
        group = pl.multiple_of(w - jax.lax.rem(w, _GROUP), _GROUP)
        return pltpu.make_async_copy(
            lut_hbm.at[jax.lax.rem(i, c), pl.ds(group, _GROUP), :],
            ring.at[slot], sems.at[slot])

    for i in range(min(RING - 1, n)):
        copy(i).start()

    def vector(dm, carry):
        def tile(dc, acc):
            i = dm * c + dc

            # into the slot that group i - 1 left, read in the step before
            @pl.when(i + RING - 1 < n)
            def _next():
                copy(i + RING - 1).start()

            copy(i).wait()
            row = ring[jax.lax.rem(i, RING), pl.ds(jax.lax.rem(w_ref[i], _GROUP), 1), :]
            return jnp.bitwise_xor(acc, row)

        out_ref[pl.ds(dm, 1), :] = jax.lax.fori_loop(
            0, c, tile, jnp.zeros((1, out_ref.shape[1]), jnp.uint32))
        return carry

    jax.lax.fori_loop(0, m, vector, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gf2_bmvm_pallas(lut: jax.Array, v_words: jax.Array, *,
                    interpret: bool = False) -> jax.Array:
    """lut: (C, P=2^k, R) uint32;  v_words: (M, C) uint32 -> (M, R) uint32."""
    C, P, R = lut.shape
    M = v_words.shape[0]
    assert v_words.shape == (M, C)
    tp = plan(M, C, P, R)
    _publish(tp)
    if tp.path == "gather":
        return pl.pallas_call(
            functools.partial(_gather_kernel, m=M, c=C),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
                scratch_shapes=[pltpu.VMEM((RING, _GROUP, R), jnp.uint32),
                                pltpu.SemaphoreType.DMA((RING,))]),
            out_shape=jax.ShapeDtypeStruct((M, R), jnp.uint32),
            interpret=interpret,
            name="gf2_bmvm",
        )(v_words.astype(jnp.int32).reshape(M * C), lut)
    v_t = jnp.pad(v_words.astype(jnp.int32).T, ((0, 0), (0, tp.mp - M)))
    v_t = v_t.reshape(C // tp.ct, tp.ct, tp.mp)
    out = pl.pallas_call(
        functools.partial(_stream_kernel, ct=tp.ct),
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
