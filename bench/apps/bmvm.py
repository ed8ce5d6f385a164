"""Williams' GF(2) block matrix-vector product (arXiv:1508.06823 §VI).

Set-up makes the n x n matrix A and the block of M vectors V from the seed
on the chip and builds the LUT with the program's own ``bmvm.preprocess``,
one square block of A at a time (:func:`build_lut`).  The timed call,
``jax.jit(partial(bmvm.iterate_kernel, cfg=cfg, r=r))`` with the Pallas
``gf2_bmvm`` kernel, applies r more products to the running block: its
output is its next input, as in a block-Wiedemann iteration.

The plain reference (:func:`reference_product`) counts the ones of each
row-by-row AND from the bits of A in int32 and keeps the parity.  It imports
nothing of the program and takes nothing it made.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.apps import bmvm

from bench.work import gf2_bmvm as work

_DOT = (((1,), (1,)), ((), ()))    # v (M, n) . a (n, n) over a's columns
# the side of the blocks of A that ``build_lut`` preprocesses one at a time:
# ``bmvm.preprocess`` keeps a temporary as large as its LUT, which at a LUT
# of more than half the chip's memory would not fit beside it
LUT_BLOCK = 4096


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number, all of its bits used."""
    s = seed % 2 ** 64
    return jax.random.fold_in(jax.random.key(s & 0xFFFFFFFF), s >> 32)


@functools.partial(jax.jit, static_argnums=(1, 2))
def make_inputs(key: jax.Array, n: int, m: int) -> tuple[jax.Array, jax.Array]:
    """A (n, n) and V (m, n), uniform bits as uint8."""
    ka, kv = jax.random.split(key)
    a = jax.random.bits(ka, (n, n), jnp.uint8) & 1
    v = jax.random.bits(kv, (m, n), jnp.uint8) & 1
    return a, v


@functools.partial(jax.jit, static_argnums=(1, 2))
def build_lut(a: jax.Array, k: int, block: int) -> jax.Array:
    """``bmvm.preprocess(a)``, built from the LUTs of the (block, block) blocks
    of ``a``: block (i, j) gives LUT[j-th block of column tiles, :, i-th block
    of row tiles], written in place into the whole LUT."""
    n = a.shape[0]
    nb, bk = n // block, block // k
    cfg = bmvm.BMVMConfig(n=block, k=k)

    def put(t, lut):
        i, j = t // nb, t % nb
        part = bmvm.preprocess(jax.lax.dynamic_slice(a, (i * block, j * block),
                                                     (block, block)), cfg)
        return jax.lax.dynamic_update_slice(lut, part, (j * bk, 0, i * bk))

    lut = jnp.zeros((n // k, 2 ** k, n // k), jnp.uint32)
    return jax.lax.fori_loop(0, nb * nb, put, lut)


@jax.jit
def reference_product(a: jax.Array, v: jax.Array) -> jax.Array:
    """V A^T over GF(2): exact int32 counts of ones, then their parity."""
    counts = jax.lax.dot_general(v.astype(jnp.int8), a.astype(jnp.int8), _DOT,
                                 preferred_element_type=jnp.int32)
    return (counts & 1).astype(jnp.uint8)


@jax.jit
def control_product(a: jax.Array, v: jax.Array) -> jax.Array:
    """The reference one precision step down: float32 counts, exact below
    2^24, rounded to bfloat16's 8 significant bits before the parity, as a
    product written out in bfloat16 would give.  ``reduce_precision`` and not
    a cast, which XLA on the TPU may skip when the result is widened again."""
    counts = jax.lax.dot_general(v.astype(jnp.bfloat16), a.astype(jnp.bfloat16),
                                 _DOT, preferred_element_type=jnp.float32)
    counts = jax.lax.reduce_precision(counts, exponent_bits=8, mantissa_bits=7)
    return (counts.astype(jnp.int32) & 1).astype(jnp.uint8)


@jax.jit
def wrong_bits(x: jax.Array, y: jax.Array) -> jax.Array:
    return jnp.sum(x != y, dtype=jnp.int32)


class App:
    """One BMVM cell on one chip: ``step(v) -> v`` is the timed call."""

    def __init__(self, config: dict, traffic: dict, seed: int, devices: list, peaks):
        n, k, m, r = config["n"], config["k"], traffic["batch"], traffic["r"]
        if len(devices) != 1:
            raise ValueError(f"the bmvm family runs on one chip, not {len(devices)}")
        self.items_per_call = m * r
        self.a, self.v0 = make_inputs(seed_key(seed), n, m)
        cfg = bmvm.BMVMConfig(n=n, k=k)
        lut = build_lut(self.a, k, math.gcd(n, LUT_BLOCK))
        run = jax.jit(functools.partial(bmvm.iterate_kernel, cfg=cfg, r=r))
        self.step = functools.partial(run, lut)
        self.work = {"min_bytes": work.min_bytes(n, k, m) * r,
                     "step_min_s": (work.min_step_seconds(n, k, m, 1, peaks) * r
                                    if peaks else None)}
        self._r = r

    def warm(self) -> None:
        """Run the steady-state call on inputs of the window's placement."""
        self.step(self.step(self.v0)).block_until_ready()

    def free(self) -> None:
        """Drop the program's state; keep A and V for the reference."""
        self.step = None

    def expected(self, answers: dict[int, jax.Array]):
        """Compare answer t (after t + 1 calls) with A^(r (t + 1)) V.

        Yields (t, wrong bits) in order of t."""
        v = self.v0
        t_done = -1
        for t in sorted(answers):
            for _ in range((t - t_done) * self._r):
                v = reference_product(self.a, v)
            t_done = t
            yield t, int(wrong_bits(v, answers[t]))
