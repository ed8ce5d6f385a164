"""Pallas TPU kernel: blocked flash attention forward (LM-stack hot spot).

This is the perf-critical compute layer of the LM generalization (prefill /
training attention).  Online-softmax over KV blocks: grid = (B, Hq,
q_blocks, kv_blocks) with the kv axis innermost; running max/denominator and
the output accumulator live in VMEM scratch and the output block is written
on the last kv step.  GQA is handled in the BlockSpec index maps (q head h
reads kv head h // group).  Block shapes default to MXU-aligned (128, 128).

Backward runs through the jnp reference (``ops.flash_attention`` wires a
custom_vjp whose bwd differentiates ref.mha) — training on TPU would swap in
a dedicated bwd kernel; serving only needs this forward.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, bq: int, bkv: int, seq_q: int, seq_kv: int):
    qb = pl.program_id(2)
    tb = pl.program_id(3)
    n_tb = pl.num_programs(3)

    @pl.when(tb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                 # (BQ, D)
    k = k_ref[0, 0].astype(jnp.float32)                 # (BKV, D)
    v = v_ref[0, 0].astype(jnp.float32)                 # (BKV, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale  # (BQ, BKV)
    q_ids = qb * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    t_ids = tb * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    mask = t_ids < seq_kv
    if causal:
        # decode-style offset: query i attends to kv positions <= i + (T - S)
        mask &= t_ids <= q_ids + (seq_kv - seq_q)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                                  # (BQ, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(tb == n_tb - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bkv", "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, bq: int = 128, bkv: int = 128,
                           interpret: bool = False) -> jax.Array:
    """q: (B, Hq, S, D); k/v: (B, Hkv, T, D), Hq % Hkv == 0 -> (B, Hq, S, D)."""
    B, Hq, S, D = q.shape
    _, Hkv, T, _ = k.shape
    assert Hq % Hkv == 0
    g = Hq // Hkv
    bq = min(bq, S)
    bkv = min(bkv, T)
    pad_q = (-S) % bq
    pad_t = (-T) % bkv
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad_t), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad_t), (0, 0)))
    grid = (B, Hq, (S + pad_q) // bq, (T + pad_t) // bkv)
    scale = D ** -0.5
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, bq=bq, bkv=bkv,
                          seq_q=S, seq_kv=T),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, t: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bkv, D), lambda b, h, i, t: (b, h // g, t, 0)),
            pl.BlockSpec((1, 1, bkv, D), lambda b, h, i, t: (b, h // g, t, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, t: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, S + pad_q, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :, :S, :]
