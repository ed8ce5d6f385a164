"""Mamba-1 selective SSM block, as Jamba's attention-free mixer has it.

Jamba normalises the three outputs of ``x_proj`` before they enter the
scan: dt (width ``dt_rank``), B and C (width ``d_state``) each pass an
RMSNorm with a learned scale (arXiv:2403.19887 §6; ``modeling_jamba
.JambaMambaMixer``).

TPU adaptation: the CUDA selective-scan kernel fuses a sequential recurrence;
on TPU we use a *chunked* scan — within a chunk the linear recurrence
h_t = a_t·h_{t-1} + b_t is evaluated with ``lax.associative_scan`` (parallel,
VPU/MXU friendly), across chunks a ``lax.scan`` carries the (B, d_inner, N)
state.  Memory per step is O(B·Q·d_inner·N) for chunk Q instead of O(B·S·…)
(the assoc-scan-over-everything variant) or an S-step sequential loop.

Decode is the O(1) recurrent update on (conv_state, ssm_state).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..core.partition import constrain
from .layers import ParamSpec, rms_norm


@dataclasses.dataclass(frozen=True)
class MambaConfig:
    d_model: int
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0     # 0 -> ceil(d/16)
    chunk: int = 256
    unroll: bool = False
    eps: float = 1e-6    # the dt/B/C norms' epsilon (the model's rms_norm_eps)

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def rank(self) -> int:
        return self.dt_rank or -(-self.d_model // 16)


def mamba_specs(c: MambaConfig, dtype=jnp.float32) -> dict:
    d, di, N, R = c.d_model, c.d_inner, c.d_state, c.rank
    return {
        "in_proj": ParamSpec((d, 2 * di), ("embed", "ssm_inner"), dtype),
        "conv_w": ParamSpec((c.d_conv, di), (None, "ssm_inner"), dtype, init="small"),
        "conv_b": ParamSpec((di,), ("ssm_inner",), dtype, init="zeros"),
        "x_proj": ParamSpec((di, R + 2 * N), ("ssm_inner", None), dtype),
        "dt_norm": ParamSpec((R,), (None,), dtype, init="ones"),
        "b_norm": ParamSpec((N,), ("ssm_state",), dtype, init="ones"),
        "c_norm": ParamSpec((N,), ("ssm_state",), dtype, init="ones"),
        "dt_w": ParamSpec((R, di), (None, "ssm_inner"), dtype),
        "dt_b": ParamSpec((di,), ("ssm_inner",), dtype, init="ones", scale=-4.6),  # softplus^-1(~0.01)
        "a_log": ParamSpec((di, N), ("ssm_inner", "ssm_state"), dtype, init="ones"),
        "d_skip": ParamSpec((di,), ("ssm_inner",), dtype, init="ones"),
        "out_proj": ParamSpec((di, d), ("ssm_inner", "embed"), dtype),
    }


def init_mamba_cache(c: MambaConfig, batch: int, dtype=jnp.float32) -> dict:
    return {
        "conv": jnp.zeros((batch, c.d_conv - 1, c.d_inner), dtype),
        "ssm": jnp.zeros((batch, c.d_inner, c.d_state), dtype),
    }


def _conv_causal(x, w, b, state: Optional[jax.Array]):
    """x (B,S,di), w (K,di) depthwise.  state: (B,K-1,di) prior context."""
    K = w.shape[0]
    if state is None:
        xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    out = sum(xp[:, i:i + x.shape[1], :] * w[i] for i in range(K)) + b
    new_state = xp[:, -(K - 1):, :] if K > 1 else None
    return out, new_state


def _ssm_params(params, xc, c: MambaConfig):
    """xc (B,S,di) post-conv -> dt (B,S,di), B_in (B,S,N), C_out (B,S,N), A."""
    R, N = c.rank, c.d_state
    proj = xc @ params["x_proj"].astype(xc.dtype)

    def norm(v, name):
        return rms_norm(v, params[name].astype(v.dtype), c.eps)

    dt_r = norm(proj[..., :R], "dt_norm")
    b_in = norm(proj[..., R:R + N], "b_norm")
    c_out = norm(proj[..., R + N:], "c_norm")
    # dt_proj's bias is -4.6·dt_b: softplus^-1(~0.01) at init (dt_b = 1)
    dt = jax.nn.softplus(dt_r @ params["dt_w"].astype(xc.dtype)
                         - 4.6 * params["dt_b"].astype(xc.dtype))
    a = -jnp.exp(params["a_log"].astype(jnp.float32))
    return dt, b_in, c_out, a


def _chunk_recurrence(h0, decay, inc):
    """h_t = decay_t * h_{t-1} + inc_t over axis 1 (chunk), assoc-scan.
    decay/inc: (B, Q, di, N); h0: (B, di, N)."""

    def combine(left, right):
        dl, il = left
        dr, ir = right
        return dl * dr, ir + dr * il

    dec, acc = lax.associative_scan(combine, (decay, inc), axis=1)
    h = acc + dec * h0[:, None]
    return h  # (B, Q, di, N) — all prefix states


def mamba_apply(params: dict, x: jax.Array, c: MambaConfig,
                cache: Optional[dict] = None) -> tuple[jax.Array, Optional[dict]]:
    """x (B,S,d) -> (out (B,S,d), cache')."""
    with jax.named_scope("mamba.mixer"):
        return _mamba(params, x, c, cache)


def _mamba(params: dict, x: jax.Array, c: MambaConfig,
           cache: Optional[dict]) -> tuple[jax.Array, Optional[dict]]:
    B, S, d = x.shape
    di, N = c.d_inner, c.d_state
    xz = x @ params["in_proj"].astype(x.dtype)
    xs, z = xz[..., :di], xz[..., di:]
    xs = constrain(xs, ("batch", "seq", "ssm_inner"))

    conv_state = cache["conv"] if cache is not None else None
    xc, new_conv = _conv_causal(xs, params["conv_w"].astype(x.dtype),
                                params["conv_b"].astype(x.dtype), conv_state)
    xc = jax.nn.silu(xc)
    dt, b_in, c_out, a = _ssm_params(params, xc, c)

    dt32 = dt.astype(jnp.float32)
    xc32 = xc.astype(jnp.float32)
    h_prev = (cache["ssm"].astype(jnp.float32) if cache is not None
              else jnp.zeros((B, di, N), jnp.float32))

    if S == 1:  # decode: single recurrent update
        decay = jnp.exp(dt32[:, 0, :, None] * a[None])                  # (B,di,N)
        inc = (dt32[:, 0, :, None] * xc32[:, 0, :, None]) * b_in[:, 0, None, :].astype(jnp.float32)
        h = decay * h_prev + inc
        y = jnp.einsum("bdn,bn->bd", h, c_out[:, 0].astype(jnp.float32))[:, None, :]
        new_h = h
    else:
        Q = min(c.chunk, S)
        pad = (-S) % Q
        if pad:
            dt32 = jnp.pad(dt32, ((0, 0), (0, pad), (0, 0)))
            xc32 = jnp.pad(xc32, ((0, 0), (0, pad), (0, 0)))
            b_in = jnp.pad(b_in, ((0, 0), (0, pad), (0, 0)))
            c_out = jnp.pad(c_out, ((0, 0), (0, pad), (0, 0)))
        nq = (S + pad) // Q
        dtc = dt32.reshape(B, nq, Q, di).transpose(1, 0, 2, 3)
        xcc = xc32.reshape(B, nq, Q, di).transpose(1, 0, 2, 3)
        bc = b_in.reshape(B, nq, Q, N).transpose(1, 0, 2, 3).astype(jnp.float32)
        cc = c_out.reshape(B, nq, Q, N).transpose(1, 0, 2, 3).astype(jnp.float32)

        def step(h0, blk):
            dtq, xq, bq, cq = blk
            decay = jnp.exp(dtq[..., None] * a[None, None])              # (B,Q,di,N)
            inc = (dtq * xq)[..., None] * bq[:, :, None, :]
            hs = _chunk_recurrence(h0, decay, inc)
            yq = jnp.einsum("bqdn,bqn->bqd", hs, cq)
            return hs[:, -1], yq

        new_h, ys = lax.scan(step, h_prev, (dtc, xcc, bc, cc),
                             unroll=nq if c.unroll else 1)
        y = ys.transpose(1, 0, 2, 3).reshape(B, nq * Q, di)[:, :S]

    y = y + xc32[:, :S] * params["d_skip"].astype(jnp.float32)
    y = (y.astype(x.dtype)) * jax.nn.silu(z)
    out = y @ params["out_proj"].astype(x.dtype)
    new_cache = None
    if cache is not None:
        new_cache = {"conv": new_conv.astype(cache["conv"].dtype), "ssm": new_h}
    return out, new_cache


def mamba_scan_ref(params: dict, x: jax.Array, c: MambaConfig) -> jax.Array:
    """Sequential-scan oracle (step-by-step decode semantics) for tests."""
    B, S, d = x.shape
    cache = init_mamba_cache(c, B)
    outs = []
    for t in range(S):
        o, cache = mamba_apply(params, x[:, t:t + 1], c, cache)
        outs.append(o)
    return jnp.concatenate(outs, axis=1)
