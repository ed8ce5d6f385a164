"""GQA / MHA attention with RoPE, QK-norm, KV cache, and three execution
impls:

* ``naive``   — full logits materialized (small shapes / decode)
* ``blocked`` — pure-jnp online-softmax over KV blocks (lax.scan) — the
                memory-roofline-honest path big pjit graphs lower (peak
                O(S·bkv) instead of O(S·T))
* ``flash``   — the Pallas kernel (TPU target; interpret-validated on CPU)

Cross-attention (whisper) = ``kv_override`` + causal=False.  Decode = S==1
with a preallocated ring cache written at ``cache["idx"]``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..core.partition import constrain
from ..kernels import ops as kops
from .layers import ParamSpec, rms_norm, rope


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qk_norm: bool = False
    rope_theta: float = 10000.0
    use_rope: bool = True
    impl: str = "blocked"           # naive | blocked | flash
    bkv: int = 512
    logit_softcap: float = 0.0
    seq_shard: bool = False         # long-context: KV seq axis over 'data'
    unroll: bool = False            # analysis mode: unroll the KV-block scan
    compute_dtype: str = "f32"      # f32 (baseline) | bf16 (beyond-paper opt:
                                    #   bf16 operands, f32 accumulation)


def attn_specs(c: AttnConfig, dtype=jnp.float32) -> dict:
    d, H, Hkv, D = c.d_model, c.n_heads, c.n_kv_heads, c.head_dim
    sp = {
        "wq": ParamSpec((d, H, D), ("embed", "heads", "head_dim"), dtype),
        "wk": ParamSpec((d, Hkv, D), ("embed", "kv_heads", "head_dim"), dtype),
        "wv": ParamSpec((d, Hkv, D), ("embed", "kv_heads", "head_dim"), dtype),
        "wo": ParamSpec((H, D, d), ("heads", "head_dim", "embed"), dtype),
    }
    if c.qk_norm:
        sp["q_norm"] = ParamSpec((D,), (None,), dtype, init="ones")
        sp["k_norm"] = ParamSpec((D,), (None,), dtype, init="ones")
    return sp


def cache_axes(c: AttnConfig) -> tuple:
    # long-context: shard head_dim, NOT seq — a dynamic-update-slice along a
    # sharded dim forces halo logic in the SPMD partitioner (pathological
    # compile); head_dim sharding keeps the token append shard-local and the
    # QK contraction reduces over 'data' with one small psum per layer.
    if c.seq_shard:
        return ("batch", "kv_heads", "seq", "head_dim_shard")
    return ("batch", "kv_heads", "seq", "head_dim")


def init_cache(c: AttnConfig, batch: int, max_len: int, dtype=jnp.bfloat16) -> dict:
    axes = cache_axes(c)
    k = jnp.zeros((batch, c.n_kv_heads, max_len, c.head_dim), dtype)
    v = jnp.zeros((batch, c.n_kv_heads, max_len, c.head_dim), dtype)
    return {"k": constrain(k, axes), "v": constrain(v, axes),
            "idx": jnp.zeros((), jnp.int32)}


def _qkv(params, x, c: AttnConfig, positions):
    q = jnp.einsum("bsd,dhk->bhsk", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bhsk", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bhsk", x, params["wv"].astype(x.dtype))
    if c.qk_norm:
        q = rms_norm(q, params["q_norm"].astype(x.dtype))
        k = rms_norm(k, params["k_norm"].astype(x.dtype))
    if c.use_rope:
        # rope expects (..., S, D); bring seq before head_dim
        q = rope(q.swapaxes(1, 2), positions, c.rope_theta).swapaxes(1, 2)
        k = rope(k.swapaxes(1, 2), positions, c.rope_theta).swapaxes(1, 2)
    return q, k, v


def _naive(q, k, v, causal: bool, kv_len, softcap: float, q_offset=None,
           compute_dtype: str = "f32"):
    B, Hq, S, D = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    g = Hq // Hkv
    if compute_dtype == "bf16":
        # beyond-paper: bf16 operands + f32 accumulation; fold the GQA group
        # into the q row dim so the KV cache is streamed ONCE per kv head
        # (not once per query group).
        qg = q.reshape(B, Hkv, g * S, D)
        s = jax.lax.dot_general(
            qg, k, (((3,), (3,)), ((0, 1), (0, 1))),
            preferred_element_type=jnp.float32) * (D ** -0.5)   # (B,Hkv,gS,T)
        s = s.reshape(B, Hkv, g, S, T)
    else:
        qg = q.reshape(B, Hkv, g, S, D).astype(jnp.float32)
        s = jnp.einsum("bhgsd,bhtd->bhgst", qg, k.astype(jnp.float32)) * (D ** -0.5)
    if softcap > 0:
        s = jnp.tanh(s / softcap) * softcap
    t_ids = jnp.arange(T)
    mask = jnp.ones((S, T), bool)
    if causal:
        off = (T - S) if q_offset is None else q_offset
        mask = mask & (t_ids[None, :] <= (jnp.arange(S)[:, None] + off))
    if kv_len is not None:
        mask = mask & (t_ids[None, :] < kv_len)
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if compute_dtype == "bf16":
        pg = p.reshape(B, Hkv, g * S, T).astype(v.dtype)
        o = jax.lax.dot_general(pg, v, (((3,), (2,)), ((0, 1), (0, 1))),
                                preferred_element_type=jnp.float32)
        o = o.reshape(B, Hkv, g, S, v.shape[-1])
    else:
        o = jnp.einsum("bhgst,bhtd->bhgsd", p, v.astype(jnp.float32))
    return o.reshape(B, Hq, S, v.shape[-1]).astype(q.dtype)


def _blocked(q, k, v, causal: bool, kv_len, bkv: int, softcap: float, q_offset=None,
             unroll: bool = False, compute_dtype: str = "f32"):
    """Online-softmax scan over KV blocks (flash algorithm in jnp)."""
    B, Hq, S, D = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    if T <= bkv:
        return _naive(q, k, v, causal, kv_len, softcap, q_offset, compute_dtype)
    pad = (-T) % bkv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nblk = (T + pad) // bkv
    g = Hq // Hkv
    cdt = jnp.bfloat16 if compute_dtype == "bf16" else jnp.float32
    qg = (q.reshape(B, Hkv, g, S, D).astype(cdt)) * jnp.asarray(D ** -0.5, cdt)
    q_ids = jnp.arange(S)[:, None]
    t0s = jnp.arange(nblk) * bkv
    if kv_len is None:
        # no cache (training): the blocks as scan inputs, so that the backward
        # pass gives each block its own cotangent
        xs = (k.reshape(B, Hkv, nblk, bkv, D).transpose(2, 0, 1, 3, 4),
              v.reshape(B, Hkv, nblk, bkv, v.shape[-1]).transpose(2, 0, 1, 3, 4), t0s)

        def blocks(x):
            return x
    else:
        # a cache: slice each block out of it where it lies, with no relayout
        # (a copy of the whole cache on every call)
        xs = t0s

        def blocks(t0):
            return (lax.dynamic_slice_in_dim(k, t0, bkv, axis=2),
                    lax.dynamic_slice_in_dim(v, t0, bkv, axis=2), t0)

    def body(carry, x):
        acc, m, lse = carry
        kblk, vblk, t0 = blocks(x)
        s = jnp.einsum("bhgsd,bhtd->bhgst", qg, kblk.astype(cdt),
                       preferred_element_type=jnp.float32)
        if softcap > 0:
            s = jnp.tanh(s / softcap) * softcap
        t_ids = t0 + jnp.arange(bkv)[None, :]
        mask = t_ids < T
        if causal:
            off = (T - S) if q_offset is None else q_offset
            mask = mask & (t_ids <= q_ids + off)
        if kv_len is not None:
            mask = mask & (t_ids < kv_len)
        s = jnp.where(mask, s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        acc = acc * alpha + jnp.einsum("bhgst,bhtd->bhgsd", p.astype(cdt),
                                       vblk.astype(cdt),
                                       preferred_element_type=jnp.float32)
        lse = lse * alpha + jnp.sum(p, axis=-1, keepdims=True)
        return (acc, m_new, lse), None

    Dv = v.shape[-1]
    acc0 = jnp.zeros((B, Hkv, g, S, Dv), jnp.float32)
    m0 = jnp.full((B, Hkv, g, S, 1), -1e30, jnp.float32)
    lse0 = jnp.zeros((B, Hkv, g, S, 1), jnp.float32)
    (acc, m, lse), _ = lax.scan(jax.checkpoint(body), (acc0, m0, lse0), xs,
                              unroll=nblk if unroll else 1)
    o = acc / jnp.maximum(lse, 1e-30)
    return o.reshape(B, Hq, S, Dv).astype(q.dtype)


def attention(params: dict, x: jax.Array, c: AttnConfig, *,
              positions: Optional[jax.Array] = None,
              cache: Optional[dict] = None,
              kv_override: Optional[tuple[jax.Array, jax.Array]] = None,
              causal: bool = True) -> tuple[jax.Array, Optional[dict]]:
    """x: (B, S, d).  Returns (out (B, S, d), updated cache or None)."""
    with jax.named_scope("attn.mixer"):
        return _attention(params, x, c, positions=positions, cache=cache,
                          kv_override=kv_override, causal=causal)


def _attention(params, x, c: AttnConfig, *, positions, cache, kv_override, causal):
    B, S, d = x.shape
    if positions is None:
        base = cache["idx"] if cache is not None else 0
        positions = base + jnp.arange(S)[None, :].astype(jnp.int32)
        positions = jnp.broadcast_to(positions, (B, S))

    if kv_override is not None:
        q = jnp.einsum("bsd,dhk->bhsk", x, params["wq"].astype(x.dtype))
        if c.qk_norm:
            q = rms_norm(q, params["q_norm"].astype(x.dtype))
        k, v = kv_override
        kv_len = None
        caus = False
        q_off = None
        new_cache = cache
    else:
        q, k, v = _qkv(params, x, c, positions)
        kv_len = None
        caus = causal
        q_off = None
        new_cache = None
        if cache is not None:
            idx = cache["idx"]
            ck = lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                          (0, 0, idx, 0))
            cv = lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                          (0, 0, idx, 0))
            axes = cache_axes(c)
            ck, cv = constrain(ck, axes), constrain(cv, axes)
            new_cache = {"k": ck, "v": cv, "idx": idx + S}
            k, v = ck.astype(x.dtype), cv.astype(x.dtype)
            kv_len = idx + S
            q_off = idx  # queries sit at absolute positions idx..idx+S-1

    q = constrain(q, ("batch", "heads", "seq", "head_dim"))
    if c.impl == "flash" and S > 1 and kv_len is None:
        o = kops.flash_attention(q, k, v, caus, True)
    elif c.impl == "blocked":
        o = _blocked(q, k, v, caus, kv_len, c.bkv, c.logit_softcap, q_off,
                     unroll=c.unroll, compute_dtype=c.compute_dtype)
    else:
        o = _naive(q, k, v, caus, kv_len, c.logit_softcap, q_off,
                   compute_dtype=c.compute_dtype)
    o = constrain(o, ("batch", "heads", "seq", "head_dim"))
    out = jnp.einsum("bhsk,hkd->bsd", o, params["wo"].astype(x.dtype))
    return out, new_cache
