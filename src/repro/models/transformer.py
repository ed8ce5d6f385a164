"""Config-driven model assembly: every assigned architecture is a
(pattern × n_periods) stack of PE-style sub-layers over one block zoo.

The layer stack is a ``lax.scan`` over *periods* (one period = one repeat of
``cfg.pattern``), so the HLO holds a single period regardless of depth —
Qwen3's 94 layers compile as one block.  Heterogeneous archs (jamba 1:7,
xLSTM m/s pattern) put the heterogeneity inside the period.

API:
  abstract_params(cfg)                  -> ParamSpec tree
  forward(params, batch, cfg, cache)    -> (logits, aux, new_cache, moe_stats)
  loss(params, batch, cfg)              -> (scalar, metrics incl. moe_drops)
  init_cache(cfg, batch, max_len)       -> decode cache pytree
  prefill / decode_step                 -> serving entry points
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..configs.base import ModelConfig
from ..core.noc import NoCConfig
from ..core.partition import constrain
from . import mla as mla_mod
from . import moe as moe_mod
from . import ssm as ssm_mod
from . import xlstm as xlstm_mod
from .attention import AttnConfig, attention, attn_specs, init_cache as attn_init_cache
from .layers import (ParamSpec, cross_entropy, mlp_apply, mlp_specs,
                     rms_norm, stack_specs)


# ---------------------------------------------------------------------------
# sub-config builders
# ---------------------------------------------------------------------------

def _attn_cfg(cfg: ModelConfig) -> AttnConfig:
    return AttnConfig(cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
                      qk_norm=cfg.qk_norm, rope_theta=cfg.rope_theta,
                      use_rope=cfg.use_rope and cfg.pos_embed == "rope",
                      impl=cfg.attn_impl, bkv=cfg.bkv,
                      logit_softcap=cfg.logit_softcap, seq_shard=cfg.seq_shard_kv,
                      unroll=cfg.analysis_unroll,
                      compute_dtype=cfg.attn_compute_dtype)


def _mla_cfg(cfg: ModelConfig) -> mla_mod.MLAConfig:
    return mla_mod.MLAConfig(cfg.d_model, cfg.n_heads, rope_theta=cfg.rope_theta,
                             impl=cfg.attn_impl, bkv=cfg.bkv,
                             unroll=cfg.analysis_unroll, absorb=cfg.mla_absorb,
                             compute_dtype=cfg.attn_compute_dtype)


def _mamba_cfg(cfg: ModelConfig) -> ssm_mod.MambaConfig:
    return ssm_mod.MambaConfig(cfg.d_model, cfg.mamba_d_state, cfg.mamba_d_conv,
                               cfg.mamba_expand, chunk=cfg.mamba_chunk,
                               unroll=cfg.analysis_unroll, eps=cfg.norm_eps)


def _xlstm_cfg(cfg: ModelConfig) -> xlstm_mod.XLSTMConfig:
    return xlstm_mod.XLSTMConfig(cfg.d_model, cfg.n_heads,
                                 proj_factor=cfg.xlstm_proj_factor,
                                 chunk=cfg.xlstm_chunk, unroll=cfg.analysis_unroll)


def _moe_cfg(cfg: ModelConfig) -> moe_mod.MoEConfig:
    # moe_flit_buffer_depth > 0 attaches a NoCConfig: the CONNECT buffer depth
    # becomes the capacity knob and capacity_factor is derived from it
    noc = (NoCConfig(flit_buffer_depth=cfg.moe_flit_buffer_depth)
           if cfg.moe_flit_buffer_depth else None)
    return moe_mod.MoEConfig(cfg.d_model, cfg.n_experts, cfg.top_k, cfg.d_ff_expert,
                             capacity_factor=cfg.capacity_factor, impl=cfg.moe_impl,
                             noc_topology=cfg.moe_topology, act=cfg.act, noc=noc,
                             n_held=cfg.experts_held, first_held=cfg.first_expert_held,
                             norm_topk_prob=cfg.norm_topk_prob)


# ---------------------------------------------------------------------------
# specs
# ---------------------------------------------------------------------------

def _sublayer_specs(cfg: ModelConfig, mixer: str, ffn: str, cross: bool, dtype) -> dict:
    d = cfg.d_model
    sp: dict = {"norm1": ParamSpec((d,), ("embed",), dtype, init="ones")}
    if mixer == "attn":
        sp["attn"] = attn_specs(_attn_cfg(cfg), dtype)
    elif mixer == "mla":
        sp["mla"] = mla_mod.mla_specs(_mla_cfg(cfg), dtype)
    elif mixer == "mamba":
        sp["mamba"] = ssm_mod.mamba_specs(_mamba_cfg(cfg), dtype)
    elif mixer == "mlstm":
        sp["mlstm"] = xlstm_mod.mlstm_specs(_xlstm_cfg(cfg), dtype)
    elif mixer == "slstm":
        sp["slstm"] = xlstm_mod.slstm_specs(_xlstm_cfg(cfg), dtype)
    else:
        raise ValueError(mixer)
    if cross:
        sp["norm_x"] = ParamSpec((d,), ("embed",), dtype, init="ones")
        sp["cross"] = attn_specs(_attn_cfg(cfg), dtype)
    if ffn == "mlp":
        sp["norm2"] = ParamSpec((d,), ("embed",), dtype, init="ones")
        sp["mlp"] = mlp_specs(d, cfg.d_ff, dtype, cfg.gated_mlp)
    elif ffn == "moe":
        sp["norm2"] = ParamSpec((d,), ("embed",), dtype, init="ones")
        sp["moe"] = moe_mod.moe_specs(_moe_cfg(cfg), dtype)
    return sp


def _period_specs(cfg: ModelConfig, cross: bool, dtype) -> dict:
    return {str(i): _sublayer_specs(cfg, m, f, cross and m == "attn", dtype)
            for i, (m, f) in enumerate(cfg.pattern)}


def abstract_params(cfg: ModelConfig) -> dict:
    dtype = jnp.float32  # master weights; compute casts per cfg.cdtype
    d, V = cfg.d_model, cfg.vocab_padded
    sp: dict = {
        "embed": ParamSpec((V, d), ("vocab", "embed"), dtype, init="embed", scale=0.02),
        "blocks": stack_specs(_period_specs(cfg, cfg.family == "encdec", dtype),
                              cfg.n_periods),
        "final_norm": ParamSpec((d,), ("embed",), dtype, init="ones"),
    }
    if not cfg.tie_embeddings:
        sp["lm_head"] = ParamSpec((d, V), ("embed", "vocab"), dtype, init="small")
    if cfg.family == "encdec":
        enc_pattern_cfg = cfg.replace(pattern=(("attn", "mlp"),), n_layers=cfg.n_enc_layers)
        sp["enc_blocks"] = stack_specs(_period_specs(enc_pattern_cfg, False, dtype),
                                       cfg.n_enc_layers)
        sp["enc_norm"] = ParamSpec((d,), ("embed",), dtype, init="ones")
        sp["frontend"] = ParamSpec((cfg.d_frontend, d), (None, "embed"), dtype)
    if cfg.family == "vlm":
        sp["frontend"] = ParamSpec((cfg.d_frontend, d), (None, "embed"), dtype)
    return sp


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def _sublayer_cache(cfg: ModelConfig, mixer: str, batch: int, max_len: int):
    if mixer == "attn":
        return attn_init_cache(_attn_cfg(cfg), batch, max_len, cfg.cdtype)
    if mixer == "mla":
        return mla_mod.init_mla_cache(_mla_cfg(cfg), batch, max_len, cfg.cdtype)
    if mixer == "mamba":
        return ssm_mod.init_mamba_cache(_mamba_cfg(cfg), batch, jnp.float32)
    if mixer == "mlstm":
        return xlstm_mod.init_mlstm_cache(_xlstm_cfg(cfg), batch, jnp.float32)
    if mixer == "slstm":
        return xlstm_mod.init_slstm_cache(_xlstm_cfg(cfg), batch, jnp.float32)
    raise ValueError(mixer)


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    per = {str(i): _sublayer_cache(cfg, m, batch, max_len)
           for i, (m, _) in enumerate(cfg.pattern)}
    P = cfg.n_periods
    stacked = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (P,) + x.shape) + jnp.zeros((), x.dtype), per)
    return {"blocks": stacked, "pos": jnp.zeros((), jnp.int32)}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _norm(x, gamma, cfg: ModelConfig):
    return rms_norm(x, gamma.astype(x.dtype), cfg.norm_eps)


def _apply_sublayer(p, x, cfg: ModelConfig, mixer: str, ffn: str, *,
                    positions, cache, enc_out, causal):
    aux = jnp.zeros((), jnp.float32)
    # (drops, peak, routed expert ids or None)
    moe = (jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32), None)
    h = _norm(x, p["norm1"], cfg)
    if mixer == "attn":
        o, new_cache = attention(p["attn"], h, _attn_cfg(cfg), positions=positions,
                                 cache=cache, causal=causal)
    elif mixer == "mla":
        o, new_cache = mla_mod.mla_apply(p["mla"], h, _mla_cfg(cfg),
                                         positions=positions, cache=cache)
    elif mixer == "mamba":
        o, new_cache = ssm_mod.mamba_apply(p["mamba"], h, _mamba_cfg(cfg), cache)
    elif mixer == "mlstm":
        o, new_cache = xlstm_mod.mlstm_apply(p["mlstm"], h, _xlstm_cfg(cfg), cache)
    elif mixer == "slstm":
        o, new_cache = xlstm_mod.slstm_apply(p["slstm"], h, _xlstm_cfg(cfg), cache)
    else:
        raise ValueError(mixer)
    x = x + o
    if enc_out is not None and "cross" in p:
        hx = _norm(x, p["norm_x"], cfg)
        kv_k = jnp.einsum("btd,dhk->bhtk", enc_out, p["cross"]["wk"].astype(x.dtype))
        kv_v = jnp.einsum("btd,dhk->bhtk", enc_out, p["cross"]["wv"].astype(x.dtype))
        o, _ = attention(p["cross"], hx, _attn_cfg(cfg), positions=positions,
                         kv_override=(kv_k, kv_v), causal=False)
        x = x + o
    if ffn == "mlp":
        h = _norm(x, p["norm2"], cfg)
        x = x + mlp_apply(p["mlp"], h, act="silu" if cfg.act == "silu" else "gelu")
    elif ffn == "moe":
        h = _norm(x, p["norm2"], cfg)
        o, aux, st = moe_mod.moe_apply(p["moe"], h, _moe_cfg(cfg))
        moe = (jnp.asarray(st.drops, jnp.int32),
               jnp.asarray(st.peak_occupancy, jnp.int32), st.experts)
        x = x + o
    return x, new_cache, aux, moe


def _run_stack(blocks, x, cfg: ModelConfig, *, pattern, positions, cache_blocks,
               enc_out, causal):
    """scan over periods; xs = (stacked period params, stacked period caches).

    MoE dispatch stats ride the carry: drops sum over layers, peak-occupancy
    maxes (the hottest (src, dst) buffer anywhere in the stack).  The routed
    expert ids of the MoE layers leave as the scan's output, ``moe_experts``
    (n_moe, B, S, k)."""

    def period_fn(carry, xs):
        x, aux, drops, peak = carry
        if cache_blocks is not None:
            pp, pc = xs
        else:
            pp, pc = xs, None
        new_pc = {}
        routes = []
        for i, (mixer, ffn) in enumerate(pattern):
            sub_cache = pc[str(i)] if pc is not None else None
            x, nc, a, (dr, pk, ex) = _apply_sublayer(
                pp[str(i)], x, cfg, mixer, ffn, positions=positions, cache=sub_cache,
                enc_out=enc_out, causal=causal)
            new_pc[str(i)] = nc if nc is not None else ()
            aux = aux + a
            drops = drops + dr
            peak = jnp.maximum(peak, pk)
            if ex is not None:
                routes.append(ex)
        x = constrain(x, ("batch", "seq", "embed"))
        routes = jnp.stack(routes) if routes else 0
        return (x, aux, drops, peak), (new_pc if pc is not None else 0, routes)

    body = jax.checkpoint(period_fn) if cfg.remat else period_fn
    xs = (blocks, cache_blocks) if cache_blocks is not None else blocks
    carry0 = (x, jnp.zeros((), jnp.float32),
              jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))
    (x, aux, drops, peak), (new_caches, routes) = lax.scan(
        body, carry0, xs, unroll=cfg.n_periods if cfg.analysis_unroll else 1)
    moe_stats = {"moe_drops": drops, "moe_peak_occupancy": peak}
    if routes.ndim > 1:
        moe_stats["moe_experts"] = routes.reshape((-1,) + routes.shape[2:])
    return x, aux, (new_caches if cache_blocks is not None else None), moe_stats


def _embed_tokens(params, tokens, cfg: ModelConfig):
    e = params["embed"].astype(cfg.cdtype)[tokens]
    return e * jnp.asarray(cfg.embed_scale, cfg.cdtype)


def _sinusoidal(positions, d, dtype):
    half = d // 2
    freqs = 10000.0 ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1).astype(dtype)


def encode(params, frames, cfg: ModelConfig):
    """Audio encoder: precomputed frame embeddings (stubbed conv frontend)
    -> frontend proj -> sinusoidal pos -> bidirectional stack."""
    x = frames.astype(cfg.cdtype) @ params["frontend"].astype(cfg.cdtype)
    pos = jnp.arange(x.shape[1])[None, :]
    x = x + _sinusoidal(pos, cfg.d_model, x.dtype)
    x, _, _, _ = _run_stack(params["enc_blocks"], x, cfg, pattern=(("attn", "mlp"),),
                            positions=jnp.broadcast_to(pos, x.shape[:2]),
                            cache_blocks=None, enc_out=None, causal=False)
    return _norm(x, params["enc_norm"], cfg)


def forward(params: dict, batch: dict, cfg: ModelConfig,
            cache: Optional[dict] = None
            ) -> tuple[jax.Array, jax.Array, Optional[dict], dict]:
    """-> (logits (B,S,V), aux_loss, new_cache, moe_stats).

    ``moe_stats``: {"moe_drops", "moe_peak_occupancy"} — capacity-dropped
    tokens summed over MoE layers and the hottest per-(src, dst) dispatch
    buffer, straight from `moe.MoEDispatchStats` (zeros for dense archs) —
    and, where the model has MoE layers, "moe_experts": the routed expert
    ids (n_moe_layers, B, S, top_k)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    pos0 = cache["pos"] if cache is not None else 0
    positions = pos0 + jnp.broadcast_to(jnp.arange(S)[None, :], (B, S)).astype(jnp.int32)

    x = _embed_tokens(params, tokens, cfg)
    if cfg.pos_embed == "sinusoidal":
        x = x + _sinusoidal(positions, cfg.d_model, x.dtype)

    enc_out = None
    if cfg.family == "encdec":
        enc_out = (cache.get("enc_out") if cache is not None else None)
        if enc_out is None:
            enc_out = encode(params, batch["frames"], cfg)
    if cfg.family == "vlm" and "patches" in batch:
        pre = batch["patches"].astype(cfg.cdtype) @ params["frontend"].astype(cfg.cdtype)
        x = jnp.concatenate([pre, x], axis=1)
        S = x.shape[1]
        positions = pos0 + jnp.broadcast_to(jnp.arange(S)[None, :], (B, S)).astype(jnp.int32)

    x = constrain(x, ("batch", "seq", "embed"))
    cache_blocks = cache["blocks"] if cache is not None else None
    x, aux, new_blocks, moe_stats = _run_stack(
        params["blocks"], x, cfg, pattern=cfg.pattern, positions=positions,
        cache_blocks=cache_blocks, enc_out=enc_out, causal=True)
    x = _norm(x, params["final_norm"], cfg)
    if cfg.family == "vlm" and "patches" in batch:
        x = x[:, -tokens.shape[1]:]  # logits only for text positions
    head = (params["embed"].astype(x.dtype).T if cfg.tie_embeddings
            else params["lm_head"].astype(x.dtype))
    logits = constrain(x @ head, ("batch", "seq", "vocab"))
    if cfg.vocab_padded != cfg.vocab:
        # mask padded classes in place (sharded-dim slice would re-layout)
        vid = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
        logits = jnp.where(vid < cfg.vocab, logits, jnp.asarray(-1e30, logits.dtype))
    new_cache = None
    if cache is not None:
        new_cache = {"blocks": new_blocks, "pos": pos0 + S}
        if cfg.family == "encdec":
            new_cache["enc_out"] = enc_out
    return logits, aux, new_cache, moe_stats


def loss(params: dict, batch: dict, cfg: ModelConfig):
    logits, aux, _, moe_stats = forward(params, batch, cfg)
    nll = cross_entropy(logits, batch["labels"])
    total = nll + cfg.aux_weight * aux
    # f32 so downstream metric pmean/averaging is well-defined
    mets = {k: moe_stats[k].astype(jnp.float32)
            for k in ("moe_drops", "moe_peak_occupancy")}
    return total, {"nll": nll, "aux": aux, **mets}


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def prefill(params: dict, batch: dict, cfg: ModelConfig, cache: dict):
    logits, _, cache, _ = forward(params, batch, cfg, cache)
    return logits[:, -1:], cache


def decode_step(params: dict, batch: dict, cfg: ModelConfig, cache: dict):
    """batch["tokens"]: (B, 1) — one new token against the cache."""
    logits, _, cache, _ = forward(params, batch, cfg, cache)
    return logits[:, -1], cache
