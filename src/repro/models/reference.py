"""A plain float32 forward pass of a whole model, for the sublayer kinds
Jamba uses: the oracle the system is tested against.

``forward(params, tokens, cfg)`` follows ``modeling_jamba`` (Hugging Face
``transformers``) and the Jamba paper (arXiv:2403.19887) in straightforward
``jax.numpy``, under ``jax.default_matmul_precision("highest")``, with no
kernels, no cache and no chunking.  It reads the program's parameter tree
(``transformer.abstract_params``) and nothing else of the program.

Per decoder layer: ``x += mixer(rms_norm(x))``, then ``x += ffn(rms_norm(x))``;
at the end ``logits = rms_norm(x) @ lm_head``.

- attention: grouped-query, causal, no positional embedding (Jamba has
  none), softmax over ``q k^T / sqrt(head_dim)``;
- Mamba-1: ``in_proj`` to (x, z); causal depthwise conv with bias; SiLU;
  ``x_proj`` to (dt, B, C), each through its RMSNorm; ``dt = softplus(dt
  dt_proj + bias)``; ``h_t = exp(dt A) h_{t-1} + dt B x_t`` and ``y_t = C h_t +
  D x_t``, one time step after another (``lax.scan``); ``y * silu(z)``;
  ``out_proj``;
- the gated MLP: ``down(silu(gate x) * up x)``;
- the MoE: softmax over the router's ``n_experts`` logits, the top ``top_k``,
  their weights renormalised only where ``cfg.norm_topk_prob``; each held
  expert computes every token and is masked by its gate weight (zero where
  the token is not routed to it).  Experts that are not held add nothing.

Departures from ``modeling_jamba``:

- parameters are the program's: ``dt_proj``'s bias is stored as
  ``-4.6 * dt_b``, ``A = -exp(a_log)``, and weights are laid out (in, out);
- only the experts held here (``cfg.experts_held`` from
  ``cfg.first_expert_held``) contribute, as in one chip's share of an
  expert-parallel deployment; the router keeps its full width;
- ``experts``, where given, are the routed expert ids (n_moe, B, S, top_k) to
  use in place of the reference's own top-k: with random weights two router
  logits can lie closer than the program's rounding, and the comparison then
  follows the program's choice.  The gate weights are still the reference's
  own softmax at those ids.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32


def rms_norm(x, gamma, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gamma


def attention(p, x, cfg):
    """x (S, d) -> (S, d); causal grouped-query attention, no positions."""
    S = x.shape[0]
    q = jnp.einsum("sd,dhk->hsk", x, p["wq"])
    k = jnp.einsum("sd,dhk->hsk", x, p["wk"])
    v = jnp.einsum("sd,dhk->hsk", x, p["wv"])
    group = q.shape[0] // k.shape[0]
    k, v = jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0)
    s = jnp.einsum("hsk,htk->hst", q, k) / math.sqrt(q.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("hst,htk->hsk", jax.nn.softmax(s, axis=-1), v)
    return jnp.einsum("hsk,hkd->sd", o, p["wo"])


def mamba(p, x, cfg):
    """x (S, d) -> (S, d); Mamba-1 with Jamba's dt/B/C norms, step by step."""
    di = cfg.mamba_expand * cfg.d_model
    N, K = cfg.mamba_d_state, cfg.mamba_d_conv
    R = p["dt_w"].shape[0]
    xz = x @ p["in_proj"]
    xs, z = xz[:, :di], xz[:, di:]
    xp = jnp.concatenate([jnp.zeros((K - 1, di), F32), xs])
    conv = sum(xp[i:i + x.shape[0]] * p["conv_w"][i] for i in range(K)) + p["conv_b"]
    u = jax.nn.silu(conv)
    proj = u @ p["x_proj"]
    dt = rms_norm(proj[:, :R], p["dt_norm"], cfg.norm_eps)
    b = rms_norm(proj[:, R:R + N], p["b_norm"], cfg.norm_eps)
    c = rms_norm(proj[:, R + N:], p["c_norm"], cfg.norm_eps)
    dt = jax.nn.softplus(dt @ p["dt_w"] - 4.6 * p["dt_b"])
    a = -jnp.exp(p["a_log"])

    def step(h, t):
        dt_t, b_t, c_t, u_t = t
        h = jnp.exp(dt_t[:, None] * a) * h + (dt_t * u_t)[:, None] * b_t[None, :]
        return h, h @ c_t

    _, y = lax.scan(step, jnp.zeros((di, N), F32), (dt, b, c, u))
    y = (y + u * p["d_skip"]) * jax.nn.silu(z)
    return y @ p["out_proj"]


def mlp(p, x):
    return (jax.nn.silu(x @ p["gate"]) * (x @ p["up"])) @ p["down"]


def moe(p, x, cfg, experts=None):
    """x (S, d) -> (S, d): the held experts' part of the routed output."""
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    if experts is None:
        _, experts = lax.top_k(probs, cfg.top_k)
    w = jnp.take_along_axis(probs, experts, axis=-1)
    if cfg.norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    out = jnp.zeros_like(x)
    for j in range(p["gate"].shape[0]):
        e = cfg.first_expert_held + j
        gate = jnp.sum(jnp.where(experts == e, w, 0.0), axis=-1)
        ffn = {"gate": p["gate"][j], "up": p["up"][j], "down": p["down"][j]}
        out = out + gate[:, None] * mlp(ffn, x)
    return out


def forward(params, tokens, cfg, experts=None):
    """tokens (B, S) -> float32 logits (B, S, vocab)."""
    unsupported = {"use_rope": cfg.use_rope, "qk_norm": cfg.qk_norm,
                   "tie_embeddings": cfg.tie_embeddings, "pad_vocab": cfg.pad_vocab,
                   "logit_softcap": cfg.logit_softcap, "gated_mlp": not cfg.gated_mlp,
                   "act": cfg.act != "silu"}
    if any(unsupported.values()):
        raise NotImplementedError(
            f"the reference has no {[k for k, v in unsupported.items() if v]}")
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: jnp.asarray(a, F32), params)
        if experts is None:
            return jax.vmap(lambda t: _sequence(p, t, cfg, None))(tokens)
        return jax.vmap(lambda t, e: _sequence(p, t, cfg, e),
                        in_axes=(0, 1))(tokens, experts)


def _sequence(p, tokens, cfg, experts):
    eps = cfg.norm_eps
    x = p["embed"][tokens] * cfg.embed_scale
    n_moe = 0
    for period in range(cfg.n_layers // len(cfg.pattern)):
        for i, (mixer, ffn) in enumerate(cfg.pattern):
            lp = jax.tree.map(lambda a: a[period], p["blocks"][str(i)])
            h = rms_norm(x, lp["norm1"], eps)
            if mixer == "attn":
                x = x + attention(lp["attn"], h, cfg)
            elif mixer == "mamba":
                x = x + mamba(lp["mamba"], h, cfg)
            else:
                raise NotImplementedError(f"the reference has no {mixer!r} mixer")
            h = rms_norm(x, lp["norm2"], eps) if ffn != "none" else None
            if ffn == "mlp":
                x = x + mlp(lp["mlp"], h)
            elif ffn == "moe":
                x = x + moe(lp["moe"], h, cfg,
                            None if experts is None else experts[n_moe])
                n_moe += 1
    return rms_norm(x, p["final_norm"], eps) @ p["lm_head"]
