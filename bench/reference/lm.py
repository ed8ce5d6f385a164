"""The plain float32 reference of an LM cell (Jamba's sublayer kinds), in
blocks that fit on one chip beside the program's bfloat16 weights.

The same equations as the program's ``src/repro/models/reference.py``, of
which this is a copy (attention without positions; Mamba-1 with the dt/B/C
norms, scanned one time step after another; the gated MLP; the held experts
as a per-expert masked sum, no renormalisation of the top-k unless the
config says so), under ``jax.default_matmul_precision("highest")``.  It
imports nothing of the program.  It differs in how the work is cut:

- one sequence at a time, and one sublayer at a time, each a jitted
  function of its own, so that only one sublayer's weights are in float32 at
  once;
- attention by blocks of ``QBLOCK`` query rows;
- the experts one at a time, each on the rows routed to it (the nonzero
  terms of the masked sum), gathered on the host from the routing;
- logits only at the rows asked for;
- each sequence padded at its end to a multiple of ``PAD`` positions, which
  changes none of its rows (causal attention, a scan forward in time) and
  lets runs of about the same length share one compiled program;
- the routing is the program's (``experts``): with random weights two router
  logits lie closer than the program's bfloat16 rounding at some tokens, and
  the reference follows the program's choice there.  Each MoE layer reports
  how far the chosen experts' lowest router logit lies below the best one
  passed over, relative to the token's router logits, for the caller to hold
  to a margin.  The gate weights are the
  reference's own softmax at the chosen ids.

The weights are the parameter tree the program serves (its own layout:
``dt_proj``'s bias is ``-4.6 * dt_b``, ``A = -exp(a_log)``), read as float32.
"""
from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
QBLOCK = 512      # query rows of one block of attention scores
PAD = 512         # sequences are padded to a multiple of this, so runs share compiles


def rms_norm(x, gamma, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gamma.astype(F32)


def _f32(p):
    return jax.tree.map(lambda a: a.astype(F32), p)


def _highest(fn):
    @functools.wraps(fn)
    def run(*args, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kw)
    return run


def _layer(blocks, period: int):
    """One layer's weights out of the stacked periods, inside a jitted function
    (a slice there is a view; outside it would copy the layer's weights)."""
    return jax.tree.map(lambda a: a[period], blocks)


@functools.partial(jax.jit, static_argnames=("period", "eps"))
@_highest
def attention(blocks, period, x, eps):
    """x + attention(rms_norm(x)) for one sequence x (T, d), causal."""
    lp = _layer(blocks, period)
    p = _f32(lp["attn"])
    h = rms_norm(x, lp["norm1"], eps)
    T = x.shape[0]
    q = jnp.einsum("sd,dhk->hsk", h, p["wq"])
    k = jnp.einsum("sd,dhk->hsk", h, p["wk"])
    v = jnp.einsum("sd,dhk->hsk", h, p["wv"])
    group = q.shape[0] // k.shape[0]
    k, v = jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0)
    nb = -(-T // QBLOCK)
    q = jnp.pad(q, ((0, 0), (0, nb * QBLOCK - T), (0, 0))) / math.sqrt(q.shape[-1])

    def block(i):
        qi = lax.dynamic_slice_in_dim(q, i * QBLOCK, QBLOCK, axis=1)
        s = jnp.einsum("hsk,htk->hst", qi, k)
        rows = i * QBLOCK + jnp.arange(QBLOCK)[:, None]
        s = jnp.where(jnp.arange(T)[None, :] <= rows, s, -jnp.inf)
        return jnp.einsum("hst,htk->hsk", jax.nn.softmax(s, axis=-1), v)

    o = lax.map(block, jnp.arange(nb))                       # (nb, H, QBLOCK, D)
    o = jnp.moveaxis(o, 0, 1).reshape(q.shape)[:, :T]
    return x + jnp.einsum("hsk,hkd->sd", o, p["wo"])


@functools.partial(jax.jit, static_argnames=("period", "d_conv", "d_state", "eps"))
@_highest
def mamba(blocks, period, x, d_conv, d_state, eps):
    """x + mamba(rms_norm(x)) for one sequence x (T, d), step by step."""
    lp = _layer(blocks, period)
    p = _f32(lp["mamba"])
    h = rms_norm(x, lp["norm1"], eps)
    T = x.shape[0]
    di = p["in_proj"].shape[1] // 2
    N, K, R = d_state, d_conv, p["dt_w"].shape[0]
    xz = h @ p["in_proj"]
    xs, z = xz[:, :di], xz[:, di:]
    xp = jnp.concatenate([jnp.zeros((K - 1, di), F32), xs])
    u = jax.nn.silu(sum(xp[i:i + T] * p["conv_w"][i] for i in range(K)) + p["conv_b"])
    proj = u @ p["x_proj"]
    dt = rms_norm(proj[:, :R], p["dt_norm"], eps)
    b = rms_norm(proj[:, R:R + N], p["b_norm"], eps)
    c = rms_norm(proj[:, R + N:], p["c_norm"], eps)
    dt = jax.nn.softplus(dt @ p["dt_w"] - 4.6 * p["dt_b"])
    a = -jnp.exp(p["a_log"])

    def step(s, t):
        dt_t, b_t, c_t, u_t = t
        s = jnp.exp(dt_t[:, None] * a) * s + (dt_t * u_t)[:, None] * b_t[None, :]
        return s, s @ c_t

    _, y = lax.scan(step, jnp.zeros((di, N), F32), (dt, b, c, u))
    y = (y + u * p["d_skip"]) * jax.nn.silu(z)
    return x + y @ p["out_proj"]


def _ffn(x, gate, up, down):
    return (jax.nn.silu(x @ gate.astype(F32)) * (x @ up.astype(F32))) @ down.astype(F32)


@functools.partial(jax.jit, static_argnames=("period", "eps"))
@_highest
def mlp(blocks, period, x, eps):
    lp = _layer(blocks, period)
    p = lp["mlp"]
    return x + _ffn(rms_norm(x, lp["norm2"], eps), p["gate"], p["up"], p["down"])


@functools.partial(jax.jit, static_argnames=("period", "norm_topk", "eps"))
@_highest
def router(blocks, period, x, experts, norm_topk, eps):
    """(rms_norm(x), gate weights (T, k) of the routed ids ``experts``, slack):
    ``slack`` (T,) is how far the lowest router logit the ids chose lies
    below the best one they passed over (positive where the choice is not
    the reference's own top-k), in units of the root mean square of the
    token's router logits."""
    lp = _layer(blocks, period)
    h = rms_norm(x, lp["norm2"], eps)
    logits = h @ lp["moe"]["router"].astype(F32)
    probs = jax.nn.softmax(logits, axis=-1)
    w = jnp.take_along_axis(probs, experts, axis=-1)
    if norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    chosen = jnp.any(experts[:, :, None] == jnp.arange(logits.shape[1]), axis=1)
    lowest = jnp.min(jnp.where(chosen, logits, jnp.inf), axis=-1)
    passed = jnp.max(jnp.where(chosen, -jnp.inf, logits), axis=-1)
    return h, w, (passed - lowest) / jnp.sqrt(jnp.mean(logits * logits, axis=-1))


@functools.partial(jax.jit, static_argnames=("period", "j"))
@_highest
def expert(moe_blocks, period, j, h, rows, gates):
    """sum_r gates[r] * expert_j(h[rows[r]]), put back at the tokens ``rows``."""
    w = [moe_blocks[k][period, j] for k in ("gate", "up", "down")]
    return jnp.zeros_like(h).at[rows].add(gates[:, None] * _ffn(h[rows], *w))


def moe(blocks, period: int, x, experts, first: int, norm_topk: bool, eps: float):
    """(x + the held experts' part of moe(rms_norm(x)), slack) for the routed
    ids ``experts`` (T, k).  Each held expert computes the rows routed to it,
    the nonzero terms of the masked sum, padded to a power of two with rows
    of gate zero."""
    h, w, slack = router(blocks, period, x, experts, norm_topk, eps)
    ids, w = np.asarray(experts), np.asarray(w)
    out = x
    for j in range(blocks["moe"]["gate"].shape[1]):
        tok, slot = np.nonzero(ids == first + j)
        size = 1 << max(8, int(len(tok) - 1).bit_length())
        rows = np.zeros(size, np.int32)
        gates = np.zeros(size, np.float32)
        rows[:len(tok)], gates[:len(tok)] = tok, w[tok, slot]
        out = out + expert(blocks["moe"], period, j, h, rows, gates)
    return out, slack


@functools.partial(jax.jit, static_argnames=("eps",))
@_highest
def head(final_norm, lm_head, x, eps):
    return rms_norm(x, final_norm, eps) @ lm_head.astype(F32)


def pattern(cfg: dict) -> list[tuple[str, str]]:
    """The (mixer, ffn) of each layer of one period, from the config's
    ``attn_layer_period``/``_offset`` and ``expert_layer_period``/``_offset``."""
    n = math.lcm(cfg["attn_layer_period"], cfg["expert_layer_period"])
    return [("attn" if i % cfg["attn_layer_period"] == cfg["attn_layer_offset"] else "mamba",
             "moe" if i % cfg["expert_layer_period"] == cfg["expert_layer_offset"] else "mlp")
            for i in range(n)]


def logits_at(params, tokens, experts, rows, cfg: dict):
    """One sequence: tokens (T,), routed ids (n_moe, T, k), rows (R,) ->
    (float32 logits (R, vocab), the routing's largest slack (T,) over the
    MoE layers).  ``cfg`` is the configuration's file."""
    eps = cfg["rms_norm_eps"]
    n = tokens.shape[0]
    pad = -n % PAD          # causal: the padding after the sequence changes none of it
    tokens = jnp.pad(tokens, (0, pad))
    experts = jnp.pad(experts, ((0, 0), (0, pad), (0, 0)), constant_values=-1)
    x = params["embed"][tokens].astype(F32)
    slack = jnp.full(tokens.shape, -jnp.inf)
    n_moe = 0
    kinds = pattern(cfg)
    for period in range(cfg["num_hidden_layers"] // len(kinds)):
        for i, (mixer, ffn) in enumerate(kinds):
            blocks = params["blocks"][str(i)]
            if mixer == "attn":
                x = attention(blocks, period, x, eps)
            else:
                x = mamba(blocks, period, x, cfg["mamba_d_conv"], cfg["mamba_d_state"], eps)
            if ffn == "mlp":
                x = mlp(blocks, period, x, eps)
            else:
                x, s = moe(blocks, period, x, experts[n_moe], cfg["first_expert_held"],
                           cfg["norm_topk_prob"], eps)
                slack = jnp.maximum(slack, s)
                n_moe += 1
    return head(params["final_norm"], params["lm_head"], x[rows], eps), slack[:n]
