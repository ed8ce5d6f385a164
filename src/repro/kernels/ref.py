"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

Every function here is the semantic ground truth; kernels must match to
numerical tolerance across the shape/dtype sweeps in tests/test_kernels.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# GF(2) BMVM — Williams' sub-quadratic algorithm (paper §VI)
# ---------------------------------------------------------------------------

def gf2_preprocess(a_bits: jax.Array, k: int) -> jax.Array:
    """One-time preprocessing (paper Fig. 13).

    a_bits: (n, n) uint8/... in {0,1}.  Returns LUT (C, 2^k, R) uint32 where
    C = R = n//k and LUT[c, p, r] = A_tile[r, c] @ b_p over GF(2), packed as a
    k-bit word (bit j = row j of the tile-product).
    """
    n = a_bits.shape[0]
    assert a_bits.shape == (n, n) and n % k == 0
    nk = n // k
    tiles = a_bits.reshape(nk, k, nk, k).transpose(0, 2, 1, 3).astype(jnp.uint32)  # (R, C, k, k)
    # all 2^k input vectors b_p: bit i of p = entry i of b_p
    p = jnp.arange(2 ** k, dtype=jnp.uint32)
    bvec = (p[:, None] >> jnp.arange(k, dtype=jnp.uint32)[None, :]) & 1  # (2^k, k)
    # product bits: tiles (R,C,k_out,k_in) x bvec (P,k_in) -> parity over k_in
    prod = jnp.einsum("rcoi,pi->rcpo", tiles, bvec) % 2                   # (R, C, P, k)
    words = (prod << jnp.arange(k, dtype=jnp.uint32)[None, None, None, :]).sum(-1)
    return words.transpose(1, 2, 0).astype(jnp.uint32)                    # (C, P, R)


def gf2_pack_vector(v_bits: jax.Array, k: int) -> jax.Array:
    """(..., n) bits -> (..., n//k) k-bit uint32 words (LUT partition indices)."""
    *lead, n = v_bits.shape
    w = v_bits.reshape(*lead, n // k, k).astype(jnp.uint32)
    return (w << jnp.arange(k, dtype=jnp.uint32)).sum(-1)


def gf2_unpack_vector(words: jax.Array, k: int) -> jax.Array:
    """inverse of gf2_pack_vector."""
    bits = (words[..., None] >> jnp.arange(k, dtype=jnp.uint32)) & 1
    return bits.reshape(*words.shape[:-1], words.shape[-1] * k).astype(jnp.uint8)


def gf2_bmvm(lut: jax.Array, v_words: jax.Array) -> jax.Array:
    """Compute A@v over GF(2) from the LUT.  v_words: (M, C) -> (M, R).

    out[m, r] = XOR_c LUT[c, v_words[m, c], r]  — each processing node c looks
    up partition v_c and the XOR-accumulate happens at node r (paper §VI-A).
    """
    C, P, R = lut.shape
    looked = jax.vmap(lambda vw: lut[jnp.arange(C), vw, :], in_axes=0)(v_words)  # (M, C, R)
    acc = looked[:, 0, :]
    for c in range(1, C):
        acc = jnp.bitwise_xor(acc, looked[:, c, :])
    return acc


def gf2_matmul_oracle(a_bits: jax.Array, v_bits: jax.Array) -> jax.Array:
    """Direct O(n^2) GF(2) mat-vec: (n,n) x (M,n) -> (M,n)."""
    return (v_bits.astype(jnp.uint32) @ a_bits.astype(jnp.uint32).T) % 2


# ---------------------------------------------------------------------------
# LDPC min-sum check-node update (paper §IV)
# ---------------------------------------------------------------------------

def minsum_check(u: jax.Array) -> jax.Array:
    """Check-node processing with the two-min trick.

    u: (n_checks, deg) incoming LLRs.  out[c, j] = prod_{i≠j} sign(u_i) *
    min_{i≠j} |u_i|.  (The paper's Listing 2 is the sign-free 3-input variant;
    this is the standard general form — reduces to it for positive inputs.)
    """
    mag = jnp.abs(u)
    neg = u < 0
    sgn = jnp.where(neg, -1.0, 1.0).astype(u.dtype)
    # prod of the ±1 signs, as the parity of the negative count: a batched
    # reduce-multiply here crashes the TPU compiler's fusion pass
    n_neg = jnp.sum(neg.astype(jnp.int32), axis=-1, keepdims=True)
    total_sign = jnp.where((n_neg & 1) == 1, -1.0, 1.0).astype(u.dtype)
    min1 = jnp.min(mag, axis=-1, keepdims=True)
    amin = jnp.argmin(mag, axis=-1)
    masked = jnp.where(jax.nn.one_hot(amin, u.shape[-1], dtype=bool), jnp.inf, mag)
    min2 = jnp.min(masked, axis=-1, keepdims=True)
    is_min = jax.nn.one_hot(amin, u.shape[-1], dtype=bool)
    mins = jnp.where(is_min, min2, min1)
    return (total_sign * sgn) * mins  # sign excluding self; |.| excluding self


def bitnode_sum(u0: jax.Array, v: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Bit-node processing (paper Listing 3): total = u0 + Σv;  u_j = total - v_j."""
    total = u0 + jnp.sum(v, axis=-1)
    return total, total[..., None] - v


# ---------------------------------------------------------------------------
# Particle filter: weighted histogram + Bhattacharyya (paper §V)
# ---------------------------------------------------------------------------

def weighted_histogram(bins: jax.Array, weights: jax.Array, n_bins: int) -> jax.Array:
    """bins: (N, px) int32 bin index per pixel; weights: (px,) distance
    weights.  -> (N, n_bins) normalized weighted histograms."""
    onehot = jax.nn.one_hot(bins, n_bins, dtype=weights.dtype)      # (N, px, B)
    hist = jnp.einsum("npb,p->nb", onehot, weights)
    return hist / jnp.maximum(hist.sum(-1, keepdims=True), 1e-12)


def bhattacharyya(hist: jax.Array, ref_hist: jax.Array) -> jax.Array:
    """(N, B), (B,) -> (N,) Bhattacharyya coefficients."""
    return jnp.sum(jnp.sqrt(hist * ref_hist[None, :]), axis=-1)


def particle_weights(bins: jax.Array, weights: jax.Array, ref_hist: jax.Array,
                     sigma: float = 0.1) -> jax.Array:
    """Full PE of paper Fig. 11: histogram -> BC -> weight = exp((BC-1)/σ²)."""
    hist = weighted_histogram(bins, weights, ref_hist.shape[-1])
    bc = bhattacharyya(hist, ref_hist)
    w = jnp.exp((bc - 1.0) / (sigma * sigma))
    return w / jnp.maximum(w.sum(), 1e-12)


# ---------------------------------------------------------------------------
# Flash attention (forward) — LM-stack hot spot
# ---------------------------------------------------------------------------

def mha(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = True,
        scale: float | None = None) -> jax.Array:
    """q: (B, Hq, S, D), k/v: (B, Hkv, T, D) with Hq % Hkv == 0 (GQA)."""
    B, Hq, S, D = q.shape
    Hkv = k.shape[1]
    g = Hq // Hkv
    qg = q.reshape(B, Hkv, g, S, D)
    scale = scale if scale is not None else D ** -0.5
    logits = jnp.einsum("bhgsd,bhtd->bhgst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        S_, T_ = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((S_, T_), bool), k=T_ - S_)
        logits = jnp.where(mask, logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgst,bhtd->bhgsd", p, v.astype(jnp.float32))
    return out.reshape(B, Hq, S, D).astype(q.dtype)
