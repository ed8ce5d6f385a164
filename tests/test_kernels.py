"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.kernels import gf2_bmvm, ops, ref


# -- GF(2) BMVM ---------------------------------------------------------------

# (n, k, m, VMEM budget of the tile plan; None keeps the kernel's own)
GF2_CASES = [pytest.param(n, k, m, None, id=f"{n}-{k}-{m}") for n, k, m in
             [(16, 4, 1), (32, 4, 3), (64, 8, 5), (128, 4, 2), (128, 8, 8)]] + [
    # C=32 column tiles: ct=4; M=13 is padded to 16
    pytest.param(256, 8, 13, None, id="256-8-13"),
    # R=256 words: a 40 KiB budget leaves rt=128, ct=1, grid (2, 256)
    pytest.param(1024, 4, 21, 40 * 2 ** 10, id="1024-4-21-rt128"),
    # R=384 words: a 48 KiB budget leaves rt=128, ct=2, grid (3, 192)
    pytest.param(1536, 4, 5, 48 * 2 ** 10, id="1536-4-5-rt128-ct2"),
]


@pytest.mark.parametrize("n,k,m,budget", GF2_CASES)
def test_gf2_bmvm_kernel_vs_oracles(n, k, m, budget, monkeypatch):
    rng = np.random.default_rng(n + k)
    A = jnp.asarray(rng.integers(0, 2, (n, n)), jnp.uint8)
    V = jnp.asarray(rng.integers(0, 2, (m, n)), jnp.uint8)
    lut = ref.gf2_preprocess(A, k)
    assert lut.shape == (n // k, 2 ** k, n // k)
    if budget is not None:
        monkeypatch.setattr(gf2_bmvm, "VMEM_BUDGET", budget)
        jax.clear_caches()    # the plan is read when the kernel is traced
        tp = gf2_bmvm.plan(m, *lut.shape)
        assert tp.rt < lut.shape[2] and tp.vmem_bytes <= budget
    vw = ref.gf2_pack_vector(V, k).astype(jnp.uint32)
    out_k = ops.gf2_bmvm(lut, vw, use_kernel=True)
    out_r = ref.gf2_bmvm(lut, vw)
    assert np.array_equal(np.asarray(out_k), np.asarray(out_r))
    # against the direct O(n^2) oracle
    direct = ref.gf2_matmul_oracle(A, V)
    assert np.array_equal(np.asarray(ref.gf2_unpack_vector(out_k, k)),
                          np.asarray(direct))


@pytest.mark.parametrize("c,p,r", [(3072, 256, 3072), (512, 256, 512),
                                   (512, 256, 128), (384, 16, 384), (8, 16, 8)])
@pytest.mark.parametrize("m", [1, 5, 8, 128, 1000, 1024, 4096])
def test_gf2_bmvm_tile_plan(m, c, p, r):
    """One LUT pass for every shape, tiles that divide it, VMEM in budget."""
    tp = gf2_bmvm.plan(m, c, p, r)
    assert tp.lut_passes == 1
    assert r % tp.rt == 0 and (tp.rt == r or tp.rt % 128 == 0)
    assert c % tp.ct == 0 and 1 <= tp.ct <= gf2_bmvm.MAX_CT
    assert tp.mp % 8 == 0 and 0 <= tp.mp - m < 8
    assert tp.vmem_bytes <= gf2_bmvm.VMEM_BUDGET < 16 * 2 ** 20


def test_gf2_bmvm_tile_plan_at_benchmark_shape():
    """The 9.66 GB LUT: the whole output row stays resident at M <= 128;
    M=1024 vectors (12 MiB of output) split R instead."""
    for m in (1, 128):
        tp = gf2_bmvm.plan(m, 3072, 256, 3072)
        assert (tp.rt, tp.ct) == (3072, 2)
    assert gf2_bmvm.plan(1024, 3072, 256, 3072).rt < 3072


def test_gf2_bmvm_publishes_lut_traffic():
    """``lut_passes`` and ``lut_bytes`` land in an enabled registry when the
    kernel is traced, and nothing lands once it is disabled."""
    from repro.telemetry.metrics import (MetricsRegistry, disable_metrics,
                                         enable_metrics)
    C, P, R = 8, 16, 8
    lut = jnp.asarray(np.random.default_rng(0).integers(0, 2 ** 32, (C, P, R)),
                      jnp.uint32)
    vw = jnp.zeros((3, C), jnp.uint32)
    reg = enable_metrics(MetricsRegistry())
    try:
        jax.clear_caches()
        ops.gf2_bmvm(lut, vw)
    finally:
        disable_metrics()
    assert reg.snapshot()["gauges"] == {"kernels.gf2_bmvm.lut_passes": 1,
                                        "kernels.gf2_bmvm.lut_bytes": C * P * R * 4}
    off = MetricsRegistry()
    enable_metrics(off)
    disable_metrics()
    jax.clear_caches()
    ops.gf2_bmvm(lut, vw)
    assert off.snapshot()["gauges"] == {}


@given(st.integers(0, 1000))
@settings(max_examples=15, deadline=None)
def test_gf2_linearity(seed):
    """A(u ⊕ v) == Au ⊕ Av — GF(2) linearity through the LUT datapath."""
    rng = np.random.default_rng(seed)
    n, k = 32, 4
    A = jnp.asarray(rng.integers(0, 2, (n, n)), jnp.uint8)
    u = jnp.asarray(rng.integers(0, 2, (1, n)), jnp.uint8)
    v = jnp.asarray(rng.integers(0, 2, (1, n)), jnp.uint8)
    lut = ref.gf2_preprocess(A, k)
    f = lambda x: np.asarray(ref.gf2_unpack_vector(
        ops.gf2_bmvm(lut, ref.gf2_pack_vector(x, k).astype(jnp.uint32)), k))
    assert np.array_equal(f(jnp.bitwise_xor(u, v)), f(u) ^ f(v))


def test_gf2_pack_unpack_roundtrip():
    rng = np.random.default_rng(7)
    v = jnp.asarray(rng.integers(0, 2, (3, 64)), jnp.uint8)
    for k in (4, 8, 16):
        w = ref.gf2_pack_vector(v, k)
        assert np.array_equal(np.asarray(ref.gf2_unpack_vector(w, k)), np.asarray(v))


# -- LDPC min-sum -------------------------------------------------------------

@pytest.mark.parametrize("shape", [(1, 3), (7, 3), (64, 6), (200, 4), (1000, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_minsum_kernel_sweep(shape, dtype):
    rng = np.random.default_rng(shape[0])
    u = jnp.asarray(rng.normal(size=shape) * 4, dtype)
    a = ops.minsum_check(u, use_kernel=True)
    b = ref.minsum_check(u)
    assert np.allclose(np.asarray(a), np.asarray(b), atol=1e-6)


@given(st.integers(2, 40), st.integers(2, 8), st.integers(0, 100))
@settings(max_examples=25, deadline=None)
def test_minsum_properties(n, deg, seed):
    rng = np.random.default_rng(seed)
    u = jnp.asarray(rng.normal(size=(n, deg)) * 3, jnp.float32)
    v = np.asarray(ref.minsum_check(u))
    un = np.asarray(u)
    for c in range(0, n, max(n // 3, 1)):
        for j in range(deg):
            others = np.delete(un[c], j)
            expect = np.prod(np.sign(others)) * np.abs(others).min()
            assert np.isclose(v[c, j], expect, atol=1e-5)


def test_minsum_positive_matches_paper_listing2():
    """Paper Listing 2: v1 = min(u2,u3) etc. for positive inputs, deg=3."""
    u = jnp.asarray([[1.0, 2.0, 3.0]])
    v = np.asarray(ref.minsum_check(u))[0]
    assert np.allclose(v, [2.0, 1.0, 1.0])


# -- particle filter histogram ------------------------------------------------

@pytest.mark.parametrize("N,px,B", [(1, 64, 8), (10, 300, 16), (33, 517, 12),
                                    (8, 1024, 32)])
def test_histogram_kernel_sweep(N, px, B):
    rng = np.random.default_rng(N + px)
    bins = jnp.asarray(rng.integers(0, B, (N, px)), jnp.int32)
    w = jnp.asarray(rng.uniform(0.1, 1, (px,)), jnp.float32)
    rh = jnp.asarray(rng.uniform(0, 1, (B,)), jnp.float32)
    rh = rh / rh.sum()
    h_k, bc_k = ops.particle_histogram(bins, w, rh, use_kernel=True)
    h_r = ref.weighted_histogram(bins, w, B)
    bc_r = ref.bhattacharyya(h_r, rh)
    assert np.allclose(np.asarray(h_k), np.asarray(h_r), atol=1e-5)
    assert np.allclose(np.asarray(bc_k), np.asarray(bc_r), atol=1e-5)


def test_histogram_normalized():
    rng = np.random.default_rng(3)
    bins = jnp.asarray(rng.integers(0, 8, (5, 100)), jnp.int32)
    w = jnp.ones((100,), jnp.float32)
    h, _ = ops.particle_histogram(bins, w, jnp.ones((8,)) / 8)
    assert np.allclose(np.asarray(h).sum(-1), 1.0, atol=1e-5)


# -- flash attention ----------------------------------------------------------

@pytest.mark.parametrize("B,Hq,Hkv,S,T,D", [
    (1, 4, 2, 64, 64, 32), (2, 2, 2, 37, 37, 16), (1, 8, 2, 16, 128, 32),
    (1, 2, 1, 128, 256, 64), (2, 4, 4, 100, 100, 8)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, Hq, Hkv, S, T, D, causal):
    rng = np.random.default_rng(S + T)
    q = jnp.asarray(rng.normal(size=(B, Hq, S, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Hkv, T, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Hkv, T, D)), jnp.float32)
    o_k = ops.flash_attention(q, k, v, causal, True)
    o_r = ref.mha(q, k, v, causal=causal)
    assert np.allclose(np.asarray(o_k), np.asarray(o_r), atol=3e-5), \
        np.abs(np.asarray(o_k) - np.asarray(o_r)).max()


def test_flash_attention_bf16():
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(1, 2, 32, 16)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 2, 32, 16)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, 2, 32, 16)), jnp.bfloat16)
    o_k = ops.flash_attention(q, k, v, True, True)
    o_r = ref.mha(q, k, v, causal=True)
    assert np.allclose(np.asarray(o_k, np.float32), np.asarray(o_r, np.float32),
                       atol=3e-2)


def test_flash_attention_grad_finite():
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(1, 4, 16, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 16, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 16, 8)), jnp.float32)
    g = jax.grad(lambda q_: ops.flash_attention(q_, k, v, True, False).sum())(q)
    assert bool(jnp.all(jnp.isfinite(g)))
