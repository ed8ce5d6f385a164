"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.kernels import gf2_bmvm, ops, ref


# -- GF(2) BMVM ---------------------------------------------------------------

# (n, k, m, VMEM budget of the stream's tile plan; None keeps the kernel's own)
GF2_SHAPES = [(n, k, m, None, f"{n}-{k}-{m}") for n, k, m in
              [(16, 4, 1), (32, 4, 3), (64, 8, 5), (128, 4, 2), (128, 8, 8)]] + [
    # C=32 column tiles: ct=4; M=13 is padded to 16
    (256, 8, 13, None, "256-8-13"),
    # R=256 words: a 40 KiB budget leaves rt=128, ct=1, grid (2, 256)
    (1024, 4, 21, 40 * 2 ** 10, "1024-4-21-rt128"),
    # R=384 words: a 48 KiB budget leaves rt=128, ct=2, grid (3, 192)
    (1536, 4, 5, 48 * 2 ** 10, "1536-4-5-rt128-ct2"),
]
# every shape on both paths; the stream keeps the ids it had as the only path
GF2_CASES = [pytest.param(n, k, m, budget, path, id=f"{i}-gather" if path == "gather" else i)
             for n, k, m, budget, i in GF2_SHAPES for path in ("gather", "stream")]


@pytest.fixture
def kernel_consts():
    """Set ``gf2_bmvm``'s module constants for one test.  The plan is read
    when the kernel is traced, so what was traced is dropped at each change
    and again once the constants are restored."""
    with pytest.MonkeyPatch.context() as mp:
        def set_consts(**consts):
            for name, value in consts.items():
                mp.setattr(gf2_bmvm, name, value)
            jax.clear_caches()
        yield set_consts
    jax.clear_caches()


def _share(path: str) -> float:
    """A gather share that steers the plan to ``path``: one no product
    reaches, or none at all."""
    return 1e9 if path == "gather" else 0.0


@pytest.mark.parametrize("n,k,m,budget,path", GF2_CASES)
def test_gf2_bmvm_kernel_vs_oracles(n, k, m, budget, path, kernel_consts):
    rng = np.random.default_rng(n + k)
    A = jnp.asarray(rng.integers(0, 2, (n, n)), jnp.uint8)
    V = jnp.asarray(rng.integers(0, 2, (m, n)), jnp.uint8)
    lut = ref.gf2_preprocess(A, k)
    assert lut.shape == (n // k, 2 ** k, n // k)
    kernel_consts(GATHER_SHARE=_share(path))
    # the budget splits the stream's R; the gather at these R keeps its own
    if budget is not None and path == "stream":
        kernel_consts(VMEM_BUDGET=budget)
        tp = gf2_bmvm.plan(m, *lut.shape)
        assert tp.rt < lut.shape[2] and tp.vmem_bytes <= budget
    assert gf2_bmvm.plan(m, *lut.shape).path == path
    vw = ref.gf2_pack_vector(V, k).astype(jnp.uint32)
    out_k = ops.gf2_bmvm(lut, vw, use_kernel=True)
    out_r = ref.gf2_bmvm(lut, vw)
    assert np.array_equal(np.asarray(out_k), np.asarray(out_r))
    # against the direct O(n^2) oracle
    direct = ref.gf2_matmul_oracle(A, V)
    assert np.array_equal(np.asarray(ref.gf2_unpack_vector(out_k, k)),
                          np.asarray(direct))


def _words_sharing_groups(c: int, p: int, seed: int) -> np.ndarray:
    """Four vectors' words: at every column tile vectors 0, 1 and 2 pick rows
    of one aligned group of 8 (vector 2 the same row as vector 0), and
    vector 3 a row of another group."""
    rng = np.random.default_rng(seed)
    base = rng.integers(0, p // 8, c) * 8
    w0 = base + rng.integers(0, 8, c)
    w1 = base + (w0 - base + 1 + rng.integers(0, 7, c)) % 8
    w3 = (base + 8 + rng.integers(0, 8, c)) % p
    return np.stack([w0, w1, w0, w3]).astype(np.uint32)


@pytest.mark.parametrize("path", ["gather", "stream"])
@pytest.mark.parametrize("words", ["m3-uniform", "m4-shared-groups"])
def test_gf2_bmvm_vectors_on_shared_row_groups(words, path, kernel_consts):
    """Several vectors a call, with some of them reading rows of one group
    of 8 at the same column tile: each XORs its own row, bit for bit."""
    n, k = 256, 8
    rng = np.random.default_rng(7)
    lut = ref.gf2_preprocess(jnp.asarray(rng.integers(0, 2, (n, n)), jnp.uint8), k)
    C, P, _ = lut.shape
    vw = (rng.integers(0, P, (3, C)).astype(np.uint32) if words == "m3-uniform"
          else _words_sharing_groups(C, P, seed=11))
    kernel_consts(GATHER_SHARE=_share(path))
    assert gf2_bmvm.plan(vw.shape[0], *lut.shape).path == path
    vw = jnp.asarray(vw)
    out_k = np.asarray(ops.gf2_bmvm(lut, vw, use_kernel=True))
    assert np.array_equal(out_k, np.asarray(ref.gf2_bmvm(lut, vw)))
    if words == "m4-shared-groups":
        assert np.array_equal(out_k[0], out_k[2]) and not np.array_equal(out_k[0], out_k[1])


@pytest.mark.parametrize("c,p,r", [(3072, 256, 3072), (512, 256, 512),
                                   (512, 256, 128), (384, 16, 384), (8, 16, 8)])
@pytest.mark.parametrize("m", [1, 5, 8, 128, 1000, 1024, 4096])
def test_gf2_bmvm_tile_plan(m, c, p, r):
    """The gather when its row groups are few enough and fit, else one LUT
    pass with tiles that divide it; VMEM in budget either way."""
    tp = gf2_bmvm.plan(m, c, p, r)
    gather = (p % 8 == 0 and m * c <= gf2_bmvm.GATHER_SHARE * c * p / 8
              and 4 * m * c <= gf2_bmvm.SMEM_BUDGET)
    assert tp.path == ("gather" if gather else "stream")
    if gather:
        assert (tp.mp, tp.ct, tp.rt) == (m, 1, r)
        assert (tp.lut_passes, tp.row_groups) == (0, m * c)
        assert tp.lut_bytes == m * c * 8 * r * 4 < c * p * r * 4
    else:
        assert (tp.lut_passes, tp.row_groups, tp.lut_bytes) == (1, 0, c * p * r * 4)
        assert r % tp.rt == 0 and (tp.rt == r or tp.rt % 128 == 0)
        assert c % tp.ct == 0 and 1 <= tp.ct <= gf2_bmvm.MAX_CT
        assert tp.mp % 8 == 0 and 0 <= tp.mp - m < 8
    assert tp.vmem_bytes <= gf2_bmvm.VMEM_BUDGET < 16 * 2 ** 20


def test_gf2_bmvm_tile_plan_at_benchmark_shape():
    """The 9.66 GB LUT: one vector gathers its 3072 row groups (302 MB, not
    the whole LUT); M=128 selects nearly every group and streams the LUT
    with the whole output row resident; M=1024 vectors (12 MiB of output)
    stream with R split."""
    tp = gf2_bmvm.plan(1, 3072, 256, 3072)
    assert (tp.path, tp.row_groups, tp.lut_passes) == ("gather", 3072, 0)
    assert tp.lut_bytes == 3072 * 8 * 3072 * 4 == 301_989_888
    tp = gf2_bmvm.plan(128, 3072, 256, 3072)
    assert (tp.path, tp.rt, tp.ct) == ("stream", 3072, 2)
    tp = gf2_bmvm.plan(1024, 3072, 256, 3072)
    assert tp.path == "stream" and tp.rt < 3072


def test_gf2_bmvm_publishes_lut_traffic(kernel_consts):
    """``lut_passes``, ``lut_bytes`` and ``row_groups`` land in an enabled
    registry when the kernel is traced, for the path it takes, and nothing
    lands once it is disabled."""
    from repro.telemetry.metrics import (MetricsRegistry, disable_metrics,
                                         enable_metrics)
    C, P, R = 8, 16, 8
    lut = jnp.asarray(np.random.default_rng(0).integers(0, 2 ** 32, (C, P, R)),
                      jnp.uint32)
    vw = jnp.zeros((3, C), jnp.uint32)
    expect = {"stream": {"kernels.gf2_bmvm.lut_passes": 1,
                         "kernels.gf2_bmvm.lut_bytes": C * P * R * 4,
                         "kernels.gf2_bmvm.row_groups": 0},
              "gather": {"kernels.gf2_bmvm.lut_passes": 0,
                         "kernels.gf2_bmvm.lut_bytes": 3 * C * 8 * R * 4,
                         "kernels.gf2_bmvm.row_groups": 3 * C}}
    for path, gauges in expect.items():
        kernel_consts(GATHER_SHARE=_share(path))
        reg = enable_metrics(MetricsRegistry())
        try:
            ops.gf2_bmvm(lut, vw)
        finally:
            disable_metrics()
        assert reg.snapshot()["gauges"] == gauges
    off = MetricsRegistry()
    enable_metrics(off)
    disable_metrics()
    jax.clear_caches()
    ops.gf2_bmvm(lut, vw)
    assert off.snapshot()["gauges"] == {}


@pytest.mark.parametrize("m,path,row_groups", [(1, "gather", 3072), (128, "stream", 0)])
def test_gf2_bmvm_publishes_row_groups_at_benchmark_shape(m, path, row_groups):
    """Traced (not run) at the benchmark's 9.66 GB LUT, the kernel reports
    the 3072 row groups one vector gathers, and none when M=128 streams."""
    from repro.telemetry.metrics import (MetricsRegistry, disable_metrics,
                                         enable_metrics)
    C, P, R = 3072, 256, 3072
    reg = enable_metrics(MetricsRegistry())
    try:
        jax.clear_caches()
        jax.eval_shape(gf2_bmvm.gf2_bmvm_pallas,
                       jax.ShapeDtypeStruct((C, P, R), jnp.uint32),
                       jax.ShapeDtypeStruct((m, C), jnp.uint32))
    finally:
        disable_metrics()
    gauges = reg.snapshot()["gauges"]
    assert gauges["kernels.gf2_bmvm.row_groups"] == row_groups
    assert gauges["kernels.gf2_bmvm.lut_passes"] == (path == "stream")
    assert gauges["kernels.gf2_bmvm.lut_bytes"] == gf2_bmvm.plan(m, C, P, R).lut_bytes


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
