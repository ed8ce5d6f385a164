"""MoE token dispatch over the compiled NoC route programs.

Four layers of guarantees:

* the **linearized route program** (`run_route_program(..., axis_name=)`) —
  the same compiled schedule the spmd executor runs, embedded in a single
  flat mesh axis — equals the transpose oracle for all 4 topologies;
* the **noc engine** matches the dense oracle on all 4 topologies and its
  flit/round/link-byte counters equal ``2 ×``
  :func:`repro.core.routing.route_program_stats` of the dispatched cube;
* **capacity semantics are unified**: gather and noc drop the *same tokens*
  under tight capacity (`dispatch_capacity` is the one shared budget, with
  ``NoCConfig.flit_buffer_depth`` as the knob and ``capacity_factor``
  derived);
* **fallbacks are loud**: engine demotions record a reason in
  `MoEDispatchStats.fallback` and warn.

Device tests run in a subprocess with fake CPU devices
(``XLA_FLAGS=--xla_force_host_platform_device_count``).
"""
import pytest

from tests.conftest import run_with_devices


# ---------------------------------------------------------------------------
# capacity helper (no devices)
# ---------------------------------------------------------------------------

def test_dispatch_capacity_one_formula():
    from repro.core.noc import NoCConfig
    from repro.models.moe import MoEConfig, dispatch_capacity, effective_capacity_factor

    c = MoEConfig(d_model=8, n_experts=8, top_k=2, d_ff=16, capacity_factor=1.0)
    # classic formula: max(8, tokens*k*cf/E), clamped to [1, tokens*k]
    assert dispatch_capacity(64, c) == 64 * 2 * 1.0 / 8
    assert dispatch_capacity(16, c) == 8       # legacy floor of 8 slots ...
    assert dispatch_capacity(2, c) == 2 * 2    # ... keeps tiny decode drop-free
    big = MoEConfig(8, 8, 2, 16, capacity_factor=100.0)
    assert dispatch_capacity(4, big) == 4 * 2    # ceiling: every packet fits
    # flit_buffer_depth IS the knob when a NoCConfig is attached
    cd = MoEConfig(8, 8, 2, 16, capacity_factor=1.0,
                   noc=NoCConfig(flit_buffer_depth=3))
    assert dispatch_capacity(16, cd) == 3
    # ... and capacity_factor is derived from it, not configured
    assert effective_capacity_factor(16, cd) == 3 * 8 / (16 * 2)
    assert effective_capacity_factor(64, c) == 1.0   # formula path round-trips
    assert effective_capacity_factor(16, c) == 2.0   # ... and reports the floor


def test_moe_stats_as_dict_fields():
    from repro.models.moe import MoEDispatchStats

    st = MoEDispatchStats(engine="noc", topology="ring", fallback=None,
                          capacity=4, capacity_factor=1.0, flits=10, rounds=6,
                          link_bytes=100, drops=2, peak_occupancy=5)
    d = st.as_dict()
    assert d["drops"] == 2 and d["rounds"] == 6 and d["topology"] == "ring"


# ---------------------------------------------------------------------------
# linearized route program == transpose oracle (device lowering)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_linearized_route_program_matches_oracle():
    """run_route_program over ONE flat mesh axis (the MoE's 'model' axis)
    equals the fused all_to_all transpose for every topology — the 2D
    programs' per-axis hops expand to full-axis ppermutes."""
    run_with_devices("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro.core import compile_routes, make_topology, run_route_program, transpose_oracle
for n in (4, 8):
    mesh = Mesh(np.array(jax.devices()[:n]), ("model",))
    rng = np.random.default_rng(n)
    x = jnp.asarray(rng.normal(size=(n * n, 3)), jnp.float32)   # (n*n, chunk)
    for name in ("fattree", "ring", "mesh2d", "torus2d"):
        prog = compile_routes(make_topology(name, n))
        def routed(xl, prog=prog):
            return run_route_program(xl.reshape(n, -1), prog,
                                     axis_name="model").reshape(xl.shape)
        def oracle(xl):
            return transpose_oracle(xl.reshape(n, -1), "model").reshape(xl.shape)
        sm = lambda f: jax.shard_map(f, mesh=mesh, in_specs=P("model"),
                                 out_specs=P("model"), check_vma=False)
        got = np.asarray(sm(routed)(x))
        want = np.asarray(sm(oracle)(x))
        assert np.array_equal(got, want), (name, n)
print("OK")
""", n_devices=8)


# ---------------------------------------------------------------------------
# noc engine: counters == 2x route_program_stats, all topologies
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_moe_noc_counters_match_route_program_stats():
    run_with_devices("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.core.routing import compile_routes, route_program_stats
from repro.core.noc import NoCConfig
from repro.core.topology import make_topology
from repro.models import moe as M
from repro.models.layers import init_params
n = 8
mesh = Mesh(np.array(jax.devices()).reshape(1, n), ("data", "model"))
rng = np.random.default_rng(2)
E, d, k = 16, 32, 2
dense = M.MoEConfig(d, E, k, 48, impl="dense")
params = init_params(M.moe_specs(dense), jax.random.key(0))
x = jnp.asarray(rng.normal(size=(2, 32, d)), jnp.float32)
ncfg = NoCConfig(flit_buffer_depth=4)
with jax.set_mesh(mesh):
    ref, _, _ = M.moe_apply(params, x, dense)
    for topo in ("fattree", "ring", "mesh2d", "torus2d"):
        c = M.MoEConfig(d, E, k, 48, impl="noc", noc_topology=topo, noc=ncfg)
        out, _, st = M.moe_apply(params, x, c)
        # exact counters: two trips (out + back) of the compiled program
        prog = compile_routes(make_topology(topo, n))
        msg = (E // n) * st.capacity * d * 4       # one (src,dst) token cube
        ss = route_program_stats(prog, n * n * msg)
        assert st.rounds == 2 * ss.rounds, topo
        assert st.link_bytes == 2 * ss.link_bytes, topo
        assert st.flits == 2 * n * n * ncfg.flits_for(msg), topo
        assert st.capacity == 4 and st.engine == "noc"
print("OK")
""", n_devices=8)


# ---------------------------------------------------------------------------
# unified capacity: gather == noc under tight capacity (drop parity)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_moe_capacity_parity_gather_vs_noc():
    """The same flit_buffer_depth drops the SAME tokens in both engines —
    outputs bit-close, drop counts and peak occupancy identical, across the
    whole depth sweep (including heavy-drop depth=1)."""
    run_with_devices("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.core.noc import NoCConfig
from repro.models import moe as M
from repro.models.layers import init_params
mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
rng = np.random.default_rng(1)
base = M.MoEConfig(d_model=32, n_experts=8, top_k=2, d_ff=64, impl="dense")
params = init_params(M.moe_specs(base), jax.random.key(0))
x = jnp.asarray(rng.normal(size=(4, 16, 32)), jnp.float32)
prev = None
with jax.set_mesh(mesh):
    for depth in (1, 2, 4, 8):
        ncfg = NoCConfig(flit_buffer_depth=depth)
        og, _, sg = M.moe_apply(params, x, M.MoEConfig(
            32, 8, 2, 64, impl="gather", noc=ncfg))
        on, _, sn = M.moe_apply(params, x, M.MoEConfig(
            32, 8, 2, 64, impl="noc", noc_topology="torus2d", noc=ncfg))
        assert sg.capacity == sn.capacity == depth
        assert int(sg.drops) == int(sn.drops), depth
        assert int(sg.peak_occupancy) == int(sn.peak_occupancy), depth
        assert float(jnp.max(jnp.abs(og - on))) < 1e-5, depth
        if prev is not None:
            assert int(sn.drops) <= prev, "drops must shrink with depth"
        prev = int(sn.drops)
    assert prev == 0            # deep enough buffer => drop-free
print("OK")
""", n_devices=8)


# ---------------------------------------------------------------------------
# loud fallbacks
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_moe_fallback_reasons_and_warnings():
    run_with_devices("""
import warnings
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.models import moe as M
from repro.models.layers import init_params
mesh = Mesh(np.array(jax.devices()).reshape(1, 4), ("data", "model"))
rng = np.random.default_rng(3)
x = jnp.asarray(rng.normal(size=(2, 8, 32)), jnp.float32)
with jax.set_mesh(mesh):
    # trigger 1: n_experts % n_ranks != 0 -> every rank holds all experts, warns
    bad = M.MoEConfig(32, 6, 2, 64, impl="gather")
    params = init_params(M.moe_specs(bad), jax.random.key(0))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        _, _, st = M.moe_apply(params, x, bad)
    assert st.engine == "held" and "not divisible" in st.fallback
    assert any("not divisible" in str(m.message) for m in w)
    # trigger 2: decode-shaped input demotes noc -> gather, warns
    dec = M.MoEConfig(32, 8, 2, 64, impl="noc")
    params = init_params(M.moe_specs(dec), jax.random.key(0))
    xd = jnp.asarray(rng.normal(size=(2, 2, 32)), jnp.float32)  # S=2 < 4
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        _, _, st = M.moe_apply(params, xd, dec)
    assert st.engine == "gather" and "decode-shaped" in st.fallback
    assert any("decode-shaped" in str(m.message) for m in w)
# no mesh: the one-chip held path — no fallback, NO warning
c = M.MoEConfig(32, 8, 2, 64, impl="gather")
params = init_params(M.moe_specs(c), jax.random.key(0))
with warnings.catch_warnings(record=True) as w:
    warnings.simplefilter("always")
    _, _, st = M.moe_apply(params, x, c)
assert st.engine == "held" and st.fallback is None
assert not any("moe_apply" in str(m.message) for m in w)
print("OK")
""", n_devices=4)


# ---------------------------------------------------------------------------
# stats thread through the full transformer stack
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_moe_stats_thread_through_transformer():
    """forward/loss surface moe_drops / moe_peak_occupancy from the stacked
    MoE layers (noc engine, tight capacity => nonzero drops in metrics)."""
    run_with_devices("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.configs import get_config
from repro.models import transformer as T
from repro.models.layers import init_params
mesh = Mesh(np.array(jax.devices()).reshape(1, 4), ("data", "model"))
cfg = get_config("qwen3-moe-235b-a22b", smoke=True).replace(
    moe_impl="noc", moe_topology="mesh2d", moe_flit_buffer_depth=1)
params = init_params(T.abstract_params(cfg), jax.random.key(0))
rng = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (2, 16)), jnp.int32),
         "labels": jnp.asarray(rng.integers(0, cfg.vocab, (2, 16)), jnp.int32)}
with jax.set_mesh(mesh):
    loss, mets = T.loss(params, batch, cfg)
assert np.isfinite(float(loss))
assert "moe_drops" in mets and "moe_peak_occupancy" in mets
assert float(mets["moe_drops"]) > 0        # depth=1 must drop at T=32,k=2,E=8
assert float(mets["moe_peak_occupancy"]) > 0
print("OK")
""", n_devices=4)
