"""SPMD flit-program execution: the compiled schedule→ppermute lowering.

Three layers of guarantees:

* the **compiler** (`compile_routes`) round-trips every message exactly once
  with conserved flit bytes, and its numpy interpreter + analytic stats are
  bit-identical to the handwritten round-by-round simulator (property-tested,
  no devices needed);
* the **device lowering** (`run_route_program` under shard_map) equals the
  transpose oracle on a fake-device mesh;
* the **executor** (`NoCExecutor.run(..., mode="spmd")`) is bit-identical —
  outputs *and* NoCStats — to ``mode="sim"`` and ``mode="direct"`` for all 4
  topologies on all three paper apps (differential harness, subprocess with 8
  fake CPU devices via ``XLA_FLAGS=--xla_force_host_platform_device_count``).
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (compile_routes, make_topology, route_program_stats,
                        simulate_route_program, simulate_schedule)
from tests.conftest import run_with_devices

TOPOLOGIES = ["ring", "mesh", "torus", "fattree"]


# ---------------------------------------------------------------------------
# schedule → ppermute compiler (no devices)
# ---------------------------------------------------------------------------

def test_compiled_rounds_match_simulator():
    for name in TOPOLOGIES:
        for n in (2, 4, 6, 8, 9, 12, 16):
            topo = make_topology(name, n)
            prog = compile_routes(topo)
            msgs = np.ones((n, n, 4), np.uint8)
            _, stats = simulate_schedule(topo, msgs)
            assert prog.n_rounds == stats.rounds <= topo.a2a_rounds(), (name, n)


@given(st.sampled_from(TOPOLOGIES), st.sampled_from([2, 4, 6, 8, 9, 12, 16]),
       st.integers(1, 9), st.integers(0, 1000))
@settings(max_examples=30, deadline=None)
def test_compiled_program_matches_simulator(name, n, c, seed):
    """Compiled hop decomposition == handwritten simulator: same delivery,
    same rounds, same link bytes, on random message cubes."""
    rng = np.random.default_rng(seed)
    topo = make_topology(name, n)
    prog = compile_routes(topo)
    msgs = rng.integers(0, 255, size=(n, n, c), dtype=np.uint8)
    d_sim, s_sim = simulate_schedule(topo, msgs)
    d_prog, s_prog = simulate_route_program(prog, msgs)
    assert np.array_equal(d_prog, d_sim)
    assert (s_prog.rounds, s_prog.link_bytes) == (s_sim.rounds, s_sim.link_bytes)
    s_model = route_program_stats(prog, msgs.nbytes)
    assert (s_model.rounds, s_model.link_bytes) == (s_sim.rounds, s_sim.link_bytes)


@given(st.sampled_from(TOPOLOGIES), st.sampled_from([3, 4, 6, 8, 12]),
       st.integers(0, 1000))
@settings(max_examples=30, deadline=None)
def test_hop_decomposition_conserves_messages(name, n, seed):
    """Round-trip property: every (src, dst) pair's flits arrive exactly once
    — nothing dropped, nothing duplicated — and total payload bytes are
    conserved through the per-hop permute rounds."""
    rng = np.random.default_rng(seed)
    topo = make_topology(name, n)
    prog = compile_routes(topo)
    # tag every (src, dst, byte) cell uniquely so duplication/loss is visible
    msgs = rng.permuted(
        np.arange(n * n * 4, dtype=np.uint32)).reshape(n, n, 4)
    delivered, _ = simulate_route_program(prog, msgs)
    # exactly-once delivery to the right node: delivered[d, s] == msgs[s, d]
    for s in range(n):
        for d in range(n):
            assert np.array_equal(delivered[d, s], msgs[s, d]), (name, s, d)
    # conservation: the delivered cube is a permutation of the sent cube
    assert np.array_equal(np.sort(delivered, axis=None), np.sort(msgs, axis=None))
    assert delivered.nbytes == msgs.nbytes


@given(st.sampled_from(TOPOLOGIES), st.sampled_from([4, 8, 9, 16]))
@settings(max_examples=16, deadline=None)
def test_permutation_rounds_are_permutations(name, n):
    """Every compiled hop is a valid ppermute argument: distinct sources,
    distinct destinations, neighbor links only."""
    topo = make_topology(name, n)
    prog = compile_routes(topo)
    for phase in prog.phases:
        size = phase.sched.size
        for rnd in phase.rounds:
            for mv in rnd.moves:
                srcs = [s for s, _ in mv.perm]
                dsts = [d for _, d in mv.perm]
                assert len(set(srcs)) == len(srcs)
                assert len(set(dsts)) == len(dsts)
                for s, d in mv.perm:
                    assert 0 <= s < size and 0 <= d < size
                    assert (d - s) % size in (1, size - 1)   # single hop
                assert len(mv.src_table) == size


# ---------------------------------------------------------------------------
# device lowering (subprocess, fake devices)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_run_route_program_matches_oracle_on_devices():
    run_with_devices("""
import numpy as np, jax
from jax.sharding import Mesh, PartitionSpec as P
from repro.core import compile_routes, make_topology
from repro.core.routing import run_route_program
for name in ("ring", "mesh", "torus", "fattree"):
    for n in (4, 12):
        topo = make_topology(name, n)
        prog = compile_routes(topo)
        sizes = [s for _, s in prog.axes]
        names = tuple(a for a, _ in prog.axes)
        mesh = Mesh(np.array(jax.devices()[:n]).reshape(sizes), names)
        def device_fn(local):
            x = local.reshape(local.shape[len(sizes):])
            return run_route_program(x, prog).reshape(local.shape)
        rng = np.random.default_rng(n)
        cube = rng.integers(0, 255, (n, n, 7)).astype(np.uint8)
        sm = jax.shard_map(device_fn, mesh=mesh, in_specs=P(*names),
                       out_specs=P(*names), check_vma=False)
        out = np.asarray(jax.jit(sm)(cube.reshape(sizes + [n, 7])))
        assert np.array_equal(out.reshape(n, n, 7), cube.swapaxes(0, 1)), (name, n)
print("OK")
""", n_devices=12)


# ---------------------------------------------------------------------------
# differential harness: mode="spmd" == mode="sim" == mode="direct"
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_spmd_executor_diamond_all_topologies():
    """Generic-graph differential incl. run_batch: spmd == sim == direct,
    outputs and NoCStats, with random placements and a 2-pod cut."""
    run_with_devices("""
import numpy as np, jax.numpy as jnp
from repro.core import NoCExecutor, PE, Port, TaskGraph, cut, make_topology

def diamond():
    g = TaskGraph("diamond")
    g.add(PE("src", lambda x: {"a": x + 1, "b": x * 3}, (Port("x", (4,)),),
             (Port("a", (4,)), Port("b", (4,)))))
    g.add(PE("l", lambda a: {"o": a * a}, (Port("a", (4,)),), (Port("o", (4,)),)))
    g.add(PE("r", lambda b: {"o": b - 2}, (Port("b", (4,)),), (Port("o", (4,)),)))
    g.add(PE("join", lambda l, r: {"out": l + r},
             (Port("l", (4,)), Port("r", (4,))), (Port("out", (4,)),)))
    g.connect("src.a", "l.a"); g.connect("src.b", "r.b")
    g.connect("l.o", "join.l"); g.connect("r.o", "join.r")
    return g

for topo_name in ("ring", "mesh", "torus", "fattree"):
    for seed in (0, 1, 2):
        g = diamond()
        n = 6
        rng = np.random.default_rng(seed)
        placement = {name: int(rng.integers(0, n)) for name in g.pes}
        pods = list(np.random.default_rng(seed + 1).integers(0, 2, n))
        ex = NoCExecutor(g, make_topology(topo_name, n), placement=placement,
                         plan=cut(g, placement, pods))
        inp = {"src.x": jnp.arange(4.0)}
        direct = g.run(inp)
        sim, st_sim = ex.run(inp, mode="sim")
        spmd, st_spmd = ex.run(inp, mode="spmd")
        buffered, st_buf = ex.run(inp, mode="buffered")
        for k in direct:
            assert np.array_equal(np.asarray(spmd[k]), np.asarray(direct[k])), (topo_name, k)
            assert np.array_equal(np.asarray(spmd[k]), np.asarray(sim[k])), (topo_name, k)
            assert np.array_equal(np.asarray(buffered[k]), np.asarray(sim[k])), (topo_name, k)
        assert st_spmd.as_dict() == st_sim.as_dict(), (topo_name, seed)
        # buffered payload parity: static accounting matches sim exactly
        for f in ("waves", "payload_bytes", "flits", "cross_pod_msgs",
                  "cross_pod_wire_bytes", "cross_pod_beats"):
            assert getattr(st_buf, f) == getattr(st_sim, f), (topo_name, seed, f)
        assert st_buf.switch_cycles == st_buf.rounds > 0, (topo_name, seed)
        B = 3
        binp = {"src.x": np.stack([np.arange(4.0) * (b + 1) for b in range(B)])}
        bs, stb_sim = ex.run_batch(binp, mode="sim")
        bp, stb_spmd = ex.run_batch(binp, mode="spmd")
        bd, _ = ex.run_batch(binp, mode="direct")
        for k in bs:
            assert np.array_equal(bp[k], bs[k]), (topo_name, k)
            assert np.array_equal(bp[k], bd[k]), (topo_name, k)
        assert stb_spmd.as_dict() == stb_sim.as_dict(), (topo_name, seed)
print("OK")
""", n_devices=8)


@pytest.mark.slow
def test_spmd_differential_bmvm():
    """BMVM (case study III) on all 4 topologies: spmd == sim == software."""
    run_with_devices("""
import numpy as np, jax.numpy as jnp
from repro.apps import bmvm

rng = np.random.default_rng(0)
cfg = bmvm.BMVMConfig(n=64, k=8, fold=2)          # 4 PEs -> 8 NoC nodes
A = rng.integers(0, 2, (64, 64)).astype(np.uint8)
v = rng.integers(0, 2, (64,)).astype(np.uint8)
lut = bmvm.preprocess(A, cfg)
sw = bmvm.software_ref(A, v[None], 3)
for topo in ("ring", "mesh", "torus", "fattree"):
    out_sim, st_sim = bmvm.iterate_noc_sim(jnp.asarray(lut), v, cfg, 3,
                                           topology=topo, mode="sim")
    out_spmd, st_spmd = bmvm.iterate_noc_sim(jnp.asarray(lut), v, cfg, 3,
                                             topology=topo, mode="spmd")
    assert np.array_equal(out_spmd, out_sim), topo
    assert np.array_equal(out_spmd.reshape(1, -1), sw), topo
    assert st_spmd.as_dict() == st_sim.as_dict(), topo
print("OK")
""", n_devices=8)


@pytest.mark.slow
def test_spmd_differential_ldpc():
    """LDPC min-sum (case study I) on all 4 topologies: identical decode and
    flit accounting between spmd and sim."""
    run_with_devices("""
import numpy as np
from repro.apps import ldpc

rng = np.random.default_rng(0)
H = ldpc.fano_plane_H()
llr = ldpc.awgn_llr(np.zeros(7, np.int8), 3.0, rng)
for topo in ("ring", "mesh", "torus", "fattree"):
    bits_sim, post_sim, st_sim = ldpc.decode_on_noc(H, llr, 5, topology=topo,
                                                    n_nodes=8, mode="sim")
    bits_spmd, post_spmd, st_spmd = ldpc.decode_on_noc(H, llr, 5, topology=topo,
                                                       n_nodes=8, mode="spmd")
    assert np.array_equal(bits_spmd, bits_sim), topo
    assert np.array_equal(post_spmd, post_sim), topo
    assert st_spmd.as_dict() == st_sim.as_dict(), topo
print("OK")
""", n_devices=8)


@pytest.mark.slow
def test_spmd_differential_particle_filter():
    """Particle filter (case study II) on all 4 topologies: identical track."""
    run_with_devices("""
import numpy as np
from repro.apps import particle_filter as pf

rng = np.random.default_rng(3)
cfg = pf.PFConfig(img=64, roi=16, n_particles=64, n_bins=16)
frames, _ = pf.synth_video(cfg, 4, rng)
for topo in ("ring", "mesh", "torus", "fattree"):
    c_sim, st_sim = pf.track_on_noc(frames, cfg, n_pe=4, topology=topo,
                                    n_nodes=8, mode="sim")
    c_spmd, st_spmd = pf.track_on_noc(frames, cfg, n_pe=4, topology=topo,
                                      n_nodes=8, mode="spmd")
    assert np.array_equal(c_spmd, c_sim), topo
    assert st_spmd.as_dict() == st_sim.as_dict(), topo
print("OK")
""", n_devices=8)


# ---------------------------------------------------------------------------
# placement → device-mesh assignment
# ---------------------------------------------------------------------------

def test_placement_to_device_coords():
    from repro.core import (node_device_coords, optimize_placement,
                            placement_to_device_coords)
    from repro.apps import ldpc

    g, _ = ldpc.build_ldpc_graph(ldpc.fano_plane_H())
    topo = make_topology("mesh", 16)
    placement = optimize_placement(g, topo, iters=300, seed=0)
    coords = placement_to_device_coords(placement, topo)
    assert set(coords) == set(g.pes)
    for pe, node in placement.items():
        x, y = topo.coords(node)
        assert coords[pe] == {"noc_y": y, "noc_x": x}
        # round-trip: coords identify the node the PE was placed on
        assert topo.node(coords[pe]["noc_x"], coords[pe]["noc_y"]) == node
    ring = make_topology("ring", 5)
    assert node_device_coords(ring, 3) == {"noc": 3}
    with pytest.raises(ValueError):
        node_device_coords(ring, 7)


def test_mesh_for_topology_insufficient_devices():
    """Single-device default environment: the spmd path must fail fast with
    an actionable error, not a shape error deep in shard_map."""
    import jax

    from repro.core import mesh_for_topology

    topo = make_topology("ring", 64)
    if jax.device_count() >= 64:
        pytest.skip("environment has enough devices")
    with pytest.raises(RuntimeError, match="xla_force_host_platform_device_count"):
        mesh_for_topology(topo)
