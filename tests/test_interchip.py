"""Inter-chip bridge subsystem: compiled route programs across pod cuts.

Four layers of guarantees:

* the **compiler** (`compile_bridges`) splits every schedule into per-pod
  programs + bridges that exactly partition the physical link traversals;
* the **simulator** (`simulate_bridged_program`) is bit-identical in delivery
  and ScheduleStats to the unpartitioned program — the cut is semantically
  transparent — while physically serializing every crossing buffer, and the
  **analytic** `bridge_program_stats` matches its BridgeStats exactly;
* the **executor** (`NoCExecutor(plan=...)`) keeps all three case-study apps
  bit-identical under any cut, with only the ``bridge_*`` NoCStats counters
  differing from the unpartitioned run;
* the **spmd lowering** (`run_bridged_program` over the ``(pod, node)`` mesh)
  equals partitioned sim in outputs *and* NoCStats — bridge counters included
  — for all 3 apps × topologies × pod cuts (subprocess, 8 fake CPU devices).
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.core import (BridgeConfig, NoCExecutor, PE, Port, TaskGraph,
                        bridge_program_stats, compile_bridges, compile_routes,
                        cut, make_topology, simulate_bridged_program,
                        simulate_route_program)
from repro.core.interchip import _walk_rounds
from repro.core.partition import PartitionPlan
from repro.core.serdes import QuasiSerdesConfig
from tests.conftest import run_with_devices

TOPOLOGIES = ["ring", "mesh", "torus", "fattree"]


def _plan_for(pods, serdes=None):
    return PartitionPlan({}, tuple(pods), (), (),
                         serdes or QuasiSerdesConfig(wire_bits=16, lanes=4))


def _pod_patterns(n, seed):
    rng = np.random.default_rng(seed)
    return [
        tuple(i // ((n + 1) // 2) for i in range(n)),   # blocked halves
        tuple(i % 2 for i in range(n)),                 # interleaved
        tuple(int(x) for x in rng.integers(0, 3, n)),   # random 3-pod
    ]


# ---------------------------------------------------------------------------
# compiler: per-pod split + bridge discovery
# ---------------------------------------------------------------------------

@given(st.sampled_from(TOPOLOGIES), st.sampled_from([4, 6, 8, 9, 12]),
       st.integers(0, 100))
@settings(max_examples=25, deadline=None)
def test_compile_bridges_partitions_traversals(name, n, seed):
    """Every physical link traversal of every round lands in exactly one
    bucket — some pod's intra list or a bridge — and bridge endpoints always
    sit in different pods."""
    topo = make_topology(name, n)
    prog = compile_routes(topo)
    for pods in _pod_patterns(n, seed):
        bprog = compile_bridges(prog, _plan_for(pods))
        assert bprog.n_pods == max(pods) + 1
        for b in bprog.bridges:
            assert pods[b.src] != pods[b.dst]
            assert (b.src_pod, b.dst_pod) == (pods[b.src], pods[b.dst])
        for rnd, (den, pairs) in zip(bprog.rounds, _walk_rounds(prog)):
            assert rnd.den == den
            split = list(rnd.intra) + [
                (bprog.bridges[i].src, bprog.bridges[i].dst)
                for i in rnd.cross]
            assert sorted(split) == sorted(pairs)
        # per-pod programs: intra hops partition by source pod
        for rnd_idx, rnd in enumerate(bprog.rounds):
            by_pods = [pr for pp in bprog.pods for pr in pp.rounds[rnd_idx]]
            assert sorted(by_pods) == sorted(rnd.intra)
        for pp in bprog.pods:
            assert all(pods[i] == pp.pod for i in pp.nodes)
            assert all(bprog.bridges[i].src_pod == pp.pod for i in pp.egress)
            assert all(bprog.bridges[i].dst_pod == pp.pod for i in pp.ingress)


def test_compile_bridges_single_pod_has_no_bridges():
    for name in TOPOLOGIES:
        topo = make_topology(name, 6)
        bprog = compile_bridges(compile_routes(topo), _plan_for([0] * 6))
        assert bprog.bridges == ()
        assert all(not r.cross for r in bprog.rounds)


def test_compile_bridges_rejects_wrong_node_count():
    topo = make_topology("ring", 6)
    with pytest.raises(ValueError, match="plan covers"):
        compile_bridges(compile_routes(topo), _plan_for([0, 1]))


def test_transfer_hook_guards():
    """run_route_program must refuse transfer= misuse instead of silently
    executing cut links un-bridged: non-linearized calls and fused programs
    (whose crossbar has no hop moves) both raise."""
    from repro.core import run_route_program

    ring = compile_routes(make_topology("ring", 4))
    with pytest.raises(ValueError, match="linearized"):
        run_route_program(jnp.zeros((4, 2)), ring, transfer=lambda b, p: b)
    fat = compile_routes(make_topology("fattree", 4))
    with pytest.raises(ValueError, match="fused"):
        run_route_program(jnp.zeros((4, 2)), fat, axis_name="noc",
                          transfer=lambda b, p: b)


# ---------------------------------------------------------------------------
# simulator: the cut is semantically transparent; analytic stats are exact
# ---------------------------------------------------------------------------

@given(st.sampled_from(TOPOLOGIES), st.sampled_from([4, 6, 8, 9, 12]),
       st.integers(1, 9), st.integers(0, 1000))
@settings(max_examples=30, deadline=None)
def test_bridged_simulator_transparent_and_exact(name, n, c, seed):
    """Partitioned delivery == unpartitioned delivery (bit for bit), same
    rounds/link_bytes, and `bridge_program_stats` == the simulator's
    BridgeStats — per bridge included."""
    rng = np.random.default_rng(seed)
    topo = make_topology(name, n)
    prog = compile_routes(topo)
    msgs = rng.integers(0, 255, size=(n, n, c), dtype=np.uint8)
    d_ref, s_ref = simulate_route_program(prog, msgs)
    for pods in _pod_patterns(n, seed):
        bprog = compile_bridges(prog, _plan_for(pods),
                                BridgeConfig(serdes=QuasiSerdesConfig(
                                    wire_bits=16, lanes=2), fifo_depth=4))
        d, s, b = simulate_bridged_program(bprog, msgs)
        assert np.array_equal(d, d_ref)
        assert (s.rounds, s.link_bytes) == (s_ref.rounds, s_ref.link_bytes)
        b_ana = bridge_program_stats(bprog, msgs.nbytes)
        assert b_ana.as_dict() == b.as_dict()
        if any(pods[s_] != pods[d_] for s_, d_ in
               [(bl.src, bl.dst) for bl in bprog.bridges]):
            assert b.beats > 0 and b.wire_bytes > 0


def test_bridged_simulator_batched_matches_per_item():
    rng = np.random.default_rng(7)
    topo = make_topology("torus", 8)
    bprog = compile_bridges(compile_routes(topo), _plan_for([0] * 4 + [1] * 4))
    msgs = rng.integers(0, 255, (3, 8, 8, 5), dtype=np.uint8)
    db, sb, bb = simulate_bridged_program(bprog, msgs, batched=True)
    assert np.array_equal(db, msgs.swapaxes(1, 2))
    for i in range(3):
        di, _, _ = simulate_bridged_program(bprog, msgs[i])
        assert np.array_equal(db[i], di)
    # bytes scale with B through the actual payload
    _, s1, b1 = simulate_bridged_program(bprog, msgs[0])
    assert sb.rounds == s1.rounds
    assert sb.link_bytes == 3 * s1.link_bytes
    assert bb.wire_bytes == 3 * b1.wire_bytes


def test_non_uint8_payloads_roundtrip_through_bridges():
    """The wire framing is dtype-agnostic (operates on the byte view)."""
    rng = np.random.default_rng(3)
    topo = make_topology("mesh", 6)
    bprog = compile_bridges(compile_routes(topo), _plan_for([0, 1, 0, 1, 0, 1]))
    msgs = rng.normal(size=(6, 6, 3)).astype(np.float32)
    d, _, b = simulate_bridged_program(bprog, msgs)
    assert d.dtype == np.float32
    assert np.array_equal(d, msgs.swapaxes(0, 1))
    assert b.beats > 0


# ---------------------------------------------------------------------------
# bridge FIFO / bandwidth model
# ---------------------------------------------------------------------------

def test_bridge_fifo_model():
    """Framing, bandwidth and back-pressure semantics of one bridge:
    beats = padded words / lanes; total stall rounds are bandwidth-limited
    (depth-invariant — the serial link can only move ``lanes`` words/round);
    the FIFO depth bounds peak occupancy and shifts stalls between
    back-pressure during the schedule and the terminal drain."""
    topo = make_topology("ring", 4)
    prog = compile_routes(topo)
    pods = [0, 0, 1, 1]
    msgs = np.zeros((4, 4, 10), np.uint8)    # 40 B/traversal on each cut link
    serdes = QuasiSerdesConfig(wire_bits=16, lanes=2)
    stalls, peaks = [], []
    for depth in (1, 2, 16, 1024):
        bprog = compile_bridges(prog, _plan_for(pods),
                                BridgeConfig(serdes=serdes, fifo_depth=depth))
        _, _, b = simulate_bridged_program(bprog, msgs)
        stalls.append(b.stall_rounds)
        peaks.append(b.peak_fifo)
        assert b.peak_fifo <= depth          # the FIFO is physically bounded
        # one traversal = ceil(40/2) = 20 words, already a lanes multiple
        for pb in b.per_bridge.values():
            assert pb["wire_bytes"] % (serdes.lanes * serdes.beat_bytes) == 0
            assert pb["beats"] == pb["wire_bytes"] // serdes.beat_bytes // serdes.lanes
        assert b.peak_fifo >= 1
    # with depth >= lanes the serial link runs at full rate and stalls are
    # bandwidth-conserved: depth only moves them between back-pressure and
    # the terminal drain; a FIFO shallower than the lane count starves the
    # serializer and really does stall longer
    assert len(set(stalls[1:])) == 1 and stalls[1] > 0, stalls
    assert stalls[0] > stalls[1], stalls
    # deeper FIFOs absorb bigger bursts
    assert peaks == sorted(peaks) and peaks[0] < peaks[-1], peaks


def test_bridge_stats_scale_with_wire_width():
    """Halving the wire width doubles the beats (same bytes, narrower link)."""
    topo = make_topology("mesh", 8)
    prog = compile_routes(topo)
    pods = [0] * 4 + [1] * 4
    msgs = np.ones((8, 8, 16), np.uint8)
    beats = {}
    for wb in (8, 16, 32):
        bprog = compile_bridges(prog, _plan_for(pods),
                                BridgeConfig(serdes=QuasiSerdesConfig(
                                    wire_bits=wb, lanes=1)))
        beats[wb] = bridge_program_stats(bprog, msgs.nbytes).beats
    assert beats[8] == 2 * beats[16] == 4 * beats[32]


# ---------------------------------------------------------------------------
# executor: partitioned == unpartitioned for the apps (sim, no devices)
# ---------------------------------------------------------------------------

def _stats_equal_modulo_bridge(a, b):
    da, db = a.as_dict(), b.as_dict()
    for k in da:
        if not (k.startswith("bridge_") or k.startswith("cross_pod_")):
            assert da[k] == db[k], (k, da[k], db[k])


@pytest.mark.parametrize("topo_name", ["mesh", "ring"])
@pytest.mark.parametrize("pods", [[0] * 8 + [1] * 8,
                                  [0, 1] * 8,
                                  [0] * 4 + [1] * 4 + [2] * 4 + [3] * 4])
def test_ldpc_partitioned_identical(topo_name, pods):
    from repro.apps import ldpc

    rng = np.random.default_rng(0)
    H = ldpc.fano_plane_H()
    llr = ldpc.awgn_llr(np.zeros(7, np.int8), 3.0, rng)
    bits0, post0, st0 = ldpc.decode_on_noc(H, llr, 6, topology=topo_name)
    bits1, post1, st1 = ldpc.decode_on_noc(H, llr, 6, topology=topo_name,
                                           pods=pods)
    assert np.array_equal(bits1, bits0)
    assert np.array_equal(post1, post0)
    _stats_equal_modulo_bridge(st0, st1)
    assert st1.bridge_beats > 0 and st1.bridge_wire_bytes > 0


@pytest.mark.parametrize("topo_name", ["mesh", "fattree"])
@pytest.mark.parametrize("pods", [[0] * 4 + [1] * 4, [0, 1, 2, 3] * 2])
def test_bmvm_partitioned_identical(topo_name, pods):
    from repro.apps import bmvm

    rng = np.random.default_rng(0)
    cfg = bmvm.BMVMConfig(n=64, k=8, fold=2)
    A = rng.integers(0, 2, (64, 64)).astype(np.uint8)
    v = rng.integers(0, 2, (64,)).astype(np.uint8)
    lut = bmvm.preprocess(A, cfg)
    out0, st0 = bmvm.iterate_noc_sim(jnp.asarray(lut), v, cfg, 2,
                                     topology=topo_name)
    out1, st1 = bmvm.iterate_noc_sim(jnp.asarray(lut), v, cfg, 2,
                                     topology=topo_name, pods=pods)
    assert np.array_equal(out1, out0)
    assert np.array_equal(out1.reshape(1, -1), bmvm.software_ref(A, v[None], 2))
    _stats_equal_modulo_bridge(st0, st1)
    assert st1.bridge_beats > 0


@pytest.mark.parametrize("pods", [[0] * 4 + [1] * 4, [0, 1] * 4])
def test_particle_filter_partitioned_identical(pods):
    from repro.apps import particle_filter as pf

    rng = np.random.default_rng(3)
    cfg = pf.PFConfig(img=64, roi=16, n_particles=64, n_bins=16)
    frames, _ = pf.synth_video(cfg, 4, rng)
    c0, st0 = pf.track_on_noc(frames, cfg, n_pe=4, topology="torus", n_nodes=8)
    c1, st1 = pf.track_on_noc(frames, cfg, n_pe=4, topology="torus", n_nodes=8,
                              pods=pods)
    assert np.array_equal(c1, c0)
    _stats_equal_modulo_bridge(st0, st1)
    assert st1.bridge_beats > 0


def test_serdes_cfg_changes_bridge_counters_not_outputs():
    from repro.apps import ldpc

    rng = np.random.default_rng(1)
    H = ldpc.fano_plane_H()
    llr = ldpc.awgn_llr(np.zeros(7, np.int8), 3.0, rng)
    pods = [0] * 8 + [1] * 8
    outs, beats = [], []
    for wb, lanes in [(8, 1), (16, 4), (32, 8)]:
        bits, post, st = ldpc.decode_on_noc(
            H, llr, 5, pods=pods,
            serdes_cfg=QuasiSerdesConfig(wire_bits=wb, lanes=lanes))
        outs.append(post)
        beats.append(st.bridge_beats)
    assert np.array_equal(outs[0], outs[1]) and np.array_equal(outs[1], outs[2])
    assert len(set(beats)) == 3               # the link model really differs


def test_executor_sim_python_bridge_parity():
    """The seed loop's analytic bridge counters == the engine's simulated
    ones, field for field (the engine-vs-baseline contract extends to the
    partitioned mode)."""
    g = TaskGraph("pair")
    g.add(PE("a", lambda x: {"y": x * 2}, (Port("x", (5,)),), (Port("y", (5,)),)))
    g.add(PE("b", lambda y: {"z": y + 1}, (Port("y", (5,)),), (Port("z", (5,)),)))
    g.connect("a.y", "b.y")
    for topo_name in TOPOLOGIES:
        topo = make_topology(topo_name, 4)
        placement = {"a": 0, "b": 3}
        plan = cut(g, placement, [0, 0, 1, 1])
        ex = NoCExecutor(g, topo, placement=placement, plan=plan)
        inp = {"a.x": jnp.arange(5.0)}
        _, st_sim = ex.run(inp, mode="sim")
        _, st_leg = ex.run(inp, mode="sim_python")
        assert st_sim.as_dict() == st_leg.as_dict(), topo_name
        assert st_sim.bridge_beats > 0, topo_name


# ---------------------------------------------------------------------------
# co-optimizer + serdes-aware objective
# ---------------------------------------------------------------------------

def test_placement_cost_serdes_aware():
    from repro.core import pair_cut_weights, placement_cost
    from repro.core.serdes import link_wire_beats

    g = TaskGraph("pair")
    g.add(PE("a", lambda x: {"y": x * 2}, (Port("x", (100,)),),
             (Port("y", (100,)),)))
    g.add(PE("b", lambda y: {"z": y + 1}, (Port("y", (100,)),),
             (Port("z", (100,)),)))
    g.connect("a.y", "b.y")
    topo = make_topology("ring", 4)
    placement = {"a": 0, "b": 2}
    scfg = QuasiSerdesConfig(wire_bits=8, lanes=8)
    # same pod: plain bytes × hops
    assert placement_cost(g, topo, placement, [0, 0, 0, 0], scfg) == 400 * 2
    # across the cut: the edge costs its serialized wire beats, not bytes
    w = link_wire_beats((100,), np.float32, scfg)
    assert placement_cost(g, topo, placement, [0, 0, 1, 1], scfg) == w
    assert pair_cut_weights(g, scfg)[("a", "b")] == w
    # compression shrinks the cut weight the optimizer sees
    w_bf16 = placement_cost(g, topo, placement, [0, 0, 1, 1],
                            QuasiSerdesConfig(wire_bits=8, lanes=8,
                                              compress="bf16"))
    assert w_bf16 < w


def test_optimize_placement_agrees_with_placement_cost():
    """The annealer's serdes-aware objective IS placement_cost — a found
    placement never scores worse than the round-robin baseline under the
    same (pods, serdes) objective."""
    from repro.apps import ldpc
    from repro.core import optimize_placement, place_round_robin, placement_cost

    g, _ = ldpc.build_ldpc_graph(ldpc.fano_plane_H())
    topo = make_topology("mesh", 16)
    pods = [0] * 8 + [1] * 8
    scfg = QuasiSerdesConfig(wire_bits=8, lanes=8)
    opt = optimize_placement(g, topo, pod_of_node=pods, iters=1200, seed=0,
                             serdes_cfg=scfg)
    c_opt = placement_cost(g, topo, opt, pods, scfg)
    c_rr = placement_cost(g, topo, place_round_robin(g, topo), pods, scfg)
    assert c_opt <= c_rr


def test_optimize_pod_cut_co_optimizes():
    from repro.apps import ldpc
    from repro.core import (optimize_pod_cut, place_round_robin, placement_cost,
                            candidate_cuts)

    g, _ = ldpc.build_ldpc_graph(ldpc.fano_plane_H())
    topo = make_topology("mesh", 16)
    grid = [QuasiSerdesConfig(wire_bits=wb, lanes=ln)
            for wb in (8, 16) for ln in (1, 8)]
    plan, cost = optimize_pod_cut(g, topo, n_pods=2, serdes_grid=grid,
                                  iters=400, seed=0)
    assert plan.n_pods == 2 and plan.serdes_cfg in grid
    # beats the naive blocked cut + rr placement + default serdes
    naive = placement_cost(g, topo, place_round_robin(g, topo),
                           candidate_cuts(topo, 2)[0], QuasiSerdesConfig())
    assert cost <= naive
    # deterministic under the seed
    plan2, cost2 = optimize_pod_cut(g, topo, n_pods=2, serdes_grid=grid,
                                    iters=400, seed=0)
    assert cost2 == cost and plan2.pod_of_node == plan.pod_of_node
    # the chosen plan actually executes, bit-identically
    rng = np.random.default_rng(0)
    llr = ldpc.awgn_llr(np.zeros(7, np.int8), 4.0, rng)
    bits, _, stt = ldpc.decode_on_noc(ldpc.fano_plane_H(), llr, 8,
                                      pods=list(plan.pod_of_node),
                                      placement=plan.placement,
                                      serdes_cfg=plan.serdes_cfg)
    assert not bits.any()


def test_wire_framing_single_source():
    """Regression (framing unification): PartitionPlan.wire_bytes ==
    wire_beats × beat_bytes for every wire width, including odd payloads."""
    from repro.core import link_bytes_on_wire, link_wire_beats

    g = TaskGraph("odd")
    g.add(PE("a", lambda x: {"y": x}, (Port("x", (7,), np.uint8),),
             (Port("y", (7,), np.uint8),)))
    g.add(PE("b", lambda y: {"z": y}, (Port("y", (7,), np.uint8),),
             (Port("z", (7,), np.uint8),)))
    g.connect("a.y", "b.y")
    for wb in (8, 16, 32):
        for lanes in (1, 8):
            scfg = QuasiSerdesConfig(wire_bits=wb, lanes=lanes)
            plan = cut(g, {"a": 0, "b": 1}, [0, 1], scfg)
            assert plan.wire_bytes(g) == plan.wire_beats(g) * scfg.beat_bytes
            assert plan.wire_bytes(g) == link_bytes_on_wire((7,), np.uint8, scfg)
            assert plan.wire_beats(g) == link_wire_beats((7,), np.uint8, scfg)
            assert plan.wire_beats(g) % lanes == 0


def test_mesh_for_partition_axes():
    import jax

    from repro.core import mesh_for_partition

    topo = make_topology("ring", 4)
    if jax.device_count() >= 4:
        pytest.skip("single-device environment expected")
    with pytest.raises(RuntimeError, match="device_count"):
        mesh_for_partition(topo, _plan_for([0, 0, 1, 1]))


# ---------------------------------------------------------------------------
# spmd differential: partitioned sim == partitioned spmd (subprocess, 8 dev)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_spmd_bridged_route_program_matches_oracle():
    """run_bridged_program over blocked ('pod','node') and irregular cuts ==
    the transpose oracle, all topologies."""
    run_with_devices("""
import numpy as np, jax
from jax.sharding import Mesh, PartitionSpec as P
from repro.core import compile_bridges, compile_routes, make_topology
from repro.core.interchip import BridgeConfig, run_bridged_program
from repro.core.partition import PartitionPlan
from repro.core.serdes import QuasiSerdesConfig

rng = np.random.default_rng(1)
for name in ("ring", "mesh", "torus", "fattree"):
    for pods, axes in (((0,)*4 + (1,)*4, ("pod", "node")),
                       ((0, 1) * 4, None),
                       ((0, 0, 1, 2, 2, 1, 0, 1), None)):
        n = 8
        topo = make_topology(name, n)
        prog = compile_routes(topo)
        plan = PartitionPlan({}, pods, (), (), QuasiSerdesConfig(wire_bits=16, lanes=4))
        bprog = compile_bridges(prog, plan, BridgeConfig(serdes=plan.serdes_cfg))
        if axes:
            mesh = Mesh(np.array(jax.devices()[:n]).reshape(2, 4), axes)
        else:
            from repro.core import mesh_for_topology
            mesh = mesh_for_topology(topo)
        names = mesh.axis_names
        sizes = mesh.devices.shape
        def device_fn(local):
            x = local.reshape(local.shape[len(sizes):])
            return run_bridged_program(x, bprog, names).reshape(local.shape)
        cube = rng.integers(0, 255, (n, n, 7)).astype(np.uint8)
        sm = jax.shard_map(device_fn, mesh=mesh, in_specs=P(*names),
                       out_specs=P(*names), check_vma=False)
        out = np.asarray(jax.jit(sm)(cube.reshape(tuple(sizes) + (n, 7))))
        assert np.array_equal(out.reshape(n, n, 7), cube.swapaxes(0, 1)), (name, pods)
print("OK")
""", n_devices=8)


@pytest.mark.slow
def test_spmd_partitioned_differential_ldpc():
    """LDPC × {mesh, ring, fattree} × {2-pod blocked, interleaved, 4-pod}:
    partitioned spmd == partitioned sim == unpartitioned sim, outputs and
    NoCStats (bridge counters included in the spmd==sim comparison)."""
    run_with_devices("""
import numpy as np
from repro.apps import ldpc

rng = np.random.default_rng(0)
H = ldpc.fano_plane_H()
llr = ldpc.awgn_llr(np.zeros(7, np.int8), 3.0, rng)
for topo in ("mesh", "ring", "fattree"):
    n = 8
    ref_bits, ref_post, ref_st = ldpc.decode_on_noc(H, llr, 5, topology=topo,
                                                    n_nodes=n)
    for pods in ([0]*4 + [1]*4, [0, 1]*4, [0, 0, 1, 1, 2, 2, 3, 3]):
        bits_s, post_s, st_s = ldpc.decode_on_noc(H, llr, 5, topology=topo,
                                                  n_nodes=n, pods=pods)
        bits_p, post_p, st_p = ldpc.decode_on_noc(H, llr, 5, topology=topo,
                                                  n_nodes=n, pods=pods,
                                                  mode="spmd")
        assert np.array_equal(bits_p, bits_s) and np.array_equal(post_p, post_s)
        assert np.array_equal(post_s, ref_post), (topo, pods)
        assert st_p.as_dict() == st_s.as_dict(), (topo, pods)
        d_ref, d_s = ref_st.as_dict(), st_s.as_dict()
        for k in d_ref:
            if not (k.startswith("bridge_") or k.startswith("cross_pod_")):
                assert d_ref[k] == d_s[k], (topo, pods, k)
print("OK")
""", n_devices=8)


@pytest.mark.slow
def test_spmd_partitioned_differential_bmvm():
    run_with_devices("""
import numpy as np, jax.numpy as jnp
from repro.apps import bmvm

rng = np.random.default_rng(0)
cfg = bmvm.BMVMConfig(n=64, k=8, fold=2)
A = rng.integers(0, 2, (64, 64)).astype(np.uint8)
v = rng.integers(0, 2, (64,)).astype(np.uint8)
lut = bmvm.preprocess(A, cfg)
sw = bmvm.software_ref(A, v[None], 3)
for topo in ("mesh", "torus"):
    for pods in ([0]*4 + [1]*4, [0, 1]*4):
        out_s, st_s = bmvm.iterate_noc_sim(jnp.asarray(lut), v, cfg, 3,
                                           topology=topo, pods=pods)
        out_p, st_p = bmvm.iterate_noc_sim(jnp.asarray(lut), v, cfg, 3,
                                           topology=topo, pods=pods,
                                           mode="spmd")
        assert np.array_equal(out_p, out_s), (topo, pods)
        assert np.array_equal(out_p.reshape(1, -1), sw), (topo, pods)
        assert st_p.as_dict() == st_s.as_dict(), (topo, pods)
print("OK")
""", n_devices=8)


@pytest.mark.slow
def test_spmd_partitioned_differential_particle_filter():
    run_with_devices("""
import numpy as np
from repro.apps import particle_filter as pf

rng = np.random.default_rng(3)
cfg = pf.PFConfig(img=64, roi=16, n_particles=64, n_bins=16)
frames, _ = pf.synth_video(cfg, 4, rng)
for topo in ("mesh", "fattree"):
    for pods in ([0]*4 + [1]*4, [0, 1]*4):
        c_s, st_s = pf.track_on_noc(frames, cfg, n_pe=4, topology=topo,
                                    n_nodes=8, pods=pods)
        c_p, st_p = pf.track_on_noc(frames, cfg, n_pe=4, topology=topo,
                                    n_nodes=8, pods=pods, mode="spmd")
        assert np.array_equal(c_p, c_s), (topo, pods)
        assert st_p.as_dict() == st_s.as_dict(), (topo, pods)
print("OK")
""", n_devices=8)
