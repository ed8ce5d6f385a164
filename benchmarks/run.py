"""Benchmark harness — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast]

Prints ``name,us_per_call,derived`` CSV rows (plus table-formatted sections).
Tables:
  table1_wrapper   — paper Tables I–III analog: PE cost without/with the NoC
                     wrapper (bytes + flit framing overhead).
  table4_bmvm_iter — paper Table IV analog: BMVM speedup vs iterations r
                     (software oracle vs kernel datapath), n=64 k=8 f=2, 4 PEs;
                     plus the NoC-sim r-sweep: compiled flit-program engine
                     (mode="sim") vs the seed per-message loop
                     (mode="sim_python"), reporting us/iter and speedup.
  table5_topology  — paper Table V analog: BMVM time vs topology
                     (ring/mesh/torus/fattree), measured round-by-round
                     schedule simulation + analytic alpha-beta model at the
                     paper's 64-PE scale.
  table5_batched   — batched flit-program engine: B input sets through one
                     (B, n, n, bytes) simulation vs B sequential sim runs.
  table6_spmd      — SPMD flit-program execution: the same compiled schedule
                     lowered onto shard_map + ppermute over an 8-device mesh
                     (mode="spmd") vs the numpy simulator (mode="sim"),
                     verifying bit-identical outputs and NoCStats; re-execs
                     itself under XLA_FLAGS when only one device is visible.
  table7_moe_noc   — MoE token dispatch over the compiled NoC route programs:
                     drops vs NoCConfig.flit_buffer_depth across all 4
                     topologies, exact flit/round/link-byte counters
                     (== 2x route_program_stats), Table-I-style wrapper
                     framing of the dispatch buffers; re-execs under
                     XLA_FLAGS when single-device.
  table8_interchip — inter-chip bridge subsystem: BMVM partitioned across pod
                     cuts over quasi-SERDES links, sweeping cut count ×
                     wire_bits × compression (multi-FPGA latency/bisection
                     trade-off), with sim/spmd/analytic parity gates and the
                     serdes-aware pod-cut co-optimizer; re-execs under
                     XLA_FLAGS when single-device.
  table9_congestion— buffered wormhole switching under load: injection rate ×
                     buffer_depth → latency/throughput saturation curves for
                     uniform / hotspot / transpose / bursty traffic on the
                     16-node mesh (cycle simulator vs the analytic
                     lower-bound/saturation model, with drain + exactly-once
                     + bound gates), plus a torus depth-1 deadlock-freedom
                     gate and an executor-level buffered-vs-sim parity row.
  table11_observability — telemetry subsystem gates: trace↔NoCStats bit-exact
                     parity (sim + buffered), zero events allocated with
                     tracing off plus the on/off overhead ratio, and the
                     committed sample Perfetto trace re-validated against the
                     Chrome trace-event schema.
  placement_search — annealing optimize_placement vs round-robin/greedy:
                     Σ traffic×hops cost (and cross-pod cut bytes) for the
                     LDPC / BMVM / particle-filter graphs.
  fig_ldpc         — LDPC decoder throughput (vectorized+kernel) + NoC stats.
  fig_pf           — particle-filter tracking throughput + accuracy.
  lm_step          — LM-stack microbench: smoke-arch train-step wall time.
"""
from __future__ import annotations

import argparse
import os
import time

import numpy as np

import jax
import jax.numpy as jnp


def _timeit(fn, n=5, warmup=2):
    for _ in range(warmup):
        fn()
    t0 = time.monotonic()
    for _ in range(n):
        fn()
    return (time.monotonic() - t0) / n * 1e6  # us


def _reexec_with_devices(table: str, fast: bool, child_env: str, n_dev: int = 8):
    """Multi-device sections need ``n_dev`` devices.  Returns None when that
    many are visible (run in process), else the rows of a child run.

    Only the CPU backend is re-exec'd, with ``n_dev`` fake host devices and
    ``JAX_PLATFORMS=cpu``.  An accelerator belongs to one process, which this
    one already holds, so on any other backend too few devices is an error.
    One re-exec only: a child that still sees too few devices fails instead of
    recursing, and failures raise so the CI gate goes red."""
    n = jax.device_count()
    if n >= n_dev:
        return None
    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(f"{table} needs {n_dev} {backend} devices, "
                           f"this host has {n}")
    if os.environ.get(child_env):
        raise RuntimeError(
            f"{table}: only {n} device(s) despite "
            f"--xla_force_host_platform_device_count={n_dev}")
    import subprocess
    import sys

    env = dict(os.environ)
    flag = f"--xla_force_host_platform_device_count={n_dev}"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + flag).strip()
    env["JAX_PLATFORMS"] = "cpu"
    env[child_env] = "1"
    cmd = [sys.executable, "-m", "benchmarks.run", "--only", table]
    if fast:
        cmd.append("--fast")
    out = subprocess.run(cmd, capture_output=True, text=True, env=env,
                         timeout=600)
    if out.returncode != 0:
        raise RuntimeError(
            f"{table} subprocess failed:\n"
            + "\n".join((out.stderr or out.stdout).strip().splitlines()[-10:]))
    prefix = table.split("_")[0] + "_"
    return [ln for ln in out.stdout.splitlines() if ln.startswith(prefix)]


def table1_wrapper(fast: bool) -> list[str]:
    from repro.apps.ldpc import build_ldpc_graph, fano_plane_H
    from repro.apps.particle_filter import PFConfig, build_pf_graph
    from repro.core import NoCConfig, wrapper_overhead

    rows = []
    g, _ = build_ldpc_graph(fano_plane_H())
    for r in wrapper_overhead(g, NoCConfig())[:2]:
        rows.append(f"table1_ldpc_{r['pe']},0,"
                    f"wo={r['wo_wrapper_bytes']}B with={r['with_wrapper_bytes']}B "
                    f"overhead={r['overhead']}")
    gpf = build_pf_graph(PFConfig(n_particles=64), 4)
    for r in wrapper_overhead(gpf, NoCConfig())[:2]:
        rows.append(f"table3_pf_{r['pe']},0,"
                    f"wo={r['wo_wrapper_bytes']}B with={r['with_wrapper_bytes']}B "
                    f"overhead={r['overhead']}")
    return rows


def table4_bmvm_iter(fast: bool) -> list[str]:
    from repro.apps import bmvm

    rng = np.random.default_rng(0)
    cfg = bmvm.BMVMConfig(n=64, k=8, fold=2)
    A = rng.integers(0, 2, (64, 64)).astype(np.uint8)
    V = rng.integers(0, 2, (4, 64)).astype(np.uint8)   # 4 "PEs"/threads analog
    lut = bmvm.preprocess(A, cfg)
    Vj = jnp.asarray(V)
    # correctness of the Pallas kernel datapath (interpret mode = validation;
    # its wall time is meaningless on CPU, so the timed "hardware" path is the
    # XLA-jitted LUT datapath that the kernel implements)
    assert np.array_equal(np.asarray(bmvm.iterate_kernel(lut, Vj, cfg, 3)),
                          bmvm.software_ref(A, V, 3))
    rows = []
    iters = [1, 10, 100] if fast else [1, 10, 100, 1000]
    for r in iters:
        t_sw = _timeit(lambda: bmvm.software_ref(A, V, r), n=3)
        it = jax.jit(lambda v: bmvm.iterate_kernel(lut, v, cfg, r, use_kernel=False))
        it(Vj)  # compile
        t_hw = _timeit(lambda: jax.block_until_ready(it(Vj)), n=3)
        rows.append(f"table4_bmvm_r{r},{t_hw:.1f},speedup_vs_sw={t_sw / t_hw:.2f}")
    # NoC-sim engine r-sweep: compiled flit program vs the seed per-message loop
    from repro.core import NoCExecutor, make_topology
    from repro.kernels import ref as kref

    v1 = V[0]
    g, feedback = bmvm.build_bmvm_graph(np.asarray(lut), cfg)
    ex = NoCExecutor(g, make_topology(cfg.topology, 2 * cfg.n_pe))
    vw = np.asarray(kref.gf2_pack_vector(jnp.asarray(v1), cfg.k), np.uint32)
    f = cfg.fold
    inputs = {f"lut{i}.v": vw[i * f:(i + 1) * f] for i in range(cfg.n_pe)}
    ex.run_iterative(inputs, feedback, 2, mode="sim")         # jit warmup
    ex.run_iterative(inputs, feedback, 2, mode="sim_python")  # fair warmup
    for r in ([1, 10] if fast else [1, 10, 100]):
        t_leg = _timeit(lambda: ex.run_iterative(inputs, feedback, r, mode="sim_python"),
                        n=1, warmup=0) / r
        t_sim = _timeit(lambda: ex.run_iterative(inputs, feedback, r, mode="sim"),
                        n=1, warmup=0) / r
        out_s, _ = ex.run_iterative(inputs, feedback, r, mode="sim")
        out_l, _ = ex.run_iterative(inputs, feedback, r, mode="sim_python")
        assert all(np.array_equal(out_s[k], out_l[k]) for k in out_s)
        rows.append(f"table4_simengine_r{r},{t_sim:.1f},"
                    f"seed_loop_us={t_leg:.1f} speedup_vs_seed_loop={t_leg / t_sim:.2f}")
    return rows


def table5_topology(fast: bool) -> list[str]:
    from repro.apps import bmvm
    from repro.core import compare

    n, k, f = (256, 4, 4) if fast else (1024, 4, 4)    # paper: n=1024 k=4 f=4
    rng = np.random.default_rng(1)
    cfg = bmvm.BMVMConfig(n=n, k=k, fold=f)
    A = rng.integers(0, 2, (n, n)).astype(np.uint8)
    v = rng.integers(0, 2, (n,)).astype(np.uint8)
    lut = np.asarray(bmvm.preprocess(A, cfg))
    rows = []
    r = 2
    sw = bmvm.software_ref(A, v[None], r)
    for topo in ("ring", "mesh", "torus", "fattree"):
        t0 = time.monotonic()
        out, stats = bmvm.iterate_noc_sim(jnp.asarray(lut), v, cfg, r, topology=topo)
        dt = (time.monotonic() - t0) * 1e6
        assert np.array_equal(out.reshape(1, -1), sw), topo
        rows.append(f"table5_bmvm_{topo},{dt:.0f},"
                    f"rounds={stats.rounds} link_bytes={stats.link_bytes} "
                    f"flits={stats.flits}")
    # analytic alpha-beta model at the paper's 64-PE scale
    for row in compare(64, chunk_bytes=2 * (n // k // f)):
        rows.append(f"table5_model_{row['topology']},{row['model_time_us']:.2f},"
                    f"rounds={row['rounds']} avg_hops={row['avg_hops']}")
    return rows


def table5_batched(fast: bool) -> list[str]:
    """Batched engine: B input sets through one (B, n, n, bytes) simulation."""
    from repro.apps import bmvm
    from repro.core import NoCExecutor, make_topology
    from repro.kernels import ref as kref

    rng = np.random.default_rng(5)
    cfg = bmvm.BMVMConfig(n=64, k=8, fold=2)
    A = rng.integers(0, 2, (64, 64)).astype(np.uint8)
    lut = np.asarray(bmvm.preprocess(A, cfg))
    g, _ = bmvm.build_bmvm_graph(lut, cfg)
    B = 8 if fast else 32
    V = rng.integers(0, 2, (B, 64)).astype(np.uint8)
    vw = np.asarray(kref.gf2_pack_vector(jnp.asarray(V), cfg.k), np.uint32)  # (B, C)
    f = cfg.fold
    rows = []
    for topo in ("ring", "mesh", "torus", "fattree"):
        ex = NoCExecutor(g, make_topology(topo, 2 * cfg.n_pe))
        binp = {f"lut{i}.v": vw[:, i * f:(i + 1) * f] for i in range(cfg.n_pe)}
        sinp = [{f"lut{i}.v": vw[b, i * f:(i + 1) * f] for i in range(cfg.n_pe)}
                for b in range(B)]
        ex.run_batch(binp)                 # vmap/jit warmup
        [ex.run(s) for s in sinp[:1]]
        t_b = _timeit(lambda: ex.run_batch(binp), n=2, warmup=0)
        t_s = _timeit(lambda: [ex.run(s) for s in sinp], n=2, warmup=0)
        bouts, bstats = ex.run_batch(binp)
        souts = [ex.run(s)[0] for s in sinp]
        assert all(np.array_equal(bouts[k][b], souts[b][k])
                   for b in range(B) for k in bouts)
        rows.append(f"table5_batched_{topo},{t_b:.0f},B={B} seq_us={t_s:.0f} "
                    f"speedup={t_s / t_b:.2f} rounds={bstats.rounds}")
    return rows


def table6_spmd(fast: bool) -> list[str]:
    """SPMD (shard_map + ppermute) vs numpy-sim execution of one flit program.

    The smoke/bench environment pins jax to one visible device, so when run
    single-device this section re-execs itself in a subprocess with 8 fake CPU
    devices and forwards the child's rows."""
    n_dev = 8
    child = _reexec_with_devices("table6_spmd", fast, "_TABLE6_SPMD_CHILD", n_dev)
    if child is not None:
        return child

    from repro.apps import bmvm
    from repro.core import NoCExecutor, make_topology
    from repro.kernels import ref as kref

    rng = np.random.default_rng(7)
    cfg = bmvm.BMVMConfig(n=64, k=8, fold=2)           # 4 PEs on 8 nodes
    A = rng.integers(0, 2, (64, 64)).astype(np.uint8)
    v = rng.integers(0, 2, (64,)).astype(np.uint8)
    lut = np.asarray(bmvm.preprocess(A, cfg))
    g, feedback = bmvm.build_bmvm_graph(lut, cfg)
    vw = np.asarray(kref.gf2_pack_vector(jnp.asarray(v), cfg.k), np.uint32)
    f = cfg.fold
    inputs = {f"lut{i}.v": vw[i * f:(i + 1) * f] for i in range(cfg.n_pe)}
    r = 2 if fast else 5
    rows = []
    for topo in ("ring", "mesh", "torus", "fattree"):
        ex = NoCExecutor(g, make_topology(topo, 2 * cfg.n_pe))
        ex.run_iterative(inputs, feedback, 1, mode="sim")    # jit warmup
        ex.run_iterative(inputs, feedback, 1, mode="spmd")   # trace/compile
        res = {}
        t_sim = _timeit(lambda: res.__setitem__(
            "sim", ex.run_iterative(inputs, feedback, r, mode="sim")),
            n=2, warmup=0) / r
        t_spmd = _timeit(lambda: res.__setitem__(
            "spmd", ex.run_iterative(inputs, feedback, r, mode="spmd")),
            n=2, warmup=0) / r
        (out_sim, st_sim), (out_spmd, st_spmd) = res["sim"], res["spmd"]
        assert all(np.array_equal(out_sim[k], out_spmd[k]) for k in out_sim), topo
        assert st_sim.as_dict() == st_spmd.as_dict(), topo
        rows.append(f"table6_spmd_{topo},{t_spmd:.0f},sim_us={t_sim:.0f} "
                    f"spmd_vs_sim={t_sim / max(t_spmd, 1e-9):.2f}x "
                    f"rounds={st_spmd.rounds} stats_identical=True")
    return rows


def table7_moe_noc(fast: bool) -> list[str]:
    """MoE token dispatch over the compiled NoC route programs: the
    drops-vs-`flit_buffer_depth` curve, Table-I wrapper framing applied to the
    dispatch buffers, and exact flit/round/link-byte counters.

    Gates (CI goes red on stats drift):
      * rounds/link_bytes == 2x `route_program_stats` of the dispatched cube,
      * drops identical across all 4 topologies (capacity is routing-blind),
      * drops == the gather engine's (unified capacity semantics),
      * drops monotone nonincreasing in buffer depth, 0 once cf_eff >= top_k.
    Re-execs itself with 8 fake CPU devices when run single-device."""
    n_dev = 8
    child = _reexec_with_devices("table7_moe_noc", fast, "_TABLE7_MOE_CHILD", n_dev)
    if child is not None:
        return child

    from jax.sharding import Mesh

    from repro.core.noc import NoCConfig
    from repro.core.routing import compile_routes, route_program_stats
    from repro.core.topology import make_topology
    from repro.models import moe as M
    from repro.models.layers import init_params

    mesh = Mesh(np.array(jax.devices()).reshape(1, n_dev), ("data", "model"))
    rng = np.random.default_rng(7)
    E, d, k = 16, 64, 2
    B, S = 2, 64
    base = M.MoEConfig(d_model=d, n_experts=E, top_k=k, d_ff=96, impl="dense")
    params = init_params(M.moe_specs(base), jax.random.key(0))
    x = jnp.asarray(rng.normal(size=(B, S, d)), jnp.float32)
    depths = [1, 2, 4, 8] if fast else [1, 2, 4, 8, 16]
    topos = ("fattree", "ring", "mesh2d", "torus2d")
    rows = []

    def jit_moe(c):
        """jit one config; capture the static half of MoEDispatchStats at
        trace time (drops/peak flow out as traced outputs)."""
        holder = {}

        def f(p, xx):
            out, _, st = M.moe_apply(p, xx, c)
            holder["st"] = st
            return out, st.drops, st.peak_occupancy

        return jax.jit(f), holder

    with jax.set_mesh(mesh):
        ref, _, _ = M.moe_apply(params, x, base)
        prev_drops = None
        for depth in depths:
            ncfg = NoCConfig(flit_buffer_depth=depth)
            gf, _ = jit_moe(M.MoEConfig(d, E, k, 96, impl="gather", noc=ncfg))
            g_drops = int(gf(params, x)[1])
            drops_at_depth = []
            for topo in topos:
                c = M.MoEConfig(d, E, k, 96, impl="noc", noc_topology=topo,
                                noc=ncfg)
                nf, holder = jit_moe(c)
                out, drops, peak = jax.block_until_ready(nf(params, x))
                t = _timeit(lambda: jax.block_until_ready(nf(params, x)[0]),
                            n=2, warmup=0)
                st = holder["st"]
                # exact-counter gate: 2x route_program_stats of the cube
                prog = compile_routes(make_topology(topo, n_dev))
                msg = (E // n_dev) * st.capacity * d * 4
                ss = route_program_stats(prog, n_dev * n_dev * msg)
                assert st.rounds == 2 * ss.rounds, topo
                assert st.link_bytes == 2 * ss.link_bytes, topo
                assert st.flits == 2 * n_dev * n_dev * ncfg.flits_for(msg), topo
                drops_at_depth.append(int(drops))
                # Table-I wrapper framing of one (src, dst-rank) buffer
                raw = msg
                flit_b = ncfg.flits_for(msg) * ncfg.flit_wire_bytes
                fifo_b = depth * ncfg.flits_for(d * 4) * ncfg.flit_wire_bytes
                rows.append(
                    f"table7_moe_noc_{topo}_d{depth},{t:.0f},"
                    f"drops={int(drops)} peak={int(peak)} "
                    f"cap={st.capacity} cf_eff={st.capacity_factor:.3f} "
                    f"flits={st.flits} rounds={st.rounds} "
                    f"link_bytes={st.link_bytes} "
                    f"wrapper_overhead={round((flit_b + fifo_b - raw) / raw, 3)}")
            # capacity is routing-blind: all topologies drop identically,
            # and the gather engine (unified semantics) agrees
            assert len(set(drops_at_depth)) == 1, drops_at_depth
            assert drops_at_depth[0] == g_drops, (drops_at_depth, g_drops)
            if prev_drops is not None:
                assert drops_at_depth[0] <= prev_drops, "drops not monotone"
            prev_drops = drops_at_depth[0]
        # deep enough buffers => drop-free => exact match with the oracle
        nf, _ = jit_moe(M.MoEConfig(d, E, k, 96, impl="noc",
                                    noc_topology="torus2d",
                                    noc=NoCConfig(flit_buffer_depth=B * S * k)))
        out, drops, _ = nf(params, x)
        err = float(jnp.max(jnp.abs(out - ref)))
        assert int(drops) == 0
        assert err < 1e-4
        rows.append(f"table7_moe_noc_dropfree,0,depth={B * S * k} drops=0 "
                    f"max_err_vs_dense={err:.2e}")
    return rows


def table8_interchip(fast: bool) -> list[str]:
    """Inter-chip bridge subsystem (paper §III, Fig. 6): the BMVM NoC
    partitioned across pod cuts over quasi-SERDES links, sweeping cut count ×
    wire_bits × compression — the multi-FPGA latency/bisection trade-off.

    Gates (CI goes red on drift):
      * partitioned sim outputs bit-identical to the unpartitioned run, and
        all non-bridge NoCStats fields identical;
      * `bridge_program_stats` exactly equals the simulator's BridgeStats;
      * partitioned spmd == partitioned sim in outputs *and* NoCStats
        (bridge counters included) on the (pod, node) device mesh.
    Effective latency = rounds + bridge stall rounds (serialization back-
    pressure); `cut_wire_bytes` is the message-level serdes framing incl.
    compression (the co-optimizer's objective term), while `bridge_wire_*`
    count the lossless flit tunnel.  Re-execs itself with 8 fake CPU devices
    when run single-device."""
    n_dev = 8
    child = _reexec_with_devices("table8_interchip", fast, "_TABLE8_ICHIP_CHILD",
                                 n_dev)
    if child is not None:
        return child

    from repro.apps import bmvm
    from repro.core import (NoCConfig, bridge_program_stats, compile_bridges,
                            compile_routes, cut, make_topology, optimize_pod_cut,
                            place_round_robin, placement_cost,
                            simulate_bridged_program)
    from repro.core.interchip import BridgeConfig
    from repro.core.serdes import QuasiSerdesConfig

    rng = np.random.default_rng(8)
    cfg = bmvm.BMVMConfig(n=64, k=8, fold=2)           # 4 PEs on 8 NoC nodes
    A = rng.integers(0, 2, (64, 64)).astype(np.uint8)
    v = rng.integers(0, 2, (64,)).astype(np.uint8)
    lut = np.asarray(bmvm.preprocess(A, cfg))
    g, _ = bmvm.build_bmvm_graph(lut, cfg)
    sw = bmvm.software_ref(A, v[None], 2)
    topo = make_topology("mesh", 8)
    cuts = {2: [0] * 4 + [1] * 4, 4: [0, 0, 1, 1, 2, 2, 3, 3]}
    wire_sweep = (8, 16) if fast else (8, 16, 32)
    comp_sweep = ("none", "bf16")
    rows = []
    out_ref, st_ref = bmvm.iterate_noc_sim(jnp.asarray(lut), v, cfg, 2,
                                           topology="mesh")
    for n_pods, pods in cuts.items():
        for wb in wire_sweep:
            for comp in comp_sweep:
                scfg = QuasiSerdesConfig(wire_bits=wb, lanes=2, compress=comp)
                t0 = time.monotonic()
                out, st = bmvm.iterate_noc_sim(jnp.asarray(lut), v, cfg, 2,
                                               topology="mesh", pods=pods,
                                               serdes_cfg=scfg)
                dt = (time.monotonic() - t0) * 1e6
                # gate 1: the cut is semantically transparent — identical to
                # the unpartitioned run AND to the software oracle
                assert np.array_equal(out, out_ref), (n_pods, wb, comp)
                assert np.array_equal(out.reshape(1, -1), sw), (n_pods, wb, comp)
                d_ref, d = st_ref.as_dict(), st.as_dict()
                for k in d_ref:
                    if not (k.startswith("bridge_") or k.startswith("cross_pod_")):
                        assert d_ref[k] == d[k], (n_pods, wb, comp, k)
                # gate 2: analytic bridge stats == simulated, on a raw cube
                plan = cut(g, place_round_robin(g, topo), pods, scfg)
                bprog = compile_bridges(compile_routes(topo), plan,
                                        BridgeConfig(serdes=scfg, fifo_depth=8))
                cube = rng.integers(0, 255, (8, 8, 16), dtype=np.uint8)
                _, _, b_sim = simulate_bridged_program(bprog, cube)
                b_ana = bridge_program_stats(bprog, cube.nbytes)
                assert b_ana.as_dict() == b_sim.as_dict(), (n_pods, wb, comp)
                msg_wire = plan.wire_bytes(g)
                rows.append(
                    f"table8_interchip_p{n_pods}_w{wb}_{comp},{dt:.0f},"
                    f"latency_rounds={st.rounds + st.bridge_stall_rounds} "
                    f"stall_rounds={st.bridge_stall_rounds} "
                    f"bridge_beats={st.bridge_beats} "
                    f"bridge_wire_bytes={st.bridge_wire_bytes} "
                    f"peak_fifo={st.bridge_peak_fifo} "
                    f"bridges={b_sim.n_bridges} cut_wire_bytes={msg_wire}")
    # gate 3: spmd differential on the (pod, node) mesh, 2- and 4-pod cuts
    for n_pods, pods in cuts.items():
        out_sim, st_sim = bmvm.iterate_noc_sim(jnp.asarray(lut), v, cfg, 2,
                                               topology="mesh", pods=pods)
        out_spmd, st_spmd = bmvm.iterate_noc_sim(jnp.asarray(lut), v, cfg, 2,
                                                 topology="mesh", pods=pods,
                                                 mode="spmd")
        assert np.array_equal(out_spmd, out_sim), n_pods
        assert st_spmd.as_dict() == st_sim.as_dict(), n_pods
        rows.append(f"table8_interchip_spmd_p{n_pods},0,"
                    f"stats_identical=True "
                    f"bridge_beats={st_spmd.bridge_beats} "
                    f"stall_rounds={st_spmd.bridge_stall_rounds}")
    # co-optimizer: pod cut × serdes settings under the shared objective
    grid = [QuasiSerdesConfig(wire_bits=wb, lanes=ln, compress=cp)
            for wb in wire_sweep for ln in (1, 8) for cp in comp_sweep]
    plan, cost = optimize_pod_cut(g, topo, n_pods=2, serdes_grid=grid,
                                  iters=300 if fast else 1500, seed=0)
    naive = placement_cost(g, topo, place_round_robin(g, topo),
                           [0] * 4 + [1] * 4, QuasiSerdesConfig())
    rows.append(f"table8_coopt,0,cost={cost:.0f} naive={naive:.0f} "
                f"wire_bits={plan.serdes_cfg.wire_bits} "
                f"lanes={plan.serdes_cfg.lanes} "
                f"compress={plan.serdes_cfg.compress} "
                f"cut_beats={plan.wire_beats(g)}")
    assert cost <= naive
    return rows


def table9_congestion(fast: bool) -> list[str]:
    """Buffered wormhole switching saturation curves (mode="buffered" stack).

    Sweeps offered load (as a fraction of the analytic saturation rate) ×
    input-FIFO depth for the four traffic patterns on the 16-node mesh.
    Gates (CI goes red on regression):
      * drain + exactly-once: every offered packet is delivered, at every
        depth including the depth=1 worst case;
      * sim/analytic agreement: cycles >= `switch_lower_bound` and accepted
        throughput <= `saturation_rate`, for every cell of the sweep;
      * deadlock freedom on wrapped topologies: a torus depth=1 hotspot mix
        (the adversarial configuration for wormhole deadlock) must drain;
      * executor parity: `mode="buffered"` delivers LDPC payloads identical
        to `mode="sim"`.
    Latency is reported in cycles (avg and max); throughput in
    flits/cycle/node against the saturation rate."""
    from repro.core.switch import (SwitchConfig, saturation_rate,
                                   simulate_switch, switch_lower_bound)
    from repro.core.topology import make_topology
    from repro.core.traffic import (TrafficConfig, generate_traffic,
                                    traffic_matrix)

    topo = make_topology("mesh", 16)
    n_pk = 16 if fast else 48
    depths = (1, 4) if fast else (1, 2, 4, 8)
    load_fracs = (0.3, 1.5) if fast else (0.2, 0.5, 0.8, 1.2, 2.0)
    rows = []
    for pattern in ("uniform", "hotspot", "transpose", "bursty"):
        tm = traffic_matrix(topo, TrafficConfig(pattern=pattern, hotspot=5))
        sat = saturation_rate(topo, tm)
        for depth in depths:
            for frac in load_fracs:
                tcfg = TrafficConfig(pattern=pattern, hotspot=5,
                                     injection_rate=frac * sat,
                                     n_packets=n_pk, seed=0)
                pkts = generate_traffic(topo, tcfg)
                t0 = time.monotonic()
                res = simulate_switch(topo, pkts,
                                      SwitchConfig(buffer_depth=depth))
                dt = (time.monotonic() - t0) * 1e6
                st = res.stats
                # gates: drain/exactly-once + analytic agreement
                assert st.packets == len(pkts), (pattern, depth, frac)
                assert st.cycles >= switch_lower_bound(topo, pkts), \
                    (pattern, depth, frac)
                thr = st.throughput(topo.n_nodes)
                assert thr <= sat + 1e-9, (pattern, depth, frac)
                rows.append(
                    f"table9_{pattern}_d{depth}_l{frac},{dt:.0f},"
                    f"offered={frac * sat:.3f} accepted={thr:.3f} "
                    f"sat_rate={sat:.3f} cycles={st.cycles} "
                    f"avg_lat={st.avg_latency:.1f} max_lat={st.latency_max} "
                    f"stalls={st.stall_cycles} arb_losses={st.arb_losses} "
                    f"max_queue={st.max_queue}")
    # deadlock-freedom gate: torus at depth=1 under a hotspot mix is the
    # adversarial wormhole configuration; dateline VCs must keep it live
    torus = make_topology("torus", 16)
    pkts = generate_traffic(torus, TrafficConfig(
        pattern="hotspot", hotspot=5, hotspot_frac=0.7,
        injection_rate=0.8, n_packets=n_pk, seed=7))
    res = simulate_switch(torus, pkts, SwitchConfig(buffer_depth=1))
    assert res.stats.packets == len(pkts), "torus depth-1 failed to drain"
    rows.append(f"table9_torus_depth1_gate,0,packets={res.stats.packets} "
                f"cycles={res.stats.cycles} deadlock_free=True")
    # executor parity gate: buffered == sim on a real app
    from repro.apps import ldpc

    rng = np.random.default_rng(0)
    llr = ldpc.awgn_llr(np.zeros(7, np.int8), 3.0, rng)
    b_s, i_s, st_s = ldpc.decode_on_noc(ldpc.fano_plane_H(), llr, 10)
    t0 = time.monotonic()
    b_b, i_b, st_b = ldpc.decode_on_noc(ldpc.fano_plane_H(), llr, 10,
                                        mode="buffered")
    dt = (time.monotonic() - t0) * 1e6
    assert np.array_equal(b_s, b_b) and np.array_equal(i_s, i_b)
    assert st_b.payload_bytes == st_s.payload_bytes
    rows.append(f"table9_ldpc_buffered,{dt:.0f},"
                f"cycles={st_b.switch_cycles} sim_rounds={st_s.rounds} "
                f"stalls={st_b.switch_stall_cycles} "
                f"arb_losses={st_b.switch_arb_losses} outputs_identical=True")
    return rows


def table10_verify(fast: bool) -> list[str]:
    """Static verifier vs simulate-to-detect on deadlock-prone configs.

    Each cell is one (topology, n_vcs) combination at depth-1 buffers (the
    adversarial wormhole configuration) under a shift-permutation workload
    that piles every node's packets up at once.  The channel-dependency
    verifier (`repro.analysis.cdg`) gives its verdict in microseconds without
    moving a flit; the simulator (``verify=False``) either drains or wedges
    into `DeadlockError`.  Gates (CI goes red on violation):
      * soundness — every config the simulator deadlocks on was flagged
        cyclic by the verifier (no false negatives on real deadlocks);
      * no false alarms on the safe set — every verifier-safe config drains
        to completion, including the 1-VC combos the old hand guard
        rejected (2-node ring, 2x2 torus);
      * the unsafe set is non-vacuous — at least one config actually
        deadlocks in simulation."""
    from repro.analysis.cdg import deadlock_cycle
    from repro.core.switch import (DeadlockError, Packet, SwitchConfig,
                                   simulate_switch)
    from repro.core.topology import make_topology

    combos = [
        ("ring2_vc1", "ring", 2, 1),       # provably safe at 1 VC
        ("torus4_vc1", "torus", 4, 1),     # 2x2 torus: safe at 1 VC
        ("mesh16_vc1", "mesh", 16, 1),
        ("ring8_vc1", "ring", 8, 1),       # cyclic: the classic wedge
        ("ring8_vc2", "ring", 8, 2),
        ("torus16_vc1", "torus", 16, 1),   # cyclic
        ("torus16_vc2", "torus", 16, 2),
        ("fattree8_vc1", "fattree", 8, 1),
    ]
    if fast:
        combos = [c for c in combos
                  if c[0] in ("ring2_vc1", "ring8_vc1", "ring8_vc2",
                              "torus16_vc1", "mesh16_vc1")]
    rows = []
    n_deadlocked = 0
    for name, tname, n, vcs in combos:
        topo = make_topology(tname, n)
        # shift permutation, everything injected at t=0: maximal pressure
        pkts = [Packet(s, (s + max(1, n // 2)) % n, 4, t_inject=0)
                for s in range(n) for _ in range(4)]
        deadlock_cycle.cache_clear()
        t0 = time.monotonic()
        cyc = deadlock_cycle(topo, vcs)
        t_verify = (time.monotonic() - t0) * 1e6
        scfg = SwitchConfig(buffer_depth=1, n_vcs=vcs, max_cycles=20_000)
        t0 = time.monotonic()
        try:
            res = simulate_switch(topo, pkts, scfg, verify=False)
            sim = "drained"
            assert res.stats.packets == len(pkts), name
        except DeadlockError:
            sim = "deadlocked"
            n_deadlocked += 1
        t_sim = (time.monotonic() - t0) * 1e6
        verdict = "cyclic" if cyc else "safe"
        # soundness: a simulated deadlock the verifier passed is a miss
        assert not (sim == "deadlocked" and cyc is None), name
        # no false alarms: verifier-safe must drain
        assert not (cyc is None and sim != "drained"), name
        rows.append(f"table10_{name},{t_verify:.0f},verdict={verdict} "
                    f"sim={sim} sim_us={t_sim:.0f} "
                    f"speedup={t_sim / max(t_verify, 1):.0f}x "
                    f"cycle_len={len(cyc) if cyc else 0}")
    assert n_deadlocked >= 1, "unsafe set never deadlocked: gate is vacuous"
    return rows


def table11_observability(fast: bool) -> list[str]:
    """Telemetry subsystem gates (CI goes red on violation):

      * parity — aggregating a full trace (`telemetry.trace_stats`) of a
        BMVM run reproduces the engine's NoCStats bit-exactly, for both the
        schedule simulator and the cycle-accurate buffered switch;
      * zero overhead off — running untraced allocates zero TraceEvents, and
        the traced/untraced wall-clock ratio is reported;
      * schema — a freshly exported trace validates against the Chrome
        trace-event schema, and the committed sample
        ``benchmarks/SAMPLE_trace_perfetto.json`` (written on first run)
        keeps validating, so the on-disk format can't drift silently."""
    import json
    import os

    from repro.apps import bmvm
    from repro.core import NoCExecutor, make_topology
    from repro.kernels import ref as kref
    from repro.telemetry import (Tracer, chrome_trace, events_allocated,
                                 trace_stats, validate_chrome_trace)

    rng = np.random.default_rng(11)
    cfg = bmvm.BMVMConfig(n=64, k=8, fold=2)
    A = rng.integers(0, 2, (64, 64)).astype(np.uint8)
    v = rng.integers(0, 2, (64,)).astype(np.uint8)
    lut = np.asarray(bmvm.preprocess(A, cfg))
    g, feedback = bmvm.build_bmvm_graph(lut, cfg)
    vw = np.asarray(kref.gf2_pack_vector(jnp.asarray(v), cfg.k), np.uint32)
    f = cfg.fold
    inputs = {f"lut{i}.v": vw[i * f:(i + 1) * f] for i in range(cfg.n_pe)}
    topo = make_topology("mesh", 2 * cfg.n_pe)
    r = 2 if fast else 5
    rows = []
    # gate 1: trace -> NoCStats parity, schedule sim + buffered switch
    for mode in ("sim", "buffered"):
        tr = Tracer()
        ex = NoCExecutor(g, topo, trace=tr)
        _, st = ex.run_iterative(inputs, feedback, r, mode=mode)
        agg = trace_stats(tr)
        assert agg.as_dict() == st.as_dict(), (mode, agg.as_dict(), st.as_dict())
        rows.append(f"table11_parity_{mode},0,events={len(tr)} "
                    f"rounds={st.rounds} bit_exact=True")
    # gate 2: tracing off allocates nothing; report the on/off overhead
    ex_off = NoCExecutor(g, topo)
    ex_off.run_iterative(inputs, feedback, 1, mode="sim")   # jit warmup
    before = events_allocated()
    t_off = _timeit(lambda: ex_off.run_iterative(inputs, feedback, r,
                                                 mode="sim"), n=3, warmup=1)
    assert events_allocated() == before, "untraced run allocated TraceEvents"
    ex_on = NoCExecutor(g, topo, trace=True)
    ex_on.run_iterative(inputs, feedback, 1, mode="sim")
    t_on = _timeit(lambda: ex_on.run_iterative(inputs, feedback, r,
                                               mode="sim"), n=3, warmup=1)
    rows.append(f"table11_overhead,{t_on:.0f},untraced_us={t_off:.0f} "
                f"traced_over_untraced={t_on / max(t_off, 1e-9):.3f}")
    # gate 3: exported trace validates; the committed sample keeps validating
    tr = Tracer()
    ex = NoCExecutor(g, topo, trace=tr)
    ex.run_iterative(inputs, feedback, 2, mode="sim")
    doc = chrome_trace(tr)
    n_ev = validate_chrome_trace(doc)
    sample = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "SAMPLE_trace_perfetto.json")
    if not os.path.exists(sample):
        with open(sample, "w") as fh:
            json.dump(doc, fh, indent=1)
            fh.write("\n")
    n_sample = validate_chrome_trace(json.load(open(sample)))
    rows.append(f"table11_schema,0,fresh_events={n_ev} "
                f"sample_events={n_sample} valid=True")
    return rows


def table12_profile(fast: bool) -> list[str]:
    """Latency-profiler gates (`repro.telemetry.profile` + `regress`):

      * decomposition — per-packet/per-message components sum bit-exactly
        to measured inject→eject latency across sim / buffered / bridged
        BMVM runs, and the critical-path length equals the final logical
        clock (the per-flow p50/p99 and above-bound gap are committed as
        deterministic counters in ``BENCH_table12.json``);
      * identity — an uncontended single packet meets
        ``latency == critical path == switch_lower_bound`` exactly;
      * zero overhead — an unprofiled run allocates no LatencyRecords (and
        still no TraceEvents), extending the `events_allocated` gate;
      * regress self-test — `telemetry.regress.compare_rows` passes on
        identical rows and trips (named metric) on an injected slowdown
        (``switch_buffer_depth=1`` vs the default 4)."""
    from repro.apps import bmvm
    from repro.core import NoCConfig, NoCExecutor, cut, make_topology
    from repro.core.partition import resolve_placement
    from repro.core.switch import (Packet, SwitchConfig, simulate_switch,
                                   switch_lower_bound)
    from repro.kernels import ref as kref
    from repro.telemetry import (Tracer, events_allocated, profile_trace,
                                 records_allocated)
    from repro.telemetry.regress import compare_rows

    rng = np.random.default_rng(12)
    cfg = bmvm.BMVMConfig(n=64, k=8, fold=2)
    A = rng.integers(0, 2, (64, 64)).astype(np.uint8)
    v = rng.integers(0, 2, (64,)).astype(np.uint8)
    lut = np.asarray(bmvm.preprocess(A, cfg))
    g, feedback = bmvm.build_bmvm_graph(lut, cfg)
    vw = np.asarray(kref.gf2_pack_vector(jnp.asarray(v), cfg.k), np.uint32)
    f = cfg.fold
    inputs = {f"lut{i}.v": vw[i * f:(i + 1) * f] for i in range(cfg.n_pe)}
    n = 2 * cfg.n_pe
    topo = make_topology("mesh", n)
    r = 2 if fast else 5
    rows = []

    def run_profiled(mode, pods=None, noc_cfg=None):
        tr = Tracer()
        plan = None
        place = None
        if pods is not None:
            place = resolve_placement(g, topo, pod_of_node=pods)
            plan = cut(g, place, pods)
        ex = NoCExecutor(g, topo, placement=place, plan=plan, cfg=noc_cfg,
                         trace=tr)
        t0 = time.monotonic()
        ex.run_iterative(inputs, feedback, r, mode=mode)
        dt = (time.monotonic() - t0) * 1e6
        prof = profile_trace(tr).check_exact()
        cp = prof.critical_path()
        assert cp.length == tr.clock, (cp.length, tr.clock)
        return prof, cp, dt

    # gate 1: exact decomposition + critical path across the transports
    pods = [0] * (n // 2) + [1] * (n - n // 2)
    for tag, mode, p in (("sim", "sim", None), ("buffered", "buffered", None),
                         ("bridged", "sim", pods)):
        prof, cp, dt = run_profiled(mode, pods=p)
        lats = sorted(l for rec in prof.records
                      for l in [rec.latency] * rec.n)
        p50 = lats[max(0, -(-50 * len(lats) // 100) - 1)]
        p99 = lats[max(0, -(-99 * len(lats) // 100) - 1)]
        rows.append(
            f"table12_bmvm_{tag},{dt:.0f},records={sum(x.n for x in prof.records)} "
            f"waves={len(prof.waves)} p50={p50} p99={p99} "
            f"crit={cp.length} gap={cp.gap} exact=True")
    # gate 2: uncontended single packet meets the analytic bound exactly
    scfg = SwitchConfig()
    tr = Tracer()
    res = simulate_switch(topo, [Packet(0, n - 1, 4, t_inject=0)], scfg,
                          tracer=tr)
    prof = profile_trace(tr).check_exact()
    rec, cp = prof.records[0], prof.critical_path()
    bound = switch_lower_bound(topo, [Packet(0, n - 1, 4, t_inject=0)], scfg)
    assert rec.latency == cp.length == bound == res.stats.cycles, (
        rec.latency, cp.length, bound, res.stats.cycles)
    assert rec.queueing == 0 and rec.bridge == 0
    rows.append(f"table12_single_packet,0,lat={rec.latency} crit={cp.length} "
                f"bound={bound} queueing=0 identity=True")
    # gate 3: profiling off allocates nothing (records AND events)
    ex_off = NoCExecutor(g, topo)
    ex_off.run_iterative(inputs, feedback, 1, mode="buffered")
    ev0, rec0 = events_allocated(), records_allocated()
    ex_off.run_iterative(inputs, feedback, r, mode="buffered")
    assert events_allocated() == ev0, "unprofiled run allocated TraceEvents"
    assert records_allocated() == rec0, "unprofiled run allocated LatencyRecords"
    rows.append("table12_zero_overhead,0,records_delta=0 events_delta=0 "
                "gate=True")
    # gate 4: the regression diff trips on an injected slowdown and only then
    def counter_row(noc_cfg):
        tr = Tracer()
        ex = NoCExecutor(g, topo, cfg=noc_cfg, trace=tr)
        _, st = ex.run_iterative(inputs, feedback, r, mode="buffered")
        prof = profile_trace(tr).check_exact()
        return {"name": "selftest_buffered", "us": 0.0,
                "cycles": st.switch_cycles, "stalls": st.switch_stall_cycles,
                "crit": prof.critical_path().length}

    base_row = counter_row(None)
    clean = compare_rows([base_row], [counter_row(None)])
    assert not clean, f"identical runs produced findings: {clean}"
    slow = compare_rows([base_row],
                        [counter_row(NoCConfig(switch_buffer_depth=1))])
    tripped = [fi for fi in slow if fi["verdict"] == "regression"]
    assert tripped, "injected slowdown (buffer_depth=1) did not trip the gate"
    rows.append(f"table12_regress_selftest,0,clean_findings={len(clean)} "
                f"tripped=True metric={tripped[0]['metric']} "
                f"delta={tripped[0]['delta']}")
    return rows


def placement_search(fast: bool) -> list[str]:
    """Annealing placement search vs round-robin/greedy on the app graphs."""
    from repro.apps import bmvm, ldpc
    from repro.apps.particle_filter import PFConfig, build_pf_graph
    from repro.core import (cut, make_topology, optimize_placement, place_greedy,
                            place_round_robin, placement_cost)

    iters = 800 if fast else 4000
    rng = np.random.default_rng(6)
    graphs = []
    g_ldpc, _ = ldpc.build_ldpc_graph(ldpc.fano_plane_H())
    graphs.append(("ldpc_fano", g_ldpc, make_topology("mesh", 16)))
    cfg = bmvm.BMVMConfig(n=64, k=8, fold=2)
    A = rng.integers(0, 2, (64, 64)).astype(np.uint8)
    g_bmvm, _ = bmvm.build_bmvm_graph(np.asarray(bmvm.preprocess(A, cfg)), cfg)
    graphs.append(("bmvm", g_bmvm, make_topology("mesh", 2 * cfg.n_pe)))
    graphs.append(("pf", build_pf_graph(PFConfig(n_particles=64), 4),
                   make_topology("mesh", 8)))
    rows = []
    for name, g, topo in graphs:
        rr = placement_cost(g, topo, place_round_robin(g, topo))
        gr = placement_cost(g, topo, place_greedy(g, topo))
        t0 = time.monotonic()
        opt = optimize_placement(g, topo, iters=iters, seed=0)
        dt = (time.monotonic() - t0) * 1e6
        oc = placement_cost(g, topo, opt)
        rows.append(f"placement_{name},{dt:.0f},cost_rr={rr} cost_greedy={gr} "
                    f"cost_opt={oc} gain_vs_rr={rr / max(oc, 1):.2f}x")
    # cut-aware variant: 2-pod split of the LDPC mesh
    pods = [0] * 8 + [1] * 8
    topo = make_topology("mesh", 16)
    opt = optimize_placement(g_ldpc, topo, pod_of_node=pods, iters=iters, seed=0)
    cb_rr = cut(g_ldpc, place_round_robin(g_ldpc, topo), pods).cut_bytes(g_ldpc)
    cb_opt = cut(g_ldpc, opt, pods).cut_bytes(g_ldpc)
    rows.append(f"placement_ldpc_cut,0,cut_bytes_rr={cb_rr} cut_bytes_opt={cb_opt}")
    return rows


def fig_ldpc(fast: bool) -> list[str]:
    from repro.apps import ldpc

    rng = np.random.default_rng(2)
    H = ldpc.pg_ldpc_H(copies=4 if fast else 16)
    idx = ldpc.build_edge_index(H)
    B = 16
    llr = jnp.asarray(np.stack([
        ldpc.awgn_llr(np.zeros(H.shape[1], np.int8), 3.0, rng) for _ in range(B)]))
    dec = jax.jit(lambda y: ldpc.decode_minsum(idx, y, 10)[0])
    dec(llr)
    t = _timeit(lambda: jax.block_until_ready(dec(llr)), n=5)
    thpt = B * H.shape[1] / (t / 1e6)
    rows = [f"fig_ldpc_decode,{t:.1f},bits_per_s={thpt:,.0f} N={H.shape[1]} iters=10"]
    _, _, stats = ldpc.decode_on_noc(ldpc.fano_plane_H(),
                                     ldpc.awgn_llr(np.zeros(7, np.int8), 3.0, rng), 10)
    rows.append(f"fig_ldpc_noc,0,rounds={stats.rounds} flits={stats.flits} "
                f"link_bytes={stats.link_bytes}")
    return rows


def fig_pf(fast: bool) -> list[str]:
    from repro.apps import particle_filter as pf

    rng = np.random.default_rng(3)
    cfg = pf.PFConfig(img=64, roi=16, n_particles=64, n_bins=16)
    frames, truth = pf.synth_video(cfg, 6 if fast else 12, rng)
    t0 = time.monotonic()
    est = pf.track(frames, cfg)
    dt = (time.monotonic() - t0) / (frames.shape[0] - 1) * 1e6
    err = float(np.linalg.norm(est - truth, axis=1).mean())
    return [f"fig_pf_track,{dt:.0f},px_err={err:.2f} fps={1e6 / dt:.1f}"]


def lm_step(fast: bool) -> list[str]:
    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import make_train_step
    from repro.models import transformer as T
    from repro.models.layers import init_params
    from repro.optim import AdamWConfig, adamw_init

    rows = []
    mesh = make_host_mesh()
    archs = ["llama3.2-1b", "qwen3-moe-235b-a22b"] if fast else [
        "llama3.2-1b", "qwen3-moe-235b-a22b", "jamba-v0.1-52b", "xlstm-350m"]
    rng = np.random.default_rng(4)
    for arch in archs:
        cfg = get_config(arch, smoke=True)
        params = init_params(T.abstract_params(cfg), jax.random.key(0))
        state = {"params": params, "opt": adamw_init(params)}
        B, S = 4, 64
        batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32),
                 "labels": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32)}
        if cfg.family == "encdec":
            batch["frames"] = jnp.zeros((B, cfg.enc_seq, cfg.d_frontend), jnp.float32)
        with jax.set_mesh(mesh):
            step = jax.jit(make_train_step(cfg, mesh, AdamWConfig()))
            state, _ = step(state, batch)  # compile
            t = _timeit(lambda: jax.block_until_ready(step(state, batch)[1]["loss"]), n=3)
        rows.append(f"lm_train_{arch},{t:.0f},tok_per_s={B * S / (t / 1e6):,.0f}")
    return rows


TABLES = {
    "table1_wrapper": table1_wrapper,
    "table4_bmvm_iter": table4_bmvm_iter,
    "table5_topology": table5_topology,
    "table5_batched": table5_batched,
    "table6_spmd": table6_spmd,
    "table7_moe_noc": table7_moe_noc,
    "table8_interchip": table8_interchip,
    "table9_congestion": table9_congestion,
    "table10_verify": table10_verify,
    "table11_observability": table11_observability,
    "table12_profile": table12_profile,
    "placement_search": placement_search,
    "fig_ldpc": fig_ldpc,
    "fig_pf": fig_pf,
    "lm_step": lm_step,
}


# tables with committed perf-trajectory snapshots (--snapshot): future PRs
# diff BENCH_<key>.json against a fresh run to track the numbers over time
SNAPSHOTS = {
    "table4_bmvm_iter": "BENCH_table4.json",
    "table9_congestion": "BENCH_table9.json",
    "table12_profile": "BENCH_table12.json",
}


def _parse_row(row: str) -> dict:
    """One 'name,us,k=v k=v ...' CSV row -> a JSON-able dict."""
    name, us, derived = row.split(",", 2)
    parsed: dict = {"name": name, "us": float(us)}
    for tok in derived.split():
        if "=" not in tok:
            continue
        k, v = tok.split("=", 1)
        try:
            parsed[k] = int(v)
        except ValueError:
            try:
                parsed[k] = float(v)
            except ValueError:
                parsed[k] = v
    return parsed


def _snapshot_meta() -> dict:
    """Provenance stamp for a snapshot: where/what produced these numbers.

    A BENCH_*.json diff is only meaningful against its recording environment
    — the stamp makes "the numbers moved" attributable to a code change vs a
    toolchain/host change."""
    import platform
    import subprocess

    try:
        sha = subprocess.run(["git", "rev-parse", "HEAD"],
                             capture_output=True, text=True, timeout=10,
                             cwd=os.path.dirname(os.path.abspath(__file__)),
                             ).stdout.strip() or "unknown"
    except Exception:
        sha = "unknown"
    return {
        "git_sha": sha,
        "jax": jax.__version__,
        "numpy": np.__version__,
        "python": platform.python_version(),
        "platform": platform.platform(),
        "devices": jax.device_count(),
        "backend": jax.default_backend(),
    }


def _write_snapshot(table: str, rows: list[str], fast: bool) -> str:
    """Persist a table's rows as benchmarks/BENCH_<key>.json.

    Timings (`us` and any *_us key) are environment noise, so the snapshot
    separates them from the derived counters a future PR can diff exactly;
    `meta` (git SHA, jax/numpy versions, host) records the environment the
    noise came from."""
    import json

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        SNAPSHOTS[table])
    payload = {"table": table, "fast": fast, "meta": _snapshot_meta(),
               "rows": [_parse_row(r) for r in rows]}
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=1, sort_keys=True)
        fh.write("\n")
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--snapshot", action="store_true",
                    help="write benchmarks/BENCH_<table>.json for tables "
                         "with a tracked perf trajectory")
    ap.add_argument("--compare", action="store_true",
                    help="instead of running tables, diff fresh runs "
                         "against the committed BENCH_*.json baselines "
                         "(delegates to repro.telemetry.regress)")
    args, extra = ap.parse_known_args()
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    if args.compare:
        from repro.telemetry.regress import main as regress_main

        raise SystemExit(regress_main(extra))
    print("name,us_per_call,derived")
    for name, fn in TABLES.items():
        if args.only and args.only != name:
            continue
        t0 = time.monotonic()
        rows = fn(args.fast)
        for row in rows:
            print(row)
        if args.snapshot and name in SNAPSHOTS:
            print(f"# snapshot: {_write_snapshot(name, rows, args.fast)}")
        print(f"# {name} done in {time.monotonic() - t0:.1f}s", flush=True)


if __name__ == "__main__":
    main()
