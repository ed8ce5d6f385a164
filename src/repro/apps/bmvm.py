"""Case study III: GF(2) matrix–vector multiplication, Williams' sub-quadratic
algorithm (paper §VI) — block-Wiedemann-style iterated products A^r·V.

The communication structure is exactly an all-to-all: node i looks up
LUT_i[v_i] and sends word j to node j, which XOR-accumulates — so topology
choice dominates performance (the paper's Table V).  Three realizations:

* ``iterate_kernel``   — single-chip datapath: the Pallas LUT-XOR kernel
                         (BRAM→VMEM adaptation) iterated r times.
* ``iterate_noc_sim``  — PE-per-node TaskGraph on a chosen topology with
                         round-by-round routing stats (Table V reproduction).
* ``iterate_spmd``     — shard_map over real devices: local lookup + the
                         topology's collective schedule + XOR reduce (the
                         production path; exercised in the dry-run + tests).

Folding (paper §VI-B): fold=f gives each PE f sub-vectors with a coalesced
LUT — here simply n/k/f PEs each owning f LUT columns.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..core import (NoCExecutor, PE, Port, TaskGraph, cut, make_topology,
                    resolve_placement)
from ..core.routing import all_to_all_for, topology_axes
from ..kernels import ops as kops
from ..kernels import ref as kref


@dataclasses.dataclass(frozen=True)
class BMVMConfig:
    n: int = 64
    k: int = 8
    fold: int = 2
    topology: str = "mesh"

    @property
    def n_sub(self) -> int:           # sub-vectors
        return self.n // self.k

    @property
    def n_pe(self) -> int:            # PEs after folding
        assert self.n_sub % self.fold == 0
        return self.n_sub // self.fold


def preprocess(a_bits: np.ndarray, cfg: BMVMConfig) -> jax.Array:
    """One-time LUT construction (paper Fig. 13): (C, 2^k, R) uint32."""
    return kref.gf2_preprocess(jnp.asarray(a_bits), cfg.k)


def software_ref(a_bits: np.ndarray, v_bits: np.ndarray, r: int) -> np.ndarray:
    """The paper's multithreaded-software analog: direct O(n²) iterated."""
    a = np.asarray(a_bits, np.uint8)
    v = np.asarray(v_bits, np.uint8)
    for _ in range(r):
        v = (v @ a.T) % 2
    return v


def iterate_kernel(lut: jax.Array, v_bits: jax.Array, cfg: BMVMConfig, r: int,
                   use_kernel: bool = True) -> jax.Array:
    """A^r·V via the Pallas kernel; v_bits: (M, n) -> (M, n)."""
    with jax.named_scope("bmvm.pack"):
        vw = kref.gf2_pack_vector(v_bits, cfg.k).astype(jnp.uint32)

    def body(vw, _):
        with jax.named_scope("kernels.gf2_bmvm"):
            return kops.gf2_bmvm(lut, vw, use_kernel=use_kernel), None

    vw, _ = jax.lax.scan(body, vw, None, length=r)
    with jax.named_scope("bmvm.unpack"):
        return kref.gf2_unpack_vector(vw, cfg.k)


# ---------------------------------------------------------------------------
# NoC simulation (Table V reproduction)
# ---------------------------------------------------------------------------

def build_bmvm_graph(lut_np: np.ndarray, cfg: BMVMConfig) -> tuple[TaskGraph, list]:
    """PE_i: lookup its (folded) LUT columns; ACC_j: XOR-accumulate words."""
    C, P, R = lut_np.shape
    npe, f = cfg.n_pe, cfg.fold
    g = TaskGraph("bmvm")
    luts = jnp.asarray(lut_np)

    def mk_lookup(i):
        def fn(**kw):
            v = kw["v"].astype(jnp.uint32)          # (f,) this PE's sub-vectors
            cols = jnp.arange(i * f, (i + 1) * f)
            words = jax.vmap(lambda c, vv: luts[c, vv, :])(cols, v)  # (f, R)
            agg = words[0]
            for t in range(1, f):
                agg = jnp.bitwise_xor(agg, words[t])  # fold-local combine
            return {f"w{j}": agg[j * f:(j + 1) * f] for j in range(npe)}
        return fn

    def acc_fn(**kw):
        vals = [kw[f"in{i}"] for i in range(npe)]
        acc = vals[0]
        for v in vals[1:]:
            acc = jnp.bitwise_xor(acc, v)
        return {"v": acc}

    for i in range(npe):
        g.add(PE(f"lut{i}", mk_lookup(i),
                 (Port("v", (f,), np.uint32),),
                 tuple(Port(f"w{j}", (f,), np.uint32) for j in range(npe))))
    for j in range(npe):
        g.add(PE(f"acc{j}", acc_fn,
                 tuple(Port(f"in{i}", (f,), np.uint32) for i in range(npe)),
                 (Port("v", (f,), np.uint32),)))
    feedback = []
    for i in range(npe):
        for j in range(npe):
            g.connect(f"lut{i}.w{j}", f"acc{j}.in{i}")
        feedback.append((f"acc{i}.v", f"lut{i}.v"))
    return g, feedback


def iterate_noc_sim(lut: jax.Array, v_bits: np.ndarray, cfg: BMVMConfig, r: int,
                    topology: Optional[str] = None, n_nodes: Optional[int] = None,
                    placement="rr", mode: str = "sim",
                    pods: Optional[list[int]] = None, serdes_cfg=None,
                    tracer=None):
    """(decoded vector, NoCStats) — the Table-V measurement path.

    ``placement``: 'rr' | 'greedy' | 'opt' (annealing search, cut-aware when
    ``pods`` is given) or an explicit PE→node mapping.  ``mode``: any
    `NoCExecutor.run` mode — ``"spmd"`` runs the same compiled flit program
    over a device mesh (needs n_nodes devices).  ``pods`` (node→pod) turns on
    partitioned execution: cut links run through quasi-SERDES bridge
    endpoints (``serdes_cfg``), results stay bit-identical and NoCStats gain
    the ``bridge_*`` counters.  ``tracer``: a `repro.telemetry.Tracer` to
    record the run's event timeline (trace↔stats parity guaranteed)."""
    from ..core.serdes import QuasiSerdesConfig

    topo_name = topology or cfg.topology
    n_nodes = n_nodes or 2 * cfg.n_pe
    g, feedback = build_bmvm_graph(np.asarray(lut), cfg)
    topo = make_topology(topo_name, n_nodes)
    place = resolve_placement(g, topo, placement, pod_of_node=pods,
                              serdes_cfg=serdes_cfg)
    plan = None
    if pods is not None:
        plan = cut(g, place, pods, serdes_cfg or QuasiSerdesConfig())
    ex = NoCExecutor(g, topo, placement=place, plan=plan, trace=tracer)
    v1 = np.asarray(v_bits).reshape(-1)               # single vector (n,)
    vw = np.asarray(kref.gf2_pack_vector(jnp.asarray(v1), cfg.k), np.uint32)
    f = cfg.fold
    inputs = {f"lut{i}.v": vw[i * f:(i + 1) * f] for i in range(cfg.n_pe)}
    outs, stats = ex.run_iterative(inputs, feedback, r, mode=mode)
    out_w = np.concatenate([np.asarray(outs[f"acc{i}.v"]) for i in range(cfg.n_pe)])
    return np.asarray(kref.gf2_unpack_vector(jnp.asarray(out_w), cfg.k)), stats


# ---------------------------------------------------------------------------
# SPMD (shard_map) realization — the production path
# ---------------------------------------------------------------------------

@functools.partial(jax.profiler.annotate_function, name="bmvm.spmd.build")
def iterate_spmd(lut: jax.Array, v_bits: jax.Array, cfg: BMVMConfig, r: int,
                 mesh=None, topology: str = "fattree"):
    """Distribute PEs over mesh devices; route via the topology schedule.

    lut (C, P, R) sharded over PEs on axis 0; v words likewise.  Each round:
    local lookup (C_loc rows of all R words) -> all-to-all (each node keeps
    its R_loc words from everyone) -> XOR-reduce.

    The host span ``bmvm.spmd.build`` covers the Python work of a call: the
    topology, mesh and route plan, and tracing the program.  Under a caller's
    ``jax.jit`` it fires only when that caller traces."""
    from jax.sharding import Mesh, PartitionSpec as P_

    topo = make_topology(topology, (mesh.devices.size if mesh else jax.device_count()))
    axes = topology_axes(topo)
    if mesh is None:
        devs = np.array(jax.devices()[: topo.n_nodes]).reshape([s for _, s in axes])
        mesh = Mesh(devs, [a for a, _ in axes])
    n_nodes = topo.n_nodes
    a2a = all_to_all_for(topo)
    C, P2k, R = lut.shape
    assert C % n_nodes == 0 and R % n_nodes == 0
    r_loc = R // n_nodes
    with jax.named_scope("bmvm.pack"):
        vw = kref.gf2_pack_vector(v_bits, cfg.k).astype(jnp.uint32)   # (M, C)
    M = vw.shape[0]
    mesh_axes = tuple(a for a, _ in axes)
    lspec = P_(mesh_axes if len(mesh_axes) > 1 else mesh_axes[0], None, None)
    vspec = P_(None, mesh_axes if len(mesh_axes) > 1 else mesh_axes[0])

    def local(lut_loc, vw_loc):
        # vw_loc: (M, C_loc) this node's sub-vector words
        def body(vw_l, _):
            with jax.named_scope("bmvm.lookup"):
                looked = jax.vmap(
                    lambda vrow: jax.vmap(lambda lc, vv: lc[vv, :])(lut_loc, vrow)
                )(vw_l)                                         # (M, C_loc, R)
                part = looked[:, 0]
                for c in range(1, looked.shape[1]):
                    part = jnp.bitwise_xor(part, looked[:, c])  # (M, R) local partial
                # packetize per destination node: dest j gets words [j*r_loc:(j+1)*r_loc]
                pkts = part.reshape(M, n_nodes, r_loc).swapaxes(0, 1)  # (n, M, r_loc)
            rcv = a2a(pkts)                                      # (n, M, r_loc)
            with jax.named_scope("bmvm.reduce"):
                acc = rcv[0]
                for s in range(1, n_nodes):
                    acc = jnp.bitwise_xor(acc, rcv[s])           # (M, r_loc) = my words
            return acc, None

        acc, _ = jax.lax.scan(body, vw_loc, None, length=1)
        return acc

    @jax.jit
    def run(lut_, vw_):
        def fn(lut_loc, vw_l):
            out = vw_l
            for _ in range(r):
                out = local(lut_loc, out)
            return out
        sm = jax.shard_map(fn, mesh=mesh, in_specs=(lspec, vspec),
                       out_specs=vspec, check_vma=False)
        return sm(lut_, vw_)

    out_w = run(lut, vw)
    with jax.named_scope("bmvm.unpack"):
        return kref.gf2_unpack_vector(out_w.astype(jnp.uint32), cfg.k)
