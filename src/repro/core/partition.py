"""Phase-2 of the paper: partitioning the NoC across chips (here: pods).

Three layers, mirroring §III:

1. **Placement** — map TaskGraph PEs onto topology nodes (the paper does this
   manually; we provide round-robin and a greedy traffic-aware placer).
2. **Cutting** — given a node→pod assignment, classify every channel as
   intra-pod (stays an on-chip NoC link) or cross-pod (gets a pair of
   quasi-SERDES endpoints stitched in, `core.serdes`).  The executor consumes
   this; the application is oblivious ("seamless" per the paper).
3. **Mesh sharding rules** — the LM-framework generalization: logical array
   axes → mesh axes (MaxText-style), plus the cross-pod collective that
   replaces XLA's flat all-reduce with a hierarchical, optionally
   serdes-compressed exchange over the cut.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Mapping, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import serdes as qserdes
from .graph import Channel, TaskGraph
from .topology import Mesh2D, Topology


# ---------------------------------------------------------------------------
# 1. placement
# ---------------------------------------------------------------------------

def place_round_robin(graph: TaskGraph, topo: Topology) -> dict[str, int]:
    names = list(graph.pes)
    return {n: i % topo.n_nodes for i, n in enumerate(names)}


def place_greedy(graph: TaskGraph, topo: Topology) -> dict[str, int]:
    """Traffic-aware: place heavy-talking PE pairs on low-hop node pairs.

    Classic greedy: order PE pairs by traffic desc; for each, put the unplaced
    endpoint on the free node closest to the placed one.
    """
    traffic = graph.traffic_bytes()
    pairs = sorted(traffic.items(), key=lambda kv: -kv[1])
    placement: dict[str, int] = {}
    free = set(range(topo.n_nodes))

    def nearest_free(anchor: int) -> int:
        if not free:
            # more PEs than nodes: fall back to min-load node
            loads: dict[int, int] = {}
            for v in placement.values():
                loads[v] = loads.get(v, 0) + 1
            return min(range(topo.n_nodes), key=lambda n: loads.get(n, 0))
        return min(free, key=lambda n: topo.hops(anchor, n))

    for (a, b), _ in pairs:
        if a not in placement and b not in placement:
            na = min(free) if free else 0
            placement[a] = na
            free.discard(na)
            nb = nearest_free(na)
            placement[b] = nb
            free.discard(nb)
        elif a in placement and b not in placement:
            nb = nearest_free(placement[a])
            placement[b] = nb
            free.discard(nb)
        elif b in placement and a not in placement:
            na = nearest_free(placement[b])
            placement[a] = na
            free.discard(na)
    for n in graph.pes:  # isolated PEs
        if n not in placement:
            node = min(free) if free else 0
            placement[n] = node
            free.discard(node)
    return placement


def pair_cut_weights(graph: TaskGraph,
                     serdes_cfg: qserdes.QuasiSerdesConfig) -> dict[tuple[str, str], int]:
    """Per (src_pe, dst_pe) pair: the serialized wire beats its channels
    occupy when the pair lands across the pod cut (`serdes.link_wire_beats` —
    padded words incl. scale words, == lanes × per-lane words)."""
    out: dict[tuple[str, str], int] = {}
    for c in graph.channels:
        p = graph.pes[c.src_pe].out_port(c.src_port)
        w = qserdes.link_wire_beats(p.shape, p.dtype, serdes_cfg)
        k = (c.src_pe, c.dst_pe)
        out[k] = out.get(k, 0) + w
    return out


def placement_cost(graph: TaskGraph, topo: Topology, placement: Mapping[str, int],
                   pod_of_node: Optional[Sequence[int]] = None,
                   serdes_cfg: Optional[qserdes.QuasiSerdesConfig] = None,
                   w_cut: float = 1.0) -> float:
    """The placement objective, shared by the greedy placer, the annealer and
    the pod-cut co-optimizer (one objective, no disagreement):

    * intra-pod edges (and all edges when no cut is given) cost
      ``traffic_bytes × hops`` — on-chip link traffic;
    * pod-crossing edges cost ``w_cut ×`` their **serialized wire beats**
      (`pair_cut_weights`) — serdes-aware: a cut edge pays for the padded
      words its messages occupy on the narrow link (compression and lane
      padding included), not its raw byte count.
    """
    traffic = graph.traffic_bytes()
    if pod_of_node is None:
        return sum(b * topo.hops(placement[a], placement[c])
                   for (a, c), b in traffic.items())
    beats = pair_cut_weights(graph, serdes_cfg or qserdes.QuasiSerdesConfig())
    cost = 0.0
    for (a, c), b in traffic.items():
        if pod_of_node[placement[a]] == pod_of_node[placement[c]]:
            cost += b * topo.hops(placement[a], placement[c])
        else:
            cost += w_cut * beats[(a, c)]
    return cost


def optimize_placement(graph: TaskGraph, topo: Topology,
                       pod_of_node: Optional[Sequence[int]] = None,
                       init: Optional[Mapping[str, int]] = None,
                       iters: int = 2000, seed: int = 0,
                       w_cut: float = 1.0,
                       max_per_node: Optional[int] = None,
                       serdes_cfg: Optional[qserdes.QuasiSerdesConfig] = None,
                       ) -> dict[str, int]:
    """Annealing/KL-style placement search (the paper places by hand; this is
    the automated analog).

    Minimizes :func:`placement_cost`: Σ traffic × hops for on-chip edges,
    plus — when a node→pod assignment is given — ``w_cut`` × the serialized
    wire beats of every edge crossing the pod cut (serdes-aware, so the
    annealer and the pod-cut co-optimizer share one objective; each cut edge
    pays for the quasi-SERDES frame its messages occupy).  Moves are single
    PE relocations and PE↔PE swaps; acceptance is simulated annealing with a
    geometric cooling schedule, deterministic under ``seed``.  Incremental
    delta evaluation touches only the moved PEs' channels, so a step is O(deg)
    not O(E) — cheap enough to run per app graph at executor-build time.

    ``max_per_node`` caps router occupancy (the paper's NoC wraps one PE per
    router); default is the balanced occupancy ``ceil(n_pes / n_nodes)`` — 1
    when PEs fit — so the search cannot game the hop objective by stacking
    every PE on one node.
    """
    rng = np.random.default_rng(seed)
    names = list(graph.pes)
    n = topo.n_nodes
    if max_per_node is None:
        max_per_node = -(-len(names) // n)

    def occupancy(p):
        o: dict[int, int] = {}
        for node in p.values():
            o[node] = o.get(node, 0) + 1
        return o

    if init is not None:
        placement = dict(init)
    else:
        # greedy seed when it respects capacity; round-robin (always balanced)
        # otherwise — greedy's both-unplaced fallback can stack node 0 when
        # PEs far outnumber nodes
        placement = place_greedy(graph, topo)
        if max(occupancy(placement).values(), default=0) > max_per_node:
            placement = place_round_robin(graph, topo)
    occ = occupancy(placement)
    if max(occ.values(), default=0) > max_per_node:
        raise ValueError(f"initial placement exceeds max_per_node={max_per_node}: "
                         f"occupancy {occ}")
    # symmetric traffic adjacency: pe -> [(other_pe, bytes, cut wire beats)]
    beats = pair_cut_weights(graph, serdes_cfg or qserdes.QuasiSerdesConfig())
    adj: dict[str, list[tuple[str, int, int]]] = {p: [] for p in names}
    for (a, b), by in graph.traffic_bytes().items():
        if a != b:
            adj[a].append((b, by, beats[(a, b)]))
            adj[b].append((a, by, beats[(a, b)]))

    def local(pe: str, node: int) -> float:
        c = 0.0
        for other, by, cw in adj[pe]:
            o = node if other == pe else placement[other]
            if pod_of_node is not None and pod_of_node[node] != pod_of_node[o]:
                c += w_cut * cw
            else:
                c += by * topo.hops(node, o)
        return c

    def total() -> float:
        return float(placement_cost(graph, topo, placement, pod_of_node,
                                    serdes_cfg, w_cut))

    cost = total()
    best_cost, best = cost, dict(placement)
    t0 = max(cost / max(len(names), 1), 1.0)
    t_end = t0 / 1000.0
    for it in range(iters):
        temp = t0 * (t_end / t0) ** (it / max(iters - 1, 1))
        if rng.random() < 0.5 or len(names) < 2:
            # relocate one PE to a random node with free capacity
            pe = names[int(rng.integers(len(names)))]
            old_node = placement[pe]
            new_node = int(rng.integers(n))
            if new_node == old_node or occ.get(new_node, 0) >= max_per_node:
                continue
            before = local(pe, old_node)
            placement[pe] = new_node
            delta = local(pe, new_node) - before
            if delta <= 0 or rng.random() < np.exp(-delta / temp):
                cost += delta
                occ[old_node] -= 1
                occ[new_node] = occ.get(new_node, 0) + 1
            else:
                placement[pe] = old_node
        else:
            # swap two PEs' nodes (KL-style exchange)
            i, j = rng.choice(len(names), size=2, replace=False)
            p, q = names[int(i)], names[int(j)]
            np_, nq = placement[p], placement[q]
            if np_ == nq:
                continue
            before = local(p, np_) + local(q, nq)
            placement[p], placement[q] = nq, np_
            delta = (local(p, nq) + local(q, np_)) - before
            if delta <= 0 or rng.random() < np.exp(-delta / temp):
                cost += delta
            else:
                placement[p], placement[q] = np_, nq
        if cost < best_cost - 1e-9:
            best_cost, best = cost, dict(placement)
    return best


def resolve_placement(graph: TaskGraph, topo: Topology, spec="rr",
                      pod_of_node: Optional[Sequence[int]] = None,
                      seed: int = 0,
                      serdes_cfg: Optional[qserdes.QuasiSerdesConfig] = None,
                      ) -> dict[str, int]:
    """Turn a placement spec into a PE→node map.

    ``spec`` is one of ``"rr"`` (round-robin), ``"greedy"``, ``"opt"``
    (annealing search, see :func:`optimize_placement` — cut-aware when
    ``pod_of_node`` is given, weighting cut edges by ``serdes_cfg``'s
    serialized wire beats so the search optimizes the objective the executor
    actually pays) or an explicit mapping, which is passed through."""
    if isinstance(spec, Mapping):
        missing = set(graph.pes) - set(spec)
        if missing:
            raise ValueError(f"placement mapping is missing PEs {sorted(missing)}")
        bad = {p: n for p, n in spec.items() if not 0 <= n < topo.n_nodes}
        if bad:
            raise ValueError(f"placement mapping has out-of-range nodes {bad} "
                             f"(topology has {topo.n_nodes} nodes)")
        return dict(spec)
    if spec == "rr":
        return place_round_robin(graph, topo)
    if spec == "greedy":
        return place_greedy(graph, topo)
    if spec == "opt":
        return optimize_placement(graph, topo, pod_of_node=pod_of_node, seed=seed,
                                  serdes_cfg=serdes_cfg)
    raise ValueError(f"unknown placement spec {spec!r}; use 'rr'|'greedy'|'opt' or a mapping")


# ---------------------------------------------------------------------------
# 1b. placement → device-mesh assignment (SPMD execution of the placed graph)
# ---------------------------------------------------------------------------

def mesh_for_topology(topo: Topology, devices: Optional[Sequence] = None) -> Mesh:
    """Build the device mesh a topology's compiled routing schedule runs over.

    Mesh axes follow ``core.routing.topology_axes`` (1D ``noc`` axis for
    ring/fat-tree, ``(noc_y, noc_x)`` for mesh/torus), so NoC node ``i`` is
    device ``i`` in mesh row-major order — the identity the spmd executor and
    :func:`node_device_coords` rely on."""
    from .routing import topology_axes

    axes = topology_axes(topo)
    shape = [s for _, s in axes]
    need = int(np.prod(shape, dtype=np.int64))
    devices = list(jax.devices()) if devices is None else list(devices)
    if len(devices) < need:
        raise RuntimeError(
            f"topology {topo.name!r} needs {need} devices for SPMD execution, "
            f"have {len(devices)}; run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need}")
    return Mesh(np.array(devices[:need]).reshape(shape),
                tuple(a for a, _ in axes))


def mesh_for_partition(topo: Topology, plan: "PartitionPlan",
                       devices: Optional[Sequence] = None) -> Mesh:
    """Device mesh for *partitioned* spmd execution (`core.interchip`).

    When the plan's pods are equal-sized contiguous node blocks, the mesh is
    2D ``(pod, node)`` — pod p owns devices ``[p*k, (p+1)*k)`` and the flat
    linearized device index over ``("pod", "node")`` is exactly the global
    NoC node id the bridged program's hop pairs use.  For irregular cuts the
    topology mesh is returned instead (pod membership then lives only in the
    bridge tables; the execution is identical because the bridged program is
    always linearized over the flat index)."""
    n = topo.n_nodes
    pods = tuple(plan.pod_of_node)
    n_pods = max(pods) + 1 if pods else 1
    blocked = (n_pods > 1 and n % n_pods == 0
               and all(pods[i] == i // (n // n_pods) for i in range(n)))
    if not blocked:
        return mesh_for_topology(topo, devices)
    devices = list(jax.devices()) if devices is None else list(devices)
    if len(devices) < n:
        raise RuntimeError(
            f"topology {topo.name!r} needs {n} devices for partitioned SPMD "
            f"execution, have {len(devices)}; run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n}")
    return Mesh(np.array(devices[:n]).reshape(n_pods, n // n_pods),
                ("pod", "node"))


def node_device_coords(topo: Topology, node: int) -> dict[str, int]:
    """Linear NoC node id → mesh-axis coordinates on ``mesh_for_topology``."""
    from .topology import Mesh2D

    if not 0 <= node < topo.n_nodes:
        raise ValueError(f"node {node} out of range for {topo.n_nodes}-node topology")
    if isinstance(topo, Mesh2D):
        x, y = topo.coords(node)
        return {"noc_y": y, "noc_x": x}
    return {"noc": node}


def placement_to_device_coords(placement: Mapping[str, int],
                               topo: Topology) -> dict[str, dict[str, int]]:
    """Map a PE→node placement (e.g. an ``optimize_placement`` result) onto
    device coordinates of the SPMD mesh — which device each PE's messages
    originate from when the schedule runs as a real collective program."""
    return {pe: node_device_coords(topo, node) for pe, node in placement.items()}


# ---------------------------------------------------------------------------
# 2. cutting across pods
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PartitionPlan:
    """Result of cutting a placed graph across pods (paper Fig. 5)."""

    placement: Mapping[str, int]          # PE -> node
    pod_of_node: tuple[int, ...]          # node -> pod
    intra: tuple[Channel, ...]
    cross: tuple[Channel, ...]            # channels that get serdes endpoints
    serdes_cfg: qserdes.QuasiSerdesConfig = qserdes.QuasiSerdesConfig()

    @property
    def n_pods(self) -> int:
        return max(self.pod_of_node) + 1 if self.pod_of_node else 1

    def cut_bytes(self, graph: TaskGraph) -> int:
        return sum(graph.pes[c.src_pe].out_port(c.src_port).nbytes for c in self.cross)

    def wire_beats(self, graph: TaskGraph) -> int:
        """Serialized wire beats (padded words incl. scale words) the cut
        channels occupy per wave — the serdes-aware cut cost the placement
        objective charges (`placement_cost` / `optimize_pod_cut`)."""
        return sum(
            qserdes.link_wire_beats(
                graph.pes[c.src_pe].out_port(c.src_port).shape,
                graph.pes[c.src_pe].out_port(c.src_port).dtype,
                self.serdes_cfg,
            )
            for c in self.cross
        )

    def wire_bytes(self, graph: TaskGraph) -> int:
        """Bytes on the narrow inter-pod wire after serdes framing/compression
        (= ``wire_beats × beat_bytes`` — one framing rule, one call site)."""
        return self.wire_beats(graph) * self.serdes_cfg.beat_bytes


def cut(graph: TaskGraph, placement: Mapping[str, int], pod_of_node: Sequence[int],
        serdes_cfg: qserdes.QuasiSerdesConfig = qserdes.QuasiSerdesConfig()) -> PartitionPlan:
    intra, cross = [], []
    for c in graph.channels:
        same = pod_of_node[placement[c.src_pe]] == pod_of_node[placement[c.dst_pe]]
        (intra if same else cross).append(c)
    return PartitionPlan(dict(placement), tuple(pod_of_node), tuple(intra), tuple(cross), serdes_cfg)


def candidate_cuts(topo: Topology, n_pods: int) -> list[tuple[int, ...]]:
    """Deterministic node→pod candidates for an ``n_pods``-way cut:

    * linear blocks (rows of a 2D grid, arcs of a ring) — the physical
      "consecutive routers per chip" split;
    * column blocks for 2D topologies (cut along the other axis);
    * strided round-robin — the adversarial control the optimizer should
      beat on locality-sensitive graphs.
    """
    n = topo.n_nodes
    cands: list[tuple[int, ...]] = []
    if n % n_pods == 0:
        blk = n // n_pods
        cands.append(tuple(i // blk for i in range(n)))
        if isinstance(topo, Mesh2D) and topo.rx % n_pods == 0:
            w = topo.rx // n_pods
            cands.append(tuple((i % topo.rx) // w for i in range(n)))
        cands.append(tuple(i % n_pods for i in range(n)))
    else:
        cands.append(tuple(min(i * n_pods // n, n_pods - 1) for i in range(n)))
    seen, out = set(), []
    for c in cands:
        if c not in seen:
            seen.add(c)
            out.append(c)
    return out


def optimize_pod_cut(graph: TaskGraph, topo: Topology, n_pods: int = 2,
                     serdes_grid: Optional[Sequence[qserdes.QuasiSerdesConfig]] = None,
                     iters: int = 800, seed: int = 0,
                     w_cut: float = 1.0) -> tuple[PartitionPlan, float]:
    """Co-optimize the pod cut with serdes compression settings (the ROADMAP
    placement/pod-cut item): for every candidate node→pod cut
    (:func:`candidate_cuts`) × serdes config in ``serdes_grid``, anneal the
    placement under the shared serdes-aware objective
    (:func:`placement_cost` = intra-pod link bytes + serialized cut beats)
    and keep the winner.  Deterministic under ``seed``.

    Returns ``(PartitionPlan, cost)`` — the plan carries the chosen
    placement, pod assignment and serdes config, ready for
    ``NoCExecutor(plan=...)``."""
    if serdes_grid is None:
        serdes_grid = [qserdes.QuasiSerdesConfig(wire_bits=wb, lanes=ln, compress=cp)
                       for wb in (8, 16, 32) for ln in (1, 8)
                       for cp in ("none", "bf16")]
    best: Optional[tuple[float, dict, tuple, qserdes.QuasiSerdesConfig]] = None
    for pods in candidate_cuts(topo, n_pods):
        for scfg in serdes_grid:
            pl = optimize_placement(graph, topo, pod_of_node=pods, iters=iters,
                                    seed=seed, w_cut=w_cut, serdes_cfg=scfg)
            c = float(placement_cost(graph, topo, pl, pods, scfg, w_cut))
            if best is None or c < best[0]:
                best = (c, pl, pods, scfg)
    cost, pl, pods, scfg = best
    return cut(graph, pl, pods, scfg), cost


# ---------------------------------------------------------------------------
# 3. LM-framework sharding rules + cross-pod collectives
# ---------------------------------------------------------------------------

# Logical axis vocabulary used by every model in src/repro/models.
DEFAULT_RULES: dict[str, Optional[str | tuple[str, ...]]] = {
    "batch": ("pod", "data"),
    "seq": None,
    "seq_kv_shard": "data",      # long-context KV/state sequence sharding
    "head_dim_shard": "data",    # long-context KV head_dim sharding (decode:
                                 #   DUS stays shard-local; QK psums over data)
    "embed": None,               # d_model stays replicated-per-shard (activations)
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "experts": "model",
    "expert_mlp": None,
    "conv": None,
    "ssm_inner": "model",
    "ssm_state": None,
    "layers": None,              # scanned-stack leading axis
}


@contextlib.contextmanager
def rules_override(**kv):
    """Temporarily rewrite DEFAULT_RULES entries (e.g. no_tp: model axes off).
    Used by the hillclimb to evaluate sharding-profile changes per cell."""
    saved = {k: DEFAULT_RULES.get(k) for k in kv}
    DEFAULT_RULES.update(kv)
    try:
        yield
    finally:
        DEFAULT_RULES.update(saved)


NO_TP = dict(vocab=None, heads=None, kv_heads=None, mlp=None, experts=None,
             ssm_inner=None, batch=("pod", "data", "model"))


def logical_to_spec(axes: Sequence[Optional[str]], rules: Mapping[str, Any] = DEFAULT_RULES,
                    mesh_axes: Optional[Sequence[str]] = None,
                    dims: Optional[Sequence[int]] = None,
                    mesh_shape: Optional[Mapping[str, int]] = None) -> P:
    """('batch','seq','embed') -> PartitionSpec(('pod','data'), None, None).

    Drops mesh axes absent from the current mesh (single-pod drops 'pod'),
    and — when ``dims``/``mesh_shape`` are given — axes whose product does not
    divide the array dimension (e.g. 8 KV heads on a model=16 axis fall back
    to replication rather than failing)."""
    parts = []
    for i, a in enumerate(axes):
        m = rules.get(a) if a is not None else None
        if m is None:
            parts.append(None)
            continue
        ms = (m,) if isinstance(m, str) else tuple(m)
        if mesh_axes is not None:
            ms = tuple(x for x in ms if x in mesh_axes)
        if dims is not None and mesh_shape is not None and ms:
            keep, prod = [], 1
            for x in ms:
                nx = mesh_shape.get(x, 1)
                if dims[i] % (prod * nx) == 0:
                    keep.append(x)
                    prod *= nx
            ms = tuple(keep)
        parts.append(ms[0] if len(ms) == 1 else (ms if ms else None))
    return P(*parts)


def named_sharding(mesh: Mesh, axes: Sequence[Optional[str]],
                   rules: Mapping[str, Any] = DEFAULT_RULES) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(axes, rules, mesh.axis_names))


def constrain(x: jax.Array, axes: Sequence[Optional[str]],
              rules: Mapping[str, Any] = DEFAULT_RULES) -> jax.Array:
    """with_sharding_constraint by logical axes over the ambient mesh's
    non-manual axes (a no-op when there are none); shape-aware: unshardable
    dims stay replicated."""
    mesh = jax.sharding.get_abstract_mesh()
    usable = tuple(a for a in mesh.axis_names if a not in mesh.manual_axes)
    if not usable:
        return x
    spec = logical_to_spec(axes, rules, usable, dims=x.shape,
                           mesh_shape=dict(mesh.shape))
    return jax.lax.with_sharding_constraint(x, spec)


# -- cross-pod gradient exchange (the "cut link" of the LM framework) --------

def cross_pod_mean(tree, axis: str = "pod", cfg: Optional[qserdes.QuasiSerdesConfig] = None,
                   residuals=None, n_pods: int = 2, serialized: bool = True):
    """Average a pytree over the pod axis *inside shard_map*.

    cfg=None      → plain ``lax.pmean`` (XLA flat collective; baseline).
    cfg given     → paper-faithful: each pod serializes its contribution
                    through quasi-SERDES endpoints over the cut links
                    (ring exchange over pods), with optional compression and
                    error-feedback residuals.
    Returns (tree, new_residuals).
    """
    if cfg is None:
        return jax.tree.map(lambda g: lax.pmean(g, axis), tree), residuals

    perm = [(i, (i + 1) % n_pods) for i in range(n_pods)]

    def sync_leaf(g, res):
        acc = g
        send = g
        r = res
        for _ in range(n_pods - 1):
            recv, r = qserdes.send_over_link(send, axis, perm, cfg, residual=r,
                                             serialized=serialized)
            acc = acc + recv
            send = recv  # forward the neighbor's contribution around the ring
        return acc / n_pods, r

    leaves, treedef = jax.tree.flatten(tree)
    res_leaves = (jax.tree.flatten(residuals)[0] if residuals is not None
                  else [None] * len(leaves))
    out, new_res = [], []
    for g, r in zip(leaves, res_leaves):
        o, nr = sync_leaf(g, r)
        out.append(o)
        new_res.append(nr if nr is not None else jnp.zeros_like(g, jnp.float32)
                       if cfg.compress == "int8" else 0.0)
    return jax.tree.unflatten(treedef, out), jax.tree.unflatten(treedef, new_res)
