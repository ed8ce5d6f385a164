"""Mixture-of-Experts layer — the paper's packet-switched NoC, verbatim.

Tokens are packets; the router's top-k gate writes the destination PE
(expert) into each packet header; dispatch/combine are the Data
Distributor / Data Collector wrappers; per-(src, expert) buffer capacity is
the CONNECT flit-buffer-depth analog (tokens beyond capacity are dropped,
exactly like a bounded FIFO back-pressuring).

On one chip (no ``model`` mesh axis, or one of size 1) the layer runs
``held``: the experts held here are a contiguous block of the router's
``n_experts``; the router keeps its published width and top-k, and the held
experts compute exactly the tokens routed to them, as a grouped matmul
(``lax.ragged_dot``) over the (token, expert) pairs sorted by expert, with no
capacity and no drops.  A token routed only to absent experts gets zero from
this layer: what those experts add lies on other chips.  On a mesh of more
than one device (data axes beside the size-1 ``model`` axis) it runs inside a
``shard_map``, each device on its own shard of the batch.

Over a ``model`` axis of more than one rank, two engines (both first-class,
selectable per config):

* ``gather`` — expert parallelism over model-axis-replicated activations:
  every model rank gathers the tokens addressed to its local experts
  (capacity-bounded), computes, scatter-adds, and a single psum over 'model'
  combines.  Comm = one d-sized all-reduce; no all-to-all.  Robust default
  for giant pjit graphs.

* ``noc`` — the paper-faithful packet route: activations arrive
  sequence-sharded over 'model'; per-destination-rank packet cubes move
  through the topology's *compiled route program*
  (`core.routing.compile_routes` → `run_route_program`, linearized over the
  'model' axis: fat-tree → one fused all_to_all; ring/mesh/torus → per-hop
  ppermute rounds), experts compute, and the return path runs the same
  program again.  This is phase-1+phase-2 of the paper applied to an LM
  layer; `core.routing.route_program_stats` yields the exact flit/round/
  link-byte counters per invocation (:class:`MoEDispatchStats`).

Capacity semantics are UNIFIED across engines (`dispatch_capacity`): both
budget token slots per (source shard, expert) dispatch FIFO, so the same
config drops the same tokens whichever engine runs (property-tested).  With
an attached :class:`~repro.core.noc.NoCConfig`, its ``flit_buffer_depth`` IS
the capacity knob — the effective ``capacity_factor`` is derived from it,
not configured separately.

Packet framing on the noc engine is *static*, like the NoC executor's
compiled flit programs: the (expert, slot) position inside the per-(src,dst)
cube encodes the destination expert, so no header bytes ride the links —
the same compile-time-contract framing `core.noc` uses for app graphs.

Every path implements the same math (property-tested against ``dense_ref``,
which stays the test oracle).
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..core.noc import NoCConfig
from ..telemetry.metrics import get_registry
from .layers import ParamSpec


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    n_experts: int
    top_k: int
    d_ff: int
    capacity_factor: float = 1.25
    impl: str = "gather"            # gather | noc | dense
    noc_topology: str = "fattree"   # fattree | ring | mesh2d | torus2d
    act: str = "silu"
    # NoC dispatch options: when set, flit_buffer_depth becomes the capacity
    # knob (capacity_factor is then *derived* — see dispatch_capacity)
    noc: Optional[NoCConfig] = None
    # the experts held here: ``n_held`` (0: all ``n_experts``) from
    # ``first_held`` on; ``n_experts`` stays the router's width
    n_held: int = 0
    first_held: int = 0
    # renormalise the top-k gate weights to sum to one (Qwen3-MoE's
    # ``norm_topk_prob``); Jamba keeps the softmax weights as they are
    norm_topk_prob: bool = True

    @property
    def held(self) -> int:
        return self.n_held or self.n_experts


@dataclasses.dataclass
class MoEDispatchStats:
    """Per-invocation dispatch accounting, returned by :func:`moe_apply`.

    ``drops`` / ``peak_occupancy`` are data-dependent (traced under jit);
    everything else is static, derived from shapes and the compiled route
    program.  For ``engine="noc"`` the flit/round/link-byte counters are
    exactly ``2 ×`` :func:`~repro.core.routing.route_program_stats` of the
    dispatched token cube (outbound trip + return trip) — tested.
    Counters are per model-axis NoC invocation (data-parallel replicas run
    their own concurrent dispatch; rounds are physical, counted once).
    """

    engine: str                     # engine that actually ran
    topology: Optional[str]         # noc engine: the routed topology
    fallback: Optional[str]         # reason a requested engine was not used
    capacity: int                   # per-(src, expert) FIFO depth, token slots
    capacity_factor: float          # effective (possibly derived) factor
    flits: int                      # framed flits on the links (out + back)
    rounds: int                     # ppermute rounds (out + back)
    link_bytes: int                 # bytes crossing topology links
    drops: Any = 0                  # tokens dropped by capacity (traced)
    peak_occupancy: Any = 0         # max tokens demanded of one (src,dst) buffer
    experts: Any = None             # (B, S, top_k) routed expert ids (traced)

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    def publish(self, registry=None) -> None:
        """Publish into the telemetry metrics registry under the canonical
        ``noc.moe.*`` names (`repro.telemetry.MOE_METRIC_NAMES`) — the same
        names the train loop's step metrics land on, so transformer metrics
        and NoC dispatch stats share one schema.  No-op when metrics are off
        or fields still hold traced values (publish host-side)."""
        if registry is None:
            from ..telemetry.metrics import get_registry
            registry = get_registry()
        if registry is not None:
            registry.record_moe_stats(self)


def moe_specs(c: MoEConfig, dtype=jnp.float32) -> dict:
    E, d, f = c.held, c.d_model, c.d_ff
    return {
        "router": ParamSpec((d, c.n_experts), ("embed", None), dtype, init="small"),
        "gate": ParamSpec((E, d, f), ("experts", "embed", "expert_mlp"), dtype),
        "up": ParamSpec((E, d, f), ("experts", "embed", "expert_mlp"), dtype),
        "down": ParamSpec((E, f, d), ("experts", "expert_mlp", "embed"), dtype),
    }


def _act(x, kind):
    return jax.nn.silu(x) if kind == "silu" else jax.nn.gelu(x, approximate=True)


# ---------------------------------------------------------------------------
# capacity — ONE formula for both engines
# ---------------------------------------------------------------------------

def dispatch_capacity(tokens_per_src: int, c: MoEConfig) -> int:
    """Per-(source shard, expert) dispatch-FIFO depth in token slots.

    The single capacity budget both engines enforce (gather == noc parity:
    the same tokens are dropped whichever engine runs).  With an attached
    NoCConfig the CONNECT ``flit_buffer_depth`` IS the knob — each
    (src, expert) FIFO holds that many token slots, exactly (depth 1 must be
    expressible for the drops-vs-depth sweep) and the effective
    capacity_factor falls out (:func:`effective_capacity_factor`).  Without
    one, the classic ``tokens·top_k·capacity_factor / n_experts`` formula
    applies with the legacy floor of 8 slots, so small-T decode-shaped
    dispatch stays drop-free as it always was.  Clamped to
    [1, tokens_per_src·top_k]."""
    if c.noc is not None:
        cap = c.noc.flit_buffer_depth
    else:
        cap = max(8, int(tokens_per_src * c.top_k * c.capacity_factor / c.n_experts))
    return max(1, min(cap, tokens_per_src * c.top_k))


def effective_capacity_factor(tokens_per_src: int, c: MoEConfig) -> float:
    """The capacity_factor implied by :func:`dispatch_capacity` — the derived
    quantity the stats report (never an independent second knob)."""
    cap = dispatch_capacity(tokens_per_src, c)
    return cap * c.n_experts / (tokens_per_src * c.top_k)


def _dispatch_slots(flat_dst, blk_of_pkt, experts, n_blocks: int, cap: int):
    """First-``cap`` (arrival order) packet slots per (expert, source block).

    flat_dst: (P,) destination expert of each packet; blk_of_pkt: (P,) source
    block; experts: (E',) expert ids to dispatch (may be traced).  Returns
    (slots, valid) of shape (E', n_blocks, cap)."""
    npkt = flat_dst.shape[0]
    arrival = -jnp.arange(npkt, dtype=jnp.float32)

    def pick(e, blk):
        mine = (flat_dst == e) & (blk_of_pkt == blk)
        score = jnp.where(mine, arrival, -jnp.inf)
        _, slots = lax.top_k(score, cap)
        return slots, mine[slots]

    ne = experts.shape[0]
    ee = jnp.repeat(experts, n_blocks)
    bb = jnp.tile(jnp.arange(n_blocks), ne)
    slots, valid = jax.vmap(pick)(ee, bb)
    return slots.reshape(ne, n_blocks, cap), valid.reshape(ne, n_blocks, cap)


def _dispatch_counts(flat_dst, blk_of_pkt, n_experts: int, n_blocks: int):
    """Demanded tokens per (expert, source block) — pre-capacity load."""
    return jnp.zeros((n_experts, n_blocks), jnp.int32).at[
        flat_dst, blk_of_pkt].add(1)


def _drops_and_peak(counts, cap: int, n_ranks: int):
    """(Σ_e relu(load_e - cap), max per-(src-block, dst-rank) demand)."""
    epr = counts.shape[0] // n_ranks
    drops = jnp.sum(jnp.maximum(counts - cap, 0))
    per_pair = counts.reshape(n_ranks, epr, -1).sum(axis=1)   # (dst_rank, blk)
    return drops, per_pair.max()


def _router(x_flat, wr, c: MoEConfig):
    """x_flat (T, d) -> (weights (T,k), idx (T,k), aux_loss, (me, ce)).

    The router dot keeps bf16 OPERANDS with f32 accumulation: casting the
    operands to f32 would make the backward emit an f32 (T, d) cotangent that
    poisons the whole residual-stream backward into f32 (2× HBM traffic on
    every layer — found via the roofline anchor dump, §Perf C2)."""
    logits = jax.lax.dot(x_flat, wr.astype(x_flat.dtype),
                         preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = lax.top_k(probs, c.top_k)
    if c.norm_topk_prob:
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss terms (reduce across shards BEFORE the
    # product — mean-of-products != product-of-means)
    E = c.n_experts
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(me * ce)
    return w.astype(x_flat.dtype), idx, aux, (me, ce)


def dense_ref(params, x, c: MoEConfig):
    """O(E·T·d·f) reference: every token through every held expert,
    gate-combined.  The oracle for every path (small shapes only)."""
    return _dense(params, x, c)[:2]


def _dense(params, x, c: MoEConfig):
    """``dense_ref`` and the routed ids, (B, S, top_k)."""
    B, S, d = x.shape
    xf = x.reshape(-1, d)
    w, idx, aux, _mece = _router(xf, params["router"], c)
    gate_full = jnp.zeros((xf.shape[0], c.n_experts), x.dtype)
    gate_full = jax.vmap(lambda g, i, ww: g.at[i].set(ww))(gate_full, idx, w)
    gate_full = gate_full[:, c.first_held:c.first_held + c.held]
    h = jnp.einsum("td,edf->tef", xf, params["gate"].astype(x.dtype))
    u = jnp.einsum("td,edf->tef", xf, params["up"].astype(x.dtype))
    y = jnp.einsum("tef,efd->ted", _act(h, c.act) * u, params["down"].astype(x.dtype))
    out = jnp.einsum("ted,te->td", y, gate_full)
    return out.reshape(B, S, d), aux, idx.reshape(B, S, c.top_k)


def _expert_ffn(xe, wg, wu, wd, act):
    """xe (E_loc, C, d) through stacked local experts."""
    h = jnp.einsum("ecd,edf->ecf", xe, wg)
    u = jnp.einsum("ecd,edf->ecf", xe, wu)
    return jnp.einsum("ecf,efd->ecd", _act(h, act) * u, wd)


# ---------------------------------------------------------------------------
# one chip: the held experts, grouped, with no capacity
# ---------------------------------------------------------------------------

def grouped_ffn(xs, sizes, wg, wu, wd, act):
    """Rows ``xs`` (P, d), sorted by held expert with ``sizes[e]`` rows for
    expert e, each through its own expert: (P, d).  Rows past ``sum(sizes)``
    belong to no expert; the caller masks them."""
    h = lax.ragged_dot(xs, wg, sizes)
    u = lax.ragged_dot(xs, wu, sizes)
    return lax.ragged_dot(_act(h, act) * u, wd, sizes, preferred_element_type=jnp.float32)


def _publish_held(c: MoEConfig) -> None:
    """The router's width and the experts held, into the opt-in registry
    (at trace time)."""
    reg = get_registry()
    if reg is not None:
        reg.gauge("models.moe.router_width").set(c.n_experts)
        reg.gauge("models.moe.experts_held").set(c.held)


def _held_local(params, x_flat, c: MoEConfig):
    """x_flat (T, d) -> (out (T, d), (me, ce), idx (T, k)): route over all
    ``n_experts``, compute the held block on exactly the tokens routed to it.

    The (token, expert) pairs are sorted by held expert, the pairs whose
    expert is absent last; ``grouped_ffn`` runs each held expert on its run
    of rows and the gate weights combine them back per token."""
    T, d = x_flat.shape
    k = c.top_k
    _publish_held(c)
    with jax.named_scope("moe.router"):
        w, idx, _, mece = _router(x_flat, params["router"], c)
        local = idx.reshape(-1) - c.first_held
        mine = (local >= 0) & (local < c.held)
        key = jnp.where(mine, local, c.held)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.zeros((c.held + 1,), jnp.int32).at[key].add(1)[:c.held]
        rows = order // k                                  # token of each sorted pair
    with jax.named_scope("moe.experts"):
        ys = grouped_ffn(x_flat[rows], sizes, params["gate"].astype(x_flat.dtype),
                         params["up"].astype(x_flat.dtype),
                         params["down"].astype(x_flat.dtype), c.act)
    with jax.named_scope("moe.combine"):
        gate = jnp.where(mine, w.reshape(-1).astype(jnp.float32), 0.0)[order]
        ys = jnp.where(mine[order][:, None], ys * gate[:, None], 0.0)
        out = ys[jnp.argsort(order)].reshape(T, k, d).sum(axis=1)
    return out.astype(x_flat.dtype), mece, idx


# ---------------------------------------------------------------------------
# engine 1: gather (EP over replicated activations)
# ---------------------------------------------------------------------------

def _gather_local(x_flat, wr, wg, wu, wd, c: MoEConfig, n_ranks: int, axis: str,
                  blk_of, n_blocks: int):
    """blk_of: (T,) source block of each token (== the noc engine's source
    rank when the sequence divides), so capacity is enforced per
    (source block, expert) — identical drop sets to the noc engine."""
    T, d = x_flat.shape
    rank = lax.axis_index(axis)
    epr = c.n_experts // n_ranks
    cap = dispatch_capacity(T // n_blocks, c)
    w, idx, _, (me, ce) = _router(x_flat, wr, c)

    # packet headers: (T*k,) destination expert + combine weight
    flat_dst = idx.reshape(-1)
    flat_w = w.reshape(-1)
    tok_of = jnp.repeat(jnp.arange(T), c.top_k)
    blk_of_pkt = jnp.repeat(blk_of, c.top_k)

    local_e = rank * epr + jnp.arange(epr)
    slots, valid = _dispatch_slots(flat_dst, blk_of_pkt, local_e, n_blocks, cap)
    slots = slots.reshape(epr, -1)                          # (epr, n_blocks*cap)
    valid = valid.reshape(epr, -1)
    toks = tok_of[slots]
    xe = x_flat[toks] * valid[..., None].astype(x_flat.dtype)
    ye = _expert_ffn(xe, wg, wu, wd, c.act)                 # (epr, B*cap, d)
    comb = (flat_w[slots] * valid.astype(flat_w.dtype))[..., None]
    out = jnp.zeros_like(x_flat)
    out = out.at[toks.reshape(-1)].add((ye * comb).reshape(-1, d))
    out = lax.psum(out, axis)                               # combine expert ranks
    counts = _dispatch_counts(flat_dst, blk_of_pkt, c.n_experts, n_blocks)
    drops, peak = _drops_and_peak(counts, cap, n_ranks)     # full-layer (replicated)
    return out, (me, ce), (drops, peak), idx


# ---------------------------------------------------------------------------
# engine 2: noc (paper packet switching over the compiled route program)
# ---------------------------------------------------------------------------

def _noc_local(x_flat, wr, wg, wu, wd, c: MoEConfig, n_ranks: int, axis: str,
               prog, cap: int):
    """x_flat: (T_loc, d) — tokens sequence-sharded over ``axis``.

    Pack per-destination-rank token cubes (static (expert, slot) framing),
    move them out and back with the compiled :class:`RouteProgram`
    (`run_route_program` linearized over ``axis``), compute, combine.
    """
    from ..core.routing import run_route_program

    T, d = x_flat.shape
    E = c.n_experts
    epr = E // n_ranks
    w, idx, _, (me, ce) = _router(x_flat, wr, c)

    flat_dst = idx.reshape(-1)                               # (T*k,) expert id
    flat_w = w.reshape(-1)
    tok_of = jnp.repeat(jnp.arange(T), c.top_k)
    blk0 = jnp.zeros_like(flat_dst)                          # one local source block

    slots, valid = _dispatch_slots(flat_dst, blk0, jnp.arange(E), 1, cap)
    slots, valid = slots[:, 0], valid[:, 0]                  # (E, cap)
    toks = tok_of[slots]
    payload = x_flat[toks] * valid[..., None].astype(x_flat.dtype)   # (E, cap, d)

    # --- outbound: Data Distributor -> compiled route program -> Collector.
    # payload row e = (dst_rank e//epr, local expert e%epr): rank-major, so the
    # (n_ranks, epr*cap, d) cube is destination-indexed as the program expects.
    cube = payload.reshape(n_ranks, epr * cap, d)
    rx = run_route_program(cube, prog, axis_name=axis)       # (src_rank, epr*cap, d)

    # --- local expert compute; slot position IS the header (static framing)
    xe = rx.reshape(n_ranks, epr, cap, d)
    xe = jnp.moveaxis(xe, 1, 0).reshape(epr, n_ranks * cap, d)
    ye = _expert_ffn(xe, wg, wu, wd, c.act)                  # (epr, R*cap, d)

    # --- return trip: the same program, cube destination-indexed by src rank
    ycube = jnp.moveaxis(ye.reshape(epr, n_ranks, cap, d), 1, 0)
    back = run_route_program(ycube.reshape(n_ranks, epr * cap, d), prog,
                             axis_name=axis)                 # (exp_rank, epr*cap, d)
    back = back.reshape(E, cap, d)                           # slot-aligned with payload
    contrib = back * (flat_w[slots] * valid.astype(flat_w.dtype))[..., None]
    out = jnp.zeros_like(x_flat)
    out = out.at[toks.reshape(-1)].add(contrib.reshape(-1, d))
    counts = _dispatch_counts(flat_dst, blk0, E, 1)
    drops, peak = _drops_and_peak(counts, cap, n_ranks)      # this shard's share
    return out, (me, ce), (drops, peak), idx


# ---------------------------------------------------------------------------
# public layer
# ---------------------------------------------------------------------------

def _static_stats(engine: str, c: MoEConfig, *, fallback=None, topology=None,
                  capacity=0, tokens_per_src=0, flits=0, rounds=0,
                  link_bytes=0, drops=0, peak=0, experts=None) -> MoEDispatchStats:
    cf = (effective_capacity_factor(tokens_per_src, c) if tokens_per_src
          else c.capacity_factor)
    return MoEDispatchStats(engine=engine, topology=topology, fallback=fallback,
                            capacity=capacity, capacity_factor=cf, flits=flits,
                            rounds=rounds, link_bytes=link_bytes, drops=drops,
                            peak_occupancy=peak, experts=experts)


def moe_apply(params: dict, x: jax.Array, c: MoEConfig
              ) -> tuple[jax.Array, jax.Array, MoEDispatchStats]:
    """x: (B, S, d) -> (out, aux_loss, MoEDispatchStats).

    With no ``model`` mesh axis, or one of size 1, the ``held`` path runs
    (one chip's experts, no drops); ``impl="dense"`` runs the oracle.  Over
    more ranks the engine is ``c.impl``.  Every engine reports the routed
    expert ids in ``stats.experts``.  Every fallback away from the
    requested engine is recorded in ``stats.fallback``; the ones that cost
    speed (expert count not divisible across ranks, decode-shaped inputs
    demoting ``noc``) also emit a ``UserWarning``."""
    if c.impl == "dense":
        out, aux, idx = _dense(params, x, c)
        return out, aux, _static_stats("dense", c, experts=idx)

    mesh = jax.sharding.get_abstract_mesh()
    n_ranks = mesh.shape["model"] if "model" in mesh.axis_names else 1
    B, S, d = x.shape
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_batch = 1
    for a in batch_axes:
        n_batch *= mesh.shape[a]
    if B % max(n_batch, 1):
        batch_axes = ()          # tiny-batch decode: replicate over data axes
        n_batch = 1
    bspec = batch_axes if batch_axes else None
    B_loc = B // n_batch

    def _aux_of(me, ce, axes):
        if axes:
            me = lax.pmean(me, axes)
            ce = lax.pmean(ce, axes)
        return c.n_experts * jnp.sum(me * ce)

    fallback = None
    if n_ranks > 1 and c.n_experts % n_ranks:
        fallback = (f"n_experts={c.n_experts} not divisible by model ranks="
                    f"{n_ranks}: every rank computes all experts")
        warnings.warn(f"moe_apply: {fallback}", stacklevel=2)
    if n_ranks == 1 or fallback:
        sharded = mesh.size > 1  # each device its own shard of the batch

        def held(xl, p):
            out, (me, ce), idx = _held_local(p, xl.reshape(-1, d), c)
            return (out.reshape(xl.shape), _aux_of(me, ce, batch_axes if sharded else ()),
                    idx.reshape(xl.shape[:2] + (c.top_k,)))
        if sharded:
            held = jax.shard_map(
                held, mesh=mesh, in_specs=(P(bspec, None, None), P()),
                out_specs=(P(bspec, None, None), P(), P(bspec, None, None)),
                check_vma=False)
        out, aux, idx = held(x, params)
        T = B_loc * S
        st = MoEDispatchStats(engine="held", topology=None, fallback=fallback,
                              capacity=T * c.top_k, capacity_factor=float(c.n_experts),
                              flits=0, rounds=0, link_bytes=0, experts=idx)
        return out, aux.reshape(()), st
    if c.held != c.n_experts:
        raise ValueError(f"a share of {c.held} of {c.n_experts} experts runs on one "
                         f"chip, not over {n_ranks} model ranks")

    wspec = P("model", None, None)
    all_axes = batch_axes + ("model",)
    impl, fallback = c.impl, None
    if impl == "noc" and (S < n_ranks or S % n_ranks):
        fallback = (f"impl='noc' needs seq len {S} divisible by model ranks="
                    f"{n_ranks} (decode-shaped input): using 'gather'")
        warnings.warn(f"moe_apply: {fallback}", stacklevel=2)
        impl = "gather"

    if impl == "gather":
        T = B_loc * S
        # source blocks == the noc engine's sequence shards when S divides,
        # so both engines enforce the SAME per-(src, expert) capacity
        n_blocks = n_ranks if S % n_ranks == 0 else 1
        blk_of = (jnp.arange(T) % S) // (S // n_blocks)

        def fn(xl, wr, wg, wu, wd):
            out, (me, ce), (drops, peak), idx = _gather_local(
                xl.reshape(T, d), wr, wg, wu, wd, c, n_ranks, "model",
                blk_of, n_blocks)
            if batch_axes:       # drops replicated over 'model'; sum replicas
                drops = lax.psum(drops, batch_axes)
                peak = lax.pmax(peak, batch_axes)
            return (out.reshape(xl.shape), _aux_of(me, ce, batch_axes),
                    drops, peak, idx.reshape(xl.shape[:2] + (c.top_k,)))
        sm = jax.shard_map(
            fn, mesh=mesh,
            in_specs=(P(bspec, None, None), P(), wspec, wspec, wspec),
            out_specs=(P(bspec, None, None), P(), P(), P(), P(bspec, None, None)),
            check_vma=False)
        out, aux, drops, peak, idx = sm(
            x, params["router"].astype(x.dtype), params["gate"].astype(x.dtype),
            params["up"].astype(x.dtype), params["down"].astype(x.dtype))
        stats = _static_stats("gather", c, fallback=fallback,
                              capacity=dispatch_capacity(T // n_blocks, c),
                              tokens_per_src=T // n_blocks,
                              drops=drops, peak=peak, experts=idx)
        return out, aux.reshape(()), stats

    # impl == "noc": compile the topology's route program once per call site
    from ..core.routing import compile_routes, route_program_stats
    from ..core.topology import make_topology

    topo = make_topology(c.noc_topology, n_ranks)
    prog = compile_routes(topo)
    ncfg = c.noc or NoCConfig()
    T_loc = B_loc * (S // n_ranks)
    cap = dispatch_capacity(T_loc, c)
    epr = c.n_experts // n_ranks
    msg_nbytes = epr * cap * d * x.dtype.itemsize   # one (src,dst) token cube
    sstats = route_program_stats(prog, n_ranks * n_ranks * msg_nbytes)

    def fn(xl, wr, wg, wu, wd):
        xl2 = xl.reshape(-1, d)
        out, (me, ce), (drops, peak), idx = _noc_local(
            xl2, wr, wg, wu, wd, c, n_ranks, "model", prog, cap)
        return (out.reshape(xl.shape), _aux_of(me, ce, all_axes),
                lax.psum(drops, all_axes), lax.pmax(peak, all_axes),
                idx.reshape(xl.shape[:2] + (c.top_k,)))
    sm = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(bspec, "model", None), P(), wspec, wspec, wspec),
        out_specs=(P(bspec, "model", None), P(), P(), P(), P(bspec, "model", None)),
        check_vma=False)
    out, aux, drops, peak, idx = sm(
        x, params["router"].astype(x.dtype), params["gate"].astype(x.dtype),
        params["up"].astype(x.dtype), params["down"].astype(x.dtype))
    stats = _static_stats(
        "noc", c, fallback=fallback, topology=c.noc_topology, capacity=cap,
        tokens_per_src=T_loc,
        # out + back trips of the same program; flits frame all n^2 buffers
        flits=2 * n_ranks * n_ranks * ncfg.flits_for(msg_nbytes),
        rounds=2 * sstats.rounds, link_bytes=2 * sstats.link_bytes,
        drops=drops, peak=peak, experts=idx)
    return out, aux.reshape(()), stats
