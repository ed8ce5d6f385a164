"""Routing schedules: execute a Topology's all-to-all as JAX collectives.

The paper's CONNECT routers move flits hop by hop at runtime.  On TPU the
equivalent is a *static* schedule of neighbor exchanges compiled into the
program: every round is one ``lax.ppermute`` (= one ICI hop for every node in
parallel); the fat-tree/crossbar case is a single fused ``lax.all_to_all``.

All functions here run *inside* ``jax.shard_map`` and operate on the
per-device view: ``x`` has shape ``(n, *chunk)`` where ``x[d]`` is the message
this node addresses to node ``d``.  They return ``(n, *chunk)`` where
``out[s]`` is the message received from node ``s``.  The semantics of every
variant is exactly the device transpose (``transpose_oracle``) — property
tested in tests/test_routing*.py.

Each ppermute round runs in the named scope ``routing.<axis>.r<t>`` (round t
over mesh axis ``axis``), inside ``routing.all_to_all`` for the whole
exchange, so a profile gives the device time of every round.

A pure-numpy round-by-round simulator (``simulate_schedule``) executes the
same schedules without devices; benchmarks use it so that measured time scales
with rounds x bytes like the paper's Table V.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

import dataclasses
from typing import Optional

from .topology import (AxisSchedule, FatTree, Mesh2D, Ring, Topology, Torus2D,
                       bwd_pairs, fwd_pairs)


# ---------------------------------------------------------------------------
# shard_map collectives (per-device view)
# ---------------------------------------------------------------------------

def transpose_oracle(x: jax.Array, axis_name: str) -> jax.Array:
    """Reference semantics: fused all_to_all (what the schedules must equal)."""
    return lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0)


def _fwd_perm(n: int, wrap: bool) -> list[tuple[int, int]]:
    return list(fwd_pairs(n, wrap))


def _bwd_perm(n: int, wrap: bool) -> list[tuple[int, int]]:
    return list(bwd_pairs(n, wrap))


def _round(axis_name: str, t: int):
    """Named scope of round ``t`` (from 1) of an exchange over ``axis_name``."""
    return jax.named_scope(f"routing.{axis_name}.r{t}")


def _put(out: jax.Array, src, val: jax.Array, valid) -> jax.Array:
    """out[src] = val where valid (dynamic index, masked)."""
    src_c = jnp.clip(src, 0, out.shape[0] - 1)
    cur = lax.dynamic_index_in_dim(out, src_c, 0, keepdims=False)
    new = jnp.where(valid, val, cur)
    return lax.dynamic_update_index_in_dim(out, new, src_c, 0)


def ring_all_to_all_unidir(x: jax.Array, axis_name: str) -> jax.Array:
    """Paper-faithful unidirectional ring rotation: n-1 rounds."""
    n = lax.axis_size(axis_name)
    i = lax.axis_index(axis_name)
    me = lax.dynamic_index_in_dim(x, i, 0, keepdims=False)
    out = _put(jnp.zeros_like(x), i, me, True)
    buf = x
    for t in range(1, n):
        with _round(axis_name, t):
            buf = lax.ppermute(buf, axis_name, _fwd_perm(n, wrap=True))
            # after t forward rotations this node holds node (i-t)'s buffer;
            # extract the message it addressed to us.
            val = lax.dynamic_index_in_dim(buf, i, 0, keepdims=False)
            out = _put(out, (i - t) % n, val, True)
    return out


def line_all_to_all(x: jax.Array, axis_name: str, wrap: bool) -> jax.Array:
    """Bidirectional 1D exchange.  wrap=True → torus ring (⌈n/2⌉-ish rounds,
    both directions concurrently); wrap=False → mesh line (n-1 rounds)."""
    n = lax.axis_size(axis_name)
    i = lax.axis_index(axis_name)
    me = lax.dynamic_index_in_dim(x, i, 0, keepdims=False)
    out = _put(jnp.zeros_like(x), i, me, True)
    if n == 1:
        return out
    fwd_steps = n // 2 if wrap else n - 1
    bwd_steps = (n - 1) // 2 if wrap else n - 1
    fbuf, bbuf = x, x
    for t in range(1, max(fwd_steps, bwd_steps) + 1):
        with _round(axis_name, t):
            if t <= fwd_steps:
                fbuf = lax.ppermute(fbuf, axis_name, _fwd_perm(n, wrap))
                src = (i - t) % n if wrap else i - t
                val = lax.dynamic_index_in_dim(fbuf, i, 0, keepdims=False)
                out = _put(out, src, val, True if wrap else src >= 0)
            if t <= bwd_steps:
                bbuf = lax.ppermute(bbuf, axis_name, _bwd_perm(n, wrap))
                src = (i + t) % n if wrap else i + t
                val = lax.dynamic_index_in_dim(bbuf, i, 0, keepdims=False)
                out = _put(out, src, val, True if wrap else src < n)
    return out


def grid_all_to_all(x: jax.Array, axis_x: str, axis_y: str, wrap: bool) -> jax.Array:
    """Factorized 2D exchange (dimension-ordered routing, like XY routing in
    the paper's mesh/torus NoCs).  ``x``: (n, *chunk), destination linear index
    d = dy*rx + dx;  returns source-linear-indexed result."""
    rx = lax.axis_size(axis_x)
    ry = lax.axis_size(axis_y)
    c = x.shape[1:]
    b = x.reshape(ry, rx, *c)          # (dy, dx, *c)
    b = jnp.moveaxis(b, 1, 0)          # (dx, dy, *c)
    b = line_all_to_all(b, axis_x, wrap)   # (sx, dy, *c)
    b = jnp.moveaxis(b, 1, 0)          # (dy, sx, *c)
    b = line_all_to_all(b, axis_y, wrap)   # (sy, sx, *c)
    return b.reshape(ry * rx, *c)      # source linear index sy*rx + sx


def crossbar_all_to_all(x: jax.Array, axis_name: str) -> jax.Array:
    """Fat-tree / ideal crossbar: single fused all_to_all."""
    with jax.named_scope("routing.all_to_all"):
        return lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0)


# ---------------------------------------------------------------------------
# schedule → ppermute-round compiler (hop decomposition)
# ---------------------------------------------------------------------------
#
# A topology's all-to-all is compiled into an explicit, value-independent
# :class:`RouteProgram`: a sequence of per-axis phases (dimension-ordered XY
# routing), each decomposed into rounds of single-hop neighbor permutations.
# Every round moves at most two rotating buffers (forward/backward direction)
# one hop via ``lax.ppermute`` and commits the messages that have reached their
# destination column, using static per-node source tables.  The same program
# drives three interpreters:
#
# * :func:`run_route_program`      — inside ``shard_map`` on a device mesh
#                                    (the NoC executor's ``mode="spmd"``);
# * :func:`simulate_route_program` — pure numpy, round-by-round (property
#                                    tests without devices);
# * :func:`route_program_stats`    — analytic rounds/link-bytes, matching the
#                                    round-by-round simulator exactly.

@dataclasses.dataclass(frozen=True)
class HopMove:
    """One single-hop buffer rotation inside a round.

    ``buf``       — which rotating buffer moves (0 = forward, 1 = backward);
    ``perm``      — the ``lax.ppermute`` (src, dst) neighbor pairs;
    ``src_table`` — per node ``i`` along the axis: the source node whose
                    message addressed to ``i`` arrives with this hop
                    (-1: nothing to commit at ``i``).
    """

    buf: int
    perm: tuple[tuple[int, int], ...]
    src_table: tuple[int, ...]


@dataclasses.dataclass(frozen=True)
class PermuteRound:
    """One synchronous NoC round: every node sends one buffer per link
    direction concurrently (1 move for unidirectional, 2 for bidirectional)."""

    moves: tuple[HopMove, ...]


@dataclasses.dataclass(frozen=True)
class LinePhase:
    """Hop-decomposed all-to-all along one mesh axis."""

    sched: AxisSchedule
    rounds: tuple[PermuteRound, ...]


@dataclasses.dataclass(frozen=True)
class RouteProgram:
    """Compiled routing schedule of a topology's all-to-all exchange."""

    topo_name: str
    n_nodes: int
    axes: tuple[tuple[str, int], ...]    # device-mesh axes (= topology_axes)
    phases: tuple[LinePhase, ...]        # empty → fused crossbar all_to_all

    @property
    def fused(self) -> bool:
        return not self.phases

    @property
    def n_rounds(self) -> int:
        return 1 if self.fused else sum(len(p.rounds) for p in self.phases)


def _compile_line_phase(sched: AxisSchedule) -> LinePhase:
    n = sched.size
    rounds = []
    for t in range(1, max(sched.fwd_steps, sched.bwd_steps) + 1):
        moves = []
        if t <= sched.fwd_steps:
            src = tuple((i - t) % n if sched.wrap else (i - t if i - t >= 0 else -1)
                        for i in range(n))
            moves.append(HopMove(0, sched.fwd_pairs(), src))
        if t <= sched.bwd_steps:
            src = tuple((i + t) % n if sched.wrap else (i + t if i + t < n else -1)
                        for i in range(n))
            moves.append(HopMove(1, sched.bwd_pairs(), src))
        rounds.append(PermuteRound(tuple(moves)))
    return LinePhase(sched, tuple(rounds))


def compile_routes(topo: Topology) -> RouteProgram:
    """Compile a topology's all-to-all into an explicit ppermute-round program."""
    phases = tuple(_compile_line_phase(s) for s in topo.axis_schedules())
    return RouteProgram(topo.name, topo.n_nodes, topology_axes(topo), phases)


def _line_exchange_compiled(x: jax.Array, phase: LinePhase,
                            axis_name: Optional[str] = None,
                            coord: Optional[jax.Array] = None,
                            expand=None, transfer=None) -> jax.Array:
    """Execute one compiled line phase on the per-device view (inside
    shard_map): x is (n, *chunk) destination-indexed, returns source-indexed.

    By default the phase runs over its own mesh axis (``phase.sched.axis``).
    With ``axis_name``/``coord``/``expand`` it runs *linearized* over a single
    flat device axis that embeds the phase axis: ``coord`` is this device's
    position along the phase axis and ``expand`` maps the phase's per-axis
    (src, dst) hop pairs to full-axis pairs (every row/column concurrently).

    ``transfer(buf, pairs)`` overrides the hop transport (default: one
    ``lax.ppermute``).  `core.interchip` uses it to funnel pod-crossing hops
    through quasi-SERDES bridge endpoints while intra-pod hops stay plain
    ppermutes; the pairs it receives are the *expanded* (full-axis) ones, i.e.
    global node ids in linearized mode."""
    sched = phase.sched
    name = axis_name or sched.axis
    i = lax.axis_index(name) if coord is None else coord
    me = lax.dynamic_index_in_dim(x, i, 0, keepdims=False)
    out = _put(jnp.zeros_like(x), i, me, True)
    bufs = [x, x]
    for t, rnd in enumerate(phase.rounds, 1):
        with _round(sched.axis, t):
            for mv in rnd.moves:
                perm = expand(mv.perm) if expand is not None else list(mv.perm)
                if transfer is None:
                    bufs[mv.buf] = lax.ppermute(bufs[mv.buf], name, perm)
                else:
                    bufs[mv.buf] = transfer(bufs[mv.buf], perm)
                src = jnp.asarray(mv.src_table, jnp.int32)[i]
                val = lax.dynamic_index_in_dim(bufs[mv.buf], i, 0, keepdims=False)
                out = _put(out, src, val, src >= 0)
    return out


def run_route_program(x: jax.Array, prog: RouteProgram,
                      axis_name: Optional[str] = None,
                      transfer=None) -> jax.Array:
    """Execute a compiled RouteProgram inside ``shard_map``.

    Same contract as the handwritten schedules: ``x`` is the per-device
    ``(n, *chunk)`` destination-indexed view; returns the source-indexed
    ``(n, *chunk)`` received view (== :func:`transpose_oracle`).

    With ``axis_name=None`` the program runs over its own mesh axes
    (``prog.axes`` — the NoC executor's ``mode="spmd"``).  Passing an
    ``axis_name`` runs the *same* program linearized over one flat device
    axis of size ``prog.n_nodes`` (node linear id = ``y*rx + x`` for 2D
    topologies): each per-axis hop permutation is statically expanded to the
    full axis so every row/column exchanges concurrently, exactly one
    ``lax.ppermute`` per hop move.  This is how callers embedded in an
    existing mesh (e.g. MoE token dispatch over the ``model`` axis) route
    through the topology without building a dedicated NoC mesh.

    ``transfer`` (see :func:`_line_exchange_compiled`) swaps the hop transport
    and requires ``axis_name`` (linearized execution) so its pairs are global
    node ids."""
    if transfer is not None and axis_name is None:
        raise ValueError("transfer= requires linearized execution (axis_name)")
    if prog.fused:
        if transfer is not None:
            # a fused crossbar has no hop moves to re-transport; silently
            # ignoring the hook would execute cut links un-bridged
            raise ValueError("transfer= is not supported for fused programs; "
                             "use interchip.run_bridged_program, which "
                             "handles the crossbar case itself")
        name = axis_name or prog.axes[0][0]
        return lax.all_to_all(x, name, split_axis=0, concat_axis=0)
    if len(prog.phases) == 1:
        return _line_exchange_compiled(x, prog.phases[0], axis_name=axis_name,
                                       transfer=transfer)
    # 2D XY routing: factorized exchange, same data motion as grid_all_to_all
    (_, ry), (_, rx) = prog.axes          # axes = (noc_y, noc_x)
    phase_x, phase_y = prog.phases        # phases ordered X then Y
    cx = cy = None
    ex_x = ex_y = None
    if axis_name is not None:
        i = lax.axis_index(axis_name)
        cx, cy = i % rx, i // rx

        def ex_x(pairs):
            return [(y * rx + s, y * rx + d)
                    for y in range(ry) for s, d in pairs]

        def ex_y(pairs):
            return [(s * rx + xc, d * rx + xc)
                    for xc in range(rx) for s, d in pairs]
    c = x.shape[1:]
    b = x.reshape(ry, rx, *c)             # (dy, dx, *c)
    b = jnp.moveaxis(b, 1, 0)             # (dx, dy, *c)
    b = _line_exchange_compiled(b, phase_x, axis_name, cx, ex_x,
                                transfer)                          # (sx, dy, *c)
    b = jnp.moveaxis(b, 1, 0)             # (dy, sx, *c)
    b = _line_exchange_compiled(b, phase_y, axis_name, cy, ex_y,
                                transfer)                          # (sy, sx, *c)
    return b.reshape(ry * rx, *c)         # source linear index sy*rx + sx


def _np_line_compiled(buf: np.ndarray, phase: LinePhase,
                      stats: "ScheduleStats") -> np.ndarray:
    """Numpy interpreter of one compiled line phase (mirrors _sim_line)."""
    n = phase.sched.size
    out = np.zeros_like(buf)
    for i in range(n):
        out[i, i] = buf[i, i]
    bufs = [buf.copy(), buf.copy()]
    for rnd in phase.rounds:
        stats.rounds += 1
        for mv in rnd.moves:
            cur = bufs[mv.buf]
            nxt = np.zeros_like(cur)
            for s, d in mv.perm:
                nxt[d] = cur[s]
                stats.link_bytes += cur[s].nbytes
            bufs[mv.buf] = nxt
            for i in range(n):
                if mv.src_table[i] >= 0:
                    out[i, mv.src_table[i]] = nxt[i, i]
    return out


def simulate_route_program(prog: RouteProgram,
                           msgs: np.ndarray) -> tuple[np.ndarray, "ScheduleStats"]:
    """Round-by-round numpy execution of a compiled program (no devices).

    msgs: (n_src, n_dst, *c); returns (delivered (n_dst, n_src, *c), stats).
    Must be bit-identical to :func:`simulate_schedule` on the same topology —
    the compiled program and the handwritten simulator are two lowerings of
    the same schedule."""
    n = prog.n_nodes
    assert msgs.shape[0] == n and msgs.shape[1] == n
    stats = ScheduleStats()
    if prog.fused:
        return msgs.swapaxes(0, 1).copy(), route_program_stats(prog, msgs.nbytes)
    if len(prog.phases) == 1:
        return _np_line_compiled(msgs, prog.phases[0], stats), stats
    (_, ry), (_, rx) = prog.axes
    phase_x, phase_y = prog.phases
    c = msgs.shape[2:]
    m = msgs.reshape(ry, rx, ry, rx, *c)            # [sy, sx, dy, dx, *c]
    b = np.moveaxis(m, (1, 3), (0, 1))              # [sx, dx, sy, dy, *c]
    b = _np_line_compiled(np.ascontiguousarray(b).reshape(rx, rx, -1),
                          phase_x, stats)
    b = b.reshape(rx, rx, ry, ry, *c)               # [dx(node), sx, sy, dy, *c]
    b = np.moveaxis(b, (2, 3), (0, 1))              # [sy, dy, dx, sx, *c]
    b = _np_line_compiled(np.ascontiguousarray(b).reshape(ry, ry, -1),
                          phase_y, stats)
    b = b.reshape(ry, ry, rx, rx, *c)               # [dy(node), sy, dx, sx, *c]
    out = np.moveaxis(b, (0, 2, 1, 3), (0, 1, 2, 3))
    return np.ascontiguousarray(out).reshape(n, n, *c), stats


def route_program_stats(prog: RouteProgram, cube_nbytes: int) -> "ScheduleStats":
    """Analytic ScheduleStats for moving one (n, n, ...) message cube of
    ``cube_nbytes`` total bytes through a compiled program.

    Exactly matches what :func:`simulate_schedule` / the round-by-round
    interpreter count (the spmd executor uses this so NoCStats stay identical
    to ``mode="sim"`` without re-running the numpy simulator)."""
    stats = ScheduleStats()
    n = prog.n_nodes
    if prog.fused:
        stats.rounds = 1
        stats.link_bytes = int(cube_nbytes * (n - 1) / n)
        return stats
    for phase in prog.phases:
        per_row = cube_nbytes // phase.sched.size
        for rnd in phase.rounds:
            stats.rounds += 1
            for mv in rnd.moves:
                stats.link_bytes += per_row * len(mv.perm)
    return stats


def topology_axes(topo: Topology) -> tuple[tuple[str, int], ...]:
    """Mesh axes a topology's schedule needs (NoC executor builds this mesh)."""
    if isinstance(topo, (Torus2D, Mesh2D)):
        return (("noc_y", topo.ry), ("noc_x", topo.rx))
    return (("noc", topo.n_nodes),)


def all_to_all_for(topo: Topology):
    """Return fn(x) usable inside shard_map over ``topology_axes(topo)``."""
    if isinstance(topo, FatTree):
        return lambda x: crossbar_all_to_all(x, "noc")
    if not isinstance(topo, (Ring, Mesh2D)):
        raise TypeError(f"no schedule for {type(topo).__name__}")

    def a2a(x):
        with jax.named_scope("routing.all_to_all"):
            if isinstance(topo, Ring):
                return ring_all_to_all_unidir(x, "noc")
            # Torus2D is a Mesh2D whose lines wrap
            return grid_all_to_all(x, "noc_x", "noc_y", wrap=isinstance(topo, Torus2D))
    return a2a


# ---------------------------------------------------------------------------
# numpy schedule simulator (no devices; benchmark + oracle for tests)
# ---------------------------------------------------------------------------

class ScheduleStats:
    def __init__(self):
        self.rounds = 0
        self.link_bytes = 0

    def __repr__(self):
        return f"ScheduleStats(rounds={self.rounds}, link_bytes={self.link_bytes})"


def _sim_line(buf: np.ndarray, wrap: bool, stats: ScheduleStats) -> np.ndarray:
    """buf: (n_nodes, n_dst_axis, *c) per-node buffers; returns (n, n_src, *c).

    Executes the same forward/backward rotation schedule round by round,
    physically moving buffers (so wall time ∝ rounds × bytes)."""
    n = buf.shape[0]
    out = np.zeros_like(buf)
    for i in range(n):
        out[i, i] = buf[i, i]
    if n == 1:
        return out
    fwd_steps = n // 2 if wrap else n - 1
    bwd_steps = (n - 1) // 2 if wrap else n - 1
    fbuf, bbuf = buf.copy(), buf.copy()
    for t in range(1, max(fwd_steps, bwd_steps) + 1):
        stats.rounds += 1
        if t <= fwd_steps:
            fbuf = np.roll(fbuf, 1, axis=0)
            if not wrap:
                fbuf[0] = 0
            stats.link_bytes += fbuf.nbytes - (fbuf.nbytes // n if not wrap else 0)
            for i in range(n):
                src = (i - t) % n if wrap else i - t
                if 0 <= src < n:
                    out[i, src] = fbuf[i, i]
        if t <= bwd_steps:
            bbuf = np.roll(bbuf, -1, axis=0)
            if not wrap:
                bbuf[-1] = 0
            stats.link_bytes += bbuf.nbytes - (bbuf.nbytes // n if not wrap else 0)
            for i in range(n):
                src = (i + t) % n if wrap else i + t
                if 0 <= src < n:
                    out[i, src] = bbuf[i, i]
    return out


def _sim_ring_unidir(buf: np.ndarray, stats: ScheduleStats) -> np.ndarray:
    n = buf.shape[0]
    out = np.zeros_like(buf)
    for i in range(n):
        out[i, i] = buf[i, i]
    fbuf = buf.copy()
    for t in range(1, n):
        stats.rounds += 1
        fbuf = np.roll(fbuf, 1, axis=0)
        stats.link_bytes += fbuf.nbytes
        for i in range(n):
            out[i, (i - t) % n] = fbuf[i, i]
    return out


def simulate_schedule(topo: Topology, msgs: np.ndarray, *,
                      batched: bool = False) -> tuple[np.ndarray, ScheduleStats]:
    """msgs: (n_src, n_dst, *c).  Returns (delivered (n_dst, n_src, *c), stats).

    Semantics oracle: delivered == msgs.swapaxes(0, 1).

    With ``batched=True`` msgs carries a leading batch axis ``(B, n, n, *c)``
    and B independent message sets move through the topology in ONE
    round-by-round simulation (the batch rides along as payload, so rounds are
    counted once while link_bytes scales with B).  Returns ``(B, n, n, *c)``
    delivered, i.e. ``msgs.swapaxes(1, 2)``."""
    if batched:
        assert msgs.ndim >= 3, "batched msgs must be (B, n_src, n_dst, *c)"
        inner = np.ascontiguousarray(np.moveaxis(msgs, 0, 2))   # (n, n, B, *c)
        delivered, stats = simulate_schedule(topo, inner)
        return np.ascontiguousarray(np.moveaxis(delivered, 2, 0)), stats
    n = topo.n_nodes
    assert msgs.shape[0] == n and msgs.shape[1] == n
    stats = ScheduleStats()
    if isinstance(topo, FatTree):
        stats.rounds = 1
        stats.link_bytes = int(msgs.nbytes * (n - 1) / n)
        return msgs.swapaxes(0, 1).copy(), stats
    if isinstance(topo, Ring):
        return _sim_ring_unidir(msgs, stats), stats
    if isinstance(topo, (Torus2D, Mesh2D)):
        wrap = isinstance(topo, Torus2D)
        rx, ry = topo.rx, topo.ry
        c = msgs.shape[2:]
        # node linear index = y*rx + x; XY dimension-ordered routing.
        m = msgs.reshape(ry, rx, ry, rx, *c)            # [sy, sx, dy, dx, *c]
        # Phase X: every row executes the line schedule concurrently — fold all
        # non-(sx,dx) indices into the payload so one _sim_line call = one
        # parallel phase (stats counted once, bytes include all rows' links).
        b = np.moveaxis(m, (1, 3), (0, 1))              # [sx, dx, sy, dy, *c]
        b = _sim_line(np.ascontiguousarray(b).reshape(rx, rx, -1), wrap, stats)
        b = b.reshape(rx, rx, ry, ry, *c)               # [dx(node), sx, sy, dy, *c]
        # Phase Y: every column concurrently, keyed by dy.
        b = np.moveaxis(b, (2, 3), (0, 1))              # [sy, dy, dx, sx, *c]
        b = _sim_line(np.ascontiguousarray(b).reshape(ry, ry, -1), wrap, stats)
        b = b.reshape(ry, ry, rx, rx, *c)               # [dy(node), sy, dx, sx, *c]
        out = np.moveaxis(b, (0, 2, 1, 3), (0, 1, 2, 3))  # [dy, dx, sy, sx, *c]
        return np.ascontiguousarray(out).reshape(n, n, *c), stats
    raise TypeError(f"no simulator for {type(topo).__name__}")
