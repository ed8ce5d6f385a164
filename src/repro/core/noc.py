"""NoC executor: run a TaskGraph over a Topology, optionally cut across pods.

This is the integration point of the framework (paper Fig. 1): PEs from
phase-1 (`core.graph`) are placed on a CONNECT-style topology
(`core.topology`), messages move via the topology's routing schedule
(`core.routing`), and cut links go through quasi-SERDES endpoints
(`core.serdes` via `core.partition`).

Execution modes — the three contracts
-------------------------------------
* ``direct``     — `TaskGraph.run`; the pure-software oracle (the paper's
  "multithreaded message passing software version").  No NoC, no stats.
* ``sim``        — the compiled **flit-program engine**: fires PEs
  wave-by-wave and physically moves every message round-by-round through the
  topology schedule with one vectorized numpy scatter/gather per wave.
  Produces the NoCStats used by the Table-IV/V-style benchmarks, and — by
  construction — bit-identical outputs to ``direct`` (tested).
* ``spmd``       — the **device-mesh execution** of the same compiled flit
  program: each wave's (n, n, buf_bytes) message cube is sharded over a
  device mesh (one NoC node per device, `partition.mesh_for_topology`) and
  moved by the topology's compiled ppermute-round schedule
  (`routing.compile_routes` / `run_route_program`) inside ``shard_map`` —
  one ``lax.ppermute`` per hop, multi-hop topologies decomposed into per-hop
  rounds, fat-tree as one fused ``lax.all_to_all``.  Outputs and NoCStats are
  bit-identical to ``sim`` (differential-tested): rounds/link_bytes come from
  `routing.route_program_stats`, which counts exactly what the round-by-round
  simulator counts.  Requires ``n_nodes`` devices (fake CPU devices via
  ``XLA_FLAGS=--xla_force_host_platform_device_count`` work).
* ``sim_python`` — the original per-message reference loop (dict framing +
  ``tobytes``/``frombuffer`` per message).  Kept as the behavioral baseline
  the engine is benchmarked and property-tested against.
* ``buffered``   — the **contention-aware wormhole transport** (`core.switch`):
  each wave's message cube moves flit-by-flit through per-port input FIFOs
  (``NoCConfig.switch_buffer_depth``) with X-Y dimension-ordered routing,
  round-robin output arbitration, credit backpressure, and dateline virtual
  channels (``switch_vcs``).  Bit-identical to ``sim``: outputs, ``waves``,
  ``payload_bytes``, ``flits``, and the ``cross_pod_*`` counters.
  Mode-specific: ``rounds`` counts switch *cycles* (contention included, so
  ≥ the contention-free schedule rounds), ``link_bytes`` counts flit-hops ×
  flit wire bytes under dimension-ordered routes, and the ``switch_*``
  counters (stalls, arbitration losses, peak queue/link occupancy) are
  populated.  With ``plan=`` it routes uncut but rolls the analytic bridge
  counters, like ``sim_python``.

The contract between the modes: ``direct`` defines values, ``sim`` defines
values + flit/round accounting, ``spmd`` must reproduce both bit-for-bit while
actually moving bytes between devices, and ``buffered`` must reproduce the
values and static counters while exposing the congestion the lock-step modes
cannot express.

Partitioned execution (``plan=``) — the inter-chip contract
-----------------------------------------------------------
Passing a `partition.PartitionPlan` turns on the paper's last automated step:
the compiled route program is split at the pod cut into per-pod programs
joined by explicit bridge endpoints (`core.interchip`).  Every pod-crossing
hop funnels its traffic through a quasi-SERDES serial link that
time-multiplexes the wide on-chip flits onto ``lanes`` narrow beats, with a
per-bridge FIFO (``NoCConfig.bridge_fifo_depth``) and bandwidth model.  The
cut is *semantically transparent* ("seamless" per the paper): outputs and all
pre-existing NoCStats fields — waves, rounds, link/payload/flit bytes, the
static cross-pod counters — are bit-identical to the unpartitioned execution
in every mode.  Only the new ``bridge_*`` counters (beats, serialized wire
bytes, stall rounds, peak FIFO occupancy — `interchip.BridgeStats`) record
what the serial links did:

* ``sim``   — `interchip.simulate_bridged_program` physically serializes
  every crossing buffer into wire words and back, round by round;
* ``spmd``  — `interchip.run_bridged_program` over
  `partition.mesh_for_partition` (a 2D ``(pod, node)`` device mesh when the
  plan's pods are equal contiguous blocks): intra-pod hops stay single
  ``lax.ppermute`` rounds, cut hops run serdes encode → ``lanes`` serialized
  beat ppermutes → decode; bridge counters come from the analytic
  `interchip.bridge_program_stats`, which matches the simulator exactly;
* ``sim_python`` — the seed loop routes unbridged but rolls in the same
  analytic bridge counters, staying field-for-field comparable.

The same compiled infrastructure also carries the LM-scale workload:
`models.moe` with ``impl="noc"`` routes expert-parallel token packets through
``routing.compile_routes`` / ``run_route_program`` (linearized over the
``model`` mesh axis — one ``lax.ppermute`` per hop, all four topologies), with
``routing.route_program_stats`` supplying exact flit/round/link-byte counters
per layer invocation (`models.moe.MoEDispatchStats`) and
``NoCConfig.flit_buffer_depth`` acting as the token-capacity knob — the
paper's "Data Distributor → routers → Data Collector" wrapper applied to a
mixture-of-experts layer.

The flit-program compile step
-----------------------------
Because the graph is *static* dataflow (every channel's shape/dtype is a
declared contract), the entire framing of a wave is known at executor
construction time.  ``NoCExecutor.__init__`` therefore compiles, per wave, a
:class:`_WaveProgram`:

* the flit-padded byte offset of every message inside its (src, dst) node
  buffer (CONNECT flit framing, ``flit_data_width`` granularity);
* flat ``pack_idx``/``gather_idx`` index vectors that scatter the wave's
  concatenated payload bytes into the ``(n, n, buf_bytes)`` message cube and
  gather them back out of the delivered ``(n_dst, n_src, buf_bytes)`` cube;
* the wave's *static* NoCStats increments (payload bytes, flit count,
  cross-pod message/wire-byte/beat counts) — these depend only on contracts
  and placement, never on values.

``run`` then does one ``reshape(-1)[pack_idx] = payload`` scatter, one
``simulate_schedule`` call, and one ``reshape(-1)[gather_idx]`` gather per
wave instead of per-message Python loops; ``run_iterative`` reuses the
compiled program across all iterations, and ``run_batch`` moves B independent
input sets through the topology in a single ``(B, n, n, bytes)`` simulation.
PE bodies are jit-cached per distinct body, so the
firing side of the wave is compiled once as well.

Flit accounting mirrors CONNECT's link model (default flit_data_width=16,
the paper's BMVM NoC config) and powers the Tables I–III "with/without
wrapper" overhead analogs: on TPU the wrapper cost is not LUTs/registers but
the padding + framing + buffer bytes the NoC abstraction adds around the raw
message payload.

Static verification (``verify=``) — the analysis contract
---------------------------------------------------------
Because everything above is compiled *before* any value moves, it can also be
*proven* before any value moves.  ``NoCExecutor(verify="strict")`` (the
default) runs `repro.analysis.verify_executor` over the artifacts it just
compiled:

* deadlock freedom of ``(topo, cfg.switch_vcs)`` via the Dally–Seitz channel
  dependency graph of the switch's actual routing function (NOC001/NOC002);
* exactly-once delivery/conservation of the compiled route program, the
  bridged pod projections, and every wave's pack/gather layout
  (NOC003/NOC004);
* placement / pod-cut / config validity (NOC007/NOC008/NOC009/NOC012) and
  framing-mismatch warnings (NOC010);
* capacity bounds: exact flit/link-byte totals plus sound peak-occupancy
  upper bounds on the `NoCStats` high-water marks (NOC005/NOC013 warnings).

``"strict"`` raises `repro.analysis.VerificationError` on any error-severity
finding, ``"warn"`` reports via ``warnings.warn``, ``"off"`` skips; the full
diagnostic list is kept on ``self.verification`` either way.  The property
suite holds the verifier to its word: artifacts it passes must simulate to
completion with stats inside the predicted bounds (see
``tests/test_analysis.py`` and the error-code reference in
`repro.analysis`).

Telemetry (``trace=``) — the observability contract
---------------------------------------------------
``NoCExecutor(trace=repro.telemetry.Tracer())`` (or ``trace=True``) threads
an event tracer through every execution mode: per-wave
scatter/route/gather/wave spans, one ``msg`` event per compiled message
slot (with the cross-pod wire cost when the message crosses the cut),
per-round ``round``/``link`` events derived from the compiled route program
(exact — `routing.route_program_stats` counts what the simulators count),
per-cycle ``cycle``/``queue`` events from the wormhole switch in
``mode="buffered"``, and ``bridge_*`` events from the bridge FIFO machine
shared by the bridged simulator and the analytic stats.  The full event
schema lives in `repro.telemetry.tracer`; timestamps are logical NoC time
(scatter 1 tick, route = rounds/cycles + bridge stalls, gather 1 tick).

The contract, differential-tested across the topology × app × mode grid:
``repro.telemetry.trace_stats(tracer)`` reproduces the run's `NoCStats`
**bit-exactly** — the trace is a proof-carrying account of the run, not a
best-effort log.  With ``trace=None`` (the default) no event object is
allocated anywhere (every hook is one ``is not None`` check;
property-tested), so tracing costs nothing when off.  On top of the raw
events, `repro.telemetry.profile.profile_trace` rebuilds per-packet /
per-message latency records (inject→eject, decomposed exactly into
serialization + hop + queueing + bridge-stall) and attributes every tick
above the analytic bounds to a named resource — see ``docs/observability.md``
for the full telemetry contract, the ``noc.latency.*`` metrics schema and
how to read the bottleneck report.  Exporters:
`repro.telemetry.chrome_trace` (Perfetto/Chrome timeline — one track per
router/link/bridge, counter tracks for queue depth and link load),
`repro.telemetry.heatmap` (text/CSV link utilization, also via
``python -m repro.launch.report --trace``), and ``python -m
repro.telemetry`` runs any case-study app traced.  Independent of tracing,
every engine publishes its `NoCStats` into the process-wide metrics
registry when one is enabled (`repro.telemetry.metrics.enable_metrics`) —
flows as counters, high-water marks as max-gauges, labeled by
``mode``/``topology``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Optional

import numpy as np

import jax

from . import serdes as qserdes
from ..telemetry.metrics import get_registry
from ..telemetry.tracer import Tracer
from .graph import GraphError, TaskGraph
from .partition import PartitionPlan
from .routing import simulate_schedule
from .topology import Topology


@dataclasses.dataclass
class NoCStats:
    waves: int = 0
    rounds: int = 0
    link_bytes: int = 0
    payload_bytes: int = 0
    flits: int = 0
    cross_pod_msgs: int = 0
    cross_pod_wire_bytes: int = 0
    cross_pod_beats: int = 0
    # bridge counters (core.interchip) — nonzero only under partitioned
    # execution (plan=); everything above is identical with or without a cut
    bridge_beats: int = 0          # serial-lane cycles on the cut links
    bridge_wire_bytes: int = 0     # serialized bytes incl. word/lane padding
    bridge_stall_rounds: int = 0   # back-pressure + drain rounds at bridges
    bridge_peak_fifo: int = 0      # max bridge FIFO occupancy (wire words)
    # buffered-switch counters (core.switch) — nonzero only in mode="buffered"
    switch_cycles: int = 0         # wormhole cycles across all waves
    switch_stall_cycles: int = 0   # head flits blocked on credit/VC allocation
    switch_arb_losses: int = 0     # eligible flits that lost an arbitration
    switch_max_queue: int = 0      # peak input-FIFO occupancy, flits
    switch_peak_link_flits: int = 0  # peak flits on links in one cycle

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    def add(self, other: "NoCStats") -> "NoCStats":
        for f in dataclasses.fields(NoCStats):
            a, b = getattr(self, f.name), getattr(other, f.name)
            # peak occupancies are high-water marks, not flows — merge by max
            setattr(self, f.name,
                    max(a, b) if f.name in _MAX_MERGE_FIELDS else a + b)
        return self

    def bridge_counters(self) -> dict:
        return {k: v for k, v in self.as_dict().items()
                if k.startswith("bridge_")}

    def _roll_bridge(self, b) -> None:
        """Fold one wave's BridgeStats in (peak merged by max)."""
        self.bridge_beats += b.beats
        self.bridge_wire_bytes += b.wire_bytes
        self.bridge_stall_rounds += b.stall_rounds
        self.bridge_peak_fifo = max(self.bridge_peak_fifo, b.peak_fifo)

    def _roll_switch(self, sw) -> None:
        """Fold one wave's SwitchStats in (peaks merged by max)."""
        self.switch_cycles += sw.cycles
        self.switch_stall_cycles += sw.stall_cycles
        self.switch_arb_losses += sw.arb_losses
        self.switch_max_queue = max(self.switch_max_queue, sw.max_queue)
        self.switch_peak_link_flits = max(self.switch_peak_link_flits,
                                          sw.peak_link_flits)


# high-water-mark fields: NoCStats.add merges these by max, not sum
_MAX_MERGE_FIELDS = frozenset(
    {"bridge_peak_fifo", "switch_max_queue", "switch_peak_link_flits"})


@dataclasses.dataclass(frozen=True)
class NoCConfig:
    """CONNECT "Network and Router Options" analog (paper §VI-B).

    ``flit_buffer_depth`` is the capacity knob for MoE dispatch over the NoC
    (`models.moe`): each (source rank, expert) dispatch FIFO holds that many
    token slots, and the MoE's effective ``capacity_factor`` is *derived* from
    it (see `models.moe.dispatch_capacity`) instead of being configured
    independently — one knob, the paper's buffer-depth sweep."""

    flit_data_width: int = 16          # bits
    flit_buffer_depth: int = 8         # per-(src, expert) FIFO depth, in slots
    bridge_fifo_depth: int = 64        # inter-chip bridge FIFO, in wire words
    switch_buffer_depth: int = 4       # buffered mode: input FIFO depth, flits
    switch_vcs: int = 2                # buffered mode: VCs per input port
    serdes: qserdes.QuasiSerdesConfig = dataclasses.field(
        default_factory=qserdes.QuasiSerdesConfig)

    def __post_init__(self):
        # eager NOC012 validation: a bad width/depth must fail at config
        # construction, not deep inside a simulation
        for f in ("flit_data_width", "flit_buffer_depth", "bridge_fifo_depth",
                  "switch_buffer_depth", "switch_vcs"):
            v = getattr(self, f)
            if not isinstance(v, int) or v < 1:
                raise ValueError(f"NOC012: NoCConfig.{f}={v!r} must be a "
                                 f"positive integer")

    @property
    def flit_wire_bytes(self) -> int:
        """On-wire/storage bytes of ONE flit: ceil(width/8).  A 12-bit flit
        occupies 2 bytes of FIFO storage — truncating division silently
        under-counted every non-byte-multiple width."""
        return -(-self.flit_data_width // 8)

    def flits_for(self, nbytes: int) -> int:
        # payload capacity of a flit is the *whole* bytes it can carry
        # (floor), never 0 for sub-byte widths
        per = max(1, self.flit_data_width // 8)
        return -(-nbytes // per)

    def flit_framed_bytes(self, nbytes: int) -> int:
        """THE flit-framing rule: payload bytes → on-link/FIFO bytes (whole
        flits × ceiling flit storage).  Every framing call site — wave
        compilation, the seed loop, wrapper-overhead accounting — goes
        through here so the ceiling-division arithmetic lives in one place."""
        return self.flits_for(nbytes) * self.flit_wire_bytes


def wrapper_overhead(graph: TaskGraph, cfg: Optional[NoCConfig] = None) -> list[dict]:
    """Tables I–III analog: per-PE cost without vs with the NoC wrapper.

    'wo_wrapper_bytes'  — the PE's raw argument/result bytes (the bare module);
    'fifo_bytes'        — Data Collector/Distributor FIFO storage;
    'flit_bytes'        — framed on-link size incl. padding to flit width;
    'overhead'          — (with - without) / without, the Table-I ratio.
    """
    cfg = cfg or NoCConfig()
    rows = []
    for pe in graph.pes.values():
        in_b = sum(p.nbytes for p in pe.inputs)
        out_b = sum(p.nbytes for p in pe.outputs)
        raw = in_b + out_b
        fifo = cfg.flit_buffer_depth * cfg.flit_wire_bytes * (len(pe.inputs) + len(pe.outputs))
        flit_b = sum(cfg.flit_framed_bytes(p.nbytes)
                     for p in list(pe.inputs) + list(pe.outputs))
        rows.append(dict(pe=pe.name, wo_wrapper_bytes=raw, fifo_bytes=fifo,
                         flit_bytes=flit_b, with_wrapper_bytes=flit_b + fifo,
                         overhead=round((flit_b + fifo - raw) / max(raw, 1), 3)))
    return rows


# ---------------------------------------------------------------------------
# compiled flit program
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _MsgSlot:
    """One channel message inside a wave's compiled layout."""

    src_pe: str
    src_port: str
    dst_pe: str
    dst_port: str
    shape: tuple[int, ...]
    dtype: np.dtype
    nbytes: int
    a: int                 # [a:b) segment in the wave's payload byte vector
    b: int


@dataclasses.dataclass(frozen=True)
class _WaveProgram:
    """Static framing layout of one wave (compiled at executor construction)."""

    slots: tuple[_MsgSlot, ...]
    payload_nbytes: int    # Σ raw message bytes (the payload vector length)
    buf_bytes: int         # per-(src,dst) buffer size incl. flit padding
    pack_idx: np.ndarray   # flat indices into (n, n, buf_bytes) per payload byte
    gather_idx: np.ndarray # flat indices into delivered (n_dst, n_src, buf_bytes)
    static: NoCStats       # value-independent stats increment for this wave
    pairs: tuple[tuple[int, int, int], ...]  # occupied (src, dst, framed_bytes)


class NoCExecutor:
    def __init__(self, graph: TaskGraph, topo: Topology,
                 placement: Optional[Mapping[str, int]] = None,
                 plan: Optional[PartitionPlan] = None,
                 cfg: Optional[NoCConfig] = None,
                 verify: str = "strict",
                 trace: Optional[Any] = None):
        from .partition import place_round_robin

        if verify not in ("strict", "warn", "off"):
            raise ValueError(f"verify must be 'strict', 'warn', or 'off', "
                             f"got {verify!r}")
        # trace: None (off, zero overhead) | a telemetry Tracer | True for a
        # default-capacity one.  Kept on self.tracer; shared across runs so
        # run_iterative/run_batch build one continuous timeline.
        self.tracer = Tracer() if trace is True else trace
        self.graph = graph
        self.topo = topo
        self.placement = dict(placement or (plan.placement if plan else place_round_robin(graph, topo)))
        self.plan = plan
        self.cfg = cfg or NoCConfig()
        graph.validate()
        self._order = graph.firing_order()
        # group PEs into waves by dataflow depth
        depth: dict[str, int] = {}
        preds: dict[str, set[str]] = {n: set() for n in graph.pes}
        for c in graph.channels:
            if c.src_pe != c.dst_pe:
                preds[c.dst_pe].add(c.src_pe)
        for n in self._order:
            depth[n] = 1 + max((depth[p] for p in preds[n]), default=-1)
        self.waves: list[list[str]] = []
        for n in self._order:
            while len(self.waves) <= depth[n]:
                self.waves.append([])
            self.waves[depth[n]].append(n)
        self._chan_by_src: dict[str, list] = {n: [] for n in graph.pes}
        for c in graph.channels:
            self._chan_by_src[c.src_pe].append(c)
        self.programs: list[_WaveProgram] = [self._compile_wave(w) for w in self.waves]
        self._hop_cache: dict[tuple[int, int], int] = {}   # (src, dst) -> hops
        # jit caches for PE firing (sim/batch modes), keyed by id(pe.fn)
        self._jit_fns: dict[int, Any] = {}
        self._vmap_fns: dict[int, Any] = {}
        # spmd lowering (mode="spmd") is built lazily on first use: it needs
        # n_nodes real/fake devices, which sim-only runs must not require.
        # The bridged program (plan=) is likewise compiled on first partitioned
        # run — it needs no devices, only the route program + the cut.
        self._route_prog = None
        self._bridge_prog = None
        self._spmd_mesh = None
        self._spmd_fn = None
        # static verification of everything just compiled (repro.analysis):
        # deadlock proof for (topo, switch_vcs), delivery proofs for the wave
        # layouts and route program, placement/cut linting, capacity bounds.
        self.verification = []
        if verify != "off":
            from ..analysis.diagnostics import (VerificationError, errors,
                                                format_diagnostics)
            from ..analysis.lint import verify_executor

            self.verification = verify_executor(self)
            if errors(self.verification) and verify == "strict":
                raise VerificationError(self.verification)
            if self.verification and verify == "warn":
                import warnings

                warnings.warn(format_diagnostics(self.verification),
                              stacklevel=2)

    def _ensure_bridge(self):
        """Compile the partitioned (bridged) program once per executor."""
        if self.plan is None:
            return None
        if self._bridge_prog is None:
            from .interchip import BridgeConfig, compile_bridges
            from .routing import compile_routes

            if self._route_prog is None:
                self._route_prog = compile_routes(self.topo)
            self._bridge_prog = compile_bridges(
                self._route_prog, self.plan,
                BridgeConfig(serdes=self.plan.serdes_cfg,
                             fifo_depth=self.cfg.bridge_fifo_depth))
        return self._bridge_prog

    # -- compile -------------------------------------------------------------
    def _compile_wave(self, wave: list[str]) -> _WaveProgram:
        g, cfg = self.graph, self.cfg
        n = self.topo.n_nodes
        pod_of = self.plan.pod_of_node if self.plan is not None else None
        slots: list[_MsgSlot] = []
        pair_off: dict[tuple[int, int], int] = {}
        static = NoCStats()
        seg = 0
        placed: list[tuple[int, int, int]] = []   # (src_node, dst_node, pair_offset)
        for name in wave:
            for c in self._chan_by_src[name]:
                port = g.pes[c.src_pe].out_port(c.src_port)
                nbytes = port.nbytes
                s, d = self.placement[c.src_pe], self.placement[c.dst_pe]
                off = pair_off.get((s, d), 0)
                pair_off[(s, d)] = off + cfg.flit_framed_bytes(nbytes)  # flit padding
                slots.append(_MsgSlot(c.src_pe, c.src_port, c.dst_pe, c.dst_port,
                                      tuple(port.shape), np.dtype(port.dtype),
                                      nbytes, seg, seg + nbytes))
                placed.append((s, d, off))
                seg += nbytes
                static.payload_bytes += nbytes
                static.flits += cfg.flits_for(nbytes)
                if pod_of is not None and pod_of[s] != pod_of[d]:
                    static.cross_pod_msgs += 1
                    static.cross_pod_wire_bytes += qserdes.link_bytes_on_wire(
                        tuple(port.shape), port.dtype, cfg.serdes)
                    static.cross_pod_beats += cfg.serdes.lanes
        buf_bytes = max(pair_off.values(), default=0)
        pack, gather = [], []
        for slot, (s, d, off) in zip(slots, placed):
            span = np.arange(off, off + slot.nbytes, dtype=np.int64)
            pack.append((s * n + d) * buf_bytes + span)
            gather.append((d * n + s) * buf_bytes + span)   # delivered is (dst, src)
        def cat(xs):
            return np.concatenate(xs) if xs else np.zeros(0, np.int64)
        return _WaveProgram(tuple(slots), seg, buf_bytes, cat(pack), cat(gather),
                            static,
                            tuple((s, d, nb) for (s, d), nb
                                  in sorted(pair_off.items())))

    # -- firing --------------------------------------------------------------
    # jit/vmap caches are keyed by the fn object, not the PE name: graphs that
    # register one body for many PEs (e.g. the particle-filter group PEs)
    # compile each distinct body once.  PE objects keep their fns alive for the
    # executor's lifetime, so id() keys are stable.

    def _fire(self, name: str, kwargs: dict[str, Any]) -> Mapping[str, Any]:
        """Call a PE body through the jit cache."""
        fn = self.graph.pes[name].fn
        jitted = self._jit_fns.get(id(fn))
        if jitted is None:
            jitted = self._jit_fns[id(fn)] = jax.jit(fn)
        return jitted(**kwargs)

    def _fire_batch(self, name: str, kwargs: dict[str, Any]) -> Mapping[str, Any]:
        """Fire one PE on stacked input sets (leading batch axis) via vmap."""
        fn = self.graph.pes[name].fn
        batched = self._vmap_fns.get(id(fn))
        if batched is None:
            batched = self._vmap_fns[id(fn)] = jax.jit(jax.vmap(fn))
        return batched(**kwargs)

    # -- spmd lowering -------------------------------------------------------
    def _ensure_spmd(self) -> None:
        """Compile the topology schedule to a ppermute-round program and jit
        the shard_map transport over the NoC device mesh (once per executor).

        With a partition plan, the transport is the *bridged* program over
        `partition.mesh_for_partition` — a ``(pod, node)`` mesh when the
        plan's pods are equal contiguous blocks — where intra-pod hops stay
        ppermute rounds and cut hops run through quasi-SERDES endpoints
        (`interchip.run_bridged_program`)."""
        if self._spmd_fn is not None:
            return
        from jax.sharding import PartitionSpec as P

        from .partition import mesh_for_partition, mesh_for_topology
        from .routing import compile_routes, run_route_program

        if self._route_prog is None:
            self._route_prog = compile_routes(self.topo)
        prog = self._route_prog
        bprog = self._ensure_bridge()
        if bprog is not None:
            from .interchip import run_bridged_program

            mesh = self._spmd_mesh = mesh_for_partition(self.topo, self.plan)
            names = mesh.axis_names
            n_lead = len(names)

            def device_fn(local):
                x = local.reshape(local.shape[n_lead:])
                return run_bridged_program(x, bprog, names).reshape(local.shape)
        else:
            mesh = self._spmd_mesh = mesh_for_topology(self.topo)
            names = tuple(a for a, _ in prog.axes)
            n_lead = len(names)

            def device_fn(local):
                # local view: (1,)*n_lead + (n_dst, *payload) → route → same
                x = local.reshape(local.shape[n_lead:])
                return run_route_program(x, prog).reshape(local.shape)

        sm = jax.shard_map(device_fn, mesh=mesh, in_specs=P(*names),
                       out_specs=P(*names), check_vma=False)
        self._spmd_fn = jax.jit(sm)

    def _route_spmd(self, msgs_arr: np.ndarray, B: Optional[int]):
        """Move one wave's message cube through the device mesh.

        msgs_arr: (n, n, buf) or (B, n, n, buf).  Same (delivered, stats)
        contract as :func:`simulate_schedule` — the batch rides along as
        payload bytes, so rounds are physical while link_bytes scale with B.
        Returns ``(delivered, ScheduleStats, BridgeStats | None)``; the
        bridge stats are analytic (`interchip.bridge_program_stats`), which
        the simulator matches exactly."""
        from .routing import route_program_stats

        self._ensure_spmd()
        prog = self._route_prog
        n = self.topo.n_nodes
        sizes = tuple(self._spmd_mesh.devices.shape)
        if B is None:
            payload = msgs_arr.shape[2:]
            cube = msgs_arr.reshape(sizes + (n,) + payload)
        else:
            payload = (B,) + msgs_arr.shape[3:]
            cube = np.moveaxis(msgs_arr, 0, 2).reshape(sizes + (n,) + payload)
        out = np.asarray(self._spmd_fn(cube)).reshape((n, n) + payload)
        delivered = out if B is None else np.moveaxis(out, 2, 0)
        bstats = None
        if self._bridge_prog is not None:
            from .interchip import bridge_program_stats

            bstats = bridge_program_stats(self._bridge_prog, msgs_arr.nbytes,
                                          tracer=self.tracer)
        return (np.ascontiguousarray(delivered),
                route_program_stats(prog, msgs_arr.nbytes), bstats)

    # -- telemetry -----------------------------------------------------------
    def _hops(self, s: int, d: int) -> int:
        """Topology hop distance ``s -> d`` under dimension-ordered routing —
        the per-message ``hops`` attribution the latency profiler charges as
        the in-flight component (cached; identical for every transport)."""
        h = self._hop_cache.get((s, d))
        if h is None:
            from .switch import dor_route

            h = len(dor_route(self.topo, s, d, max(2, self.cfg.switch_vcs))[0]) - 1
            self._hop_cache[(s, d)] = h
        return h

    def _trace_msgs(self, tr, prog: _WaveProgram, scale: int, t0: int) -> None:
        """One ``msg`` event per compiled slot — the event-level mirror of
        ``prog.static`` (payload/flit/cross-pod counters, scaled by the batch
        via the ``n`` arg), which is what makes trace aggregation exact."""
        cfg = self.cfg
        pod_of = self.plan.pod_of_node if self.plan is not None else None
        for slot in prog.slots:
            s, d = self.placement[slot.src_pe], self.placement[slot.dst_pe]
            args = dict(src=s, dst=d, bytes=slot.nbytes,
                        flits=cfg.flits_for(slot.nbytes), n=scale,
                        hops=self._hops(s, d))
            if pod_of is not None and pod_of[s] != pod_of[d]:
                args["wire_bytes"] = qserdes.link_bytes_on_wire(
                    slot.shape, slot.dtype, cfg.serdes)
                args["beats"] = cfg.serdes.lanes
            tr.instant("msg", f"node {s}", ts=t0, **args)

    def _trace_rounds(self, tr, t0: int, cube_nbytes: int) -> None:
        """Per-round ``round`` instants + per-link ``link`` load counters for
        the schedule transports, derived from the compiled route program —
        `interchip._walk_rounds` traversals move ``cube_nbytes // den`` each,
        summing to exactly `routing.route_program_stats` (== what the
        simulators count), so the events are exact, not estimated."""
        from .interchip import _walk_rounds

        if self._route_prog is None:
            from .routing import compile_routes

            self._route_prog = compile_routes(self.topo)
        for r, (den, pairs) in enumerate(_walk_rounds(self._route_prog)):
            per = cube_nbytes // den
            agg: dict[tuple[int, int], int] = {}
            for p in pairs:
                agg[p] = agg.get(p, 0) + per
            tr.instant("round", "noc", ts=t0 + r,
                       bytes=per * len(pairs), links=len(agg))
            for (s, d), b in agg.items():
                tr.counter("link", f"link {s}->{d}", b, ts=t0 + r)

    # -- packing -------------------------------------------------------------
    @staticmethod
    def _payload_segment(val: Any, slot: _MsgSlot, lead: tuple[int, ...] = ()) -> np.ndarray:
        v = np.asarray(val)
        if v.shape != lead + slot.shape or v.dtype != slot.dtype:
            raise GraphError(
                f"message {slot.src_pe}.{slot.src_port} -> {slot.dst_pe}.{slot.dst_port}: "
                f"value {v.shape}/{v.dtype} violates contract {lead + slot.shape}/{slot.dtype}")
        flat = np.ascontiguousarray(v).reshape(*lead, -1) if lead else \
            np.ascontiguousarray(v).reshape(-1)
        return flat.view(np.uint8).reshape(*lead, -1) if lead else flat.view(np.uint8)

    # ------------------------------------------------------------------
    def run(self, inputs: Mapping[str, Any], mode: str = "sim") -> tuple[dict[str, Any], NoCStats]:
        if mode == "direct":
            return self.graph.run(inputs), NoCStats()
        if mode == "sim_python":
            return self._run_sim_python(inputs)
        if mode not in ("sim", "spmd", "buffered"):
            raise GraphError(f"unknown mode {mode!r}; use "
                             f"'direct'|'sim'|'spmd'|'buffered'|'sim_python'")
        mailbox: dict[tuple[str, str], Any] = {}
        for k, v in inputs.items():
            pe, port = k.split(".")
            mailbox[(pe, port)] = np.asarray(v)
        return self._run_compiled(mailbox, B=None, transport=mode)

    def run_batch(self, inputs: Mapping[str, Any],
                  mode: str = "sim") -> tuple[dict[str, Any], NoCStats]:
        """Run B independent input sets at once; every input carries a leading
        batch axis ``(B, *port.shape)`` and so does every output.

        ``sim`` fires each PE once on the stacked batch (vmap) and moves
        all B message sets through the topology in a single
        ``(B, n, n, bytes)`` :func:`simulate_schedule` call.  Stats:
        waves/rounds are physical (counted once — the batch shares the
        schedule), while payload/flit/link/cross-pod byte counters scale with
        B (each input set's messages really occupy the links)."""
        if not inputs:
            raise GraphError("run_batch needs at least one input")
        B = int(np.asarray(next(iter(inputs.values()))).shape[0])
        if mode == "direct":
            items = [self.graph.run({k: np.asarray(v)[b] for k, v in inputs.items()})
                     for b in range(B)]
            outs = {k: np.stack([np.asarray(it[k]) for it in items]) for k in items[0]}
            return outs, NoCStats()
        if mode not in ("sim", "spmd", "buffered"):
            raise GraphError(f"unknown mode {mode!r}; use "
                             f"'direct'|'sim'|'spmd'|'buffered'")
        mailbox: dict[tuple[str, str], Any] = {}
        for k, v in inputs.items():
            pe, port = k.split(".")
            arr = np.asarray(v)
            if arr.shape[0] != B:
                raise GraphError(f"input {k} batch axis {arr.shape[0]} != {B}")
            mailbox[(pe, port)] = arr
        return self._run_compiled(mailbox, B=B, transport=mode)

    def _switch_cfg(self):
        """NoCConfig knobs → the buffered transport's SwitchConfig."""
        from .switch import SwitchConfig

        return SwitchConfig(buffer_depth=self.cfg.switch_buffer_depth,
                            n_vcs=self.cfg.switch_vcs,
                            flit_bytes=self.cfg.flit_wire_bytes)

    def _run_compiled(self, mailbox: dict[tuple[str, str], Any],
                      B: Optional[int],
                      transport: str = "sim") -> tuple[dict[str, Any], NoCStats]:
        """Execute the compiled flit program; ``B=None`` single-set, else a
        leading batch axis rides through every pack/route/unpack step.

        ``transport`` swaps how each wave's message cube moves: ``"sim"`` is
        the round-by-round numpy schedule simulator, ``"spmd"`` the compiled
        ppermute program on the device mesh, ``"buffered"`` the cycle-accurate
        wormhole switch (`core.switch`).  Everything else — firing, framing,
        stats accumulation — is shared, which is what makes the modes
        bit-identical on values by construction."""
        g, topo = self.graph, self.topo
        n = topo.n_nodes
        lead = () if B is None else (B,)
        scale = 1 if B is None else B
        stats = NoCStats()
        if transport == "spmd":
            self._ensure_spmd()     # fail fast if the mesh can't be built
        tr = self.tracer
        if tr is not None:
            tr.instant("run", "noc", mode=transport,
                       topology=type(topo).__name__, n_nodes=n, batch=scale)
        for iw, (wave, prog) in enumerate(zip(self.waves, self.programs)):
            stats.waves += 1
            for name in wave:
                pe = g.pes[name]
                kwargs = {p.name: mailbox[(name, p.name)] for p in pe.inputs}
                results = (self._fire(name, kwargs) if B is None
                           else self._fire_batch(name, kwargs))
                for p in pe.outputs:
                    mailbox[(name, p.name)] = np.asarray(results[p.name])
            if not prog.slots:
                if tr is not None:   # message-free wave: scatter+gather only
                    tr.span("wave", "noc", tr.clock, 2, wave=iw, msgs=0)
                    tr.clock += 2
                continue
            payload = np.empty(lead + (prog.payload_nbytes,), np.uint8)
            for slot in prog.slots:
                payload[..., slot.a:slot.b] = self._payload_segment(
                    mailbox[(slot.src_pe, slot.src_port)], slot, lead)
            msgs_arr = np.zeros(lead + (n * n * prog.buf_bytes,), np.uint8)
            msgs_arr[..., prog.pack_idx] = payload
            cube = msgs_arr.reshape(lead + (n, n, prog.buf_bytes))
            t0 = 0
            if tr is not None:
                t0 = tr.clock
                self._trace_msgs(tr, prog, scale, t0)
                tr.clock = t0 + 1   # transport events base at route start
            bstats = None
            if transport == "spmd":
                delivered, sstats, bstats = self._route_spmd(cube, B)
                rounds, link_bytes = sstats.rounds, sstats.link_bytes
            elif transport == "buffered":
                from .switch import simulate_wormhole_cube

                delivered, swst = simulate_wormhole_cube(
                    topo, cube, self._switch_cfg(), pairs=prog.pairs,
                    batched=B is not None, tracer=tr)
                # mode-specific accounting: rounds are switch cycles (with
                # contention), link_bytes are flit-hops on the wormhole routes
                rounds = swst.cycles
                link_bytes = swst.link_flits * self.cfg.flit_wire_bytes
                stats._roll_switch(swst)
                if self.plan is not None:
                    # uncut routing + analytic bridge counters, the
                    # sim_python precedent for non-bridged transports
                    from .interchip import bridge_program_stats

                    bstats = bridge_program_stats(self._ensure_bridge(),
                                                  cube.nbytes, tracer=tr)
            elif self.plan is not None:
                # partitioned execution: same schedule, but pod-crossing hops
                # physically serialize through the bridge endpoints
                from .interchip import simulate_bridged_program

                delivered, sstats, bstats = simulate_bridged_program(
                    self._ensure_bridge(), cube, batched=B is not None,
                    tracer=tr)
                rounds, link_bytes = sstats.rounds, sstats.link_bytes
            else:
                delivered, sstats = simulate_schedule(topo, cube,
                                                      batched=B is not None)
                rounds, link_bytes = sstats.rounds, sstats.link_bytes
            recv = delivered.reshape(lead + (-1,))[..., prog.gather_idx]
            for slot in prog.slots:
                seg = recv[..., slot.a:slot.b].copy()   # owns + aligns the bytes
                mailbox[(slot.dst_pe, slot.dst_port)] = (
                    seg.view(slot.dtype).reshape(lead + slot.shape))
            # prog.static only carries per-message counters (waves/rounds/
            # link_bytes stay zero there), so the whole struct scales by B
            for f in dataclasses.fields(NoCStats):
                setattr(stats, f.name,
                        getattr(stats, f.name) + scale * getattr(prog.static, f.name))
            stats.rounds += rounds
            stats.link_bytes += link_bytes
            if bstats is not None:
                stats._roll_bridge(bstats)
            if tr is not None:
                durR = rounds + (bstats.stall_rounds
                                 if bstats is not None else 0)
                if transport in ("sim", "spmd"):
                    # buffered emitted its own per-cycle events; the schedule
                    # transports get the compiled program's exact rounds
                    self._trace_rounds(tr, t0 + 1, cube.nbytes)
                tr.span("scatter", "engine", t0, 1, msgs=len(prog.slots),
                        bytes=scale * prog.payload_nbytes)
                tr.span("route", "engine", t0 + 1, max(durR, 1),
                        mode=transport)
                tr.span("gather", "engine", t0 + 1 + durR, 1)
                tr.span("wave", "noc", t0, durR + 2, wave=iw,
                        msgs=len(prog.slots))
                tr.clock = t0 + durR + 2
        outs = {f"{pe}.{port.name}": mailbox[(pe, port.name)] for pe, port in g.graph_outputs()}
        reg = get_registry()
        if reg is not None:
            reg.record_noc_stats(stats, mode=transport,
                                 topology=type(topo).__name__)
        return outs, stats

    # ------------------------------------------------------------------
    def _run_sim_python(self, inputs: Mapping[str, Any]) -> tuple[dict[str, Any], NoCStats]:
        """The seed per-message reference loop (framing re-derived every wave).

        Kept verbatim as the baseline the compiled engine is benchmarked and
        property-tested against."""
        g, topo, cfg = self.graph, self.topo, self.cfg
        stats = NoCStats()
        mailbox: dict[tuple[str, str], Any] = {}
        for k, v in inputs.items():
            pe, port = k.split(".")
            mailbox[(pe, port)] = np.asarray(v)

        pod_of = None
        if self.plan is not None:
            pod_of = self.plan.pod_of_node

        tr = self.tracer
        if tr is not None:
            tr.instant("run", "noc", mode="sim_python",
                       topology=type(topo).__name__, n_nodes=topo.n_nodes,
                       batch=1)
        for iw, wave in enumerate(self.waves):
            stats.waves += 1
            # fire
            outbox: list[tuple[Any, int, int, str, str]] = []  # (val, src_node, dst_node, dst_pe, dst_port)
            for name in wave:
                pe = g.pes[name]
                kwargs = {p.name: mailbox[(name, p.name)] for p in pe.inputs}
                results = pe.fn(**kwargs)
                for p in pe.outputs:
                    mailbox[(name, p.name)] = np.asarray(results[p.name])
                for c in self._chan_by_src[name]:
                    val = np.asarray(results[c.src_port])
                    outbox.append((val, self.placement[c.src_pe], self.placement[c.dst_pe],
                                   c.dst_pe, c.dst_port))
            if not outbox:
                if tr is not None:
                    tr.span("wave", "noc", tr.clock, 2, wave=iw, msgs=0)
                    tr.clock += 2
                continue
            # frame messages into per-(src,dst) flit buffers and route them
            n = topo.n_nodes
            t0 = tr.clock if tr is not None else 0
            per_pair: dict[tuple[int, int], list] = {}
            for val, s, d, dpe, dport in outbox:
                per_pair.setdefault((s, d), []).append((val, dpe, dport))
                stats.payload_bytes += val.nbytes
                stats.flits += cfg.flits_for(val.nbytes)
                margs = None
                if tr is not None:
                    margs = dict(src=s, dst=d, bytes=val.nbytes,
                                 flits=cfg.flits_for(val.nbytes), n=1,
                                 hops=self._hops(s, d))
                if pod_of is not None and pod_of[s] != pod_of[d]:
                    wb = qserdes.link_bytes_on_wire(val.shape, val.dtype,
                                                    cfg.serdes)
                    stats.cross_pod_msgs += 1
                    stats.cross_pod_wire_bytes += wb
                    stats.cross_pod_beats += cfg.serdes.lanes
                    if margs is not None:
                        margs["wire_bytes"] = wb
                        margs["beats"] = cfg.serdes.lanes
                if margs is not None:
                    tr.instant("msg", f"node {s}", ts=t0, **margs)
            buf_bytes = max(
                (sum(cfg.flit_framed_bytes(v.nbytes) for v, _, _ in msgs)
                 for msgs in per_pair.values()), default=0)
            durR = 0
            if buf_bytes:
                msgs_arr = np.zeros((n, n, buf_bytes), np.uint8)
                for (s, d), msgs in per_pair.items():
                    off = 0
                    for v, _, _ in msgs:
                        raw = v.tobytes()
                        msgs_arr[s, d, off:off + len(raw)] = np.frombuffer(raw, np.uint8)
                        off += cfg.flit_framed_bytes(v.nbytes)  # flit padding
                if tr is not None:
                    tr.clock = t0 + 1
                delivered, sstats = simulate_schedule(topo, msgs_arr)
                stats.rounds += sstats.rounds
                stats.link_bytes += sstats.link_bytes
                durR = sstats.rounds
                bstats = None
                if pod_of is not None:
                    # seed-loop bridge accounting: the analytic stats are
                    # exact (== the bridged simulator), so the baseline stays
                    # field-for-field comparable with the compiled engine
                    from .interchip import bridge_program_stats
                    bstats = bridge_program_stats(
                        self._ensure_bridge(), msgs_arr.nbytes, tracer=tr)
                    stats._roll_bridge(bstats)
                    durR += bstats.stall_rounds
                if tr is not None:
                    self._trace_rounds(tr, t0 + 1, msgs_arr.nbytes)
                for (s, d), msgs in per_pair.items():
                    off = 0
                    for v, dpe, dport in msgs:
                        raw = delivered[d, s, off:off + v.nbytes].tobytes()
                        mailbox[(dpe, dport)] = np.frombuffer(raw, v.dtype).reshape(v.shape).copy()
                        off += cfg.flit_framed_bytes(v.nbytes)
            if tr is not None:
                tr.span("scatter", "engine", t0, 1, msgs=len(outbox),
                        bytes=sum(v.nbytes for v, *_ in outbox))
                tr.span("route", "engine", t0 + 1, max(durR, 1),
                        mode="sim_python")
                tr.span("gather", "engine", t0 + 1 + durR, 1)
                tr.span("wave", "noc", t0, durR + 2, wave=iw,
                        msgs=len(outbox))
                tr.clock = t0 + durR + 2
        outs = {f"{pe}.{port.name}": mailbox[(pe, port.name)] for pe, port in g.graph_outputs()}
        reg = get_registry()
        if reg is not None:
            reg.record_noc_stats(stats, mode="sim_python",
                                 topology=type(topo).__name__)
        return outs, stats

    def run_iterative(self, inputs: Mapping[str, Any], feedback, n_iters: int,
                      mode: str = "sim") -> tuple[dict[str, Any], NoCStats]:
        state = dict(inputs)
        total = NoCStats()
        outs: dict[str, Any] = {}
        for _ in range(n_iters):
            outs, st = self.run(state, mode=mode)
            total.add(st)
            for src, dst in feedback:
                state[dst] = outs[src]
        return outs, total
