"""Latency profiler + perf-regression gate (`repro.telemetry.profile` /
`regress`): decomposition exactness across the topology × app × mode grid,
critical-path identities against the analytic bounds, zero-overhead-off for
LatencyRecords, saved-trace round-trips, and both directions of the
regression diff."""
import json

import numpy as np
import pytest

from repro.telemetry import (Tracer, chrome_trace, enable_metrics,
                             disable_metrics, events_allocated,
                             events_from_chrome, profile_trace,
                             records_allocated, trace_stats)
from repro.telemetry.regress import compare_rows, metric_class

from test_telemetry import APPS, TOPOLOGIES, _bmvm_executor, _pods


# ---------------------------------------------------------------------------
# the keystone contract: exact decomposition + critical path, whole grid
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("topology", TOPOLOGIES)
@pytest.mark.parametrize("app", list(APPS))
@pytest.mark.parametrize("variant", ["sim", "buffered", "bridged"])
def test_decomposition_exact_grid(topology, app, variant):
    run, n_nodes = APPS[app]
    mode = "buffered" if variant == "buffered" else "sim"
    pods = _pods(n_nodes) if variant == "bridged" else None
    tr = Tracer()
    run(topology, mode, pods, tr)
    assert tr.dropped == 0
    prof = profile_trace(tr)
    assert prof.records, "profiled run produced no latency records"
    # bit-exact decomposition for every record, attribution sums per wave
    prof.check_exact()
    for r in prof.records:
        assert r.serialization + r.hop + r.queueing + r.bridge == r.latency
        assert r.latency > 0 and r.hops >= 0 and r.flits >= 1
    # waves tile the logical clock: critical path length == final clock
    cp = prof.critical_path()
    assert cp.length == tr.clock
    assert cp.length == sum(w.dur for w in prof.waves)
    assert cp.gap == sum(w.gap for w in prof.waves)
    # attribution never invents or loses cycles
    assert sum(c for _, c in cp.attribution) == cp.gap
    if variant == "buffered":
        assert any(r.kind == "pkt" for r in prof.records)
        assert any(w.kind == "switch" for w in prof.waves)
    else:
        assert all(r.kind == "msg" for r in prof.records)
    if variant == "bridged":
        # bridge stalls land in the bridge component (a schedule wave is a
        # barrier: every message in it carries the wave's stall), and the
        # event-derived stall total matches the duration-derived one
        for w in prof.waves:
            recs = [r for r in prof.records if r.wave == w.index]
            assert all(r.bridge == w.bridge_stalls for r in recs)


def test_single_packet_meets_bound_exactly():
    """Uncontended packet: latency == critical path == switch_lower_bound
    == simulated cycles, with zero queueing — the acceptance identity."""
    from repro.core.switch import (Packet, SwitchConfig, simulate_switch,
                                   switch_lower_bound)
    from repro.core.topology import make_topology

    for topology, n in (("mesh", 16), ("ring", 8), ("torus", 16)):
        topo = make_topology(topology, n)
        pkts = [Packet(0, n - 1, 4, t_inject=0)]
        tr = Tracer()
        res = simulate_switch(topo, pkts, SwitchConfig(), tracer=tr)
        prof = profile_trace(tr).check_exact()
        assert len(prof.records) == 1
        r = prof.records[0]
        cp = prof.critical_path()
        bound = switch_lower_bound(topo, pkts, SwitchConfig())
        assert r.latency == cp.length == bound == res.stats.cycles
        assert r.queueing == 0 and r.bridge == 0
        assert r.serialization == 4 and r.hop == r.hops
        assert cp.gap == 0 and not cp.attribution


def test_contended_run_attributes_every_gap_cycle():
    """Two packets fighting for one link: the cycles above the bound are
    charged to named resources, and the sum is exact."""
    from repro.core.switch import (Packet, SwitchConfig, simulate_switch,
                                   switch_lower_bound)
    from repro.core.topology import make_topology

    topo = make_topology("mesh", 16)
    cfg = SwitchConfig()
    # same source row, same destination: they serialize through shared links
    pkts = [Packet(0, 15, 8, t_inject=0), Packet(1, 15, 8, t_inject=0),
            Packet(2, 15, 8, t_inject=0)]
    tr = Tracer()
    res = simulate_switch(topo, pkts, cfg, tracer=tr)
    prof = profile_trace(tr).check_exact()
    w = prof.waves[0]
    bound = switch_lower_bound(topo, pkts, cfg)
    assert res.stats.cycles > bound          # the cell is non-vacuous
    assert w.gap == res.stats.cycles - bound
    assert sum(c for _, c in w.attribution) == w.gap
    for resource, cycles in w.attribution:
        assert cycles > 0
        assert ("link" in resource or "bridge" in resource
                or "switch" in resource)
    # someone queued: at least one record has a nonzero queueing component
    assert any(r.queueing > 0 for r in prof.records)


def test_bridged_gap_names_the_gating_bridge():
    """A partitioned schedule run's bridge stalls are charged to the
    arg-max stalling bridge pair, src/dst named."""
    run, n_nodes = APPS["ldpc"]
    tr = Tracer()
    run("torus", "sim", _pods(n_nodes), tr)
    prof = profile_trace(tr).check_exact()
    stalls = sum(w.bridge_stalls for w in prof.waves)
    assert stalls > 0, "bridged ldpc run produced no bridge stalls"
    bridge_attr = [(res, c) for res, c in prof.critical_path().attribution
                   if res.startswith("bridge ")]
    assert bridge_attr
    assert sum(c for _, c in bridge_attr) == stalls
    # every record carries its wave's stall in the bridge component
    assert any(r.bridge > 0 for r in prof.records)


# ---------------------------------------------------------------------------
# zero overhead off
# ---------------------------------------------------------------------------

def test_profiling_disabled_allocates_no_records():
    ex, inputs, feedback = _bmvm_executor()
    ex.run_iterative(inputs, feedback, 1, mode="sim")   # warmup/compile
    ev0, rec0 = events_allocated(), records_allocated()
    ex.run_iterative(inputs, feedback, 2, mode="sim")
    ex.run_iterative(inputs, feedback, 2, mode="buffered")
    assert events_allocated() == ev0
    assert records_allocated() == rec0
    # tracing on but profiler not invoked: events yes, records still none
    ex2, inputs2, feedback2 = _bmvm_executor(trace=True)
    rec1 = records_allocated()
    ex2.run_iterative(inputs2, feedback2, 1, mode="buffered")
    assert events_allocated() > ev0
    assert records_allocated() == rec1
    # only profile_trace materializes records
    profile_trace(ex2.tracer)
    assert records_allocated() > rec1


def test_profile_strict_refuses_dropped_events():
    ex, inputs, feedback = _bmvm_executor(trace=Tracer(capacity=32))
    ex.run_iterative(inputs, feedback, 2, mode="buffered")
    assert ex.tracer.dropped > 0
    with pytest.raises(ValueError, match="dropped"):
        profile_trace(ex.tracer)
    prof = profile_trace(ex.tracer, strict=False)   # degrades, not crashes
    prof.check_exact()                              # survivors stay exact


# ---------------------------------------------------------------------------
# saved traces round-trip into the same profile
# ---------------------------------------------------------------------------

def test_events_from_chrome_roundtrip():
    run, _ = APPS["bmvm"]
    tr = Tracer()
    run("mesh", "buffered", None, tr)
    doc = json.loads(json.dumps(chrome_trace(tr)))   # through real JSON
    evs = events_from_chrome(doc)
    # trace_stats parity survives the round trip
    assert trace_stats(evs).as_dict() == trace_stats(tr).as_dict()
    p1 = profile_trace(tr).check_exact()
    p2 = profile_trace(evs).check_exact()
    assert [(r.src, r.dst, r.latency) for r in p1.records] == \
           [(r.src, r.dst, r.latency) for r in p2.records]
    assert p1.critical_path().length == p2.critical_path().length
    assert p1.links == p2.links


def test_report_and_flows_smoke():
    run, _ = APPS["pf"]
    tr = Tracer()
    run("mesh", "buffered", None, tr)
    prof = profile_trace(tr).check_exact()
    txt = prof.report()
    for needle in ("bottleneck report", "critical path", "serialization",
                   "queueing", "flows", "p99.9"):
        assert needle in txt
    flows = prof.flows()
    assert flows
    for st in flows.values():
        assert st["p50"] <= st["p99"] <= st["p999"] <= st["max"]
        assert st["count"] > 0


def test_publish_noc_latency_schema():
    reg = enable_metrics()
    try:
        run, _ = APPS["bmvm"]
        tr = Tracer()
        run("mesh", "buffered", None, tr)
        prof = profile_trace(tr)
        prof.publish(mode="buffered")
        hists = reg.histograms("noc.latency.")
        names = {h.name for h in hists.values()}
        assert {"noc.latency.total", "noc.latency.serialization",
                "noc.latency.hop", "noc.latency.queueing",
                "noc.latency.bridge", "noc.latency.flow"} <= names
        total = reg.histogram("noc.latency.total", mode="buffered")
        assert total.count == sum(r.n for r in prof.records)
        assert total.p50 <= total.p99 <= total.p999
        # component histogram sums reproduce the total sum exactly
        parts = sum(reg.histogram(f"noc.latency.{c}", mode="buffered").total
                    for c in ("serialization", "hop", "queueing", "bridge"))
        assert parts == total.total
        # prefix accessor filters: no serve/train histograms leak in
        assert all(k.startswith("noc.latency.") for k in hists)
    finally:
        disable_metrics()


def test_publish_noop_when_registry_disabled():
    disable_metrics()
    run, _ = APPS["pf"]
    tr = Tracer()
    run("mesh", "sim", None, tr)
    profile_trace(tr).publish()   # must not raise


# ---------------------------------------------------------------------------
# the regression gate: both directions
# ---------------------------------------------------------------------------

def test_metric_classes():
    assert metric_class("us", 1.0) == "timing"
    assert metric_class("seed_loop_us", 1.0) == "timing"
    assert metric_class("speedup_vs_sw", 1.0) == "timing"
    assert metric_class("tok_per_s", 1.0) == "timing"
    assert metric_class("cycles", 100) == "counter"
    assert metric_class("stalls", 100) == "counter"
    assert metric_class("deadlock_free", "True") == "text"


def test_compare_rows_counter_regression_and_improvement():
    base = [{"name": "t_x", "us": 10.0, "cycles": 100, "accepted": 0.5}]
    # unchanged: clean
    assert compare_rows(base, [dict(base[0])]) == []
    # counter worsens -> regression with named metric + delta
    worse = compare_rows(base, [{**base[0], "cycles": 120}])
    assert [f["verdict"] for f in worse] == ["regression"]
    assert worse[0]["metric"] == "cycles" and worse[0]["delta"] == "+20"
    # counter improves -> reported, not fatal
    better = compare_rows(base, [{**base[0], "cycles": 90}])
    assert [f["verdict"] for f in better] == ["improvement"]
    # higher-is-better direction: accepted dropping is the regression
    acc = compare_rows(base, [{**base[0], "accepted": 0.4}])
    assert acc[0]["metric"] == "accepted"
    assert acc[0]["verdict"] == "regression"


def test_compare_rows_timing_tolerance_and_gate():
    """Host wall times are reported, with their relative delta, and never
    fail the gate, however far they move in either direction."""
    base = [{"name": "t_x", "us": 100.0, "tok_per_s": 50.0}]
    for us, tok in ((110.0, 55.0), (900.0, 5.0), (10.0, 500.0)):
        found = compare_rows(base, [{"name": "t_x", "us": us, "tok_per_s": tok}])
        assert {f["metric"] for f in found} == {"us", "tok_per_s"}
        assert {(f["cls"], f["verdict"]) for f in found} == {("timing", "drift")}
    slow = compare_rows(base, [{"name": "t_x", "us": 900.0, "tok_per_s": 50.0}])
    assert [f["delta"] for f in slow] == ["+800.0%"]
    assert compare_rows(base, [dict(base[0])]) == []


def test_compare_rows_text_and_presence():
    base = [{"name": "t_gate", "us": 0.0, "deadlock_free": "True"},
            {"name": "t_only_base", "us": 0.0, "cycles": 1}]
    flipped = compare_rows(base, [
        {"name": "t_gate", "us": 0.0, "deadlock_free": "False"}])
    verdicts = {(f["row"], f["metric"]): f["verdict"] for f in flipped}
    assert verdicts[("t_gate", "deadlock_free")] == "regression"
    assert verdicts[("t_only_base", "(row)")] == "regression"


def test_regress_main_gate_both_ways(tmp_path):
    """End-to-end `regress.main` on fabricated baselines + fresh rows:
    exit 0 when unchanged, exit 1 naming the metric on a slowdown."""
    from repro.telemetry import regress

    rows = [{"name": "table12_bmvm_buffered", "us": 5.0, "cycles": 100,
             "crit": 98}]
    baseline = {"table": "table12_profile", "fast": True,
                "meta": {"platform": "nowhere", "python": "0"},
                "rows": rows}
    bdir = tmp_path / "baselines"
    bdir.mkdir()
    (bdir / "BENCH_table12.json").write_text(json.dumps(baseline))
    fresh = tmp_path / "fresh.json"
    fresh.write_text(json.dumps({"table12_profile": rows}))
    argv = ["--tables", "table12_profile", "--baseline-dir", str(bdir),
            "--fresh-json", str(fresh)]
    assert regress.main(argv) == 0
    # injected slowdown: the gate goes red and names the metric
    slow = [dict(rows[0], cycles=150)]
    fresh.write_text(json.dumps({"table12_profile": slow}))
    report = tmp_path / "report.json"
    assert regress.main(argv + ["--json", str(report)]) == 1
    findings = json.loads(report.read_text())
    assert findings["failed"] is True
    f = findings["findings"][0]
    assert f["metric"] == "cycles" and f["verdict"] == "regression"
    # mismatched fast/full baselines are refused, not silently diffed
    baseline["fast"] = False
    (bdir / "BENCH_table12.json").write_text(json.dumps(baseline))
    assert regress.main(argv) == 1
