"""Perf-regression gate: fresh benchmark runs vs the committed baselines.

The ``benchmarks/BENCH_*.json`` snapshots record the repo's perf trajectory
(deterministic engine counters + wall-clock timings with an environment
stamp).  This module turns them from a record into a **gate**: it re-runs
the snapshotted tables (``benchmarks/run.py --only <table>``), parses the
rows, and diffs them against the committed baselines with noise-aware
thresholds.  Nonzero exit ⇔ regression, with the offending metric and
delta named — wired into CI next to the functional gates.

Metric classes (the whole point — counters and timings fail differently):

* **counters** — deterministic engine numbers (cycles, stalls, flits,
  arb_losses, …).  Any mismatch is reported; a *worsening* fails the gate,
  an improvement or neutral drift is reported as such (the fix is to
  re-record the snapshot deliberately, via ``benchmarks/run.py --snapshot``,
  never to widen a tolerance).
* **timings** — ``us`` / any ``*_us`` key / throughput-like keys: host-CPU
  wall times, the **median of k runs** (default 3).  A change is reported
  with its relative delta but **never fails**: host wall time on a shared
  machine swings >50% on an unchanged tree, and it says nothing of the
  chip (the chip benchmark, ``bench/``, measures that).  The deterministic
  counters are the gate's teeth — an injected slowdown moves cycle counts,
  not just the clock (``table12_regress_selftest`` proves it).
* **text** — strings/bools (verdicts like ``deadlock_free=True``): any
  change fails.

Direction matters: ``speedup``/``accepted``/``*_per_s``-style metrics are
higher-is-better; everything else numeric lower-is-better.

Usage::

    python -m repro.telemetry.regress                  # gate all tables
    python -m repro.telemetry.regress --tables table9_congestion -k 5
    python -m repro.telemetry.regress --json report.json
    benchmarks/run.py --compare                        # same thing

The self-test that the gate actually trips lives in
``benchmarks/run.py::table12_profile`` (an injected ``buffer_depth=1``
slowdown must fail the diff) and runs in CI.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import statistics
import subprocess
import sys
from typing import Optional

# keys whose values are wall-clock / throughput noise, not deterministic
_TIMING_KEY = re.compile(
    r"(^|_)us$|per_s|fps|traced_over_untraced|speedup|gain")
# numeric metrics where bigger is better (everything else: smaller better)
_HIGHER_BETTER = re.compile(
    r"speedup|accepted|gain|per_s|fps|throughput|sat_rate")

DEFAULT_TABLES = ("table4_bmvm_iter", "table9_congestion", "table12_profile")


def _repo_root() -> str:
    # telemetry/ -> repro/ -> src/ -> repo root
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))


def metric_class(key: str, value) -> str:
    """``"timing"`` | ``"counter"`` | ``"text"`` for one row field."""
    if isinstance(value, str) or isinstance(value, bool):
        return "text"
    return "timing" if _TIMING_KEY.search(key) else "counter"


def _worse(key: str, base: float, new: float) -> bool:
    if _HIGHER_BETTER.search(key):
        return new < base
    return new > base


def _fmt(v) -> str:
    return f"{v:g}" if isinstance(v, (int, float)) else str(v)


def compare_rows(base_rows: list, new_rows: list) -> list:
    """Diff two row-dict lists (same format as ``BENCH_*.json["rows"]``).

    Returns a list of finding dicts ``{row, metric, cls, base, new, delta,
    verdict}`` where ``verdict`` is ``"regression"`` (fails the gate),
    ``"improvement"`` or ``"drift"`` (reported, non-fatal; every timing
    change is a drift).  Rows are
    matched by name; rows present on only one side are a ``"regression"``
    (a vanished benchmark can hide a vanished feature).
    """
    base_by = {r["name"]: r for r in base_rows}
    new_by = {r["name"]: r for r in new_rows}
    findings = []
    for name in sorted(set(base_by) | set(new_by)):
        if name not in new_by:
            findings.append(dict(row=name, metric="(row)", cls="presence",
                                 base="present", new="missing", delta="",
                                 verdict="regression"))
            continue
        if name not in base_by:
            findings.append(dict(row=name, metric="(row)", cls="presence",
                                 base="missing", new="present", delta="",
                                 verdict="drift"))
            continue
        b, n = base_by[name], new_by[name]
        for key in sorted(set(b) & set(n) - {"name"}):
            bv, nv = b[key], n[key]
            cls = metric_class(key, bv)
            if cls == "text":
                if str(bv) != str(nv):
                    findings.append(dict(
                        row=name, metric=key, cls=cls, base=str(bv),
                        new=str(nv), delta="changed", verdict="regression"))
                continue
            if bv == nv:
                continue
            if cls == "timing":
                rel = (nv - bv) / bv if bv else float("inf")
                findings.append(dict(
                    row=name, metric=key, cls=cls, base=bv, new=nv,
                    delta=f"{rel:+.1%}", verdict="drift"))
                continue
            # deterministic counter: any move is a finding
            verdict = ("regression" if _worse(key, bv, nv) else
                       "improvement")
            findings.append(dict(
                row=name, metric=key, cls=cls, base=bv, new=nv,
                delta=f"{nv - bv:+g}", verdict=verdict))
    return findings


def run_fresh(table: str, *, fast: bool = True, k: int = 3,
              repo_root: Optional[str] = None) -> list:
    """Run ``benchmarks/run.py --only <table>`` ``k`` times and fold the
    parsed rows: deterministic fields from the first run (they must not
    move between invocations — if they do, that IS the finding), timing
    fields replaced by the median across runs (noise suppression)."""
    root = repo_root or _repo_root()
    sys.path.insert(0, os.path.join(root, "benchmarks"))
    try:
        from run import _parse_row   # noqa: the benchmark's own parser
    finally:
        sys.path.pop(0)
    cmd = [sys.executable, "-m", "benchmarks.run", "--only", table]
    if fast:
        cmd.append("--fast")
    env = dict(os.environ)
    src = os.path.join(root, "src")
    env["PYTHONPATH"] = src + (os.pathsep + env["PYTHONPATH"]
                               if env.get("PYTHONPATH") else "")
    samples = []
    for _ in range(max(1, k)):
        out = subprocess.run(cmd, capture_output=True, text=True, cwd=root,
                             env=env)
        if out.returncode != 0:
            raise RuntimeError(
                f"benchmarks.run --only {table} failed "
                f"(exit {out.returncode}):\n{out.stdout[-2000:]}"
                f"\n{out.stderr[-2000:]}")
        rows = [_parse_row(ln) for ln in out.stdout.splitlines()
                if ln.startswith(table.split("_")[0]) and "," in ln
                and not ln.startswith("#")]
        if not rows:
            raise RuntimeError(
                f"benchmarks.run --only {table}: no rows parsed from:\n"
                f"{out.stdout[-2000:]}")
        samples.append(rows)
    folded = []
    for i, row in enumerate(samples[0]):
        merged = dict(row)
        for key, v in row.items():
            if key != "name" and metric_class(key, v) == "timing":
                vals = [s[i][key] for s in samples
                        if i < len(s) and key in s[i]]
                merged[key] = statistics.median(vals)
        folded.append(merged)
    return folded


def _load_baseline(table: str, baseline_dir: str) -> Optional[dict]:
    sys.path.insert(0, os.path.join(_repo_root(), "benchmarks"))
    try:
        from run import SNAPSHOTS
    finally:
        sys.path.pop(0)
    fname = SNAPSHOTS.get(table)
    if fname is None:
        return None
    path = os.path.join(baseline_dir, fname)
    if not os.path.exists(path):
        return None
    with open(path) as fh:
        return json.load(fh)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.telemetry.regress",
        description="perf-regression gate vs committed BENCH_*.json")
    ap.add_argument("--tables", default=",".join(DEFAULT_TABLES),
                    help="comma-separated snapshot tables to gate")
    ap.add_argument("--full", action="store_true",
                    help="run the full (non --fast) benchmark variants; "
                         "only valid against full-recorded baselines")
    ap.add_argument("-k", type=int, default=3,
                    help="fresh runs per table; timings take the median")
    ap.add_argument("--baseline-dir", default=None,
                    help="directory holding BENCH_*.json (default: the "
                         "repo's benchmarks/)")
    ap.add_argument("--fresh-json", default=None,
                    help="read fresh rows from this JSON instead of "
                         "re-running (as written by --save-fresh)")
    ap.add_argument("--save-fresh", default=None,
                    help="write the fresh rows to this JSON for reuse")
    ap.add_argument("--json", default=None,
                    help="write the findings report as JSON here")
    args = ap.parse_args(argv)

    root = _repo_root()
    baseline_dir = args.baseline_dir or os.path.join(root, "benchmarks")
    fast = not args.full
    tables = [t for t in args.tables.split(",") if t]
    prior_fresh = {}
    if args.fresh_json:
        with open(args.fresh_json) as fh:
            prior_fresh = json.load(fh)

    all_findings, fresh_out = [], {}
    failed = False
    for table in tables:
        base = _load_baseline(table, baseline_dir)
        if base is None:
            print(f"[regress] {table}: no committed baseline — skipping "
                  f"(record one with benchmarks/run.py --snapshot)")
            continue
        if bool(base.get("fast")) != fast:
            print(f"[regress] {table}: baseline recorded with "
                  f"fast={base.get('fast')} but this run is fast={fast}; "
                  f"refusing an apples-to-oranges diff", file=sys.stderr)
            failed = True
            continue
        if table in prior_fresh:
            fresh = prior_fresh[table]
        else:
            fresh = run_fresh(table, fast=fast, k=args.k, repo_root=root)
        fresh_out[table] = fresh
        findings = compare_rows(base["rows"], fresh)
        regressions = [f for f in findings if f["verdict"] == "regression"]
        tag = "FAIL" if regressions else "ok"
        print(f"[regress] {table}: {len(base['rows'])} rows, "
              f"{len(findings)} finding(s), "
              f"{len(regressions)} regression(s) -> {tag}")
        for f in findings:
            f["table"] = table
            mark = {"regression": "!!", "improvement": "++"}.get(
                f["verdict"], "~ ")
            print(f"  {mark} {f['row']}.{f['metric']} [{f['cls']}]: "
                  f"{_fmt(f['base'])} -> {_fmt(f['new'])}  {f['delta']}  "
                  f"({f['verdict']})")
        all_findings.extend(findings)
        failed = failed or bool(regressions)

    if args.save_fresh:
        with open(args.save_fresh, "w") as fh:
            json.dump(fresh_out, fh, indent=1, sort_keys=True)
            fh.write("\n")
    if args.json:
        with open(args.json, "w") as fh:
            json.dump({"failed": failed, "findings": all_findings}, fh,
                      indent=1, sort_keys=True)
            fh.write("\n")
    if failed:
        print("[regress] FAIL: performance regressed vs committed "
              "baselines (see metrics above)", file=sys.stderr)
        return 1
    print("[regress] all gated tables within thresholds")
    return 0


if __name__ == "__main__":
    sys.exit(main())
