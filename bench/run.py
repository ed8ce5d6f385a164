"""Chip benchmark: run one cell of ``BENCHMARK.json``, print one result line.

    python bench/run.py --workload bmvm.n24576.m128 --seed 7 --seconds 51 --trace 0

A cell names a configuration, whose file ``BENCHMARK.json`` gives, and a
traffic mix, ``bench/traffic/<traffic>.json``.  The configuration's
``family`` names ``bench/apps/<family>.py``, which sets the cell up, makes
the timed call and holds the plain reference.  Each per-layer metric is
read by ``bench/metrics/<metric>.py``.  A new cell, mix, configuration or
metric is new files and a new entry in ``BENCHMARK.json``; no code changes.

Set-up makes the inputs from ``--seed``, compiles and warms the cell's own
shapes; it counts as ``setup_s``.  The window then makes the timed call back
to back for ``--seconds``: one caller, a closed loop, each call ending in
``block_until_ready``.  Once it has closed, the answers of a sample of calls
drawn from the seed, and of the last call, are compared with the plain
reference.  ``--trace 1`` records the window with JAX's profiler and prints
the cell's per-layer metrics in place of the end-to-end ones.

Exits non-zero and prints no result where JAX finds no TPU, fewer chips
than the cell asks for, or a ``device_kind`` that ``bench/peaks.json`` lacks.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if __name__ == "__main__":
    # run as a script: import the benchmark as the package ``bench`` and the
    # program from ``src``, and keep ``bench/`` itself off the path
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))
    # the TPU runtime logs to /tmp/tpu_logs unless told otherwise; a run
    # writes only inside its checkout and the directories it is given
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np  # noqa: E402

import jax  # noqa: E402

from bench import trace as btrace  # noqa: E402

CACHE_DIR = BENCH / ".jax_cache"
TRACE_DIR = BENCH / ".trace"


class NoChip(RuntimeError):
    """The machine lacks what the cell needs; no result is printed."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Find cell ``name`` and its files by name under ``root``."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has {sorted(cells)}")
    w = cells[name]
    (conf,) = [c for c in spec["configs"] if c["name"] == w["config"]]
    e2e = [m for m in spec["end_to_end"] if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m else m["moves"] in reported)]
    return Cell(name=name, chips=w["chips"],
                config=json.loads((root / conf["file"]).read_text()),
                traffic=json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json")
                                   .read_text()),
                end_to_end=e2e, per_layer=per_layer)


def load_peaks(kind: str, path: Path = BENCH / "peaks.json") -> dict:
    """The chip's published peaks; a ``device_kind`` not in the table is an error."""
    table = json.loads(path.read_text())
    if kind not in table:
        raise NoChip(f"device_kind {kind!r} has no row in {path.name} ({sorted(table)})")
    return table[kind]


def load_reader(metric: str):
    """``read(ctx)`` of ``bench/metrics/<metric>.py``."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench.metrics.{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileCounter:
    """Programs handed to the backend, from ``jax.monitoring``: each one is
    compiled or loaded from the persistent cache, and the cache hits say
    which."""

    REQUESTS = "/jax/core/compile/backend_compile_duration"
    HITS = "/jax/compilation_cache/cache_hits"
    EVENTS = (REQUESTS, HITS)
    _installed = None

    def __init__(self):
        self.by_event = dict.fromkeys(self.EVENTS, 0)

    @classmethod
    def get(cls) -> "CompileCounter":
        """The process's one counter; ``jax.monitoring`` listeners stay for good."""
        if cls._installed is None:
            c = cls._installed = cls()
            jax.monitoring.register_event_listener(
                lambda name, **_: c._seen(name))
            jax.monitoring.register_event_duration_secs_listener(
                lambda name, _secs, **__: c._seen(name))
        return cls._installed

    def _seen(self, name: str) -> None:
        if name in self.by_event:
            self.by_event[name] += 1


def chips_for(cell: Cell, allow_cpu: bool) -> list:
    devices = jax.devices()
    if devices[0].platform != "tpu" and not allow_cpu:
        raise NoChip(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < cell.chips:
        raise NoChip(f"cell {cell.name} needs {cell.chips} chips, JAX found {len(devices)}")
    return devices[:cell.chips]


def use_cache(cache_dir) -> None:
    """Keep every program, however quick to compile, in the cache at ``cache_dir``."""
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class GcClock:
    """Seconds the garbage collector held the host, from ``gc.callbacks``."""

    def __init__(self):
        self.pauses: list[float] = []
        self._t = None

    def __call__(self, phase: str, _info) -> None:
        if phase == "start":
            self._t = time.monotonic()
        elif self._t is not None:
            self.pauses.append(time.monotonic() - self._t)

    def __enter__(self) -> "GcClock":
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self)


def window(app, seconds: float, seed: int, keep: int):
    """Call ``app.step`` back to back for ``seconds``.  Returns the call
    latencies, the window's seconds, the answers kept (a reservoir sample of
    ``keep`` calls drawn from ``seed``, and the last call) and, for the
    longest call, what the host did in it: seconds to dispatch, seconds
    waiting for the chip, and the calling thread's CPU seconds."""
    rng = random.Random(seed)
    slots: list[tuple[int, jax.Array]] = []
    lat = []
    longest = (0.0, 0.0, 0.0, 0.0)
    v = app.v0
    with jax.profiler.TraceAnnotation(btrace.WINDOW):
        t0 = time.monotonic()
        while True:
            c0, u0 = time.monotonic(), time.thread_time()
            with jax.profiler.TraceAnnotation(btrace.CALL):
                out = app.step(v)
                c_mid = time.monotonic()
                v = out.block_until_ready()
            c1 = time.monotonic()
            lat.append(c1 - c0)
            if c1 - c0 > longest[0]:
                longest = (c1 - c0, c_mid - c0, c1 - c_mid, time.thread_time() - u0)
            t = len(lat) - 1
            if t < keep:
                slots.append((t, v))
            elif (j := rng.randrange(t + 1)) < keep:
                slots[j] = (t, v)
            if c1 - t0 >= seconds:
                break
    kept = dict(slots)
    kept[t] = v
    return lat, c1 - t0, kept, longest[1:]


def memory_peak(devices) -> int | None:
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):
        return None
    return max(s["peak_bytes_in_use"] for s in stats)


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool, *,
             t_start: float, allow_cpu: bool = False, cache_dir=CACHE_DIR,
             trace_root: Path = TRACE_DIR, replace_step=None) -> dict:
    """Set up, measure and check one run of ``cell``; returns the result.

    ``replace_step(app)``, where given, returns the call that stands in for
    the program's in the window (the control)."""
    devices = chips_for(cell, allow_cpu)
    kind = devices[0].device_kind
    peaks = None if allow_cpu else load_peaks(kind)
    if cache_dir is not None:
        use_cache(cache_dir)
    compiles = CompileCounter.get()
    family = importlib.import_module(f"bench.apps.{cell.config['family']}")
    app = family.App(cell.config, cell.traffic, seed, devices, peaks)
    if replace_step is not None:
        app.step = replace_step(app)
    app.warm()
    trace_dir = trace_root / cell.name
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    setup_s = time.monotonic() - t_start
    before = dict(compiles.by_event)
    with GcClock() as gcc:
        lat, window_s, kept, (dispatch, wait, cpu) = window(app, seconds, seed,
                                                           cell.traffic["checked"])
    n_compiles, n_hits = (compiles.by_event[k] - before[k] for k in compiles.EVENTS)
    med = float(np.median(lat))
    print(f"bench: set-up {setup_s:.3f} s, {len(lat)} calls in {window_s:.3f} s "
          f"(median {med * 1e3:.3f} ms, {sum(x > 2 * med for x in lat)} over twice the "
          f"median); longest call {max(lat) * 1e3:.3f} ms at call {int(np.argmax(lat))}: "
          f"dispatch {dispatch * 1e3:.3f} ms, wait for the chip {wait * 1e3:.3f} ms, "
          f"thread CPU {cpu * 1e3:.3f} ms; garbage collector {len(gcc.pauses)} pauses, "
          f"{sum(gcc.pauses) * 1e3:.3f} ms in all, longest "
          f"{max(gcc.pauses, default=0.0) * 1e3:.3f} ms; in the window {n_compiles} "
          f"programs compiled or loaded, {n_hits} of them from the compile cache",
          file=sys.stderr)
    if traced:
        jax.profiler.stop_trace()
    peak = memory_peak(devices)
    app.free()
    wrong = dict(app.expected(kept))
    n_wrong = sum(wrong.values())

    calls = len(lat)
    values = {"throughput": calls * app.items_per_call / window_s,
              "call_p95_ms": float(np.percentile(lat, 95)) * 1e3,
              "setup_s": setup_s}
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": peak}
    out = {"correct": n_wrong == 0, "attempted": calls,
           "failed": sum(1 for w in wrong.values() if w)}
    if traced:
        tr = btrace.Trace.load(next(trace_dir.glob("plugins/profile/*/*.xplane.pb")))
        if not tr.chips and not allow_cpu:
            raise RuntimeError(f"no TPU ops in the trace under {trace_dir}")
        ctx = types.SimpleNamespace(trace=tr if tr.chips else None, calls=calls,
                                    window_s=window_s, compiles=n_compiles,
                                    work=app.work, peaks=peaks)
        values = {m["name"]: load_reader(m["name"])(ctx) for m in cell.per_layer}
        if tr.chips:
            device.update(busy_s=tr.busy_s(), window_s=tr.window_s)
            out["breakdown"] = {"device_ops": tr.op_seconds()[:10],
                                "idle_gaps": tr.gaps_by_host(min(tr.chips))[:10]}
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        v = values.get(m["name"])
        if allow_cpu and m["source"] != "program_counter":
            v = None            # a CPU rehearsal reports no device metric
        elif v is None:
            continue            # the reader found nothing to read
        metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out.update(metrics=metrics, device=device,
               checks={"wrong_bits": {"value": n_wrong, "limit": 0}})
    return out


def main(argv=None, *, allow_cpu: bool = False, cache_dir=CACHE_DIR,
         trace_root: Path = TRACE_DIR, root: Path = ROOT, t_start: float = T_START) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload, root)
    try:
        res = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       t_start=t_start, allow_cpu=allow_cpu, cache_dir=cache_dir,
                       trace_root=trace_root)
    except NoChip as e:
        print(f"bench: {e}; nothing run", file=sys.stderr)
        return 2
    for name, c in res["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
