"""Which program scope each device op of a traced window ran in.

The program names its layers with ``jax.named_scope``: ``bmvm.pack``,
``kernels.gf2_bmvm``, ``routing.noc_x.r1`` and so on (``docs/observability.md``,
"On the chip").  A scope path is HLO metadata (``op_name``).  The XLA-op
events of the ``.xplane.pb`` carry none of it, but the ``*.trace.json.gz``
that the profiler writes beside it gives each op event its ``tf_op`` (the
``op_name``) and its ``long_name`` (the HLO text, which is the event's name
in :class:`bench.trace.Trace`).

The readers' context holds the loaded trace and not its path, so the profile
is the one under :data:`TRACE_ROOT` whose ``bench.window`` span is the
trace's window.  An op with no program scope maps to None, as does every op
of a program that opens none.
"""
from __future__ import annotations

import gzip
import json
import re
from pathlib import Path

from bench.trace import WINDOW, length, union

PREFIXES = ("bmvm.", "kernels.", "routing.")
TRACE_ROOT = Path(__file__).resolve().parents[1] / ".trace"

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


def innermost(op_name: str) -> str | None:
    """The innermost program scope on an ``op_name`` path, or None."""
    scopes = [c for c in op_name.split("/") if c.startswith(PREFIXES)]
    return scopes[-1] if scopes else None


def _read(path: Path) -> bytes:
    data = path.read_bytes()
    return gzip.decompress(data) if path.suffix == ".gz" else data


def _window(xplane: Path) -> tuple[float, float] | None:
    """The ``bench.window`` span of a profile, in seconds as Trace reads it."""
    from jax.profiler import ProfileData

    host = ProfileData.from_serialized_xspace(_read(xplane)).find_plane_with_name("/host:CPU")
    for line in host.lines if host else ():
        for e in line.events:
            if e.name == WINDOW:
                return e.start_ns * 1e-9, e.end_ns * 1e-9
    return None


def find_profile(trace, root: Path | None = None) -> Path | None:
    """The ``*.trace.json.gz`` of the profile under ``root`` that ``trace`` was read from."""
    for xplane in sorted((root or TRACE_ROOT).glob("**/*.xplane.pb*")):
        doc = xplane.with_name(xplane.name.split(".xplane.pb")[0] + ".trace.json.gz")
        if doc.exists() and _window(xplane) == (trace.t0, trace.t1):
            return doc
    return None


def scope_map(trace) -> dict[int, dict[str, str | None]] | None:
    """Per chip, each op event name of the window -> its innermost scope;
    None where no profile of this window is found."""
    doc = find_profile(trace)
    if doc is None:
        return None
    events = json.loads(_read(doc))["traceEvents"]
    chips = {e["pid"]: int(m[1]) for e in events
             if e.get("ph") == "M" and e.get("name") == "process_name"
             and (m := _DEVICE.match(e["args"]["name"]))}
    t0, t1 = trace.t0 * 1e6, trace.t1 * 1e6     # the document's clock is in us
    out: dict[int, dict[str, str | None]] = {c: {} for c in chips.values()}
    for e in events:
        args = e.get("args") or {}
        if e.get("pid") in chips and "long_name" in args and t0 <= e.get("ts", -1.0) <= t1:
            out[chips[e["pid"]]][args["long_name"]] = innermost(args.get("tf_op", ""))
    return out


def per_call_ms(ctx, match) -> float | None:
    """Device milliseconds a call spends in the scopes ``match`` accepts,
    mean over the chips; None where no op of the window carries one."""
    if ctx.trace is None or (names := scope_map(ctx.trace)) is None:
        return None
    tr = ctx.trace
    spans = [union((e.start, e.end) for e in tr.chips[c]
                   if (s := names.get(c, {}).get(e.name)) and match(s))
             for c in tr.chips]
    if not any(spans):
        return None
    return 1e3 * sum(length(s) for s in spans) / len(spans) / ctx.calls

