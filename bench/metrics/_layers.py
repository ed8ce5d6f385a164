"""Device time per call in the LM layers' program scopes.

The model marks its sublayers with ``jax.named_scope``: ``moe.router``,
``moe.experts`` and ``moe.combine`` in the held-experts MoE layer,
``mamba.mixer`` and ``attn.mixer`` around the mixers
(``docs/observability.md``, "On the chip").  :mod:`bench.metrics._scopes`
maps ops only to the BMVM and routing scopes; this maps each device op of the
same profile (:func:`bench.metrics._scopes.find_profile`) to the innermost of
these.  An op outside them maps to None, as does every op of a program that
opens none.

The grouped matmuls of ``moe.experts`` (``lax.ragged_dot``) reach the chip as
custom calls that the TPU compiler adds without the scope's metadata:
``%ragged-dot-none.<n> = bf16[32,14336] custom-call(...)``, with its
``ragged-dot-metadata`` beside it.  They map to ``moe.experts`` by name; the
held-experts layer is where the program makes them.
"""
from __future__ import annotations

import json
import re

from bench.metrics._scopes import _read, find_profile
from bench.trace import length, union

PREFIXES = ("moe.", "mamba.", "attn.")

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_RAGGED_DOT = re.compile(r"^%ragged-dot[\w.-]* = .* custom-call\(")


def innermost(op_name: str) -> str | None:
    scopes = [c for c in op_name.split("/") if c.startswith(PREFIXES)]
    return scopes[-1] if scopes else None


def scope_of(long_name: str, op_name: str) -> str | None:
    """The innermost layer scope of an op, ``moe.experts`` for a grouped matmul."""
    return innermost(op_name) or ("moe.experts" if _RAGGED_DOT.match(long_name) else None)


def scope_map(trace) -> dict[int, dict[str, str | None]] | None:
    """Per chip, each op event name of the window -> its innermost layer
    scope; None where no profile of this window is found."""
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
            out[chips[e["pid"]]][args["long_name"]] = scope_of(args["long_name"],
                                                               args.get("tf_op", ""))
    return out


def seconds(ctx, match) -> float | None:
    """Device seconds of the window in the scopes ``match`` accepts, mean over
    the chips; None where no op of the window carries one."""
    if ctx.trace is None or (names := scope_map(ctx.trace)) is None:
        return None
    tr = ctx.trace
    spans = [union((e.start, e.end) for e in tr.chips[c]
                   if (s := names.get(c, {}).get(e.name)) and match(s))
             for c in tr.chips]
    if not any(spans):
        return None
    return sum(length(s) for s in spans) / len(spans)


def per_call_ms(ctx, match) -> float | None:
    s = seconds(ctx, match)
    return None if s is None else 1e3 * s / ctx.calls
