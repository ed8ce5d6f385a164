"""Reading a JAX profiler trace (``.xplane.pb``) into what the metrics need.

A :class:`Trace` holds, for each TPU chip, the events of its ``XLA Ops``
line, and the host's events on the thread that drove the run: the line of
the ``/host:CPU`` plane that holds the window's annotation.  The harness
marks the measured window with a host annotation named :data:`WINDOW`;
every reduction here is clipped to it.  Times are in seconds.
"""
from __future__ import annotations

import dataclasses
import gzip
import heapq
import re
from pathlib import Path

WINDOW = "bench.window"
CALL = "bench.call"

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE = "XLA Ops"
_HOST_PLANE = "/host:CPU"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


def union(intervals) -> list[tuple[float, float]]:
    """Sorted, merged union of (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


# an XLA op event is named by its HLO text: "%name = shape opcode(operands), ..."
_HLO = re.compile(r"(%[\w.-]+) = (.*?) ([\w-]+)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")


def opcode(name: str) -> str:
    m = _HLO.match(name)
    return m[3] if m else name


def op_name(name: str) -> str:
    """``%name opcode shape`` of an XLA op event, the shape without layouts."""
    m = _HLO.match(name)
    return f"{m[1]} {m[3]} {_LAYOUT.sub('', m[2])[:60]}" if m else name[:120]


class Trace:
    """Device op events per chip and host events, clipped to the window."""

    def __init__(self, chips: dict[int, list[Event]], host: list[Event]):
        wins = [e for e in host if e.name == WINDOW]
        if len(wins) != 1:
            raise ValueError(f"trace holds {len(wins)} '{WINDOW}' spans, not 1")
        self.t0, self.t1 = wins[0].start, wins[0].end
        self.host = [e for e in host if e.end > self.t0 and e.start < self.t1]
        self.chips = {c: sorted((self._clip(e) for e in evs
                                 if e.end > self.t0 and e.start < self.t1),
                                key=lambda e: e.start)
                      for c, evs in sorted(chips.items())}

    @classmethod
    def load(cls, path) -> "Trace":
        """Read an ``.xplane.pb`` (or ``.xplane.pb.gz``) profile."""
        from jax.profiler import ProfileData

        data = Path(path).read_bytes()
        if str(path).endswith(".gz"):
            data = gzip.decompress(data)
        prof = ProfileData.from_serialized_xspace(data)
        chips: dict[int, list[Event]] = {}
        host: list[Event] = []
        for plane in prof.planes:
            m = _DEVICE_PLANE.match(plane.name)
            for line in plane.lines:
                evs = [Event(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                       for e in line.events]
                if m and line.name == _OPS_LINE:
                    chips[int(m.group(1))] = evs
                elif plane.name == _HOST_PLANE and any(e.name == WINDOW for e in evs):
                    host = evs      # the thread that ran the window
        return cls(chips, host)

    def _clip(self, e: Event) -> Event:
        return Event(e.name, max(e.start, self.t0), min(e.end, self.t1))

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def busy(self, chip: int) -> list[tuple[float, float]]:
        """Union of the intervals in which an op ran on ``chip``."""
        return union((e.start, e.end) for e in self.chips[chip])

    def busy_s(self) -> float:
        """Device-busy seconds in the window, mean over the chips."""
        return sum(length(self.busy(c)) for c in self.chips) / len(self.chips)

    def ops(self, chip: int, match) -> list[Event]:
        """Events on ``chip`` whose name ``match(name)`` accepts."""
        return [e for e in self.chips[chip] if match(e.name)]

    def op_seconds(self) -> list[tuple[str, float]]:
        """Device time by :func:`op_name`, summed over the chips, longest first."""
        tot: dict[str, float] = {}
        for evs in self.chips.values():
            for e in evs:
                tot[op_name(e.name)] = tot.get(op_name(e.name), 0.0) + e.dur
        return sorted(tot.items(), key=lambda kv: -kv[1])

    def idle_gaps(self, chip: int) -> list[tuple[float, float]]:
        """Intervals of the window in which no op ran on ``chip``."""
        gaps, t = [], self.t0
        for s, e in self.busy(chip):
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < self.t1:
            gaps.append((t, self.t1))
        return gaps

    def gaps_by_host(self, chip: int) -> list[tuple[str, float]]:
        """Idle seconds of ``chip`` by the innermost host span in progress at
        each instant of the gap, longest first.  Gap time that no host span
        other than the window covers is named after the window."""
        # one sweep over span starts, span ends and gap edges; the innermost
        # open span is the shortest one, kept on a heap with lazy removal
        marks = []
        for i, e in enumerate(self.host):
            if e.name != WINDOW and e.dur > 0:
                marks += [(e.start, 1, i), (e.end, 0, i)]
        for gs, ge in self.idle_gaps(chip):
            marks += [(gs, 2, -1), (ge, 2, -1)]
        marks.sort()
        heap: list[tuple[float, int]] = []
        open_, in_gap, last = set(), False, None
        tot: dict[str, float] = {}
        for t, kind, i in marks:
            if in_gap and last is not None and t > last:
                while heap and heap[0][1] not in open_:
                    heapq.heappop(heap)
                name = self.host[heap[0][1]].name if heap else WINDOW
                tot[name] = tot.get(name, 0.0) + (t - last)
            last = t
            if kind == 2:
                in_gap = not in_gap
            elif kind == 1:
                open_.add(i)
                heapq.heappush(heap, (self.host[i].dur, i))
            else:
                open_.discard(i)
        return sorted(tot.items(), key=lambda kv: -kv[1])
