"""A JAX profiler trace reduced to what the metric readers need.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
three kinds of events, each as ``(name, start_ns, end_ns)`` on the trace's
one clock:

- ``ops``: one list per device, the events of its "XLA Ops" line;
- ``modules``: one list per device, the events of its "XLA Modules"
  line, one per execution of a compiled program (``jit_<name>(<id>)``);
- ``spans``: the harness's own host spans (``TraceAnnotation`` names that
  start with ``bench.``).

``window`` is the ``bench.window`` span: the part of the run that was
traced.
"""

from __future__ import annotations

import dataclasses
import glob
from typing import Iterable, List, Sequence, Tuple

Event = Tuple[str, float, float]

SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Trace:
    window: Tuple[float, float]
    ops: List[List[Event]]
    modules: List[List[Event]]
    spans: List[Event]

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def spans_named(self, name: str) -> List[Event]:
        lo, hi = self.window
        return [s for s in self.spans if s[0] == name and s[1] >= lo and s[2] <= hi]

    def busy_ns(self) -> float:
        """Device busy time in the window, averaged over the devices."""
        lo, hi = self.window
        return sum(union_ns(dev, [(lo, hi)]) for dev in self.ops) / max(len(self.ops), 1)


def merge(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def union_ns(events: Sequence[Event], within: Sequence[Tuple[float, float]]) -> float:
    """Length of the union of ``events`` inside the union of ``within``."""
    busy = merge((s, e) for _, s, e in events)
    total = 0.0
    for lo, hi in merge(within):
        for s, e in busy:
            total += max(0.0, min(e, hi) - max(s, lo))
    return total


def idle_gaps(events: Sequence[Event], lo: float, hi: float) -> List[Tuple[float, float]]:
    """The intervals of ``[lo, hi]`` in which no event runs."""
    gaps, t = [], lo
    for s, e in merge((s, e) for _, s, e in events):
        if e <= lo or s >= hi:
            continue
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def load(directory: str) -> Trace:
    """Read the one ``.xplane.pb`` under ``directory``."""
    from jax.profiler import ProfileData

    files = glob.glob(f"{directory}/**/*.xplane.pb", recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {directory}, found {files}")
    data = ProfileData.from_file(files[0])
    ops, modules, spans = [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" not in lines:
                continue
            ops.append(_events(lines["XLA Ops"]))
            modules.append(_events(lines["XLA Modules"]) if "XLA Modules" in lines else [])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [ev for ev in _events(line) if ev[0].startswith(SPAN_PREFIX)]
    windows = [s for s in spans if s[0] == SPAN_PREFIX + "window"]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {SPAN_PREFIX}window span, found {len(windows)}")
    return Trace(window=windows[0][1:], ops=ops, modules=modules, spans=spans)


def _events(line) -> List[Event]:
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns) for ev in line.events]
