"""Reduction of a profiler trace to device busy time, per-program device
time, top device operations and named idle gaps.

The benchmark marks its own host spans with `jax.profiler.TraceAnnotation`
(`bench.unit` around each unit of work, and inside it `bench.dispatch`,
`bench.wait` and `bench.check`).  The traced window runs from the start of
the first `bench.unit` to the end of the last one.  On the device planes
(`/device:...`) the line `XLA Modules` holds one event per execution of a
compiled program, named after its module (`jit_relabel_ring(...)`), and the
line `XLA Ops` one event per operation.  Busy time is the union of the
operation intervals inside the window; an idle gap is a stretch of the
window with no operation, named by the innermost benchmark span that was
open on the host at its midpoint.

    python benchmarks/chip/devtrace.py <file.xplane.pb>   # look by hand
"""

from __future__ import annotations

import bisect
import dataclasses
import re
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

UNIT_SPAN = "bench.unit"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float
    stats: Tuple[Tuple[str, object], ...] = ()

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass(frozen=True)
class Plane:
    name: str
    lines: Dict[str, List[Event]]


def load_xplane(path: str) -> List[Plane]:
    """Planes of one `.xplane.pb` file, through `jax.profiler.ProfileData`."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines: Dict[str, List[Event]] = {}
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            for ev in line.events:
                stats = tuple((k, v) for k, v in ev.stats if k in ("hlo_module", "hlo_op"))
                evs.append(Event(ev.name, float(ev.start_ns), float(ev.duration_ns), stats))
        planes.append(Plane(plane.name, lines))
    return planes


def module_of(event_name: str) -> str:
    """`jit_relabel_ring(12)` -> `jit_relabel_ring`."""
    return event_name.split("(", 1)[0].strip()


def op_of(event_name: str) -> str:
    """An operation's HLO line, cut to its name and result shape:
    `%fusion.4 = s32[67109889,2]{0,1:T(2,128)} fusion(...)` ->
    `fusion.4 s32[67109889,2]`."""
    name, _, rest = event_name.partition(" = ")
    rest = re.sub(r"\{[^{}]*\}", "", rest)
    shape = rest[:rest.find(")") + 1] if rest.startswith("(") else rest.split(" ", 1)[0]
    return f"{name.lstrip('%')} {shape[:48]}".strip()


def _self_times(events: List[Event], lo: float, hi: float) -> List[Tuple[Event, float]]:
    """Each operation's time inside [lo, hi] less that of the operations
    nested in it (a loop's body runs inside the loop's own event)."""
    out: List[List] = []
    stack: List[List] = []
    for ev in sorted(events, key=lambda e: (e.start_ns, -e.dur_ns)):
        while stack and stack[-1][0].end_ns <= ev.start_ns:
            stack.pop()
        a, b = _clip(ev, lo, hi)
        item = [ev, max(b - a, 0.0)]
        if stack:
            stack[-1][1] -= item[1]
        stack.append(item)
        out.append(item)
    return [(ev, max(t, 0.0)) for ev, t in out]


def _union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if merged and lo <= merged[-1][1]:
            if hi > merged[-1][1]:
                merged[-1] = (merged[-1][0], hi)
        else:
            merged.append((lo, hi))
    return merged


def _clip(ev: Event, lo: float, hi: float) -> Tuple[float, float]:
    return max(ev.start_ns, lo), min(ev.end_ns, hi)


class TraceSummary:
    """What the per-layer metrics read from one traced window."""

    def __init__(self, planes: Sequence[Plane]):
        self.spans: List[Event] = [
            ev for p in planes if p.name.startswith("/host:")
            for evs in p.lines.values() for ev in evs if ev.name.startswith(SPAN_PREFIX)]
        units = [ev for ev in self.spans if ev.name == UNIT_SPAN]
        self.units = len(units)
        self.devices = [p for p in planes if p.name.startswith("/device:")
                        and (p.lines.get(OPS_LINE) or p.lines.get(MODULES_LINE))]
        if units:
            self.lo = min(ev.start_ns for ev in units)
            self.hi = max(ev.end_ns for ev in units)
        else:
            self.lo = self.hi = 0.0

    # -- window and busy time ------------------------------------------------

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def _ops(self, dev: Plane) -> List[Event]:
        return dev.lines.get(OPS_LINE) or dev.lines.get(MODULES_LINE) or []

    def _busy(self, dev: Plane) -> List[Tuple[float, float]]:
        return _union(_clip(ev, self.lo, self.hi) for ev in self._ops(dev))

    @property
    def busy_s(self) -> Optional[float]:
        """Seconds with an operation running, averaged over the devices."""
        if not self.devices or not self.units:
            return None
        total = sum(hi - lo for dev in self.devices for lo, hi in self._busy(dev))
        return total / len(self.devices) / 1e9

    def idle_share(self) -> Optional[float]:
        busy = self.busy_s
        if busy is None or self.window_s <= 0:
            return None
        return 100.0 * (1.0 - busy / self.window_s)

    # -- programs --------------------------------------------------------------

    def module_s_per_unit(self, modules: Iterable[str]) -> Optional[float]:
        """Device seconds of the named programs per unit of work, averaged
        over the devices; None where none of them ran in the window."""
        wanted = set(modules)
        if not self.devices or not self.units:
            return None
        total, found = 0.0, False
        for dev in self.devices:
            for ev in dev.lines.get(MODULES_LINE, []):
                if module_of(ev.name) in wanted:
                    lo, hi = _clip(ev, self.lo, self.hi)
                    if hi > lo:
                        total += hi - lo
                        found = True
        if not found:
            return None
        return total / len(self.devices) / self.units / 1e9

    def top_ops(self, k: int = 10) -> List[List[object]]:
        """The k operations with the most device seconds of their own in
        the window, averaged over the devices, labelled
        `<module>/<op> <shape>`."""
        per: Dict[str, float] = {}
        for dev in self.devices:
            modules = sorted(dev.lines.get(MODULES_LINE, []), key=lambda e: e.start_ns)
            starts = [e.start_ns for e in modules]
            for ev, ns in _self_times(dev.lines.get(OPS_LINE, []), self.lo, self.hi):
                if ns <= 0:
                    continue
                mod = dict(ev.stats).get("hlo_module")
                if mod is None:
                    i = bisect.bisect_right(starts, ev.start_ns) - 1
                    if i >= 0 and modules[i].end_ns >= ev.start_ns:
                        mod = module_of(modules[i].name)
                label = f"{mod}/{op_of(ev.name)}" if mod else op_of(ev.name)
                per[label] = per.get(label, 0.0) + ns
        ndev = max(len(self.devices), 1)
        top = sorted(per.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns / ndev / 1e9] for name, ns in top]

    def _span_at(self, t: float) -> str:
        best = None
        for ev in self.spans:
            if ev.start_ns <= t <= ev.end_ns and (best is None or ev.dur_ns < best.dur_ns):
                best = ev
        return best.name if best is not None else "outside benchmark spans"

    def idle_gaps(self, k: int = 10) -> List[List[object]]:
        """The k longest stretches of the window with no device operation,
        each named by the host span open at its midpoint."""
        gaps = []
        for i, dev in enumerate(self.devices):
            t = self.lo
            for lo, hi in self._busy(dev) + [(self.hi, self.hi)]:
                if lo > t:
                    gaps.append((lo - t, (t + lo) / 2, i))
                t = max(t, hi)
        gaps.sort(reverse=True)
        many = len(self.devices) > 1
        out = []
        for dur, mid, i in gaps[:k]:
            name = self._span_at(mid)
            out.append([f"{name}@{i}" if many else name, dur / 1e9])
        return out


def main(argv: Sequence[str]) -> int:
    """Print the planes, lines and a few events of a trace, and its summary."""
    planes = load_xplane(argv[0])
    for p in planes:
        print(f"plane {p.name!r}")
        for name, evs in p.lines.items():
            print(f"  line {name!r}: {len(evs)} events")
            for ev in evs[:5]:
                print(f"    {ev.name!r} start_ns={ev.start_ns} dur_ns={ev.dur_ns} {dict(ev.stats)}")
    s = TraceSummary(planes)
    print(f"units {s.units} window_s {s.window_s} busy_s {s.busy_s} idle % {s.idle_share()}")
    for name, secs in s.top_ops():
        print(f"  op {name}: {secs}")
    for name, secs in s.idle_gaps():
        print(f"  gap {name}: {secs}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
