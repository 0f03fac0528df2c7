"""Device time of one kind of operation that nothing else on the same chip
covers: the union of that kind's intervals less the union of every other
operation's, per chip, averaged over the chips.

An operation's intervals are the stretches of its event inside the traced
window that no event nested in it covers (a loop's event holds its body's),
so on one line of operations the innermost running event is what the chip
runs.  The kind's own intervals come from the `XLA Ops` line, as its own
time does (scopes.py), so the result is at most that time.  What hides it is
another operation on that line or one on the `Async XLA Ops` line, where
the chip shows the work that runs beside its main stream; on a chip that
shows none, the result equals the kind's own time.  Each operation's scope
is scopes.py's, from the programs the window ran; an asynchronous event
that its program does not have is left out.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Tuple

import devtrace
import scopes

Interval = Tuple[float, float]
ASYNC_LINE = "Async XLA Ops"


def own_intervals(events: List[devtrace.Event], lo: float,
                  hi: float) -> List[Tuple[devtrace.Event, List[Interval]]]:
    """Each event with its stretches inside [lo, hi] that no event nested
    in it covers."""
    out: List[list] = []
    stack: List[list] = []      # [event, intervals, time it last resumed]

    def run_until(item, t):
        a, b = max(item[2], lo), min(t, hi)
        if b > a:
            item[1].append((a, b))

    def pop():
        item = stack.pop()
        run_until(item, item[0].end_ns)
        if stack:
            stack[-1][2] = item[0].end_ns

    for ev in sorted(events, key=lambda e: (e.start_ns, -e.dur_ns)):
        while stack and stack[-1][0].end_ns <= ev.start_ns:
            pop()
        if stack:
            run_until(stack[-1], ev.start_ns)
        item = [ev, [], ev.start_ns]
        stack.append(item)
        out.append(item)
    while stack:
        pop()
    return [(ev, ivs) for ev, ivs, _ in out]


def _length(intervals: List[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def _common(a: List[Interval], b: List[Interval]) -> float:
    """Length of the overlap of two sorted, disjoint interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _scoped(dev: devtrace.Plane, line: str, lo: float, hi: float):
    """(module, event, own intervals) of the events of one line of a device."""
    modules = sorted(dev.lines.get(devtrace.MODULES_LINE, []), key=lambda e: e.start_ns)
    starts = [e.start_ns for e in modules]
    return [(scopes._module_at(ev, modules, starts), ev, ivs)
            for ev, ivs in own_intervals(dev.lines.get(line, []), lo, hi) if ivs]


def exposed_s_per_unit(reading, match: Callable[[scopes.Path], bool]) -> Optional[float]:
    """Seconds per unit of work during which an operation whose scope path
    `match`es runs and no other does, averaged over the devices; None where
    the trace has no device operations, the programs no scopes, or no
    operation matched."""
    trace = reading.trace
    if not trace.devices or not trace.units:
        return None
    programs = scopes.program_scopes(reading)
    if not programs:
        return None
    main = [_scoped(dev, devtrace.OPS_LINE, trace.lo, trace.hi) for dev in trace.devices]
    side = [_scoped(dev, ASYNC_LINE, trace.lo, trace.hi) for dev in trace.devices]
    # as ScopedTrace does: a module with an operation unlike its program is unscoped
    unmatched = {mod for ops in main for mod, ev, _ in ops
                 if mod in programs and not scopes._matches(programs[mod], ev.name)}

    def scope(mod, ev) -> Optional[scopes.Path]:
        if mod not in programs or mod in unmatched or not scopes._matches(programs[mod], ev.name):
            return None
        return programs[mod][scopes._instruction(ev.name)].scope

    total, found = 0.0, False
    for ops, beside in zip(main, side):
        kind: List[Interval] = []
        rest: List[Interval] = []
        for mod, ev, ivs in ops:
            path = scope(mod, ev)
            hit = bool(path) and match(path)
            (kind if hit else rest).extend(ivs)
            found = found or hit
        for mod, ev, ivs in beside:
            path = scope(mod, ev)
            if path is not None and not (path and match(path)):
                rest.extend(ivs)
        kind, rest = devtrace._union(kind), devtrace._union(rest)
        total += _length(kind) - _common(kind, rest)
    if not found:
        return None
    return total / len(trace.devices) / trace.units / 1e9
