"""Device time by scope: the `jax.named_scope`s of the phase and walk
programs, read through the optimised HLO of the programs that a traced
window ran.

The program opens a phase scope around each phase program (`shuffle`,
`edges`, `relabel`, `redistribute`, `csr`), `exchange` inside
capacity_all_to_all and return_all_to_all, and one kind scope around each
operation of substance (`sort`, `permute`, `lookup`, `place`, `search`,
`collective`, `merge`, `rng`).  The scopes reach each HLO instruction's
op_name.  A TPU trace names each device operation by its HLO instruction
and carries no op_name, so an operation's scope is looked up by (module,
instruction) in the HLO text of its program; a fusion takes its own op_name
or, lacking one, its root's.

The texts come from compiling every program of a unit again after the
window, as the cell's traffic kind names them (`program_texts`: the
phases of `pipeline.compile_phases` for `gen`, `distributed_walks` for
`walks`), through the persistent cache, keyed on the metadata too, in
`--trace 1` runs only, so set-up is untouched.  The graph's and the walk's
seeds are constants of their programs and are not in the trace: the
programs are compiled for a stand-in seed, and every operation of the
window is checked against its program by name and result shape.  A module
with an operation that its program lacks is left unscoped.  A program that
opens no scopes (or a checkout without `compile_phases`) gives no scoped
operation, and the readers then give nothing.

    python benchmarks/chip/scopes.py <file.xplane.pb> --workload <cell> --seed <n>

prints, for looking by hand, the device seconds per scope path, the share
of each program's operation time that has a kind scope, the top operations
labelled `<module>/<scope path>/<op> <shape>`, and the idle gaps named by
the benchmark's and the program's (`gen.`) host spans.
"""

from __future__ import annotations

import argparse
import bisect
import contextlib
import copy
import dataclasses
import os
import re
import sys
from typing import Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import devtrace

PHASES = ("shuffle", "edges", "relabel", "redistribute", "csr")
EXCHANGE = "exchange"
KINDS = ("sort", "permute", "lookup", "place", "search", "collective", "merge", "rng")
VOCABULARY = frozenset(PHASES + (EXCHANGE,) + KINDS)
GAP_SPANS = (devtrace.SPAN_PREFIX, "gen.")
STAND_IN_SEED = 0x5EED

Path = Tuple[str, ...]

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%([\w.-]+)")
_MODULE = re.compile(r"^HloModule ([\w.-]+)", re.M)
_SHAPE = re.compile(r"\w+\[[\d,]*\]")


def scope_path(op_name: str) -> Path:
    """The scopes of the vocabulary in an op_name, outermost first
    (`jit(relabel_ring)/relabel/sort/jit(argsort)/sort` -> relabel, sort).
    The last component is the primitive, not a scope.  XLA joins the
    op_names of merged instructions with ';': of the parts traced under a
    jitted function, those with scopes give their common prefix."""
    paths = []
    for part in op_name.split(";"):
        if part.startswith("jit("):
            path = tuple(p for p in part.split("/")[:-1] if p in VOCABULARY)
            if path:
                paths.append(path)
    return tuple(os.path.commonprefix(paths)) if paths else ()


def kind_of(path: Path) -> Optional[str]:
    return path[-1] if path and path[-1] in KINDS else None


@dataclasses.dataclass(frozen=True)
class Instruction:
    scope: Path
    shape: str    # the first array shape of the result, without layout


def module_scopes(hlo_text: str) -> Tuple[str, Dict[str, Instruction]]:
    """(module name, {instruction name: Instruction}) of one optimised HLO
    module, every computation included."""
    from repro.launch.hlo_cost import parse_module

    comps, _ = parse_module(hlo_text)

    def scope(ins) -> Path:
        m = _OP_NAME.search(ins.attrs)
        path = scope_path(m.group(1)) if m else ()
        if not path and ins.op == "fusion":
            body = comps[_CALLS.search(ins.attrs).group(1)]
            return scope(body.instrs[body.root])
        return path

    out = {}
    for comp in comps.values():
        for ins in comp.instrs.values():
            shape = _SHAPE.search(ins.type_str)
            out[ins.name] = Instruction(scope(ins), shape.group(0) if shape else "")
    return _MODULE.search(hlo_text).group(1), out


@dataclasses.dataclass(frozen=True)
class Op:
    module: Optional[str]
    scope: Path
    label: str      # `<op> <shape>`, as devtrace.op_of gives it
    ns: float       # own time in the window, summed over the devices


class ScopedTrace:
    """The device operations of one traced window, each with its scope."""

    def __init__(self, summary: devtrace.TraceSummary,
                 scopes: Dict[str, Dict[str, Instruction]]):
        self.summary = summary
        seen = []
        for dev in summary.devices:
            modules = sorted(dev.lines.get(devtrace.MODULES_LINE, []), key=lambda e: e.start_ns)
            starts = [e.start_ns for e in modules]
            ops = dev.lines.get(devtrace.OPS_LINE, [])
            for ev, ns in devtrace._self_times(ops, summary.lo, summary.hi):
                if ns > 0:
                    seen.append((_module_at(ev, modules, starts), ev.name, ns))
        unmatched = {mod for mod, name, _ in seen if mod in scopes
                     and not _matches(scopes[mod], name)}
        self.ops: List[Op] = []
        for mod, name, ns in seen:
            ins = None
            if mod in scopes and mod not in unmatched:
                ins = scopes[mod].get(_instruction(name))
            self.ops.append(Op(mod, ins.scope if ins else (), devtrace.op_of(name), ns))
        self.unmatched = sorted(unmatched)

    def _per_unit(self, ns: float) -> float:
        return ns / len(self.summary.devices) / self.summary.units / 1e9

    def scope_s_per_unit(self, match: Callable[[Path], bool]) -> Optional[float]:
        """Device seconds per unit of work of the operations whose scope
        path `match`es, averaged over the devices; None where none did."""
        hits = [op.ns for op in self.ops if op.scope and match(op.scope)]
        if not hits or not self.summary.units:
            return None
        return self._per_unit(sum(hits))

    def top_ops(self, k: int = 10) -> List[List[object]]:
        """The k operations with the most own time, labelled
        `<module>/<scope path>/<op> <shape>`."""
        per: Dict[str, float] = {}
        for op in self.ops:
            label = "/".join([op.module or "?", *op.scope, op.label])
            per[label] = per.get(label, 0.0) + op.ns
        top = sorted(per.items(), key=lambda kv: -kv[1])[:k]
        return [[name, self._per_unit(ns)] for name, ns in top]

    def kind_shares(self) -> Dict[str, Tuple[float, float]]:
        """{module: (% of its operations' own time that has a kind scope,
        those operations' seconds per unit)}."""
        total: Dict[str, float] = {}
        kinded: Dict[str, float] = {}
        for op in self.ops:
            mod = op.module or "?"
            total[mod] = total.get(mod, 0.0) + op.ns
            if kind_of(op.scope):
                kinded[mod] = kinded.get(mod, 0.0) + op.ns
        return {mod: (100.0 * kinded.get(mod, 0.0) / ns, self._per_unit(ns))
                for mod, ns in total.items() if ns > 0}

    def by_scope(self) -> List[Tuple[str, float]]:
        """Seconds per unit for each scope path, the unscoped as ''."""
        per: Dict[Path, float] = {}
        for op in self.ops:
            per[op.scope] = per.get(op.scope, 0.0) + op.ns
        return sorted((("/".join(p), self._per_unit(ns)) for p, ns in per.items()),
                      key=lambda kv: -kv[1])


def _module_at(ev, modules, starts) -> Optional[str]:
    mod = dict(ev.stats).get("hlo_module")
    if mod is None:
        i = bisect.bisect_right(starts, ev.start_ns) - 1
        if i >= 0 and modules[i].end_ns >= ev.start_ns:
            mod = devtrace.module_of(modules[i].name)
    return mod


def _instruction(event_name: str) -> str:
    """`%fusion.4 = s32[...] fusion(...)` -> `fusion.4`."""
    return event_name.partition(" = ")[0].strip().lstrip("%")


def _matches(program: Dict[str, Instruction], event_name: str) -> bool:
    ins = program.get(_instruction(event_name))
    if ins is None:
        return False
    shape = _SHAPE.search(event_name.partition(" = ")[2])
    return ins.shape == (shape.group(0) if shape else "")


# ---------------------------------------------------------------------------
# the programs of a run, and what the metric readers read
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def metadata_in_key() -> Iterator[None]:
    """Compiles keyed on the metadata too.  The persistent cache's key
    leaves out metadata by default, so a program compiled first by another
    checkout of the same code less its scopes (the parent of a change, say)
    would come back with that checkout's op_names."""
    import jax

    flag = "jax_compilation_cache_include_metadata_in_key"
    previous = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        yield
    finally:
        jax.config.update(flag, previous)


def program_texts(cfg, mesh, shuffle_variant: str) -> Optional[List[str]]:
    """The optimised HLO of every program generate() runs; None where the
    program cannot compile its phases alone."""
    from repro.core import pipeline

    compile_phases = getattr(pipeline, "compile_phases", None)
    if compile_phases is None:
        return None
    with metadata_in_key():
        compiled = compile_phases(cfg, mesh, shuffle_variant=shuffle_variant)
        return [c.as_text() for c in compiled.values()]


def program_scopes(reading) -> Optional[Dict[str, Dict[str, Instruction]]]:
    """Scopes of the programs the reading's window ran, as its traffic
    compiles them for a stand-in seed (`programs`); None where it cannot."""
    texts = reading.programs()
    return dict(module_scopes(t) for t in texts) if texts else None


def for_reading(reading) -> Optional[ScopedTrace]:
    """The scoped operations of a reading's trace, built once and kept on
    the reading for all the readers of one run; None where the trace has no
    device operations or the programs no scopes."""
    if "scoped" not in vars(reading):
        trace, scoped = reading.trace, None
        if trace.devices and trace.units:
            scopes = program_scopes(reading)
            if scopes:
                scoped = ScopedTrace(trace, scopes)
        reading.scoped = scoped
    return reading.scoped


def ms_per_unit(reading, match: Callable[[Path], bool]) -> Optional[float]:
    scoped = for_reading(reading)
    seconds = None if scoped is None else scoped.scope_s_per_unit(match)
    return None if seconds is None else 1e3 * seconds


# ---------------------------------------------------------------------------
# idle gaps named by the program's spans too
# ---------------------------------------------------------------------------


def gap_spans(planes: Sequence[devtrace.Plane]) -> List[devtrace.Event]:
    return [ev for p in planes if p.name.startswith("/host:")
            for evs in p.lines.values() for ev in evs if ev.name.startswith(GAP_SPANS)]


def idle_gaps(summary: devtrace.TraceSummary, planes: Sequence[devtrace.Plane],
              k: int = 10) -> List[List[object]]:
    """`summary.idle_gaps`, each gap named by the innermost benchmark or
    program (`gen.`) span open over its midpoint."""
    named = copy.copy(summary)
    named.spans = gap_spans(planes)
    return named.idle_gaps(k)


def main(argv: Sequence[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="an .xplane.pb file of a --trace 1 run (--keep-trace)")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True, help="the run's --seed")
    args = ap.parse_args(argv)

    import numpy as np
    import jax
    from jax.sharding import Mesh

    import bench
    import spec
    import units

    bench.cache_every_program()
    cell = spec.resolve(spec.load_benchmark(), args.workload)
    nb = int(cell.config["nb"])
    mesh = Mesh(np.asarray(jax.devices()[:nb]), ("shards",))
    traffic = units.make(cell.config, cell.traffic, args.seed, mesh)
    texts = traffic.program_texts() or []
    planes = devtrace.load_xplane(args.trace)
    summary = devtrace.TraceSummary(planes)
    scoped = ScopedTrace(summary, dict(module_scopes(t) for t in texts))
    print(f"units {summary.units} window_s {summary.window_s} busy_s {summary.busy_s}")
    if scoped.unmatched:
        print(f"unscoped, operations unlike their program: {scoped.unmatched}")
    for path, secs in scoped.by_scope():
        print(f"  scope {path or '(none)'}: {secs!r} s per unit")
    for mod, (share, secs) in sorted(scoped.kind_shares().items()):
        print(f"  kind share {mod}: {share:.3f} % of {secs!r} s per unit")
    for name, secs in scoped.top_ops():
        print(f"  op {name}: {secs!r}")
    for name, secs in idle_gaps(summary, planes):
        print(f"  gap {name}: {secs!r}")
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..", "src"))
    sys.exit(main(sys.argv[1:]))
