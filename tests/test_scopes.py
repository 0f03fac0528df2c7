"""Every operation of the phase programs lies under a scope of the device
path's vocabulary (core/trace.py): one phase scope and exactly one kind
scope, as its op_name in the optimised HLO shows.

The programs are compiled at scale 10 for nb=1 and for nb=4 on virtual CPU
devices, in a subprocess (the pytest process keeps one device), for every
variant generate() can run.  What is checked: every instruction of the
entry computation, of the fusion bodies and of the loop bodies it reaches;
not the regions of a sort, reduce or scatter (`to_apply`).  Skipped as
bookkeeping: parameter, constant, tuple, get-tuple-element, bitcast and
copies; a broadcast or fusion of constants alone (a constant in another
shape); a loop's own control (the `while`, its condition and its counter).
Only an op_name that starts at a jitted function (`jit(...)/...`) was traced
under the program's scopes.  An instruction without one, made by the
compiler or by a lowering rule (cumsum's `reduce_window_sum`), takes the
scope of the fusion it lies in, and is skipped outside any fusion.
"""

import json
import os
import re
import subprocess
import sys

import pytest

from repro.core.trace import EXCHANGE_SCOPE, KIND_SCOPES, PHASE_SCOPES
from repro.launch.hlo_cost import parse_module

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

VARIANTS = {
    "paper": ({}, "paper"),
    "recompute": ({}, "recompute"),
    "argsort": ({}, "argsort"),
    "alltoall-scatter": ({"relabel_variant": "alltoall", "csr_variant": "scatter",
                          "capacity_factor": 4.0}, "paper"),
}
PROGRAMS = {
    "paper": ("distributed_shuffle", "generate_edges", "relabel_ring",
              "redistribute_sorted", "build_csr_sorted"),
    "recompute": ("shuffle_recompute", "relabel_recompute"),
    "argsort": ("shuffle_argsort",),
    "alltoall-scatter": ("relabel_alltoall", "redistribute", "build_csr_scatter"),
}

BOOKKEEPING = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast", "copy",
               "copy-start", "copy-done"}
OP_NAME = re.compile(r'op_name="([^"]*)"')
CALLS = re.compile(r"\b(calls|body|condition)=%([\w.-]+)")
LOOP_CONTROL = re.compile(r"/while/(cond|body)/[^/]+$")

COMPILE = """
import json, sys
import numpy as np, jax
from jax.sharding import Mesh
from repro.core.pipeline import compile_phases
from repro.core.types import GraphConfig

nb, variants = int(sys.argv[1]), json.loads(sys.argv[2])
mesh = Mesh(np.asarray(jax.devices()[:nb]), ("shards",))
texts = {}
for kw, shuffle in variants.values():
    cfg = GraphConfig(scale=10, nb=nb, **kw)
    for name, c in compile_phases(cfg, mesh, shuffle_variant=shuffle).items():
        texts[name] = c.as_text()
json.dump(texts, open(sys.argv[3], "w"))
"""


@pytest.fixture(scope="module", params=[1, 4], ids=["nb1", "nb4"])
def hlo(request, tmp_path_factory):
    out = tmp_path_factory.mktemp(f"hlo{request.param}") / "hlo.json"
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH="src", JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", COMPILE, str(request.param),
                        json.dumps(VARIANTS), str(out)],
                       env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    with open(out) as f:
        return json.load(f)


def scopes(op_name: str):
    """(phases, kinds) of each part of an op_name; XLA joins the names of
    merged instructions with ';'.  The last component is the primitive."""
    parts = []
    for part in op_name.split(";"):
        path = part.split("/")[:-1]
        parts.append(([p for p in path if p in PHASE_SCOPES],
                      [p for p in path if p in KIND_SCOPES]))
    return parts


def verdict(op_name: str) -> str:
    """'ok' where the parts that carry scopes name one phase and one kind,
    and agree on the kind."""
    kinds = set()
    for phases, ks in scopes(op_name):
        if len(phases) > 1 or len(ks) > 1:
            return "nested"
        if phases and ks:
            kinds.add(ks[0])
    if not kinds:
        return "unscoped"
    return "ok" if len(kinds) == 1 else "ambiguous"


def _is_constant_splat(ins, comp) -> bool:
    return ins.op in ("broadcast", "fusion") and all(
        o in comp.instrs and comp.instrs[o].op == "constant" for o in ins.operands)


def instructions(text: str, skip):
    """(computation, instruction, op_name) of every instruction reached from
    the entry computation, each with its own traced op_name or, lacking one,
    that of the fusion or loop it lies in (None outside any).  An
    instruction for which skip(instruction, computation) holds is passed
    over, with what it calls."""
    comps, entry = parse_module(text)
    seen = set()

    def visit(cname, inherited):
        if cname in seen:
            return
        seen.add(cname)
        comp = comps[cname]
        for ins in comp.instrs.values():
            if skip(ins, comp):
                continue
            m = OP_NAME.search(ins.attrs)
            traced = m is not None and m.group(1).startswith("jit(")
            name = m.group(1) if traced else inherited
            for _, callee in CALLS.findall(ins.attrs):
                yield from visit(callee, name)
            yield comp, ins, name

    yield from visit(entry, None)


def uncovered(text: str):
    bad = []
    skip = lambda ins, comp: ins.op in BOOKKEEPING or _is_constant_splat(ins, comp)  # noqa: E731
    for comp, ins, name in instructions(text, skip):
        if name is None:
            continue  # made by the compiler or a lowering rule, outside any fusion
        if ins.op == "while" or LOOP_CONTROL.search(name.split(";")[0]):
            continue
        v = verdict(name)
        if v != "ok":
            bad.append((comp.name, ins.name, ins.op, f"{v}: {name}"))
    return bad


@pytest.mark.parametrize("variant,program", [
    (v, p) for v, progs in PROGRAMS.items() for p in progs])
def test_every_operation_has_a_phase_and_one_kind(hlo, variant, program):
    assert program in hlo, sorted(hlo)
    assert uncovered(hlo[program]) == []


def test_the_exchange_lies_under_the_phase_that_calls_it(hlo):
    """capacity_all_to_all opens `exchange` inside redistribute (and, with
    the all_to_all relabel, inside relabel); no other program has one."""
    vocab = set(PHASE_SCOPES) | {EXCHANGE_SCOPE}
    for program, text in hlo.items():
        paths = {tuple(p for p in part.split("/")[:-1] if p in vocab)
                 for name in OP_NAME.findall(text) for part in name.split(";")
                 if part.startswith("jit(")}
        inside = {path for path in paths if EXCHANGE_SCOPE in path}
        if program in ("redistribute_sorted", "redistribute", "relabel_alltoall"):
            phase = "relabel" if program.startswith("relabel") else "redistribute"
            assert inside == {(phase, EXCHANGE_SCOPE)}, (program, inside)
        else:
            assert not inside, (program, inside)


def exchange_ops(text: str):
    """{(op, kind)} of every instruction under `exchange`, fused ones
    included, with the kind scope its op_name (or its fusion's) names."""
    found = set()
    for _, ins, name in instructions(text, lambda ins, comp: ins.op in BOOKKEEPING):
        for part in (name or "").split(";"):
            path = part.split("/")[:-1]
            if EXCHANGE_SCOPE in path:
                found.add((ins.op, next((p for p in path if p in KIND_SCOPES), None)))
    return found


def test_only_the_sorted_redistribute_buckets_by_runs(hlo):
    """redistribute_sorted's records arrive sorted by owner, so its exchange
    slices runs: no sort, no permute and no scatter.  The unsorted
    redistribute and the all_to_all relabel keep the general bucketing."""
    ops = exchange_ops(hlo["redistribute_sorted"])
    assert ops and {kind for _, kind in ops} <= {"search", "place", "collective"}, ops
    assert "scatter" not in {op for op, _ in ops}, ops
    for program in ("redistribute", "relabel_alltoall"):
        ops = exchange_ops(hlo[program])
        kinds = {kind for _, kind in ops}
        assert {"sort", "permute"} <= kinds, (program, kinds)
        assert "scatter" in {op for op, _ in ops}, program


def kinds_of(text: str):
    """{kind} of every instruction of a program, fused ones included."""
    return {k for _, ins, name in instructions(text, lambda ins, comp: ins.op in BOOKKEEPING)
            for part in (name or "").split(";") for k in part.split("/")[:-1]
            if k in KIND_SCOPES}


def test_the_paper_programs_cross_chips_under_collective_and_merge(hlo, request):
    """What the four-chip cell's collective and merge readers sum: at nb = 4
    the shuffle rounds, the ring relabel and the redistribute each run a
    collective (the exchange's drop psum and peak-load pmax among them), and
    the redistribute merges the runs it received; at nb = 1 only the merge's
    bookkeeping of its one run is left."""
    merged = "merge" in kinds_of(hlo["redistribute_sorted"])
    crossing = {p for p in PROGRAMS["paper"] if "collective" in kinds_of(hlo[p])}
    if request.node.callspec.params["hlo"] == 4:
        assert merged and crossing == {"distributed_shuffle", "relabel_ring",
                                       "redistribute_sorted"}, crossing
        assert re.search(r"all-reduce\(.*op_name=\"[^\"]*/exchange/collective/pmax",
                         hlo["redistribute_sorted"]), "the peak load's pmax"
    else:
        assert merged


def test_the_vocabulary_is_disjoint():
    vocab = list(PHASE_SCOPES) + [EXCHANGE_SCOPE] + list(KIND_SCOPES)
    assert len(set(vocab)) == len(vocab)


def test_an_unscoped_or_doubly_scoped_name_is_caught():
    assert verdict("jit(f)/relabel/sort/jit(argsort)/sort") == "ok"
    assert verdict("jit(f)/relabel/jit(argsort)/sort") == "unscoped"
    assert verdict("jit(f)/relabel/lookup/collective/ppermute") == "nested"
    assert verdict("jit(f)/csr/place/x;jit(f)/csr/search/y") == "ambiguous"
    # a merged name whose other part is a bare loop wrapper takes the scoped part
    assert verdict("jit(f)/shuffle/rng/add;while/body/closed_call") == "ok"
