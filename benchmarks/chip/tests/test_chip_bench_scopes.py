"""Device time by scope (scopes.py): the scope of each operation from the
optimised HLO, the three readers that sum it, and idle gaps named by the
program's host spans, on a hand-built trace whose every number is known;
and the scopes of the real programs, compiled at scale 10."""

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import scopes  # noqa: E402
import spec  # noqa: E402
import units  # noqa: E402
from devtrace import Event, Plane, TraceSummary  # noqa: E402

HLO = '''HloModule jit_redistribute_sorted, is_scheduled=true

%fused_computation (param_0: s32[8]) -> s32[8] {
  %param_0 = s32[8]{0} parameter(0)
  ROOT %gather.1 = s32[8]{0} gather(%param_0, %param_0), metadata={op_name="jit(redistribute_sorted)/redistribute/permute/gather"}
}

%region_0 (a: s32[], b: s32[]) -> pred[] {
  %a = s32[] parameter(0)
  %b = s32[] parameter(1)
  ROOT %lt = pred[] compare(%a, %b), direction=LT, metadata={op_name="lt"}
}

ENTRY %main (src: s32[8]) -> s32[9,2] {
  %src = s32[8]{0} parameter(0), metadata={op_name="src"}
  %sort.0 = (s32[8]{0}, s32[8]{0}) sort(%src, %src), dimensions={0}, to_apply=%region_0, metadata={op_name="jit(redistribute_sorted)/redistribute/sort/jit(argsort)/sort" stack_frame_id=3}
  %fusion.1 = s32[8]{0} fusion(%src), kind=kCustom, calls=%fused_computation
  %sort.2 = (s32[8]{0}, s32[8]{0}) sort(%src, %src), dimensions={0}, to_apply=%region_0, metadata={op_name="jit(redistribute_sorted)/redistribute/exchange/sort/jit(argsort)/sort"}
  %sort.8 = (s32[8]{0}, s32[8]{0}) sort(%src, %src), dimensions={0}, to_apply=%region_0, metadata={op_name="jit(redistribute_sorted)/redistribute/exchange/permute/scatter"}
  %fusion.4 = s32[9,2]{0,1} fusion(%src), kind=kCustom, calls=%fused_computation, metadata={op_name="jit(redistribute_sorted)/redistribute/exchange/place/scatter"}
  %slice.3 = s32[8]{0} slice(%src), slice={[0:8]}, metadata={op_name="jit(redistribute_sorted)/redistribute/merge/slice;jit(redistribute_sorted)/redistribute/merge/squeeze"}
  ROOT %copy-start = (s32[9,2]{0,1}, u32[]) copy-start(%fusion.4)
}
'''

# own device time of each operation, in ns, in each of the two units
OPS = [("%sort.0 = (s32[8]{0:T(1024)}, s32[8]{0:T(1024)}) sort(s32[8] %src)", 100),
       ("%fusion.1 = s32[8]{0:T(1024)} fusion(s32[8] %src), kind=kCustom", 40),
       ("%sort.2 = (s32[8]{0:T(1024)}, s32[8]{0:T(1024)}) sort(s32[8] %src)", 30),
       ("%sort.8 = (s32[8]{0:T(1024)}, s32[8]{0:T(1024)}) sort(s32[8] %src)", 20),
       ("%fusion.4 = s32[9,2]{0,1:T(2,128)} fusion(s32[8] %src), kind=kCustom", 200),
       ("%slice.3 = s32[8]{0:T(1024)} slice(s32[8] %src)", 5),
       ("%copy-start = (s32[9,2]{0,1:T(2,128)}, u32[]) copy-start(%fusion.4)", 5)]


def _trace(ops=OPS, module="jit_redistribute_sorted"):
    """Two units of [1000, 2000) and [2000, 3000) ns; in each, the program
    runs from 100 ns in, its operations back to back, after the host span
    gen.redistribute has dispatched it."""
    spans, mods, evs = [], [], []
    for base in (1000, 2000):
        spans += [Event("bench.unit", base, 1000),
                  Event("bench.dispatch", base, 80),
                  Event("gen.redistribute", base + 10, 60, (("seed", 7),)),
                  Event("bench.wait", base + 80, 900)]
        t = base + 100
        mods.append(Event(f"{module}(5)", t, sum(ns for _, ns in ops)))
        for name, ns in ops:
            evs.append(Event(name, t, ns))
            t += ns
    host = Plane("/host:CPU", {"python": spans})
    device = Plane("/device:TPU:0", {"XLA Modules": mods, "XLA Ops": evs})
    return [host, device]


@pytest.fixture
def planes():
    return _trace()


@pytest.fixture
def scoped(planes):
    name, program = scopes.module_scopes(HLO)
    return scopes.ScopedTrace(TraceSummary(planes), {name: program})


def test_module_scopes_read_each_instruction_and_a_fusion_takes_its_root():
    name, program = scopes.module_scopes(HLO)
    assert name == "jit_redistribute_sorted"
    assert program["sort.0"].scope == ("redistribute", "sort")
    assert program["sort.0"].shape == "s32[8]"
    assert program["fusion.1"].scope == ("redistribute", "permute")   # by its root
    assert program["fusion.4"].scope == ("redistribute", "exchange", "place")
    assert program["fusion.4"].shape == "s32[9,2]"
    assert program["slice.3"].scope == ("redistribute", "merge")      # merged names agree
    assert program["copy-start"].scope == ()
    assert program["lt"].scope == ()                                  # not traced under a jit


def test_scope_path_keeps_the_vocabulary_and_drops_the_primitive():
    assert scopes.scope_path("jit(f)/csr/search/jit(searchsorted)/while/body/sort") == \
        ("csr", "search")
    assert scopes.scope_path("jit(f)/relabel/shard_map/exchange/collective/all_to_all") == \
        ("relabel", "exchange", "collective")
    # a scatter sorts its indices: the primitive `sort` is not a scope
    assert scopes.scope_path("jit(f)/relabel/permute/sort") == ("relabel", "permute")
    # an argsort outside a kind scope has no kind
    assert scopes.scope_path("jit(f)/relabel/jit(argsort)/sort") == ("relabel",)
    # merged parts that disagree keep what they share
    assert scopes.scope_path("jit(f)/csr/place/x;jit(f)/csr/search/y") == ("csr",)
    assert scopes.scope_path("jit(f)/shuffle/rng/add;while/body/closed_call") == \
        ("shuffle", "rng")
    assert scopes.kind_of(("redistribute", "exchange")) is None
    assert scopes.kind_of(("redistribute", "exchange", "sort")) == "sort"


def test_operations_take_their_scope_and_sum_per_unit(scoped):
    per_unit = scoped.scope_s_per_unit
    assert per_unit(lambda p: scopes.kind_of(p) == "sort") == pytest.approx(130e-9)
    assert per_unit(lambda p: scopes.kind_of(p) == "permute") == pytest.approx(60e-9)
    assert per_unit(lambda p: scopes.EXCHANGE in p) == pytest.approx(250e-9)
    assert per_unit(lambda p: scopes.kind_of(p) == "lookup") is None
    assert scoped.unmatched == []


def test_top_ops_carry_the_scope_path(scoped):
    top = dict(scoped.top_ops())
    assert top["jit_redistribute_sorted/redistribute/exchange/place/fusion.4 s32[9,2]"] == \
        pytest.approx(200e-9)
    assert top["jit_redistribute_sorted/redistribute/permute/fusion.1 s32[8]"] == \
        pytest.approx(40e-9)
    assert top["jit_redistribute_sorted/copy-start (s32[9,2], u32[])"] == pytest.approx(5e-9)


def test_kind_shares_count_the_operations_with_a_kind(scoped):
    share, seconds = scoped.kind_shares()["jit_redistribute_sorted"]
    assert seconds == pytest.approx(400e-9)
    assert share == pytest.approx(100.0 * 395 / 400)


def test_a_module_whose_operations_are_not_its_programs_is_left_unscoped():
    ops = OPS[:-1] + [("%fusion.99 = s32[8]{0} fusion(s32[8] %src)", 5)]
    name, program = scopes.module_scopes(HLO)
    scoped = scopes.ScopedTrace(TraceSummary(_trace(ops)), {name: program})
    assert scoped.unmatched == ["jit_redistribute_sorted"]
    assert scoped.scope_s_per_unit(lambda p: True) is None
    # a shape unlike the program's is caught too
    ops = [("%fusion.4 = s32[9,3]{0,1} fusion(s32[8] %src)", 200)]
    scoped = scopes.ScopedTrace(TraceSummary(_trace(ops)), {name: program})
    assert scoped.unmatched == ["jit_redistribute_sorted"]


def _Reading(trace):
    return SimpleNamespace(trace=trace, graph=None, peak=None, id_bytes=4)


@pytest.mark.parametrize("metric,want_ns", [
    ("exchange.device_ms", 250), ("sort.device_ms", 130), ("permute.device_ms", 60)])
def test_the_readers_give_milliseconds_per_unit(monkeypatch, planes, metric, want_ns):
    name, program = scopes.module_scopes(HLO)
    monkeypatch.setattr(scopes, "program_scopes", lambda reading: {name: program})
    reading = _Reading(TraceSummary(planes))
    assert spec.load_reader(metric).read(reading) == pytest.approx(want_ns * 1e-6)


@pytest.mark.parametrize("metric", ["exchange.device_ms", "sort.device_ms", "permute.device_ms"])
def test_the_readers_read_nothing_without_scopes(monkeypatch, planes, metric):
    reader = spec.load_reader(metric)
    # a program that opens no scopes, as before the scopes were added
    bare = scopes.re.sub(r"(redistribute|exchange|sort|permute|place|merge)/", "", HLO)
    name, program = scopes.module_scopes(bare)
    monkeypatch.setattr(scopes, "program_scopes", lambda reading: {name: program})
    assert reader.read(_Reading(TraceSummary(planes))) is None
    # a checkout that cannot compile its phases alone
    monkeypatch.setattr(scopes, "program_scopes", lambda reading: None)
    assert reader.read(_Reading(TraceSummary(planes))) is None
    # a trace with no device
    assert reader.read(_Reading(TraceSummary(planes[:1]))) is None


def test_an_idle_gap_in_a_dispatch_is_named_by_the_program_span(planes):
    summary = TraceSummary(planes)
    # unit 1: idle 1000-1100, midpoint 1050 inside gen.redistribute [1010, 1070)
    gaps = scopes.idle_gaps(summary, planes)
    assert ["gen.redistribute", pytest.approx(100e-9)] in gaps
    # the benchmark's own naming is left as it is
    assert "gen.redistribute" not in [name for name, _ in summary.idle_gaps()]


@pytest.fixture(scope="module")
def tiny_programs():
    """Scopes of the real programs of the gen cell, compiled at scale 10 on
    the CPU for two seeds."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    config = dict(spec.load_json(HERE / "configs" / "graph500-22.json"), scale=10)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("shards",))
    out = []
    for seed in (scopes.STAND_IN_SEED, units.derive_seed(2**31 + 977, "graph")):
        texts = scopes.program_texts(units.graph_config(config, seed), mesh, "paper")
        out.append(dict(scopes.module_scopes(t) for t in texts))
    return out


def test_the_real_programs_carry_the_scopes(tiny_programs):
    programs = tiny_programs[0]
    assert set(programs) == {"jit_distributed_shuffle", "jit_generate_edges",
                             "jit_relabel_ring", "jit_redistribute_sorted",
                             "jit_build_csr_sorted"}
    paths = {mod: {ins.scope for ins in prog.values()} for mod, prog in programs.items()}
    assert ("relabel", "sort") in paths["jit_relabel_ring"]
    assert ("relabel", "lookup") in paths["jit_relabel_ring"]
    assert ("redistribute", "exchange", "place") in paths["jit_redistribute_sorted"]
    assert ("csr", "search") in paths["jit_build_csr_sorted"]
    assert ("edges", "rng") in paths["jit_generate_edges"]


def test_a_stand_in_seed_gives_the_same_instructions(tiny_programs):
    """The seed is a constant of the shuffle and edge programs: another
    seed compiles to the same instructions, scopes and shapes."""
    stand_in, real = tiny_programs
    assert stand_in == real


def _traffic(workload, seed=2**31 + 977, **config):
    import jax
    import numpy as np
    from jax.sharding import Mesh

    cell = spec.resolve(spec.load_benchmark(), workload)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("shards",))
    return units.make(dict(cell.config, **config), cell.traffic, seed, mesh)


def test_program_scopes_finds_the_configuration_of_the_graph(monkeypatch):
    """The scope map of a gen cell compiles its own configuration's
    phases, for the stand-in seed, whatever the run's seed."""
    seen = {}

    def fake_texts(cfg, mesh, variant):
        seen.update(cfg=cfg, variant=variant)
        return [HLO]

    monkeypatch.setattr(scopes, "program_texts", fake_texts)
    traffic = _traffic("g500-22.gen-paper")
    got = scopes.program_scopes(SimpleNamespace(programs=traffic.program_texts))
    assert set(got) == {"jit_redistribute_sorted"}
    assert seen["cfg"].seed == scopes.STAND_IN_SEED and seen["cfg"].scale == 22
    assert seen["cfg"].capacity_factor == 1.0 and seen["variant"] == "paper"
    # a checkout that cannot compile its phases alone
    assert scopes.program_scopes(SimpleNamespace(programs=lambda: None)) is None


def test_the_gen_cells_scope_map_is_its_phase_programs(tiny_programs):
    """Asked through its traffic, the gen cell's map is the one built from
    its configuration's phase programs at the stand-in seed."""
    traffic = _traffic("g500-22.gen-paper", scale=10)
    assert scopes.program_scopes(SimpleNamespace(programs=traffic.program_texts)) == \
        tiny_programs[0]


CACHE_TRAP = """
import sys
from contextlib import nullcontext
sys.path.insert(0, sys.argv[1])
import numpy as np, jax
jax.config.update("jax_compilation_cache_dir", sys.argv[2])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
from jax.sharding import Mesh
import scopes, spec, units
from repro.core import pipeline

config = dict(spec.load_json(spec.HERE / "configs" / "graph500-22.json"), scale=8)
cfg = units.graph_config(config, scopes.STAND_IN_SEED)
mesh = Mesh(np.asarray(jax.devices()[:1]), ("shards",))
if sys.argv[3] == "fill":
    jax.named_scope = lambda name: nullcontext()
    pipeline.compile_phases(cfg, mesh)
else:
    programs = dict(scopes.module_scopes(t) for t in scopes.program_texts(cfg, mesh, "paper"))
    print(sorted({ins.scope for ins in programs["jit_relabel_ring"].values()}))
"""


def test_the_scopes_come_from_the_program_even_where_the_cache_holds_another(tmp_path):
    """The persistent cache's key leaves out metadata by default: the same
    programs compiled first without their scopes, by another process, must
    not lend their op_names."""
    import os
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(HERE.parents[1] / "src"))
    out = []
    for step in ("fill", "read"):
        proc = subprocess.run([sys.executable, "-c", CACHE_TRAP, str(HERE), str(tmp_path), step],
                              env=env, capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-3000:]
        out.append(proc.stdout)
    assert any(tmp_path.iterdir())
    assert "('relabel', 'sort')" in out[1].strip().splitlines()[-1]
