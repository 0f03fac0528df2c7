"""The walk cell at a small size on the CPU: the plain reference walks as
the program does, entry for entry; a run is correct; its controls and each
fault it can have make `correct` false; its scope map and readers.

One chip has no exchange between chips, so that fault does not apply to
the walk cell."""

import dataclasses
import json
import os
import subprocess
import sys
import textwrap
from functools import partial
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

HERE = Path(__file__).resolve().parents[1]
REPO = HERE.parents[1]
sys.path.insert(0, str(HERE))

import bench  # noqa: E402
import controls  # noqa: E402
import peaks  # noqa: E402
import reference_walks as ref_walks  # noqa: E402
import scopes  # noqa: E402
import spec  # noqa: E402
import units  # noqa: E402
from devtrace import Event, Plane, TraceSummary  # noqa: E402

from repro.data import walks as walks_mod  # noqa: E402

WALKS = "n2v-22.walks"
SEED = 2**31 + 5151
SMALL = dict(scale=10)
WALKERS = 4096
# graphs of many sinks: one edge a vertex, or most edges in one quadrant
SINKY = {"ef1": dict(edge_factor=1), "skewed": dict(a=0.85, b=0.05, c=0.05, d=0.05)}


def _cell(**config):
    cell = spec.resolve(spec.load_benchmark(), WALKS)
    return dataclasses.replace(cell, config=dict(cell.config, **SMALL, **config),
                               traffic=dict(cell.traffic, walkers=WALKERS))


def _run(cell, seed=SEED):
    return bench.run(cell, seed, 0.3, False, require_tpu=False)


def _compared(result):
    return {k: c["value"] for k, c in result["checks"].items()
            if k not in ("compiles_in_window", "units_unlike_checked")}


# --- the reference is the program's walk ----------------------------------


def _by_walker(hist, valid, wid):
    hist, valid, wid = map(np.asarray, (hist, valid, wid))
    order = np.argsort(wid[valid])
    return wid[valid][order], hist[valid][order]


@pytest.mark.parametrize("seed", [7, SEED, 2**32 + 12345])
@pytest.mark.parametrize("graph", ["graph500", "ef1", "skewed"])
def test_the_reference_walks_as_the_program_does(graph, seed):
    config = dict(SINKY.get(graph, {}))
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("shards",))
    traffic = units.make(dict(_cell().config, **config), _cell().traffic, seed, mesh)
    traffic.setup()
    hist, valid, wid, dropped = traffic.unit()
    assert int(dropped) == 0
    offsets, adj = ref_walks.csr(traffic.spec, jnp.uint32(traffic.graph_seed))
    want = np.asarray(ref_walks.walks(traffic.walk, offsets, adj, jnp.uint32(traffic.walk_seed)))
    ids, got = _by_walker(hist, valid, wid)
    np.testing.assert_array_equal(ids, np.arange(WALKERS))
    np.testing.assert_array_equal(got, want)
    degrees = np.diff(np.asarray(offsets))
    sinks_hit = np.mean(degrees[want[:, :-1]] == 0)
    if graph != "graph500":
        assert sinks_hit > 0.02, sinks_hit   # thousands of hops take the sink rule
    assert traffic.compare(traffic.keep((hist, valid, wid, dropped))) == \
        {"walk_mismatch": 0, "walkers_missing": 0}


@pytest.mark.parametrize("module", ["reference.py", "reference_walks.py"])
def test_the_references_import_nothing_of_the_program(module):
    import ast

    tree = ast.parse((HERE / module).read_text())
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names]
    names += [n.module or "" for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)]
    assert names and not [m for m in names if m.split(".")[0] == "repro"], names


FOUR_SHARDS = textwrap.dedent("""
    import dataclasses, json, sys
    sys.path.insert(0, sys.argv[1])
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import Mesh
    import reference_walks as ref_walks, spec, units
    cell = spec.resolve(spec.load_benchmark(), "n2v-22.walks")
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("shards",))
    out = []
    for name, extra in json.loads(sys.argv[2]):
        config = dict(cell.config, scale=10, nb=4, capacity_factor=2.0,
                      walk_capacity_factor=4.0, **extra)
        for seed in (7, 2**31 + 5151):
            t = units.make(config, dict(cell.traffic, walkers=4096), seed, mesh)
            t.setup()
            hist, valid, wid, dropped = t.unit()
            hist, valid, wid = map(np.asarray, (hist, valid, wid))
            order = np.argsort(wid[valid])
            want = ref_walks.walks(t.walk, *ref_walks.csr(t.spec, jnp.uint32(t.graph_seed)),
                                   jnp.uint32(t.walk_seed))
            out.append([name, seed, int(dropped),
                        bool(np.array_equal(wid[valid][order], np.arange(4096))),
                        bool(np.array_equal(hist[valid][order], np.asarray(want)))])
    print(json.dumps(out))
""")


def test_the_reference_walks_as_the_program_does_on_four_shards(tmp_path):
    script = tmp_path / "four.py"
    script.write_text(FOUR_SHARDS)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(REPO / "src"))
    graphs = [["graph500", {}], ["ef1", SINKY["ef1"]]]
    proc = subprocess.run([sys.executable, str(script), str(HERE), json.dumps(graphs)],
                          env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    rows = json.loads(proc.stdout.strip().splitlines()[-1])
    assert len(rows) == 4
    for name, seed, dropped, all_back, same in rows:
        assert dropped == 0 and all_back and same, (name, seed)


# --- a run, its controls and its faults -------------------------------------


def test_a_small_walk_run_is_correct_with_every_check_0():
    result = _run(_cell())
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert all(c["value"] == 0 for c in result["checks"].values())
    assert result["metrics"]["walk_hops_per_s"]["value"] > 0


def test_a_traced_small_walk_run_reads_its_trace_and_stays_correct():
    """On the CPU the trace has no device plane: the readers read nothing,
    and the run is judged as an untraced one."""
    result = bench.run(_cell(), SEED, 0.3, True, require_tpu=False)
    assert result["correct"], result["checks"]
    assert result["metrics"] == {} and result["device"]["window_s"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("name,number", [("neighbour-order-rows", "walk_mismatch"),
                                         ("lossy-walk-exchange", "walkers_missing")])
def test_each_walk_control_fails_its_comparison(name, number):
    with controls.control(_cell(), name) as cell:
        result = _run(cell)
    assert not result["correct"]
    assert result["checks"][number]["value"] > 0
    if name == "lossy-walk-exchange":
        assert result["failed"] == result["attempted"]   # every call drops walkers
        assert result["checks"]["walk_mismatch"]["value"] == 0


def _broken(orig, fault):
    @partial(jax.jit, static_argnames=("cfg", "mesh", "length", "seed", "axis",
                                       "walkers_per_shard", "capacity_factor"))
    def walks(cfg, mesh, offv, adjv, *, length, seed=0, walkers_per_shard=64,
              capacity_factor=4.0, axis="shards"):
        hist, valid, wid, dropped = orig(cfg, mesh, offv, adjv, length=length, seed=seed,
                                         walkers_per_shard=walkers_per_shard,
                                         capacity_factor=capacity_factor, axis=axis)
        if fault == "state_unchanged":      # no hop moves a walker
            hist = jnp.broadcast_to(hist[:, :1], hist.shape)
        elif fault == "half_left_out":      # half the walkers do not come back
            valid = valid & (jnp.arange(valid.shape[0]) < valid.shape[0] // 2)
        elif fault == "answer_altered":     # one vertex of one history
            row = jnp.argmax(valid)
            hist = hist.at[row, -1].set((hist[row, -1] + 1) % cfg.n)
        return hist, valid, wid, dropped
    return walks


@pytest.mark.parametrize("fault,number", [("state_unchanged", "walk_mismatch"),
                                          ("half_left_out", "walkers_missing"),
                                          ("answer_altered", "walk_mismatch")])
def test_walk_faults_are_not_correct(monkeypatch, fault, number):
    monkeypatch.setattr(walks_mod, "distributed_walks",
                        _broken(walks_mod.distributed_walks, fault))
    result = _run(_cell())
    assert not result["correct"]
    assert result["checks"][number]["value"] > 0
    if fault == "answer_altered":
        assert result["checks"]["walk_mismatch"]["value"] == 1


# --- scopes and readers -----------------------------------------------------


def test_the_walk_programs_exchange_maps_to_the_exchange_scope():
    """The map compiled for the stand-in seed and traced shapes is the one
    of the program the window runs, for the run's own seed and arrays."""
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("shards",))
    cell = _cell()
    traffic = units.make(cell.config, cell.traffic, SEED, mesh)
    stand_in = scopes.program_scopes(SimpleNamespace(programs=traffic.program_texts))
    assert set(stand_in) == {"jit_distributed_walks"}
    paths = {ins.scope for ins in stand_in["jit_distributed_walks"].values()}
    for kind in ("sort", "permute", "place", "search"):
        assert ("exchange", kind) in paths, kind
    assert all(p == () or p[0] == "exchange" for p in paths)
    traffic.setup()
    with scopes.metadata_in_key():
        real = traffic._call(walks_mod.distributed_walks.lower, traffic.cfg, traffic.offv,
                             traffic.adjv, traffic.walk_seed).compile().as_text()
    assert dict([scopes.module_scopes(real)]) == stand_in


def _walk_trace():
    """Two walk calls of 1000 ns each; in each the program runs 600 ns of
    which the exchange's sort is 200 and its permute 100."""
    spans, mods, evs = [], [], []
    for base in (1000, 2000):
        spans.append(Event("bench.unit", base, 1000))
        mods.append(Event("jit_distributed_walks(3)", base + 100, 600))
        evs += [Event("sort.1", base + 100, 200), Event("fusion.2", base + 300, 100),
                Event("fusion.3", base + 400, 300)]
    return TraceSummary([Plane("/host:CPU", {"python": spans}),
                         Plane("/device:TPU:0", {"XLA Modules": mods, "XLA Ops": evs})])


def _walk_reading():
    walks = ref_walks.WalkSpec(units.graph_spec(_cell().config, "paper"), 2**20, 80)
    program = {"sort.1": scopes.Instruction(("exchange", "sort"), ""),
               "fusion.2": scopes.Instruction(("exchange", "permute"), ""),
               "fusion.3": scopes.Instruction((), "")}
    return bench.Reading(trace=_walk_trace(), graph=walks.graph,
                         peak=peaks.peak_for("TPU v5 lite"), walks=walks,
                         programs=lambda: None), {"jit_distributed_walks": program}


@pytest.mark.parametrize("metric,want", [
    ("walks.device_ms", 600e-6), ("exchange.device_ms.walks", 300e-6),
    ("sort.device_ms.walks", 200e-6), ("permute.device_ms.walks", 100e-6),
    ("idle_share.walks", 40.0),
    ("walks_roofline", 100.0 * 4 * (3 * 2**20 * 80 + 2**20 * 81) / 819e9 / 600e-9)])
def test_the_walk_readers(monkeypatch, metric, want):
    reading, program = _walk_reading()
    monkeypatch.setattr(scopes, "program_scopes", lambda reading: program)
    assert spec.load_reader(metric).read(reading) == pytest.approx(want)


def test_the_walk_roofline_counts_two_offsets_and_an_entry_a_hop():
    reading, _ = _walk_reading()
    assert spec.load_reader("walks_roofline").min_bytes(reading.walks, 4) == 1_346_371_584
    # a gen cell's reading has no walks: nothing to read
    assert spec.load_reader("walks_roofline").read(
        dataclasses.replace(reading, walks=None)) is None
