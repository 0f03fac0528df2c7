"""BENCHMARK.json and the files it names: each loads, each cell resolves,
a cell can be added as data alone, and a run without a chip or outside a
checkout prints no result."""

import dataclasses
import json
import os
import re
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
REPO = HERE.parents[1]
sys.path.insert(0, str(HERE))

import bench  # noqa: E402
import spec  # noqa: E402
import units  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BIG_SEED = 2**31 + 977


@pytest.fixture(scope="module")
def benchmark():
    return spec.load_benchmark()


def test_benchmark_keys_and_names(benchmark):
    assert set(benchmark) == {"command", "paths", "run_seconds", "configs", "workloads",
                              "end_to_end", "per_layer"}
    assert benchmark["paths"] == ["benchmarks/chip"]
    assert (REPO / benchmark["command"][1]).resolve().parent == HERE
    assert 1 <= benchmark["run_seconds"] <= 51
    names = [e["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for e in benchmark[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for e in benchmark["configs"] + benchmark["workloads"] + benchmark["per_layer"]:
        for key in ("why", "source", "layer"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and not set(e[key]) & {"\n", "\t"}, e
    for m in benchmark["end_to_end"] + benchmark["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in benchmark["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in benchmark["end_to_end"])
    e2e = {m["name"] for m in benchmark["end_to_end"]}
    for m in benchmark["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_every_named_file_loads_and_every_cell_resolves(benchmark):
    used = set()
    for w in benchmark["workloads"]:
        cell = spec.resolve(benchmark, w["name"])
        used.add(cell.config_name)
        assert cell.traffic["kind"] in units.KINDS
        assert int(cell.config["nb"]) <= cell.chips
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer, w["name"]
        for m, reader in cell.per_layer:
            assert callable(reader.read), m["name"]
            assert m["moves"] in names
    for c in benchmark["configs"]:
        assert c["name"] in used
        assert (REPO / c["file"]).resolve().is_relative_to(HERE)
        cfg = spec.load_json(REPO / c["file"])
        assert set(c["reduced"]) <= set(cfg["reduced"])
        units.graph_spec(cfg, "paper")  # every size the harness reads is there


def test_each_metric_reader_is_a_file_of_its_own(benchmark):
    for m in benchmark["per_layer"]:
        assert (HERE / "metrics" / f"{m['name']}.py").is_file()
    with pytest.raises(FileNotFoundError):
        spec.load_reader("no_such_metric")


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/bench.py", "--workload", "g500-22.gen-paper",
         "--seed", str(BIG_SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def test_a_run_without_a_tpu_exits_nonzero_and_prints_no_metrics():
    proc = _run_cli(REPO)
    assert proc.returncode != 0
    assert "metrics" not in proc.stdout
    assert "no TPU" in proc.stderr


def test_the_benchmark_alone_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_cli(tmp_path)
    assert proc.returncode != 0
    assert "metrics" not in proc.stdout


def _data_only_tree(tmp_path, benchmark, config_name, config, traffic_name, traffic,
                    workload, like):
    """A copy of the benchmark directory with one more cell, added as a
    workload entry, a configuration file and a traffic file alone; the
    cell reports the metrics that the cell `like` reports."""
    root = tmp_path / "benchmarks" / "chip"
    shutil.copytree(HERE, root, ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (root / "configs" / f"{config_name}.json").write_text(json.dumps(config))
    (root / "traffic" / f"{traffic_name}.json").write_text(json.dumps(traffic))
    bench_json = json.loads(json.dumps(benchmark))
    if config_name not in {c["name"] for c in bench_json["configs"]}:
        bench_json["configs"].append({
            "name": config_name, "source": "test", "reduced": ["scale"], "why": "test",
            "file": f"benchmarks/chip/configs/{config_name}.json"})
    bench_json["workloads"].append(dict(workload, traffic=traffic_name, config=config_name))
    for m in bench_json["end_to_end"] + bench_json["per_layer"]:
        if like in m.get("workloads", []):
            m["workloads"].append(workload["name"])
    return bench_json, root


def _tiny(cell, scale=10):
    return dataclasses.replace(cell, config=dict(cell.config, scale=scale))


def test_the_recompute_cell_is_data_alone(tmp_path, benchmark):
    config = spec.load_json(HERE / "configs" / "graph500-22.json")
    bench_json, root = _data_only_tree(
        tmp_path, benchmark, "graph500-22", config, "gen-recompute",
        {"kind": "gen", "shuffle_variant": "recompute"},
        {"name": "g500-22.gen-recompute", "chips": 1, "why": "test"}, "g500-22.gen-paper")
    cell = spec.resolve(bench_json, "g500-22.gen-recompute", root)
    assert cell.traffic["shuffle_variant"] == "recompute"
    assert {m["name"] for m, _ in cell.per_layer} >= {"relabel.device_ms", "csr_roofline"}
    result = bench.run(_tiny(cell), BIG_SEED, 0.5, False, require_tpu=False)
    assert result["correct"], result["checks"]
    assert result["checks"]["pv_mismatch"]["value"] == 0


@pytest.mark.parametrize("walkers,length", [(2048, 80), (4096, 40)])
def test_a_walk_cell_at_another_size_is_data_alone(tmp_path, benchmark, walkers, length):
    config = dict(spec.load_json(HERE / "configs" / "node2vec-g500-22.json"),
                  walk_length=length)
    name = f"node2vec-g500-22-l{length}"
    bench_json, root = _data_only_tree(
        tmp_path, benchmark, name, config, f"walks-{walkers}",
        {"kind": "walks", "walkers": walkers, "shuffle_variant": "paper"},
        {"name": f"n2v-22.walks-{walkers}-{length}", "chips": 1, "why": "test"}, "n2v-22.walks")
    cell = spec.resolve(bench_json, f"n2v-22.walks-{walkers}-{length}", root)
    assert {m["name"] for m in cell.end_to_end} == {"setup_s", "walk_hops_per_s", "peak_hbm_gib"}
    assert {m["name"] for m, _ in cell.per_layer} >= {"walks.device_ms", "walks_roofline"}
    result = bench.run(_tiny(cell), BIG_SEED, 0.3, False, require_tpu=False)
    assert result["correct"], result["checks"]
    assert result["checks"]["walk_mismatch"]["value"] == 0


FOUR_CHIP = textwrap.dedent("""
    import dataclasses, json, sys
    sys.path.insert(0, sys.argv[1])
    import bench, spec
    bench_json = json.loads(open(sys.argv[2]).read())
    cell = spec.resolve(bench_json, sys.argv[3], __import__("pathlib").Path(sys.argv[4]))
    assert cell.chips == 4 and cell.config["nb"] == 4
    cell = dataclasses.replace(cell, config=dict(cell.config, scale=10))
    print(json.dumps(bench.run(cell, int(sys.argv[5]), 0.5, False, require_tpu=False)))
""")


@pytest.mark.parametrize("traffic_name,workload,like", [
    ("gen-paper", "g500-22x4.gen-paper", "g500-22.gen-paper"),
])
def test_a_four_chip_cell_is_data_alone(tmp_path, benchmark, traffic_name, workload, like):
    base = "graph500-22"
    config = dict(spec.load_json(HERE / "configs" / f"{base}.json"),
                  name=f"{base}-x4", chips=4, nb=4, capacity_factor=1.1)
    traffic = spec.load_json(HERE / "traffic" / f"{traffic_name}.json")
    bench_json, root = _data_only_tree(
        tmp_path, benchmark, f"{base}-x4", config, traffic_name, traffic,
        {"name": workload, "chips": 4, "why": "test"}, like)
    (tmp_path / "bench.json").write_text(json.dumps(bench_json))
    (tmp_path / "run.py").write_text(FOUR_CHIP)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(REPO / "src"))
    proc = subprocess.run(
        [sys.executable, str(tmp_path / "run.py"), str(root), str(tmp_path / "bench.json"),
         workload, str(root), str(BIG_SEED)],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["device"]["count"] == 4
    assert result["correct"], result["checks"]
