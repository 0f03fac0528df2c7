"""The four-chip cell's readers of collective and merge time
(`collective.device_ms`, `collective.exposed_ms`, `merge.device_ms`) on a
hand-built trace of four devices whose every number is known, and the
interval arithmetic behind the exposed time (overlap.py)."""

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import overlap  # noqa: E402
import scopes  # noqa: E402
import spec  # noqa: E402
from devtrace import Event, Plane, TraceSummary  # noqa: E402

HLO = '''HloModule jit_redistribute_sorted, is_scheduled=true

%fused_computation (param_0: s32[8]) -> s32[8] {
  %param_0 = s32[8]{0} parameter(0)
  ROOT %gather.1 = s32[8]{0} gather(%param_0, %param_0), metadata={op_name="jit(redistribute_sorted)/redistribute/permute/gather"}
}

ENTRY %main (src: s32[8]) -> s32[4,8] {
  %src = s32[8]{0} parameter(0), metadata={op_name="src"}
  %fusion.2 = s32[8]{0} fusion(%src), kind=kLoop, calls=%fused_computation
  %fusion.5 = s32[8]{0} fusion(%src), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(redistribute_sorted)/redistribute/sort/while"}
  %slice.7 = s32[8]{0} slice(%src), slice={[0:8]}, metadata={op_name="jit(redistribute_sorted)/redistribute/merge/slice"}
  ROOT %all-to-all.1 = s32[4,8]{1,0} all-to-all(%src), dimensions={0}, metadata={op_name="jit(redistribute_sorted)/redistribute/exchange/collective/all_to_all"}
}
'''

A2A = "%all-to-all.1 = s32[4,8]{1,0:T(4,128)} all-to-all(s32[8] %src)"
PERMUTE = "%fusion.2 = s32[8]{0:T(1024)} fusion(s32[8] %src), kind=kLoop"
LOOP = "%fusion.5 = s32[8]{0:T(1024)} fusion(s32[8] %src), kind=kLoop"
MERGE = "%slice.7 = s32[8]{0:T(1024)} slice(s32[8] %src)"

# (main line, asynchronous line) of each device, as (name, start, end) in ns;
# one unit spans [1000, 2000)
DEVICES = [
    # the collective alone between two compute operations: all of it exposed
    ([(PERMUTE, 1100, 1200), (A2A, 1200, 1250), (PERMUTE, 1250, 1280), (MERGE, 1280, 1290)], []),
    # the collective inside a loop's event: the loop does not hide it
    ([(LOOP, 1100, 1300), (A2A, 1150, 1200)], []),
    # a compute operation runs beside the collective for 30 of its 100 ns
    ([(A2A, 1100, 1200)], [(PERMUTE, 1150, 1180)]),
    # no collective on the main line; an asynchronous collective hides nothing
    ([(PERMUTE, 1100, 1300)], [(A2A, 1100, 1200)]),
]


def _trace(devices=DEVICES):
    host = Plane("/host:CPU", {"python": [Event("bench.unit", 1000, 1000)]})
    planes = [host]
    for i, (main, beside) in enumerate(devices):
        lines = {"XLA Modules": [Event("jit_redistribute_sorted(3)", 1050, 400)],
                 "XLA Ops": [Event(name, a, b - a) for name, a, b in main]}
        if beside:
            lines[overlap.ASYNC_LINE] = [Event(name, a, b - a) for name, a, b in beside]
        planes.append(Plane(f"/device:TPU:{i}", lines))
    return planes


def _reading(planes):
    return SimpleNamespace(trace=TraceSummary(planes), graph=None, peak=None, id_bytes=4)


@pytest.fixture
def programs(monkeypatch):
    name, program = scopes.module_scopes(HLO)
    monkeypatch.setattr(scopes, "program_scopes", lambda reading: {name: program})
    return {name: program}


@pytest.mark.parametrize("metric,want_ns", [
    # own time of the collective on each device: 50, 50, 100, 0
    ("collective.device_ms", (50 + 50 + 100 + 0) / 4),
    # less what runs beside it: 50, 50, 70, 0
    ("collective.exposed_ms", (50 + 50 + 70 + 0) / 4),
    ("merge.device_ms", 10 / 4),
])
def test_the_readers_give_milliseconds_per_unit_averaged_over_the_chips(programs, metric, want_ns):
    got = spec.load_reader(metric).read(_reading(_trace()))
    assert got == pytest.approx(want_ns * 1e-6)


def test_the_exposed_time_is_at_most_the_collective_time(programs):
    reading = _reading(_trace())
    exposed = spec.load_reader("collective.exposed_ms").read(reading)
    assert exposed < spec.load_reader("collective.device_ms").read(reading)
    # one operation at a time on every device: nothing is hidden
    alone = [(main, []) for main, _ in DEVICES]
    reading = _reading(_trace(alone))
    assert spec.load_reader("collective.exposed_ms").read(reading) == pytest.approx(
        spec.load_reader("collective.device_ms").read(reading))


@pytest.mark.parametrize("metric", ["collective.device_ms", "collective.exposed_ms",
                                    "merge.device_ms"])
def test_the_readers_read_nothing_where_their_operations_are_absent(programs, monkeypatch,
                                                                     metric):
    reader = spec.load_reader(metric)
    # no operation of the metric's kind ran (the merge and the collective left out)
    absent = [([op for op in main if op[0] not in (A2A, MERGE)], []) for main, _ in DEVICES]
    assert reader.read(_reading(_trace(absent))) is None
    # a trace with no device
    assert reader.read(_reading(_trace()[:1])) is None
    # a checkout that cannot compile its phases alone
    monkeypatch.setattr(scopes, "program_scopes", lambda reading: None)
    assert reader.read(_reading(_trace())) is None


def test_an_operation_unlike_its_program_leaves_the_module_unscoped(programs):
    odd = [(main + [("%fusion.99 = s32[8]{0} fusion(s32[8] %src)", 1400, 1410)], beside)
           for main, beside in DEVICES]
    assert spec.load_reader("collective.exposed_ms").read(_reading(_trace(odd))) is None


def test_own_intervals_leave_out_what_is_nested():
    loop = Event("loop", 0, 100)
    inner = Event("inner", 20, 30)
    deeper = Event("deeper", 25, 5)
    after = Event("after", 100, 10)
    got = dict((ev.name, ivs) for ev, ivs in
               overlap.own_intervals([after, deeper, loop, inner], 0, 105))
    assert got == {"loop": [(0, 20), (50, 100)], "inner": [(20, 25), (30, 50)],
                   "deeper": [(25, 30)], "after": [(100, 105)]}


CELL = "g500-22-x4.gen-paper"
NEW = {"collective.device_ms", "collective.exposed_ms", "merge.device_ms"}


def test_the_four_chip_cell_reports_its_listed_metrics_and_no_roofline():
    """The roofline readers divide the whole graph's bytes by one chip's
    bandwidth: at nb = 4 they would read up to 4x high, so the cell leaves
    them out."""
    benchmark = spec.load_benchmark()
    cell = spec.resolve(benchmark, CELL)
    assert cell.chips == 4 and cell.config["nb"] == 4 and cell.config_name == "graph500-22-x4"
    assert cell.traffic == spec.load_json(HERE / "traffic" / "gen-paper.json")
    assert {m["name"] for m in cell.end_to_end} == {"setup_s", "gen_edges_per_s", "peak_hbm_gib"}
    assert {m["name"] for m, _ in cell.per_layer} == {
        "shuffle.device_ms", "edges.device_ms", "relabel.device_ms", "redistribute.device_ms",
        "csr.device_ms", "idle_share.gen", "exchange.device_ms", "sort.device_ms",
        "permute.device_ms"} | NEW
    # the new metrics are this cell's alone
    for name in ("g500-22.gen-paper", "n2v-22.walks"):
        assert not {m["name"] for m, _ in spec.resolve(benchmark, name).per_layer} & NEW


def test_the_four_chip_configuration_is_graph500_22_with_three_keys_set():
    base = spec.load_json(HERE / "configs" / "graph500-22.json")
    shipped = spec.load_json(HERE / "configs" / "graph500-22-x4.json")
    want = dict(base, chips=4, nb=4, capacity_factor=1.1)
    numbers = {k for k, v in {**want, **shipped}.items() if not isinstance(v, (str, dict, list))}
    assert {k: shipped.get(k) for k in numbers} == {k: want.get(k) for k in numbers}
