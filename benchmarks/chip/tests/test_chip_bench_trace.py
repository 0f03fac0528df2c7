"""The reduction from a profiler trace to the per-layer metrics, on a
hand-built trace whose every number is known."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import devtrace  # noqa: E402
import peaks  # noqa: E402
import reference  # noqa: E402
import spec  # noqa: E402
from devtrace import Event, Plane, TraceSummary  # noqa: E402


def _host():
    # two units: [1000, 2000) and [2000, 3000); inside each, dispatch, wait, check
    spans = []
    for base in (1000, 2000):
        spans += [Event("bench.unit", base, 1000),
                  Event("bench.dispatch", base, 100),
                  Event("bench.wait", base + 100, 800),
                  Event("bench.check", base + 900, 100)]
    return Plane("/host:CPU", {"python3": spans + [Event("other", 0, 5000)]})


def _device():
    modules = [Event("jit_relabel_ring(3)", 1100, 400),       # inside unit 1
               Event("jit_build_csr_sorted(4)", 1500, 300),
               Event("jit_relabel_ring(3)", 2100, 400),       # inside unit 2
               Event("jit_build_csr_sorted(4)", 2500, 300),
               Event("jit_distributed_shuffle(1)", 500, 200)]  # before the window
    ops = [Event("sort.1", 1100, 300), Event("fusion.2", 1400, 100),   # 1100-1500
           Event("fusion.7", 1500, 300),                              # 1500-1800
           Event("sort.1", 2100, 400), Event("fusion.7", 2500, 300),
           Event("copy.9", 500, 200, (("hlo_module", "jit_distributed_shuffle"),))]
    return Plane("/device:TPU:0", {"XLA Modules": modules, "XLA Ops": ops})


@pytest.fixture
def summary():
    return TraceSummary([_host(), _device(), Plane("/host:metadata", {})])


def test_window_and_units_come_from_unit_spans(summary):
    assert summary.units == 2
    assert summary.window_s == pytest.approx(2000e-9)


def test_busy_is_the_union_of_op_intervals_inside_the_window(summary):
    # 1100-1800 and 2100-2800: 1400 ns; the op at 500 lies outside
    assert summary.busy_s == pytest.approx(1400e-9)
    assert summary.idle_share() == pytest.approx(100.0 * (1 - 1400 / 2000))


def test_module_time_is_attributed_by_module_name_per_unit(summary):
    assert summary.module_s_per_unit(["jit_relabel_ring"]) == pytest.approx(400e-9)
    assert summary.module_s_per_unit(["jit_build_csr_sorted", "jit_relabel_ring"]) == \
        pytest.approx(700e-9)
    # outside the window, or absent: nothing to read
    assert summary.module_s_per_unit(["jit_distributed_shuffle"]) is None
    assert summary.module_s_per_unit(["jit_distributed_walks"]) is None


def test_top_ops_are_labelled_by_their_module(summary):
    top = dict(summary.top_ops())
    assert top["jit_relabel_ring/sort.1"] == pytest.approx(700e-9)
    assert top["jit_build_csr_sorted/fusion.7"] == pytest.approx(600e-9)
    assert top["jit_relabel_ring/fusion.2"] == pytest.approx(100e-9)
    assert not any("copy.9" in k for k in top)


def test_top_ops_count_the_own_time_of_nested_ops():
    host = Plane("/host:CPU", {"main": [Event("bench.unit", 0, 1000)]})
    ops = [Event("%while.4 = (s32[], s32[8]{0:T(1024)}) while(%t)", 100, 600),
           Event("%fusion.1 = s32[67108864]{0:T(1024)} fusion(%a, %b), kind=kCustom", 150, 200),
           Event("%fusion.2 = s32[8]{0} fusion(%c)", 400, 250)]
    trace = TraceSummary([host, Plane("/device:TPU:0", {
        "XLA Modules": [Event("jit_build_csr_sorted(9)", 100, 600)], "XLA Ops": ops})])
    top = dict(trace.top_ops())
    assert top == pytest.approx({"jit_build_csr_sorted/while.4 (s32[], s32[8])": 150e-9,
                                 "jit_build_csr_sorted/fusion.1 s32[67108864]": 200e-9,
                                 "jit_build_csr_sorted/fusion.2 s32[8]": 250e-9})
    assert trace.busy_s == pytest.approx(600e-9)


def test_idle_gaps_are_named_by_the_host_span_open_over_them(summary):
    gaps = summary.idle_gaps()
    # 1000-1100: unit 1's dispatch; 1800-2100: the end of unit 1 and unit
    # 2's dispatch, midpoint 1950 in unit 1's check; 2800-3000: midpoint
    # 2900, where unit 2's wait ends and its check begins (the shorter wins)
    assert [(round(s * 1e9), name) for name, s in gaps] == [
        (300, "bench.check"), (200, "bench.check"), (100, "bench.dispatch")]


def test_a_trace_without_devices_gives_nothing(summary):
    bare = TraceSummary([_host()])
    assert bare.busy_s is None and bare.idle_share() is None
    assert bare.module_s_per_unit(["jit_relabel_ring"]) is None


def test_roofline_share_follows_its_bytes_formula(summary):
    g = reference.GraphSpec(scale=10, edge_factor=16, a=.57, b=.19, c=.19, d=.05, nb=1)
    reader = spec.load_reader("relabel_roofline")
    assert reader.min_bytes(g, 4) == 4 * (4 * g.m + g.n)
    peak = peaks.peak_for("TPU v5 lite")

    class Reading:
        trace, graph, id_bytes = summary, g, 4

    Reading.peak = peak
    want = 100.0 * 4 * (4 * g.m + g.n) / 819e9 / 400e-9
    assert reader.read(Reading) == pytest.approx(want)
    Reading.peak = None
    assert reader.read(Reading) is None


def test_every_roofline_reader_reads_nothing_where_its_phase_did_not_run():
    g = reference.GraphSpec(scale=10, edge_factor=16, a=.57, b=.19, c=.19, d=.05, nb=1)

    class Reading:
        trace = TraceSummary([_host(), Plane("/device:TPU:0", {
            "XLA Modules": [Event("jit_other(1)", 1100, 10)],
            "XLA Ops": [Event("x", 1100, 10)]})])
        graph, id_bytes, peak = g, 4, peaks.peak_for("TPU v5e")

    for name in ("relabel_roofline", "redistribute_roofline", "csr_roofline",
                 "shuffle.device_ms", "edges.device_ms"):
        assert spec.load_reader(name).read(Reading) is None, name


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peak_for("TPU v99")


def test_module_of_strips_the_program_id():
    assert devtrace.module_of("jit_relabel_ring(12)") == "jit_relabel_ring"
    assert devtrace.module_of("jit_generate_edges") == "jit_generate_edges"
