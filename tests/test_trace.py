"""Unit tests for core/trace.py — the run-wide tracing + metrics subsystem.

Covers the tracer lifecycle (null default, install/idempotence/uninstall,
bounded-buffer drops), the merge/validate/export pipeline (torn lines,
negative durations, the nesting law, Perfetto structure), the unified
telemetry schema (unified_snapshot, MetricsRegistry, run_metadata), the
checkpoint-key contract (trace is normalized out of result_config_key),
phase spans across kill+resume (no duplicates for checkpointed phases),
and the CI kernel-coverage lint.  The hypothesis twins live in
tests/test_trace_property.py.
"""

import dataclasses
import json
import os

import pytest

from repro.core.blockstore import IOLedger
from repro.core.phases import PhaseOrchestrator, PlainCfg, result_config_key
from repro.core import trace as trace_mod
from repro.core.trace import (
    GLOBAL,
    MetricsRegistry,
    Tracer,
    get_tracer,
    install_tracer,
    lint_kernel_coverage,
    maybe_install_tracer,
    merge_traces,
    phase_durations,
    run_metadata,
    to_perfetto,
    trace_files,
    uninstall_tracer,
    unified_snapshot,
    validate_timeline,
    write_perfetto,
)


@pytest.fixture(autouse=True)
def _reset_tracer():
    """The tracer is process-global state; every test starts and ends with
    the NullTracer installed (and the global registry empty)."""
    uninstall_tracer()
    GLOBAL.clear()
    yield
    uninstall_tracer()
    GLOBAL.clear()


def _read_jsonl(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


# ---------------------------------------------------------------------------
# Tracer lifecycle
# ---------------------------------------------------------------------------


def test_default_tracer_is_null_and_free(tmp_path):
    tr = get_tracer()
    assert tr.enabled is False
    tr.event("x", "phase", 0.0, 1.0)
    tr.instant("y")
    with tr.span("z"):
        pass
    tr.flush()
    assert list(tmp_path.iterdir()) == []   # nothing ever touches disk


def test_maybe_install_disabled_is_noop(tmp_path):
    tr = maybe_install_tracer(str(tmp_path), enabled=False)
    assert tr.enabled is False
    assert not (tmp_path / "trace").exists()


def test_tracer_writes_labeled_spans(tmp_path):
    tr = install_tracer(str(tmp_path), host=1, job="job0001")
    assert get_tracer() is tr and tr.enabled
    tr.event("generate", "kernel", 100.0, 2.5, args={"bucket": 3})
    tr.instant("recv:edges", cat="wire", bytes=64)
    with tr.span("send:edges", cat="wire", bytes=128):
        pass
    uninstall_tracer()   # close() flushes
    recs = _read_jsonl(tmp_path / "trace" / f"trace_{os.getpid()}.jsonl")
    assert len(recs) == 3
    by_name = {r["name"]: r for r in recs}
    ev = by_name["generate"]
    assert ev["ph"] == "X" and ev["cat"] == "kernel"
    assert ev["ts"] == 100.0 and ev["dur"] == 2.5
    assert ev["args"] == {"bucket": 3}
    assert ev["host"] == 1 and ev["job"] == "job0001"
    assert ev["pid"] == os.getpid() and "tid" in ev
    assert by_name["recv:edges"]["ph"] == "i"
    assert by_name["send:edges"]["dur"] >= 0.0
    assert by_name["send:edges"]["args"] == {"bytes": 128}


def test_install_is_idempotent_first_wins(tmp_path):
    a = install_tracer(str(tmp_path / "a"))
    b = install_tracer(str(tmp_path / "b"))
    assert a is b
    assert b.path.startswith(str(tmp_path / "a"))
    assert not (tmp_path / "b").exists()


def test_bounded_buffer_drops_instead_of_blocking(tmp_path):
    tr = Tracer(str(tmp_path), max_buffer=4, flush_interval=3600.0)
    for i in range(10):
        tr.event(f"e{i}", "kernel", float(i), 0.1)
    assert tr.dropped == 6
    tr.close()
    recs = _read_jsonl(tr.path)
    # 4 kept events + the final trace_dropped meta instant
    assert len(recs) == 5
    assert recs[-1]["name"] == "trace_dropped"
    assert recs[-1]["args"]["dropped"] == 6


# ---------------------------------------------------------------------------
# Merge + validation + export
# ---------------------------------------------------------------------------


def _span(name, cat, ts, dur, **kw):
    return {"name": name, "cat": cat, "ph": "X", "ts": ts, "dur": dur,
            "pid": 1, "tid": 1, **kw}


def test_merge_traces_skips_torn_lines_and_sorts(tmp_path):
    a = tmp_path / "trace_1.jsonl"
    b = tmp_path / "trace_2.jsonl"
    a.write_text(json.dumps(_span("late", "phase", 5.0, 1.0)) + "\n"
                 + '{"name": "torn", "ts": 1')          # killed mid-flush
    b.write_text("not json at all\n"
                 + json.dumps(_span("early", "phase", 1.0, 1.0)) + "\n"
                 + json.dumps({"no_ts": True}) + "\n")
    events = merge_traces([str(tmp_path)])
    assert [e["name"] for e in events] == ["early", "late"]
    # dir scan and explicit file list agree
    assert merge_traces([str(a), str(b)]) == events
    assert trace_files([str(tmp_path)]) == sorted([str(a), str(b)])


def test_merge_parent_precedes_child_at_equal_ts():
    # sort key (ts, -dur, name): the longer span comes first
    events = sorted(
        [_span("child", "kernel", 1.0, 1.0), _span("parent", "phase", 1.0, 5.0)],
        key=lambda r: (r["ts"], -r["dur"], r["name"]))
    assert [e["name"] for e in events] == ["parent", "child"]


def test_validate_timeline_flags_negative_duration():
    problems = validate_timeline([_span("bad", "io", 1.0, -0.5)])
    assert len(problems) == 1 and "negative duration" in problems[0]


def test_validate_timeline_nesting_law():
    ok = [_span("phase_a", "phase", 0.0, 10.0),
          _span("k1", "kernel", 1.0, 2.0),
          _span("k2", "kernel", 4.0, 5.0)]
    assert validate_timeline(ok) == []
    bad = [_span("phase_a", "phase", 0.0, 10.0),
           _span("k_overflow", "kernel", 8.0, 5.0)]   # ends at 13 > 10
    problems = validate_timeline(bad)
    assert len(problems) == 1 and "overflows its parent" in problems[0]
    # leaf categories are exempt: interleaved io spans legally overlap
    assert validate_timeline([_span("merge:a", "io", 0.0, 10.0),
                              _span("sort:b", "io", 8.0, 5.0)]) == []
    # distinct lanes never nest against each other
    other_lane = _span("k_other", "kernel", 8.0, 5.0, host=2)
    assert validate_timeline([ok[0], other_lane]) == []


def test_to_perfetto_structure_and_rebasing():
    events = [_span("p", "phase", 100.0, 1.5, host=0),
              _span("k", "kernel", 100.5, 0.25, host=1, job="job0001"),
              {"name": "i", "cat": "wire", "ph": "i", "ts": 101.0,
               "pid": 2, "tid": 9, "host": 1}]
    doc = to_perfetto(events)
    evs = doc["traceEvents"]
    metas = [e for e in evs if e["ph"] == "M"]
    spans = [e for e in evs if e["ph"] == "X"]
    insts = [e for e in evs if e["ph"] == "i"]
    # one process_name metadata row per (host, pid) lane
    assert {m["args"]["name"] for m in metas} == \
        {"host 0 / pid 1", "host 1 / pid 1", "host 1 / pid 2"}
    assert len(spans) == 2 and len(insts) == 1
    by = {e["name"]: e for e in spans}
    assert by["p"]["ts"] == 0 and by["p"]["dur"] == 1_500_000     # µs, rebased
    assert by["k"]["ts"] == 500_000 and by["k"]["dur"] == 250_000
    assert by["k"]["args"]["job"] == "job0001"
    assert by["p"]["pid"] != by["k"]["pid"]
    assert to_perfetto([]) == {"traceEvents": [], "displayTimeUnit": "ms"}


def test_write_perfetto_round_trips(tmp_path):
    path = write_perfetto([_span("p", "phase", 0.0, 1.0)],
                          str(tmp_path / "out.json"))
    with open(path) as f:
        doc = json.load(f)
    assert any(e["name"] == "p" for e in doc["traceEvents"])


def test_phase_durations_sums_phase_cat_only():
    events = [_span("generate", "phase", 0.0, 2.0),
              _span("generate", "phase", 5.0, 3.0),
              _span("generate", "kernel", 0.5, 1.0),     # not a phase span
              _span("csr", "phase", 10.0, 4.0)]
    assert phase_durations(events) == {"generate": 5.0, "csr": 4.0}


# ---------------------------------------------------------------------------
# Unified telemetry schema
# ---------------------------------------------------------------------------


def test_unified_snapshot_sections_and_duck_typing():
    led = IOLedger()
    led.write(1024)
    led.stall(read_wait_s=0.5, overlap_s=0.1)
    snap = unified_snapshot(ledger=led)
    assert snap["schema"] == 1
    assert snap["io"]["bytes_written"] == 1024
    assert "read_wait_s" not in snap["io"]        # stalls are split out
    assert snap["stalls"] == {"read_wait_s": 0.5, "write_wait_s": 0.0,
                              "overlap_s": 0.1}
    assert "wire" not in snap and "memory" not in snap   # omitted, not null
    # a ledger that crossed the wire as a dict snapshots identically
    assert unified_snapshot(ledger=led.as_dict()) == snap


def test_metrics_registry_combined_sums_and_maxes():
    reg = MetricsRegistry()
    reg.update("a", {"schema": 1, "io": {"bytes_read": 10},
                     "memory": {"peak_rows": 5, "budget_rows": 100}})
    reg.update("b", {"schema": 1, "io": {"bytes_read": 7, "seq_reads": 2},
                     "memory": {"peak_rows": 9, "budget_rows": 100}})
    reg.update("b", {"schema": 1, "io": {"bytes_read": 8, "seq_reads": 2},
                     "memory": {"peak_rows": 9, "budget_rows": 100}})
    combined = reg.combined()
    assert combined["sources"] == ["a", "b"]
    assert combined["io"] == {"bytes_read": 18, "seq_reads": 2}  # latest-wins
    assert combined["memory"] == {"peak_rows": 9, "budget_rows": 100}
    reg.clear()
    assert reg.combined() == {"schema": 1}


def test_run_metadata_values_are_all_strings():
    meta = run_metadata(config_digest="abc123")
    for key in ("schema", "hostname", "timestamp", "python", "git_sha"):
        assert isinstance(meta[key], str) and meta[key]
    assert meta["config_digest"] == "abc123"


# ---------------------------------------------------------------------------
# Checkpoint-key contract + kernel-coverage lint
# ---------------------------------------------------------------------------


def _pcfg(**kw):
    base = dict(scale=8, edge_factor=2, seed=1, a=0.57, b=0.19, c=0.19,
                d=0.05, nb=2, chunk_edges=256, rounds=2)
    base.update(kw)
    return PlainCfg(**base)


def test_result_config_key_erases_trace():
    pcfg = _pcfg()
    assert result_config_key(dataclasses.replace(pcfg, trace=True)) == \
        result_config_key(dataclasses.replace(pcfg, trace=False))


def test_lint_kernel_coverage_is_clean():
    assert lint_kernel_coverage() == []


def test_lint_catches_unwrapped_kernel(monkeypatch):
    from repro.core import phases

    def naked(pcfg, workdir, *a, **kw):   # pragma: no cover - never called
        pass

    monkeypatch.setitem(phases._KERNELS, "generate", naked)
    problems = lint_kernel_coverage()
    assert any("generate" in p and "not wrapped" in p for p in problems)


# ---------------------------------------------------------------------------
# Phase spans across kill + resume
# ---------------------------------------------------------------------------


def test_resume_emits_no_duplicate_phase_spans(tmp_path):
    """Run 1 completes p1, p2 with checkpoints; run 2 (same workdir, as
    after a kill) resumes both and runs p3.  The merged timeline must hold
    exactly ONE phase span per completed phase — resumed phases did no
    work, so they contribute no span."""
    workdir = str(tmp_path)
    save = lambda r: {"v": r}
    load = lambda d: d["v"]

    install_tracer(workdir)
    orch = PhaseOrchestrator(workdir, IOLedger(), checkpoint=True,
                             config_key="k")
    orch.run_phase("p1", lambda: 1, save=save, load=load)
    orch.run_phase("p2", lambda: 2, save=save, load=load)
    uninstall_tracer()                     # the "kill": flush + reset

    install_tracer(workdir)                # the resumed process
    orch2 = PhaseOrchestrator(workdir, IOLedger(), checkpoint=True,
                              config_key="k")
    assert orch2.run_phase("p1", lambda: 99, save=save, load=load) == 1
    assert orch2.run_phase("p2", lambda: 99, save=save, load=load) == 2
    orch2.run_phase("p3", lambda: 3, save=save, load=load)
    statuses = {r["phase"]: r["status"] for r in orch2.report()}
    assert statuses == {"p1": "resumed", "p2": "resumed", "p3": "done"}
    uninstall_tracer()

    events = merge_traces([os.path.join(workdir, "trace")])
    names = [e["name"] for e in events if e.get("cat") == "phase"]
    assert sorted(names) == ["p1", "p2", "p3"]      # one span each, ever
    assert validate_timeline(events) == []
    # the GLOBAL registry picked up the orchestrator's unified snapshot
    assert "orchestrator" in GLOBAL.names()
    assert GLOBAL.combined()["schema"] == 1


def test_run_phase_emits_nothing_when_untraced(tmp_path):
    orch = PhaseOrchestrator(str(tmp_path), IOLedger())
    orch.run_phase("p1", lambda: 1)
    assert not (tmp_path / "trace").exists()
    assert [r["status"] for r in orch.report()] == ["done"]


def test_trace_cli_lint_entry():
    assert trace_mod.main(["lint"]) == 0
    assert trace_mod.main([]) == 2


# ---------------------------------------------------------------------------
# The device path: phase_span and generate()'s spans
# ---------------------------------------------------------------------------


def test_phase_span_records_a_phase_span_when_traced(tmp_path):
    tr = install_tracer(str(tmp_path))
    with trace_mod.phase_span("gen.relabel", seed=7):
        pass
    tr.flush()
    recs = _read_jsonl(tr.path)
    assert [(r["name"], r["cat"], r["args"]) for r in recs] == [
        ("gen.relabel", "phase", {"seed": 7})]


def test_phase_span_writes_nothing_untraced(tmp_path):
    with trace_mod.phase_span("gen.csr", seed=1):
        pass
    assert not get_tracer().enabled
    assert not os.path.exists(tmp_path / "trace")


def test_generate_spans_each_phase_with_the_graphs_seed(tmp_path):
    from repro.core.pipeline import generate
    from repro.core.types import GraphConfig

    tr = install_tracer(str(tmp_path))
    cfg = GraphConfig(scale=8, seed=1234)
    generate(cfg)
    tr.flush()
    recs = [r for r in _read_jsonl(tr.path) if r["cat"] == "phase"]
    assert [r["name"] for r in recs] == [
        "gen.shuffle", "gen.edges", "gen.relabel", "gen.redistribute", "gen.csr"]
    assert all(r["args"] == {"seed": 1234} for r in recs)
    assert validate_timeline(recs) == []


def test_compile_phases_compiles_what_generate_runs_and_runs_nothing():
    from repro.core.pipeline import compile_phases
    from repro.core.types import GraphConfig

    cfg = GraphConfig(scale=8)
    compiled = compile_phases(cfg)
    assert sorted(compiled) == ["build_csr_sorted", "distributed_shuffle", "generate_edges",
                                "redistribute_sorted", "relabel_ring"]
    text = compiled["relabel_ring"].as_text()
    assert "/relabel/sort/" in text and "/lookup/" in text
    recompute = compile_phases(cfg, shuffle_variant="recompute")
    assert "shuffle_recompute" in recompute and "relabel_recompute" in recompute
