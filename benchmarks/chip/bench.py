"""On-chip benchmark of the device generation pipeline, one cell per run.

    python3 benchmarks/chip/bench.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell is a workload of BENCHMARK.json; its configuration, traffic mix and
per-layer metrics are files found by name (spec.py).  One process runs the
cell once:

1. names the device, and stops with exit code 2 and no result unless JAX
   sees a TPU with as many chips as the cell asks for;
2. turns on the persistent compilation cache at <checkout>/.jax_cache, or
   where JAX_COMPILATION_CACHE_DIR points, for every program;
3. sets the cell up from --seed: compiles every program the window calls
   without running a unit;
4. runs units back to back until the first one that ends after --seconds;
   after each unit a device-side check counts it as failed if it broke an
   invariant, and a compile inside the window makes the run incorrect;
5. compares the last unit with the plain reference of its traffic kind
   (reference.py, reference_walks.py), and
   every other unit with the last one by fingerprint;
6. prints one JSON line: the end-to-end metrics with --trace 0, or with
   --trace 1 the per-layer metrics read from a profiler trace of the
   window, with the device's busy time and a breakdown.

Each number that decides `correct` is printed beside its limit, as the last
lines of standard error and under `checks`, the last key of the result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, Dict, List, Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))
# The TPU runtime logs to /tmp/tpu_logs unless told otherwise; keep its
# logs under the run's own temporary directory.
os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(), "tpu_logs"))

import numpy as np  # noqa: E402

import jax  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro.launch.compile_cache import use_compile_cache  # noqa: E402

import devtrace  # noqa: E402
import peaks  # noqa: E402
import spec  # noqa: E402
import units  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
GIB = float(1 << 30)


class NoChip(RuntimeError):
    """JAX sees no accelerator, or fewer chips than the cell asks for."""


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


class CompileLog:
    """Compiles (or loads from the persistent cache) per program, with the
    time each was recorded."""

    def __init__(self):
        self.events: List[tuple] = []

    def __call__(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.events.append((time.perf_counter(), kw.get("fun_name", "?"), duration))

    def between(self, lo: float, hi: float) -> List[tuple]:
        return [e for e in self.events if lo <= e[0] <= hi]


@dataclasses.dataclass
class Reading:
    """What a per-layer metric reader may read."""

    trace: devtrace.TraceSummary
    graph: units.ref.GraphSpec
    peak: Optional[peaks.Peak]
    programs: Callable[[], Optional[List[str]]]   # the window's programs' HLO texts
    walks: Optional[units.ref_walks.WalkSpec] = None
    id_bytes: int = 4


def _devices(cell: spec.Cell, require_tpu: bool):
    devices = jax.devices()
    dev = devices[0]
    say(f"device: platform={dev.platform} kind={dev.device_kind} count={len(devices)}")
    if require_tpu and dev.platform != "tpu":
        raise NoChip(f"no TPU (platform {dev.platform}): the benchmark measures the chip")
    if len(devices) < cell.chips:
        raise NoChip(f"cell {cell.name} asks for {cell.chips} chips, JAX sees {len(devices)}")
    return devices


def _memory_peak(devices) -> Optional[int]:
    peaks_seen = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks_seen = [p for p in peaks_seen if p is not None]
    return max(peaks_seen) if peaks_seen else None


def _say_memory(devices, when: str) -> None:
    """The fullest chip's memory in use, and its peak so far, to stderr:
    read after set-up and after the window, they tell which of the two
    set the process peak."""
    stats = [d.memory_stats() or {} for d in devices]
    fullest = max(stats, key=lambda s: s.get("peak_bytes_in_use", 0))
    say(f"memory after {when}: in use {fullest.get('bytes_in_use')} "
        f"peak {fullest.get('peak_bytes_in_use')} limit {fullest.get('bytes_limit')}")


def _trace_options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
        require_tpu: bool = True, keep_trace: Optional[str] = None,
        t_start: Optional[float] = None) -> dict:
    """Run one cell once and return the result line as a dict."""
    t_start = time.perf_counter() if t_start is None else t_start
    devices = _devices(cell, require_tpu)
    peak = peaks.peak_for(devices[0].device_kind) if require_tpu else None
    log = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(log)
    try:
        return _run(cell, seed, seconds, trace, devices, peak, log, keep_trace, t_start)
    finally:
        jax.monitoring.unregister_event_duration_listener(log)


def _run(cell, seed, seconds, trace, devices, peak, log, keep_trace, t_start) -> dict:
    nb = int(cell.config["nb"])
    mesh = Mesh(np.asarray(devices[:nb]), ("shards",))
    traffic = units.make(cell.config, cell.traffic, seed, mesh)
    traffic.setup()
    t_setup = time.perf_counter()
    setup_s = t_setup - t_start
    _say_memory(devices[:nb], "set-up")
    for _, name, secs in log.between(t_start, t_setup):
        if secs >= 0.05:
            say(f"setup compile_s {name}: {secs!r}")

    trace_dir = tempfile.mkdtemp(prefix="chip-bench-trace-") if trace else None
    try:
        if trace:
            jax.profiler.start_trace(trace_dir, profiler_options=_trace_options())
        checks, unit_s, t0, t1, last = _window(traffic, seconds)
        if trace:
            jax.profiler.stop_trace()
        memory_peak = _memory_peak(devices[:nb])
        _say_memory(devices[:nb], "the window")
        summary = _read_trace(trace_dir, keep_trace) if trace else None
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    elapsed = t1 - t0
    in_window = log.between(t0, t1)
    say(f"window: {len(unit_s)} units in {elapsed!r} s; unit seconds {unit_s}")
    say(f"compiles_in_window: {len(in_window)} {[name for _, name, _ in in_window]}")

    kept = traffic.keep(last)
    del last
    t_ref = time.perf_counter()
    compared = traffic.compare(kept)
    del kept
    say(f"reference comparison seconds: {time.perf_counter() - t_ref!r}")

    failed = sum(not c.ok for c in checks)
    limits: Dict[str, dict] = {
        "compiles_in_window": {"value": len(in_window), "limit": 0},
        "units_unlike_checked": {
            "value": sum(c.fingerprint != checks[-1].fingerprint for c in checks), "limit": 0},
    }
    limits.update({k: {"value": v, "limit": 0} for k, v in compared.items()})
    correct = failed == 0 and all(c["value"] <= c["limit"] for c in limits.values())

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": len(checks), "failed": failed}
    if trace:
        result["metrics"] = _per_layer(cell, summary, traffic, peak)
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
    else:
        result["metrics"] = _end_to_end(cell, {
            "setup_s": setup_s,
            traffic.rate_metric: len(checks) * traffic.work_per_unit / elapsed,
            "peak_hbm_gib": None if memory_peak is None else memory_peak / GIB,
        })
    result["device"] = device
    if trace:
        result["breakdown"] = {"device_ops": summary.top_ops(), "idle_gaps": summary.idle_gaps()}
    result["checks"] = limits
    for name, c in limits.items():
        say(f"check {name}: {c['value']} (limit {c['limit']})")
    return result


def _window(traffic, seconds: float):
    """Units back to back until the first that ends after `seconds`."""
    checks: List[units.Check] = []
    unit_s: List[float] = []
    out = None
    t0 = time.perf_counter()
    while True:
        out = None  # free the previous unit before the next one runs
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(devtrace.UNIT_SPAN):
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                out = traffic.unit()
            with jax.profiler.TraceAnnotation("bench.wait"):
                out = jax.block_until_ready(out)
            with jax.profiler.TraceAnnotation("bench.check"):
                checks.append(traffic.check(out))
        t1 = time.perf_counter()
        unit_s.append(t1 - t)
        if t1 - t0 >= seconds:
            return checks, unit_s, t0, t1, out


def _read_trace(trace_dir: str, keep_trace: Optional[str]) -> devtrace.TraceSummary:
    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace file under {trace_dir}, found {files}")
    if keep_trace:
        Path(keep_trace).mkdir(parents=True, exist_ok=True)
        shutil.copy(files[0], keep_trace)
    return devtrace.TraceSummary(devtrace.load_xplane(files[0]))


def _end_to_end(cell: spec.Cell, known: dict) -> dict:
    out = {}
    for m in cell.end_to_end:
        if m["name"] not in known:
            raise KeyError(f"cell {cell.name} lists {m['name']}, which its traffic does not give")
        value = known[m["name"]]
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def _per_layer(cell: spec.Cell, summary: devtrace.TraceSummary, traffic, peak) -> dict:
    reading = Reading(trace=summary, graph=traffic.spec, peak=peak,
                      programs=traffic.program_texts, walks=traffic.walk)
    out = {}
    for m, reader in cell.per_layer:
        value = reader.read(reading)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def cache_every_program() -> None:
    """The persistent compilation cache, for every program however quick
    to compile: the next run of the cell then loads all it needs."""
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="also copy the raw trace of a --trace 1 run into DIR")
    args = ap.parse_args(argv)
    cell = spec.resolve(spec.load_benchmark(), args.workload)
    cache_every_program()
    try:
        result = run(cell, args.seed, args.seconds, bool(args.trace),
                     keep_trace=args.keep_trace, t_start=T_START)
    except NoChip as e:
        say(f"error: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
