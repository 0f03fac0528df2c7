"""`correct` comes out false for the control and for each fault a cell can
have, with the rest of a run driven as on the chip (at a small size, on
the CPU, past the harness's look for a chip).

The control of each cell is controls.control.  The faults are planted in
the program where it produces its answer.  One chip has no exchange
between chips, so that fault does not apply to these cells.
"""

import contextlib
import dataclasses
import sys
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import bench  # noqa: E402
import controls  # noqa: E402
import spec  # noqa: E402

from repro.core import pipeline  # noqa: E402

SEED = 2**31 + 4242
GEN = "g500-22.gen-paper"


def _cell(workload):
    cell = spec.resolve(spec.load_benchmark(), workload)
    return dataclasses.replace(cell, config=dict(cell.config, scale=10))


def _run(cell):
    return bench.run(cell, SEED, 0.3, False, require_tpu=False)


def _compared(result):
    return {k: c["value"] for k, c in result["checks"].items()
            if k not in ("compiles_in_window", "units_unlike_checked")}


@pytest.mark.parametrize("workload", [GEN])
def test_sound_runs_are_correct(workload):
    result = _run(_cell(workload))
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1


@pytest.mark.parametrize("workload", [GEN])
def test_the_control_is_not_correct(workload):
    with controls.control(_cell(workload)) as cell:
        result = _run(cell)
    assert not result["correct"]
    assert max(_compared(result).values()) > 0


# --- generation faults ---------------------------------------------------


@partial(jax.jit, static_argnames=("cfg", "mesh", "axis"))
def _relabel_unchanged(cfg, mesh, src, dst, pv, axis="shards"):
    return src, dst


def _halved_redistribute(orig):
    @partial(jax.jit, static_argnames=("cfg", "mesh", "axis"))
    def halved(cfg, mesh, src, dst, axis="shards"):
        owned = orig(cfg, mesh, src, dst, axis)
        keep = jnp.arange(owned.valid.size).reshape(owned.valid.shape) < cfg.m // 2
        return owned._replace(valid=owned.valid & keep)
    return halved


def _altered_csr(orig):
    @partial(jax.jit, static_argnames=("cfg", "mesh", "axis"))
    def altered(cfg, mesh, owned, axis="shards"):
        csr = orig(cfg, mesh, owned, axis)
        return csr._replace(adjv=csr.adjv.at[0].set((csr.adjv[0] + 1) % cfg.n))
    return altered


@pytest.mark.parametrize("fault", ["state_unchanged", "half_left_out", "answer_altered",
                                   "shuffle_swapped"])
def test_generation_faults_are_not_correct(monkeypatch, fault):
    cell = _cell(GEN)
    if fault == "state_unchanged":
        monkeypatch.setattr(pipeline, "relabel_ring", _relabel_unchanged)
    elif fault == "half_left_out":
        monkeypatch.setattr(pipeline, "redistribute_sorted",
                            _halved_redistribute(pipeline.redistribute_sorted))
    elif fault == "answer_altered":
        monkeypatch.setattr(pipeline, "build_csr_sorted",
                            _altered_csr(pipeline.build_csr_sorted))
    # shuffle_swapped: the one-shot shuffle in the paper's place, a control
    with controls.control(cell, "one-shot-shuffle") if fault == "shuffle_swapped" \
            else contextlib.nullcontext():
        result = _run(cell)
    assert not result["correct"]
    assert max(_compared(result).values()) > 0
    if fault == "shuffle_swapped":
        assert result["checks"]["pv_mismatch"]["value"] > 0
