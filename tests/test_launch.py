"""Start-up behaviour of the entry points: importing the package opens no
JAX backend (so the disk tier's worker processes never claim the chip), and
the persistent compilation cache lands in one fixed place."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def test_import_starts_no_backend():
    body = (
        "import repro.core, repro.core.phases, repro.launch.cluster, repro.data.walks\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized(), 'import started a JAX backend'\n"
        "print('NOBACKEND')\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    r = subprocess.run([sys.executable, "-c", body], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "NOBACKEND" in r.stdout


@pytest.mark.parametrize("env_set", [True, False], ids=["env-set", "env-unset"])
def test_compile_cache_dir(monkeypatch, tmp_path, env_set):
    before = jax.config.jax_compilation_cache_dir
    try:
        if env_set:
            monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
            assert compile_cache.use_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before  # set nothing
        else:
            monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
            first = compile_cache.use_compile_cache()
            second = compile_cache.use_compile_cache()
            assert first == second == str(ROOT / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
