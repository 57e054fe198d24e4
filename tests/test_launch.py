"""Launcher set-up (compile cache, compile clock) and the chip smoke
script, on the CPU."""

import importlib.util
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp

from repro.launch.mesh import CHECKOUT_CACHE_DIR, compile_clock, use_compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_compile_cache_follows_env_else_checkout(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(ROOT / "elsewhere"))
        assert use_compile_cache() == str(ROOT / "elsewhere")
        assert jax.config.jax_compilation_cache_dir == before  # left to JAX
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert use_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_clock_splits_compile_from_run():
    f = jax.jit(lambda x: jnp.sin(x) * 2.0)
    x = jnp.ones(8)
    with compile_clock() as cold:
        f(x).block_until_ready()
    with compile_clock() as warm:
        f(x).block_until_ready()
    assert cold.seconds > 0.0
    assert warm.seconds == 0.0


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")], env=env, cwd=ROOT,
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    assert "phase=" not in r.stdout and '"ok"' not in r.stdout


def test_chip_smoke_one_chip_phases_at_tiny_scale(capsys):
    """Every check of the one-chip smoke, on a scale-8 graph."""
    _chip_smoke().one_chip(scale=8)
    out = capsys.readouterr().out
    for phase in ("graph", "solve", "solve_batch", "router", "update",
                  "cold_after_update"):
        assert f"phase={phase} " in out
