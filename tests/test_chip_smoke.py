"""The chip smoke (`chip_smoke.py`) and the compile-cache placement it uses.

The smoke itself needs a TPU and refuses to start without one; its
serving-and-checking phases are plain functions, so here they run at a
tiny Graph500 scale on the CPU, which keeps every check in them honest
between chip runs.
"""
from __future__ import annotations

import os
import pathlib
import subprocess
import sys

import jax
import pytest

from repro import compile_cache

ROOT = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def chip_smoke():
    sys.path.insert(0, str(ROOT))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(ROOT))
    return chip_smoke


def test_smoke_refuses_to_run_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         env=env, cwd=tmp_path, capture_output=True,
                         text=True, timeout=120)
    assert res.returncode != 0
    assert "needs a TPU" in res.stderr
    assert '"ok"' not in res.stdout


def test_smoke_serving_phase_checks_pass_on_a_tiny_graph(chip_smoke, capsys):
    chip_smoke.one_chip(jax.devices(), scale=10, corpus=300)
    out = capsys.readouterr().out
    assert "check ok: cc == cc_baseline" in out
    assert "pr served by the 'xla' path" in out
    assert "check ok: warm answers == cold answers" in out


def test_smoke_sharded_phase_checks_pass_on_a_tiny_graph(chip_smoke,
                                                         capsys):
    chip_smoke.four_chips(jax.devices(), scale=10, corpus=300)
    out = capsys.readouterr().out
    assert "check ok: sharded knn == single-device knn" in out


def test_smoke_check_raises_on_a_mismatch(chip_smoke):
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check(False, "a wrong answer")


def test_compile_cache_dir_follows_the_environment(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.compile_cache_dir() == tmp_path
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache.compile_cache_dir() == ROOT / ".jax_cache"


def test_compile_cache_entries_land_only_in_the_named_dir(tmp_path):
    """In a child process, so this process never turns the cache on."""
    cache = tmp_path / "cache"
    prog = ("import jax, jax.numpy as jnp\n"
            "from repro.compile_cache import enable_compile_cache\n"
            "enable_compile_cache()\n"
            "jax.config.update('jax_persistent_cache_min_compile_time_secs',"
            " 0)\n"
            "jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8))"
            ".block_until_ready()\n")
    local = ROOT / ".jax_cache"
    before = sorted(local.iterdir()) if local.exists() else None
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache),
               PYTHONPATH=str(ROOT / "src"))
    res = subprocess.run([sys.executable, "-c", prog], env=env, cwd=tmp_path,
                         capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr
    assert any(cache.iterdir())
    assert (sorted(local.iterdir()) if local.exists() else None) == before
