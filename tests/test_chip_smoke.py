"""The chip entry path off the chip: ``chip_smoke.py`` refuses a machine
without a TPU, and the compile cache lands where it should."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

import chip_smoke
from repro.launch.compile_cache import DEFAULT_DIR, setup_compile_cache

ROOT = Path(__file__).resolve().parent.parent


def test_chip_smoke_refuses_cpu():
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert "'cpu'" in str(exc.value.code)


def test_default_cache_dir_is_fixed_and_ignored(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert setup_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    ignored = (ROOT / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored


def test_env_cache_dir_gets_every_entry(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, compiled programs land there
    and the checkout's default directory gains nothing."""
    before = set(os.listdir(DEFAULT_DIR)) if DEFAULT_DIR.exists() else set()
    script = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.launch.compile_cache import setup_compile_cache
        print(setup_compile_cache())
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
    """)
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"),
                                           os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run([sys.executable, "-c", script], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip() == str(tmp_path)
    assert any(n.startswith("jit__lambda") for n in os.listdir(tmp_path))
    after = set(os.listdir(DEFAULT_DIR)) if DEFAULT_DIR.exists() else set()
    assert after == before
