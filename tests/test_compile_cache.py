"""The persistent compilation cache lands in one fixed place."""
import os
import subprocess
import sys
import textwrap

import jax

from repro.launch import compile_cache

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")


def test_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        assert got == os.path.join(os.path.realpath(ROOT), ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_cache_follows_the_environment(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the helper sets nothing, and the
    entries of a compile land in that directory."""
    script = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.launch.compile_cache import enable_compile_cache
        d = enable_compile_cache()
        assert d == jax.config.jax_compilation_cache_dir, (d, jax.config.jax_compilation_cache_dir)
        jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
        print(d)
    """)
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path), JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", script], env=env, capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == str(tmp_path)
    assert os.listdir(tmp_path), "no cache entry was written"


def test_programs_differing_in_names_only_are_cached_apart(tmp_path):
    """Two programs that differ only in a named scope get two cache entries,
    so each loads its own names into a device profile."""
    script = textwrap.dedent("""
        import os, jax, jax.numpy as jnp
        from repro.launch.compile_cache import enable_compile_cache
        d = enable_compile_cache()
        def named(name):
            @jax.named_scope(name)
            def f(x):
                return jnp.sin(x) @ x.T
            return f
        for name in ("a", "b", "a"):
            jax.jit(named(name))(jnp.ones((64, 64))).block_until_ready()
        print(len([e for e in os.listdir(d) if e.startswith("jit_f")]))
    """)
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path), JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", script], env=env, capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "2", (out.stdout, os.listdir(tmp_path))
