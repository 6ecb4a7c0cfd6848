"""``repro.compile_cache``: where the persistent compile cache goes.

Each case runs in a fresh interpreter, since JAX fixes its cache directory
at the first compile of a process."""

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = textwrap.dedent("""
    import sys
    import jax
    before = jax.config.jax_compilation_cache_dir
    import repro.pems_apps
    assert jax.config.jax_compilation_cache_dir == before, \\
        "importing the library changed the cache directory"
    from repro.compile_cache import DEFAULT_CACHE_DIR, enable_compile_cache
    print("DIR", enable_compile_cache())
    print("CONFIG", jax.config.jax_compilation_cache_dir)
    print("DEFAULT", DEFAULT_CACHE_DIR)
    if sys.argv[1] == "compile":
        jax.jit(lambda x: x * 3 + 1)(jax.numpy.arange(8)).block_until_ready()
""")


def _probe(tmp_path, action, **env_extra):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(REPO, "src"),
               **env_extra)
    r = subprocess.run([sys.executable, "-c", _PROBE, action],
                       capture_output=True, text=True, timeout=300,
                       cwd=str(tmp_path), env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    return dict(ln.split(" ", 1) for ln in r.stdout.splitlines())


def test_env_dir_is_used_and_written(tmp_path):
    cache = tmp_path / "cache"
    out = _probe(tmp_path, "compile", JAX_COMPILATION_CACHE_DIR=str(cache),
                 JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
                 JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    assert out["DIR"] == out["CONFIG"] == str(cache)
    assert cache.is_dir() and any(cache.iterdir())
    # Nothing else in the working directory, and not the checkout's default.
    assert sorted(p.name for p in tmp_path.iterdir()) == ["cache"]


def test_default_dir_is_fixed_inside_checkout(tmp_path):
    out = _probe(tmp_path, "no-compile")
    assert out["DIR"] == out["CONFIG"] == out["DEFAULT"]
    assert out["DEFAULT"] == os.path.join(REPO, ".jax_cache")
