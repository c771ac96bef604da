"""Where the persistent compilation cache goes (repro.launch.compile_cache).

Each case runs in a fresh interpreter: JAX reads the cache settings once per
process, before its first compile."""

import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

_PROBE = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
print("CACHE", enable_compile_cache())
print("CONFIG", jax.config.jax_compilation_cache_dir)
if {compile}:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones((8,))).block_until_ready()
"""


def _probe(env_dir, compile_):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(ROOT / "src")
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    r = subprocess.run([sys.executable, "-c", _PROBE.format(compile=compile_)],
                       capture_output=True, text=True, timeout=300, env=env,
                       cwd=ROOT)
    assert r.returncode == 0, r.stdout + r.stderr
    return dict(line.split(" ", 1) for line in r.stdout.splitlines()
                if line.startswith(("CACHE", "CONFIG")))


def test_cache_stays_where_the_environment_puts_it(tmp_path):
    out = _probe(tmp_path / "cc", compile_=True)
    assert out["CACHE"] == out["CONFIG"] == str(tmp_path / "cc")
    assert any((tmp_path / "cc").iterdir()), "no cache entry was written"


def test_cache_defaults_to_a_fixed_ignored_path_in_the_checkout():
    out = _probe(None, compile_=False)
    assert out["CACHE"] == out["CONFIG"] == str(ROOT / ".jax_cache")
    ignored = (ROOT / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored
