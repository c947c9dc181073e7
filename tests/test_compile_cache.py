"""Where the persistent compilation cache lives.

``$JAX_COMPILATION_CACHE_DIR`` wins over any directory named in code, and a
second process finds what the first one cached; without the variable and
without a named directory the cache sits at the fixed ``.jax_cache/`` of the
checkout. The variable is read by JAX at import, so its case runs in child
processes.
"""
import json
import os
import subprocess
import sys

import jax

from repro.ckpt import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = """
import json, sys
import jax, jax.numpy as jnp
from repro.ckpt import compile_cache
where = compile_cache.enable(sys.argv[1])
ev = compile_cache.CompileEvents()
jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)(jnp.arange(8.0)).block_until_ready()
misses, hits = ev.delta()
print(json.dumps({"dir": where, "misses": misses, "hits": hits}))
"""


def _child(env_dir, named_dir):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=env_dir,
               PYTHONPATH=os.path.join(ROOT, "src") + os.pathsep +
               os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", _CHILD, named_dir],
                          capture_output=True, text=True, timeout=300,
                          env=env)
    assert proc.returncode == 0, proc.stderr
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_env_dir_wins_and_a_second_process_hits(tmp_path):
    env_dir, named = str(tmp_path / "env"), str(tmp_path / "named")
    first = _child(env_dir, named)
    second = _child(env_dir, named)
    assert first["dir"] == second["dir"] == env_dir
    assert first["misses"] >= 1 and first["hits"] == 0
    assert second["hits"] >= 1 and second["misses"] == 0
    assert os.listdir(env_dir)
    assert not os.path.exists(named)


def test_default_dir_is_fixed_in_the_checkout(cache_dir, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    where = compile_cache.enable()
    assert where == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == where
    assert compile_cache.enable(cache_dir) == cache_dir
    assert jax.config.jax_compilation_cache_dir == cache_dir
