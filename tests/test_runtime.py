"""Where the entry points keep JAX's persistent compilation cache."""
import json
import os
import subprocess
import sys

from repro.runtime import CHECKOUT_ROOT

_PROBE = """
import json, jax, jax.numpy as jnp
hits = []
jax.monitoring.register_event_listener(
    lambda e, **k: hits.append(e) if e.endswith("cache_hits") else None)
from repro.runtime import enable_compile_cache
path = enable_compile_cache()
if {compile}:
    jax.jit(lambda x: x * 3.0 + 1.0)(jnp.ones(7)).block_until_ready()
print(json.dumps({{"path": path, "hits": len(hits),
                  "dir": jax.config.jax_compilation_cache_dir}}))
"""


def _probe(env_dir, compile_=True):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(CHECKOUT_ROOT / "src")
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c",
                          _PROBE.format(compile=compile_)],
                         env=env, capture_output=True, text=True, check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_env_dir_is_used_and_a_second_run_hits(tmp_path):
    cache = tmp_path / "xla"
    first = _probe(cache)
    assert first["path"] == str(cache) == first["dir"]
    assert any(cache.iterdir())                  # entries written there
    second = _probe(cache)
    assert second["hits"] >= 1


def test_default_dir_is_fixed_under_the_checkout():
    got = _probe(None, compile_=False)
    assert got["path"] == str(CHECKOUT_ROOT / ".jax_cache") == got["dir"]
