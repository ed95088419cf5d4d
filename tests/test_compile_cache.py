"""enable_compile_cache: the persistent compilation cache lives where
JAX_COMPILATION_CACHE_DIR says, else at the fixed <checkout>/.jax_cache."""
import os
import subprocess
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

PROBE = """
import jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
path = enable_compile_cache()
print("PATH", path)
print("CONFIG", jax.config.jax_compilation_cache_dir)
if {compile}:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
"""


def _probe(env_dir, compile_):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    r = subprocess.run([sys.executable, "-c",
                        PROBE.format(compile=compile_)],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    lines = dict(l.split(" ", 1) for l in r.stdout.splitlines()
                 if l.startswith(("PATH", "CONFIG")))
    return lines["PATH"], lines["CONFIG"]


def test_env_dir_is_used_and_holds_the_entries(tmp_path):
    default = os.path.join(ROOT, ".jax_cache")
    before = set(os.listdir(default)) if os.path.isdir(default) else set()
    path, config = _probe(str(tmp_path), True)
    assert path == config == str(tmp_path)
    assert os.listdir(tmp_path)                  # entries landed there
    after = set(os.listdir(default)) if os.path.isdir(default) else set()
    assert after == before                       # and nowhere else


def test_default_dir_is_fixed_in_the_checkout():
    first = _probe(None, False)
    second = _probe(None, False)
    want = os.path.join(ROOT, ".jax_cache")
    assert first == second == (want, want)       # no pid, time or temp name
