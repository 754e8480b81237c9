"""repro.compile_cache: where the entry points' persistent cache lands.

Each case runs in a fresh interpreter: JAX fixes its cache directory the
first time it compiles, and the test workers have compiled long before."""
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

PROBE = """
import json, os, jax, jax.numpy as jnp
from repro.compile_cache import DEFAULT_DIR, enable_compile_cache
where = enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
if os.environ.get("PROBE_COMPILE"):
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(8.0)).block_until_ready()
print(json.dumps({"where": where, "default": str(DEFAULT_DIR),
                  "config": jax.config.jax_compilation_cache_dir}))
"""


def _probe(env_dir=None, compile_=False):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    if compile_:
        env["PROBE_COMPILE"] = "1"
    out = subprocess.run([sys.executable, "-c", PROBE], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_env_dir_wins_and_receives_the_cache(tmp_path):
    got = _probe(env_dir=tmp_path, compile_=True)
    assert got["where"] == got["config"] == str(tmp_path)
    assert any(tmp_path.iterdir()), "nothing was written to the env dir"


def test_default_dir_is_in_the_checkout_and_ignored():
    got = _probe()
    default = Path(got["default"])
    assert got["where"] == got["config"] == str(default)
    assert default.parent == ROOT
    ignored = (ROOT / ".gitignore").read_text().split()
    assert f"{default.name}/" in ignored or default.name in ignored
