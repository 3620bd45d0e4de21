"""repro.compile_cache: JAX_COMPILATION_CACHE_DIR stands where it is
set, and otherwise the cache goes to the checkout's fixed ``.jax_cache``.

Each case runs in a child process: turning the cache on changes
process-wide JAX config, which the test process must keep off.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

_CHILD = """
import json, jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
path = enable_compile_cache()
jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
print(json.dumps({
    "path": path,
    "config_dir": jax.config.jax_compilation_cache_dir,
    "min_secs": jax.config.jax_persistent_cache_min_compile_time_secs,
}))
"""


def _run(cwd, env_dir, code=_CHILD):
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=cwd, env=env,
        check=True, capture_output=True, text=True,
    ).stdout
    return json.loads(out.strip().splitlines()[-1])


def test_env_var_dir_is_respected(tmp_path):
    cache = tmp_path / "cache"
    got = _run(tmp_path, cache)
    assert got["path"] == str(cache)
    assert got["config_dir"] == str(cache)
    assert got["min_secs"] == 0
    # the compile above was cached there, and nothing went to the cwd
    assert any(cache.iterdir())
    assert sorted(p.name for p in tmp_path.iterdir()) == ["cache"]


def test_default_dir_is_fixed_inside_the_checkout(tmp_path):
    code = (
        "import json, jax\n"
        "from repro.compile_cache import enable_compile_cache\n"
        "print(json.dumps({'path': enable_compile_cache(),\n"
        "  'config_dir': jax.config.jax_compilation_cache_dir}))\n"
    )
    a = _run(tmp_path, None, code)
    b = _run(ROOT, None, code)
    assert a == b
    assert a["path"] == str(ROOT / ".jax_cache")
    assert a["config_dir"] == a["path"]


def test_default_dir_is_gitignored():
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
