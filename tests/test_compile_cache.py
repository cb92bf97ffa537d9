"""The persistent compilation cache lands where ``enable_compile_cache``
says: ``JAX_COMPILATION_CACHE_DIR`` when it is set (left to JAX), else
``<checkout>/.jax_cache``.  Each case runs in a child process, because
the cache directory is process-wide JAX state fixed at first compile."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]

_CHILD = textwrap.dedent("""\
    import json
    import jax, jax.numpy as jnp
    from repro.launch.compile_cache import enable_compile_cache

    where = enable_compile_cache()
    configured = jax.config.jax_compilation_cache_dir
    # cache even a sub-second compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.jit(lambda x: jnp.sin(x) * 3.0)(jnp.arange(8.0)).block_until_ready()
    print(json.dumps({"where": where, "configured": configured}))
""")


def _run(cache_dir=None) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"       # never the chip
    env["PYTHONPATH"] = str(REPO / "src")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    out = subprocess.run(
        [sys.executable, "-c", _CHILD], env=env, capture_output=True,
        text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_env_dir_is_left_to_jax_and_receives_the_cache(tmp_path):
    got = _run(tmp_path)
    assert got["where"] == str(tmp_path)
    assert got["configured"] == str(tmp_path)
    assert any(tmp_path.iterdir()), "no cache entry was written"


def test_default_dir_is_the_checkout():
    got = _run()
    cache = REPO / ".jax_cache"
    assert got["where"] == str(cache)
    assert got["configured"] == str(cache)
    assert any(cache.iterdir()), "no cache entry was written"
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
