"""The persistent compilation cache has one home: the directory
``JAX_COMPILATION_CACHE_DIR`` names, else ``<repo>/.jax_cache``.

Each case runs in a fresh interpreter so the cache setting never leaks
into this test process."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = r"""
import json, jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
from repro.launch.cache import enable_compile_cache
path = enable_compile_cache()
jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
print(json.dumps({"path": path,
                  "config": jax.config.jax_compilation_cache_dir}))
"""


def _probe(env_dir):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    res = subprocess.run([sys.executable, "-c", _PROBE], env=env,
                         capture_output=True, text=True, timeout=240)
    assert res.returncode == 0, res.stderr[-2000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_lands_in_its_one_directory(tmp_path, from_env):
    if from_env:
        want = str(tmp_path / "cache")
        out = _probe(want)
        assert out == {"path": want, "config": want}
        assert os.listdir(want), "nothing was cached in the env directory"
    else:
        out = _probe(None)
        want = os.path.join(REPO, ".jax_cache")
        assert out == {"path": want, "config": want}
        assert os.path.isdir(want)
