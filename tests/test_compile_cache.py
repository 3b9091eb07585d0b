"""Where the entry points put JAX's persistent compilation cache.

Each case runs in a fresh interpreter: JAX reads
``JAX_COMPILATION_CACHE_DIR`` once, at start-up.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

_PROBE = """
import json
import jax
from repro.runtime.compile_cache import enable_compile_cache

path = enable_compile_cache()
jax.jit(lambda x: x * 3 + 1)(jax.numpy.arange(7.0)).block_until_ready()
print(json.dumps({"returned": str(path),
                  "config": jax.config.jax_compilation_cache_dir}))
"""


def _probe(env_dir):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    proc = subprocess.run([sys.executable, "-c", _PROBE], env=env,
                          capture_output=True, text=True, timeout=120,
                          cwd=str(REPO))
    assert proc.returncode == 0, proc.stderr
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", ["env", "checkout"])
def test_compile_cache_location(case, tmp_path):
    if case == "env":
        want = tmp_path / "xla"
        got = _probe(want)
        # JAX's own setting is left alone, and the program lands there.
        assert Path(got["config"]) == want
        assert any(want.iterdir())
    else:
        want = REPO / ".jax_cache"
        got = _probe(None)
        assert Path(got["config"]) == want
        assert want.is_dir() and any(want.iterdir())
    assert Path(got["returned"]) == want
