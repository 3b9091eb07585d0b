"""Helpers of the benchmark's CPU tests, and the tiny bench: a copy of ``BENCHMARK.json`` whose
configurations are cut to sizes the CPU runs in a second, with everything
else (traffic, drivers, metrics, limits of ``correct``) as committed."""
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
TINY = {
    "cannon-16384": {"m": 256, "k": 256, "n": 256},
}


def write_tiny_bench(tmp: Path) -> Path:
    """A spec under ``tmp`` naming tiny copies of the configurations."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp / "configs").mkdir(parents=True, exist_ok=True)
    for entry in spec["configs"]:
        config = json.loads((ROOT / entry["file"]).read_text())
        config.update(TINY[entry["name"]])
        path = tmp / "configs" / f"{entry['name']}.json"
        path.write_text(json.dumps(config))
        entry["file"] = str(path.relative_to(tmp))
    spec_path = tmp / "BENCHMARK.json"
    spec_path.write_text(json.dumps(spec))
    return spec_path


def run_python(args: list[str], tmp: Path, devices: int = 4, timeout: int = 600):
    """A child process on ``devices`` CPU devices, with its own compile cache."""
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               JAX_COMPILATION_CACHE_DIR=str(tmp / "jax_cache"))
    return subprocess.run([sys.executable, *args], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=timeout)
