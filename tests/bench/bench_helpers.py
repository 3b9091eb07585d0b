"""Helpers of the benchmark's CPU tests, and the tiny bench: a copy of
``BENCHMARK.json`` whose configurations are cut to their ``cpu_sizes``, the
sizes the CPU runs in a second, with everything else (traffic, drivers,
metrics, limits of ``correct``) as committed.

An overlay is a directory laid out like the repository whose files are
found before the repository's: its ``BENCHMARK.json`` and the
configuration files that names, ``bench/`` (traffic, drivers, metrics),
``tests/bench/faults/`` and ``src/``. A test adds a configuration there as
new files only."""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def _first(rel: str, overlay: Path | None) -> Path:
    """``rel`` in the overlay where it is there, else in the repository."""
    if overlay is not None and (overlay / rel).is_file():
        return overlay / rel
    return ROOT / rel


def read_spec(overlay: Path | None = None) -> dict:
    return json.loads(_first("BENCHMARK.json", overlay).read_text())


def read_config(entry: dict, overlay: Path | None = None) -> dict:
    """The configuration file that a ``configs`` entry names."""
    return json.loads(_first(entry["file"], overlay).read_text())


def write_tiny_bench(tmp: Path, overlay: Path | None = None) -> Path:
    """A spec under ``tmp`` naming tiny copies of the configurations: each
    with the keys of its ``cpu_sizes`` set to those sizes."""
    spec = read_spec(overlay)
    (tmp / "configs").mkdir(parents=True, exist_ok=True)
    for entry in spec["configs"]:
        config = read_config(entry, overlay)
        if "cpu_sizes" not in config:
            raise KeyError(f"{entry['file']} has no 'cpu_sizes': the keys the "
                           f"CPU tests set to run {entry['name']!r} in seconds")
        config.update(config["cpu_sizes"])
        path = tmp / "configs" / f"{entry['name']}.json"
        path.write_text(json.dumps(config))
        entry["file"] = str(path.relative_to(tmp))
    spec_path = tmp / "BENCHMARK.json"
    spec_path.write_text(json.dumps(spec))
    return spec_path


def faults(driver: str, overlay: Path | None = None):
    """The planted faults of ``driver``: ``tests/bench/faults/<driver>.py``."""
    path = _first(f"tests/bench/faults/{driver}.py", overlay)
    if not path.is_file():
        raise FileNotFoundError(f"driver {driver!r} has no planted faults: "
                                f"tests/bench/faults/{driver}.py")
    spec = importlib.util.spec_from_file_location(f"bench_faults_{driver}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_faults(overlay: Path | None = None) -> list[tuple[str, str]]:
    """(fault, cell) for every cell and each fault its driver can suffer on
    the cell's chips."""
    spec = read_spec(overlay)
    configs = {c["name"]: read_config(c, overlay) for c in spec["configs"]}
    out = []
    for cell in spec["workloads"]:
        mod = faults(configs[cell["config"]]["driver"], overlay)
        out += [(fault, cell["name"]) for fault, chips in mod.FAULTS.items()
                if cell["chips"] in chips]
    return out


def run_python(args: list[str], tmp: Path, devices: int = 4, timeout: int = 600):
    """A child process on ``devices`` CPU devices, with its own compile cache."""
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               JAX_COMPILATION_CACHE_DIR=str(tmp / "jax_cache"))
    return subprocess.run([sys.executable, *args], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=timeout)
