"""Drive runs of tiny cells on CPU devices, with the timed path sound or
broken underneath, and print one JSON line per scenario.

    python tests/bench/run_tiny.py <spec> <scenario>:<cell> ...

Scenarios: ``sound`` (a run; also prints the driver's counts per step),
``readings`` (the control's readings against the limits, as
``bench/calibrate.py`` reads them), ``control`` (a run with the control in
the program's place), and the faults ``unchanged`` (a step returns its state
unchanged), ``no_exchange`` (the exchange between chips left out) and
``altered`` (one answer altered where it is produced). A run skips the
harness's look for a chip and otherwise runs as on the chip.
"""
import json
import sys
from contextlib import ExitStack
from pathlib import Path
from unittest import mock

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import run as harness  # noqa: E402


def _altered(entry):
    def wrapped(*args, **kwargs):
        out = entry(*args, **kwargs)
        return out.at[1, 2].add(1.0 + jnp.abs(out[1]).max())
    return wrapped


def faults(kind: str) -> list:
    """Patches that break the timed path of the Cannon program underneath."""
    from bench.references import matmul as reference
    from repro.matmul import cannon

    zero = lambda a, b, *_: jnp.zeros((a.shape[0], b.shape[1]), jnp.float32)  # noqa: E731
    return {
        "control": [mock.patch.object(
            cannon, "matmul", lambda a, b, _grid: reference.control_product(a, b))],
        "unchanged": [mock.patch.object(cannon, "local_matmul", zero)],
        "no_exchange": [mock.patch.object(cannon, "shift", lambda x, *a: x),
                        mock.patch.object(cannon, "skew", lambda x, *a, **k: x)],
        "altered": [mock.patch.object(cannon, "matmul", _altered(cannon.matmul))],
    }[kind]


def main(spec: str, scenarios: list[str]) -> None:
    bench = harness.Bench(Path(spec), [ROOT / "bench"])
    for item in scenarios:
        kind, cell = item.split(":")
        entry = bench.cell(cell)
        config = bench.config(entry["config"])
        out = {"scenario": kind, "cell": cell}
        if kind == "readings":
            drv = bench.module("drivers", config["driver"]).Driver(
                config, 11, jax.devices()[:entry["chips"]])
            out["readings"] = drv.control()
            out["limits"] = config["check"]
        else:
            with ExitStack() as stack:
                for p in ([] if kind == "sound" else faults(kind)):
                    stack.enter_context(p)
                res, counts = harness.run(bench, cell, 3_000_000_019, 0.3, False,
                                          devices=jax.devices())
            out.update(correct=res["correct"], check=res["check"], **counts)
            if kind == "sound":
                drv = bench.module("drivers", config["driver"]).Driver(
                    config, 11, jax.devices()[:entry["chips"]])
                out["work"] = drv.work()
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2:])
