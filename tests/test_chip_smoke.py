"""``chip_smoke.py`` on the host: it refuses a CPU, and its phases run
end to end when its platform check is shown a TPU."""
import functools
import json
import os
import subprocess
import sys
from pathlib import Path

import jax

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=120, cwd=str(REPO))
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "no TPU" in proc.stderr


class _ReportsTpu:
    platform = "tpu"

    def __init__(self, device):
        self._device = device

    def __getattr__(self, name):
        return getattr(self._device, name)


def test_all_phases_on_host(monkeypatch, capsys):
    """Rehearsal of the one-chip run: every app at registry size, the
    pricer, and the model at its reduced scale."""
    real = jax.devices

    def once():
        monkeypatch.setattr(jax, "devices", real)
        return [_ReportsTpu(d) for d in real()]

    monkeypatch.setattr(jax, "devices", once)
    monkeypatch.setattr(chip_smoke, "model_phase",
                        functools.partial(chip_smoke.model_phase,
                                          scale="reduced"))
    assert chip_smoke.main([]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert sum(ln.startswith("[app ") for ln in lines) == 9
    assert json.loads(lines[-1]) == {"ok": True, "device": {
        "platform": "tpu", "kind": real()[0].device_kind, "count": 1}}
