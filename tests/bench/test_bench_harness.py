"""The harness on the CPU: what it finds by name, what a new cell needs,
and what it does without a chip."""
import json
import re
import textwrap

import pytest

from bench_helpers import ROOT, run_python, write_tiny_bench

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def bench():
    from bench import run as harness

    return harness.Bench(ROOT / "BENCHMARK.json", [ROOT / "bench"])


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_parts_found_by_name(cell):
    b = bench()
    entry = b.cell(cell)
    config = b.config(entry["config"])
    assert b.traffic(entry["traffic"])["procs"] == entry["chips"]
    assert hasattr(b.module("drivers", config["driver"]), "Driver")
    assert set(config["check"]) and all(v > 0 for v in config["check"].values())
    reported = {m["name"] for m in b.metrics("per_layer", cell)}
    assert {"dispatch_ms", "mfu", "device_idle.step"} <= reported
    assert [m["name"] for m in b.metrics("end_to_end", cell)] == ["step_ms", "setup_s"]


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_metric_reader_found_by_name(metric):
    assert callable(bench().module("metrics", metric).read)


def test_spec_names_and_files():
    names = [e["name"] for s in ("configs", "workloads", "end_to_end", "per_layer")
             for e in SPEC[s]]
    assert all(NAME.match(n) for n in names) and len(names) == len(set(names))
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).is_file() and c["file"].startswith("bench/")
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= len(pairs) // 2
    for m in SPEC["per_layer"]:
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
        assert set(m["workloads"]) <= {w["name"] for w in SPEC["workloads"]}
    peaks = json.loads((ROOT / "bench/peaks.json").read_text())
    assert peaks["TPU v5 lite"]["source"]


def test_new_cell_config_and_metric_need_only_new_files(tmp_path):
    """A throwaway configuration, traffic mix, cell and per-layer metric,
    written as files and entries beside copies of the spec, run through the
    committed harness and drivers without an edit to any committed file."""
    spec_path = write_tiny_bench(tmp_path)
    spec = json.loads(spec_path.read_text())
    config = json.loads((ROOT / "bench/configs/cannon-16384.json").read_text())
    config.update(m=128, k=64, n=256)
    (tmp_path / "configs/rect-128x64x256.json").write_text(json.dumps(config))
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic/closed1.throwaway.json").write_text(json.dumps(
        {"loop": "closed", "callers": 1, "procs": 1, "checked_calls": 2}))
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics/flops_per_step.py").write_text(textwrap.dedent("""
        def read(ctx):
            return float(ctx.work["flops"])
    """))
    spec["configs"].append({"name": "rect-128x64x256", "source": "test",
                            "file": "configs/rect-128x64x256.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "rect.x1", "config": "rect-128x64x256",
                              "traffic": "closed1.throwaway", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "flops_per_step", "unit": "FLOP",
                              "better": "higher", "source": "program_counter",
                              "layer": "test", "moves": "step_ms",
                              "workloads": ["rect.x1"]})
    spec_path.write_text(json.dumps(spec))
    before = {p: p.read_bytes() for p in (ROOT / "bench").rglob("*")
              if p.is_file() and "__pycache__" not in p.parts}
    script = textwrap.dedent(f"""
        import json, sys
        sys.path[:0] = [{str(ROOT)!r}, {str(ROOT / "src")!r}]
        from pathlib import Path
        import jax
        from bench import run as harness
        b = harness.Bench(Path({str(spec_path)!r}),
                          [Path({str(tmp_path)!r}), Path({str(ROOT / "bench")!r})])
        for traced in (False, True):
            print(json.dumps(harness.run(b, "rect.x1", 5, 0.2, traced,
                                         devices=jax.devices())[0]))
    """)
    proc = run_python(["-c", script], tmp_path, devices=1)
    assert proc.returncode == 0, proc.stderr[-4000:]
    plain, traced = [json.loads(ln) for ln in proc.stdout.splitlines()
                     if ln.startswith("{")]
    assert plain["correct"] and traced["correct"]
    assert set(plain["metrics"]) == {"step_ms", "setup_s"}
    assert traced["metrics"]["flops_per_step"]["value"] == 2 * 128 * 64 * 256
    assert list(plain)[-1] == list(traced)[-1] == "check"
    assert {"busy_s", "window_s"} <= set(traced["device"])
    assert set(traced["breakdown"]) == {"device_ops", "idle_gaps"}
    after = {p: p.read_bytes() for p in (ROOT / "bench").rglob("*")
             if p.is_file() and "__pycache__" not in p.parts}
    assert after == before


def test_tiny_bench_needs_cpu_sizes(tmp_path):
    """A configuration without ``cpu_sizes`` is refused by name, not run at
    its chip sizes on the CPU."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    config = json.loads((ROOT / "bench/configs/cannon-16384.json").read_text())
    del config["cpu_sizes"]
    (tmp_path / "bench/configs").mkdir(parents=True)
    (tmp_path / "bench/configs/bare.json").write_text(json.dumps(config))
    spec["configs"].append({"name": "bare", "source": "test",
                            "file": "bench/configs/bare.json", "reduced": [],
                            "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    with pytest.raises(KeyError, match="bench/configs/bare.json has no 'cpu_sizes'"):
        write_tiny_bench(tmp_path / "tiny", overlay=tmp_path)


def test_without_a_chip_the_command_fails_and_prints_no_result(tmp_path):
    proc = run_python(["bench/run.py", "--workload", "cannon-16384.x1",
                       "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
                      tmp_path, devices=1)
    assert proc.returncode != 0
    assert "{" not in proc.stdout and "step_ms" not in proc.stdout
    assert "no accelerator" in proc.stderr
