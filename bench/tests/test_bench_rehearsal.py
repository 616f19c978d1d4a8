"""The benchmark's command end to end on host-CPU devices (``--rehearse``:
the smoke sizes of each configuration and mix), its last line read
against the benchmark's output contract, and what it refuses."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
SEED = 2 ** 31 + 12345          # larger than 32 signed bits hold


def bench(root, workload, *, trace=0, rehearse=True, timeout=600):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, "bench/run.py", "--workload", workload,
           "--seed", str(SEED), "--seconds", "1", "--trace", str(trace)]
    if rehearse:
        cmd.append("--rehearse")
    return subprocess.run(cmd, cwd=root, env=env, capture_output=True,
                          text=True, timeout=timeout)


def result(p, spec, cell, trace):
    """The last line, checked against the contract; returns it."""
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    for k in ("correct", "attempted", "failed", "metrics", "device"):
        assert k in out
    dev = out["device"]
    assert dev["platform"] == "cpu" and dev["count"] == cell["chips"]
    assert isinstance(dev["memory_peak_bytes"], int)
    group = spec["per_layer"] if trace else spec["end_to_end"]
    allowed = {m["name"]: m["unit"] for m in group
               if cell["name"] in m.get("workloads", [cell["name"]])}
    assert set(out["metrics"]) <= set(allowed)
    for name, m in out["metrics"].items():
        assert m["unit"] == allowed[name] and m["value"] > 0
    if trace:
        assert {"busy_s", "window_s"} <= set(dev)
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(out)[-1] == "compared"
    tail = p.stderr.strip().splitlines()[-len(out["compared"]):]
    assert all(line.startswith("compared ") for line in tail)
    return out


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_cell_rehearses(cell):
    p = bench(ROOT, cell["name"])
    out = result(p, SPEC, cell, 0)
    assert out["correct"] is True and out["failed"] == 0
    assert "setup_s" in out["metrics"] and len(out["metrics"]) >= 2


def test_traced_reload_cell():
    cell = next(c for c in SPEC["workloads"] if "reload" in c["name"])
    out = result(bench(ROOT, cell["name"], trace=1), SPEC, cell, 1)
    # host spans and JAX's compile durations; no device metric from a CPU
    assert {"replace_ms", "retrace_s"} <= set(out["metrics"])
    assert out["correct"] is True


def test_chip_path_refuses_cpu():
    p = bench(ROOT, SPEC["workloads"][0]["name"], rehearse=False)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_bare_checkout_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for d in SPEC["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = bench(tmp_path, SPEC["workloads"][0]["name"])
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_new_cell_metric_and_mix_are_files_only(tmp_path):
    """A later change adds a mix, a metric and a cell (here also the
    four-device OLMoE file set) by adding files and entries alone."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "src").symlink_to(ROOT / "src")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    mix = json.loads((ROOT / "bench/traffic/train_b1_s4096.json").read_text())
    mix["smoke"] = {"batch": 4, "seq": 32}
    (tmp_path / "bench/traffic/train_b4_extra.json").write_text(
        json.dumps(mix))
    (tmp_path / "bench/metrics/window_steps.py").write_text(
        "def read(rec):\n    return rec['steps']\n")
    cells = [{"name": "qwen3-1.7b.extra", "config": "qwen3-1.7b",
              "traffic": "train_b4_extra", "chips": 1, "why": "test"},
             {"name": "olmoe-1b-7b.train.2x2.size_aware",
              "config": "olmoe-1b-7b", "traffic": "train_dp2_tp2_b4_s4096",
              "chips": 4, "why": "test"}]
    spec["workloads"] += cells
    if not any(c["name"] == "olmoe-1b-7b" for c in spec["configs"]):
        spec["configs"].append(
            {"name": "olmoe-1b-7b", "source": "test",
             "file": "bench/configs/olmoe-1b-7b.json",
             "reduced": ["num_hidden_layers"], "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "tokens_per_s":
            m["workloads"] = m["workloads"] + [c["name"] for c in cells]
    spec["per_layer"].append(
        {"name": "window_steps", "unit": "steps", "better": "higher",
         "source": "host_clock", "layer": "trainer", "moves": "tokens_per_s",
         "workloads": [c["name"] for c in cells]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    for cell in cells:
        out = result(bench(tmp_path, cell["name"], trace=1), spec, cell, 1)
        assert out["metrics"]["window_steps"]["value"] >= 2
