"""The readers of the program's spans: both cells rehearsed with
``--trace 1`` report them, and the spans they read nest; a recorded v5e
profile puts the device's wait for a batch under the program's span."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from bench import trace_reduce as tr
from bench.tests.test_bench_rehearsal import ROOT, SEED, SPEC, result

DATA = Path(__file__).resolve().parent.parent / "testdata"

NEW = {"qwen3-1.7b.train.1chip": {"data_wait_ms": "ms", "batch_make_s": "s",
                                  "device_wait_ms": "ms"},
       "qwen3-1.7b.reload.1chip": {"swap_data_wait_s": "s",
                                   "swap_dispatch_s": "s",
                                   "batch_use_ratio": "%",
                                   "reload_batch_make_s": "s"}}

# bench/run.py in one process, then the program's spans written out
DRIVE = """
import json, sys
out = sys.argv.pop(1)
sys.path.insert(0, ".")
from bench import run
rc = run.main(sys.argv[1:])
from repro.obs import spans
with open(out, "w") as f:
    json.dump(spans.snapshot(), f)
sys.exit(rc)
"""


@pytest.mark.parametrize("name", sorted(NEW))
def test_traced_cell_reports_span_metrics(name, tmp_path):
    cell = next(c for c in SPEC["workloads"] if c["name"] == name)
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    snap = tmp_path / "spans.json"
    p = subprocess.run(
        [sys.executable, "-c", DRIVE, str(snap), "--workload", name,
         "--seed", str(SEED), "--seconds", "1", "--trace", "1",
         "--rehearse"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    out = result(p, SPEC, cell, 1)
    assert out["correct"] is True
    got = {k: v["unit"] for k, v in out["metrics"].items() if k in NEW[name]}
    assert got == NEW[name]

    held = json.loads(snap.read_text())
    assert held["dropped"] == 0
    kids = {}
    for s in held["spans"]:
        kids.setdefault(s["parent"], []).append(s)
    steps = [s for s in held["spans"] if s["name"] == "repro.train.step"]
    assert len(steps) == out["attempted"]
    for st in steps:
        took = [s["end_ns"] - s["start_ns"] for s in kids[st["id"]]]
        assert len(took) >= 5 and sum(took) <= st["end_ns"] - st["start_ns"]


def test_recorded_v5e_spans():
    """Recorded on one TPU v5e by bench/tools/record_spans.py (the part the
    reduction reads): two steps of a one-layer smoke trainer whose batches
    take 0.2 s.  The device's 208 ms gap between the steps lies under the
    trainer's wait for the second batch once the device clock is put on
    the host's; the 11 us of gaps inside the steps lie under its wait for
    the device."""
    pd = jax.profiler.ProfileData.from_file(str(DATA / "v5e_spans.xplane.pb"))
    r = tr.reduce(*tr.load(pd), steps=2, window_s=0.5, labels=("repro.",))
    assert r["devices"] == 1
    assert r["busy_s"] == pytest.approx(122440e-9)
    assert r["idle_gaps"] == [
        ["repro.data.wait", pytest.approx(0.208010247)],
        ["repro.train.device_wait", pytest.approx(11442e-9)]]
    # the benchmark's own labels find none of the program's spans
    plain = tr.reduce(*tr.load(pd), steps=2, window_s=0.5)
    assert plain["idle_gaps"] == [
        ["unlabelled", pytest.approx(0.208010247 + 11442e-9)]]
