"""``correct`` comes out false when the timed path is broken, and the
control fails the limits that sound runs pass.

Each case runs the benchmark's command in a host-CPU process at the
smoke sizes (``--rehearse``) with the program's training step broken
underneath, and reads its last line.  The control is the reference in
the program's place at float8 (``bench/calibrate.py``)."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = SPEC["workloads"][0]["name"]          # the one-chip training cell
LIMITS = json.loads((ROOT / "bench/configs/qwen3-1.7b.json").read_text()
                    )["limits"]

FAULTS = {
    # the step hands back its parameters and optimizer state unchanged
    "state_unchanged": """
        import repro.train.step as st
        orig = st.adamw_update
        def fault(params, grads, state, cfg, lr_scale=1.0):
            _, _, metrics = orig(params, grads, state, cfg, lr_scale)
            return params, state, metrics
        st.adamw_update = fault
    """,
    # half of the batch left out, the mean taken over the rest
    "half_batch": """
        import repro.train.step as st
        orig = st.loss_fn
        def fault(params, batch, cfg, ax):
            B, S = batch["tokens"].shape
            cut = (lambda v: v[: B // 2]) if B > 1 else \\
                (lambda v: v[:, : S // 2])
            return orig(params, {k: cut(v) for k, v in batch.items()},
                        cfg, ax)
        st.loss_fn = fault
    """,
    # the answer altered where it is produced: the loss off by one percent
    "loss_altered": """
        import repro.train.step as st
        orig = st.loss_fn
        def fault(params, batch, cfg, ax):
            return orig(params, batch, cfg, ax) * 1.01
        st.loss_fn = fault
    """,
}


def run_py(code, timeout=600):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_step_is_not_correct(fault):
    code = "import sys\nsys.path[:0] = ['src', '.']\n" + \
        textwrap.dedent(FAULTS[fault]) + textwrap.dedent(f"""
        from bench import run
        sys.exit(run.main(["--workload", "{CELL}", "--seed", "31",
                           "--seconds", "1", "--rehearse"]))
    """)
    p = run_py(code)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is False, out["compared"]


def test_control_fails_and_program_passes():
    """At the smoke sizes the control's loss gap is over the limit on 10
    of 12 seeds tried (1, ..., 6, 41, ..., 46); seed 41 reads 5.6e-3."""
    p = subprocess.run(
        [sys.executable, "bench/calibrate.py", "--workload", CELL,
         "--seeds", "41", "--faults", "--rehearse"], cwd=ROOT,
        env={**{k: v for k, v in os.environ.items() if k != "XLA_FLAGS"},
             "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    rows = {r["reading"]: r for r in
            (json.loads(x) for x in p.stdout.strip().splitlines())}
    over = {name: [k for k in LIMITS if r[k] > LIMITS[k]]
            for name, r in rows.items()}
    assert over["program"] == [], rows["program"]
    assert over["control"], rows["control"]
    assert over["half_batch"], rows["half_batch"]
