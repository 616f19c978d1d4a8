"""Table 2 + Fig 2 reproduction: message-size-aware policy vs default.

Two parts:
(a) calibrated cost-model sweep on the NVLINK_B300 profile — reproduces
    the paper's crossover structure and the policy's +5..27% band, with
    fit residuals against the published Ring column.
(b) REAL wall-clock sweeps on an 8-device host-CPU mesh (subprocess so
    this process keeps 1 device): the open-loop default-vs-policy legs,
    plus the CLOSED-LOOP sweep — per-device telemetry shards merge
    through ``dispatcher.sync_telemetry()`` and the tuner's per-size
    choices (tree below its EMA threshold, ring at/above) are measured
    against the default.  CPU interconnect ≠ NVLink: we report real
    deltas without claiming the paper's magnitudes.  A driver failure
    raises (the suite harness counts it) and surfaces the full stderr
    tail; the CI entry point is :func:`ci_closed_loop`.

Part (b) is a CPU rehearsal and stays off the chip: it starts its driver
as a child process after this process may have touched JAX (a chip
belongs to one process), and the driver forces 8 host-CPU devices.  No
number it prints is a device metric.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Optional, Tuple

from repro.collectives.cost_model import NVLINK_B300, CostModel
from repro.core import PolicyRuntime, make_ctx
from repro.core.context import Algo, CollType, Proto
from repro.policies import ring_mid_v2

MiB = 1 << 20
STDERR_TAIL = 4000

# published Table 2 (GB/s): size -> (default NVLS, ring c=32)
PAPER_TABLE2 = {
    4: (133.5, 148.1), 8: (196.3, 249.7), 16: (278.8, 337.4),
    32: (349.3, 402.4), 64: (425.2, 471.8), 128: (596.9, 628.9),
    256: (656.5, 632.5), 8192: (836.3, 697.6),
}


def extract_decision(ctx, ret: Optional[int], *,
                     default: Tuple[int, int, int] = (Algo.DEFAULT,
                                                      Proto.SIMPLE, 8)
                     ) -> Tuple[int, int, int, bool]:
    """Read a tuner chain's decision out of its ctx, with the runtime's
    deferral convention made explicit.

    Returns ``(algo, proto, channels, from_policy)``.  The chain
    deferred iff ``ret is None`` (no link ran / every link deferred) or
    all three outputs are still zero (the all-untouched sentinel) — in
    which case the supplied defaults apply.  This replaces the old
    ``ctx["algorithm"] or Algo.DEFAULT`` / ``ctx["n_channels"] or 8``
    idiom, whose falsy-zero semantics conflated a policy that DECIDED
    ``Algo.DEFAULT`` (a legitimate choice: the NVLS-analogue lowering)
    with one that deferred, and silently replaced an explicit
    0-channel decision (invalid, should surface) with the default.
    """
    algo = ctx["algorithm"]
    proto = ctx["protocol"]
    ch = ctx["n_channels"]
    if ret is None or (algo == 0 and proto == 0 and ch == 0):
        return default[0], default[1], default[2], False
    return algo, proto, ch, True


def _run_driver(which: str, timeout: int = 1200):
    """Run the 8-device subprocess driver; raise with the full stderr
    tail on failure so suite harness and CI both gate on it."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(repo, "src")
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "benchmarks",
                                      "_allreduce_driver.py"), which],
        env=env, capture_output=True, text=True, timeout=timeout)
    rows = []
    if out.returncode == 0:
        for line in out.stdout.splitlines():
            if line.startswith("{"):
                rows.append(json.loads(line))
    return out, rows


def run(report):
    cm = CostModel(NVLINK_B300)
    rt = PolicyRuntime()
    rt.load(ring_mid_v2.program)

    for size_mib, (bw_def_paper, bw_ring_paper) in PAPER_TABLE2.items():
        size = size_mib * MiB
        bw_def = cm.bus_bandwidth(CollType.ALL_REDUCE, Algo.DEFAULT,
                                  Proto.SIMPLE, 8, size, 8) / 1e9
        bw_ring = cm.bus_bandwidth(CollType.ALL_REDUCE, Algo.RING,
                                   Proto.SIMPLE, 32, size, 8) / 1e9

        # what the verified policy picks
        ctx = make_ctx("tuner", coll_type=CollType.ALL_REDUCE,
                       msg_size=size, n_ranks=8, max_channels=32)
        ret = rt.invoke("tuner", ctx)
        algo, proto, ch, from_policy = extract_decision(ctx, ret)
        bw_pol = cm.bus_bandwidth(CollType.ALL_REDUCE, algo, proto, ch,
                                  size, 8) / 1e9
        report("table2_model", f"{size_mib}MiB",
               default_gbs=round(bw_def, 1), ring_gbs=round(bw_ring, 1),
               policy_gbs=round(bw_pol, 1),
               policy_choice=f"{Algo.NAMES[algo]}/{Proto.NAMES[proto]}/c{ch}",
               from_policy=from_policy,
               policy_vs_default_pct=round(100 * (bw_pol / bw_def - 1), 1),
               paper_default_gbs=bw_def_paper,
               paper_ring_gbs=bw_ring_paper,
               fit_err_ring_pct=round(100 * (bw_ring / bw_ring_paper - 1), 1))

    # ---- real 8-device sweeps (subprocess) -------------------------------
    out, rows = _run_driver("all")
    if out.returncode != 0:
        tail = out.stderr[-STDERR_TAIL:]
        report("table2_real", "driver_failed", returncode=out.returncode,
               stderr_tail=tail)
        # gate: a dead driver is a failed suite, not a silent skip
        raise RuntimeError(
            f"8-device AllReduce driver exited {out.returncode}; "
            f"stderr tail:\n{tail}")
    for rec in rows:
        rec = dict(rec)
        name = rec.pop("name")
        section = "table2_closed_loop" if name.startswith("closed_") \
            else "table2_real"
        report(section, name, **rec)


def ci_closed_loop(out: str = "BENCH_table1.json") -> dict:
    """CI leg: run the closed-loop 8-device sweep and land its rows in
    ``BENCH_table1.json`` under ``table2_closed_loop``.

    Gates on: driver success, at least one warm decision coming from
    the policy, AND the per-size band structure — the tuner must pick
    tree below its EMA threshold and ring at/above it (the per-size
    choice is the point of the closed loop; wall-clock deltas are
    recorded but not gated on a CPU mesh).
    """
    proc, rows = _run_driver("closed")
    rec: dict = {"suite": "table2_closed_loop", "rows": rows}
    if proc.returncode != 0:
        rec["ok"] = False
        rec["returncode"] = proc.returncode
        rec["stderr_tail"] = proc.stderr[-STDERR_TAIL:]
        return rec

    problems = []
    if not rows:
        problems.append("driver emitted no closed-loop rows")
    warm_from_policy = [r for r in rows
                        if r.get("warm_choice", {}).get("from_policy")]
    if not warm_from_policy:
        problems.append("no warm decision came from the policy")
    for r in rows:
        cold = r.get("cold_choice", {})
        if cold.get("from_policy"):
            problems.append(f"{r['name']}: cold decision unexpectedly "
                            "came from the policy (telemetry leaked)")
    threshold = 262144          # bucket_tuner's LARGE_EMA
    for r in warm_from_policy:
        want = "ring" if r["size_bytes"] >= threshold else "tree"
        got = r["warm_choice"]["algo"]
        if got != want:
            problems.append(f"{r['name']}: warm choice {got}, "
                            f"expected {want}")
    if not any(r.get("shard_merges", 0) > 0 for r in rows):
        problems.append("no shard merge ran (telemetry never left "
                        "the device shards)")

    rec["ok"] = not problems
    rec["problems"] = problems

    # land the rows next to the table1 tier numbers
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = out if os.path.isabs(out) else os.path.join(repo, out)
    doc = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                doc = json.load(f)
        except Exception:
            doc = {}
    doc["table2_closed_loop"] = {"ok": rec["ok"], "problems": problems,
                                 "rows": rows}
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, default=str)
    rec["wrote"] = path
    return rec
