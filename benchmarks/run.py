"""Benchmark harness — one module per paper table/figure.

Usage:
  PYTHONPATH=src python -m benchmarks.run            # all
  PYTHONPATH=src python -m benchmarks.run table1 table2
  PYTHONPATH=src python -m benchmarks.run --ci       # CI guard

``--ci`` is the single entry the builder runs as the merge gate: the
perf-smoke suite (JIT >= interpreter, cache >= uncached, pallas-tier
differential rows incl. the zero-warm-upload bridge assertion, the
guarded-decide overhead bound, and the always-on-profiler dispatch-step
overhead bound), the observability exporter schema check (non-empty
histogram + straggler records in a valid JSON-lines batch), the
``table1_pallas`` five-tier
differential (interp == v1 == v2 == jaxc == pallas, zero retraces), the
``table1_pallas32`` SIX-tier differential (+ the Mosaic-ready
32-bit-pair lowering, whose leg runs without ``enable_x64``), the
``table1_native_diff`` machine-code differential (native == interp on
every policy, no eligibility gate), the ``BENCH_table1.json`` writer
(ns/decision per tier per policy, gating the ISSUE-8 >=5x-median
native-vs-v2 acceptance AND the per-policy eligibility audit: zero
unexplained ineligible policies on any tier at either word width), the
table2 closed-loop leg (8-device host-CPU mesh: per-device telemetry
shards -> ``sync_telemetry()`` merge -> warm per-size policy choices,
rows landed in ``BENCH_table1.json`` under ``table2_closed_loop``), the
warm pallas ``link.replace()`` leg (hash + subroutine policy swapped
in place, T3 flush contract asserted end-to-end), the
runtime fault-containment matrix (injected faults at every trust
boundary — hash RMW and bpf-to-bpf call entry included — x every tier
must degrade to the cost-model default, never
escape), then the tier-1 pytest suite; exit status is nonzero if any
leg fails.

Prints ``section,name,key=value,...`` CSV-ish lines and writes
results/bench.json.

Every suite here is a CPU rehearsal, and none is on the chip's path
(``chip_smoke.py`` is): ``--ci`` and ``table2`` start child processes
after this process may have touched JAX, which a chip held by the parent
would refuse, and the table2 driver forces 8 host-CPU devices.  Their
timings say nothing about a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
import traceback

SUITES = {
    "perf_smoke": ("benchmarks.perf_smoke",
                   "CI guard: JIT v2 >= interpreter, cache >= uncached"),
    "table1": ("benchmarks.table1_overhead", "Table 1: per-decision overhead"),
    "safety": ("benchmarks.safety_suite", "5.2: 7 safe / 7 unsafe"),
    "hot_reload": ("benchmarks.hot_reload", "5.2: atomic hot-reload"),
    "table2": ("benchmarks.table2_allreduce", "Table 2/Fig 2: AllReduce sweep"),
    "composability": ("benchmarks.composability", "5.3: profiler->tuner loop"),
    "net": ("benchmarks.net_overhead", "5.3: net plugin overhead"),
    "roofline": ("benchmarks.roofline_table", "Dry-run roofline table"),
}

RESULTS = []


def report(section: str, name: str, **kv):
    rec = {"section": section, "name": name, **kv}
    RESULTS.append(rec)
    parts = [f"{k}={v}" for k, v in kv.items()]
    print(f"{section},{name}," + ",".join(parts), flush=True)


def run_ci() -> int:
    """CI guard: perf smoke + tier-1 pytest, one exit status.

    The pytest leg is baseline-aware: environments whose jax build lacks
    ``shard_map``/``enable_x64`` fail a known set of tests regardless of
    the change under review (see ``benchmarks/ci_known_failures.txt``),
    so the gate is "no NEW failures", exactly the repo's no-worse-than-
    seed contract.  A fully green environment stays fully gated: tests
    on the known list still pass wherever they can run."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    src = os.path.join(repo, "src")
    env["PYTHONPATH"] = src + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    failures = 0

    print("=== ci: perf smoke ===", flush=True)
    r = subprocess.run([sys.executable, "-m", "benchmarks.perf_smoke"],
                       cwd=repo, env=env)
    if r.returncode != 0:
        print("CI: perf smoke FAILED", flush=True)
        failures += 1

    for suite in ("pallas_differential", "pallas32_differential",
                  "native_differential"):
        print(f"=== ci: table1_{suite.split('_')[0]} differential ===",
              flush=True)
        r = subprocess.run(
            [sys.executable, "-c",
             "import json, sys;"
             f"from benchmarks.table1_overhead import {suite};"
             f"rec = {suite}();"
             "print(json.dumps(rec, separators=(',', ':'), default=str));"
             "sys.exit(0 if rec['ok'] else 1)"],
            cwd=repo, env=env)
        if r.returncode != 0:
            print(f"CI: {suite} FAILED", flush=True)
            failures += 1

    print("=== ci: table1 ns/decision -> BENCH_table1.json ===", flush=True)
    r = subprocess.run(
        [sys.executable, "-c",
         "import json, sys;"
         "from benchmarks.table1_overhead import ci_table1;"
         "rec = ci_table1();"
         "print(json.dumps(rec, separators=(',', ':'), default=str));"
         "sys.exit(0 if rec['ok'] else 1)"],
        cwd=repo, env=env)
    if r.returncode != 0:
        print("CI: table1 BENCH writer FAILED", flush=True)
        failures += 1

    print("=== ci: table2 closed-loop 8-device mesh -> BENCH_table1.json "
          "===", flush=True)
    r = subprocess.run(
        [sys.executable, "-c",
         "import json, sys;"
         "from benchmarks.table2_allreduce import ci_closed_loop;"
         "rec = ci_closed_loop();"
         "print(json.dumps(rec, separators=(',', ':'), default=str));"
         "sys.exit(0 if rec['ok'] else 1)"],
        cwd=repo, env=env)
    if r.returncode != 0:
        print("CI: table2 closed loop FAILED", flush=True)
        failures += 1

    print("=== ci: observability export schema ===", flush=True)
    r = subprocess.run(
        [sys.executable, "-c",
         "import json, sys;"
         "from benchmarks.perf_smoke import export_schema_section;"
         "rec = export_schema_section();"
         "print(json.dumps(rec, separators=(',', ':'), default=str));"
         "sys.exit(0 if rec['ok'] else 1)"],
        cwd=repo, env=env)
    if r.returncode != 0:
        print("CI: observability export schema FAILED", flush=True)
        failures += 1

    print("=== ci: pallas warm link.replace (hash + subroutines) ===",
          flush=True)
    r = subprocess.run(
        [sys.executable, "-c",
         "import json, sys;"
         "from benchmarks.hot_reload import pallas_reload_section;"
         "rec = pallas_reload_section();"
         "print(json.dumps(rec, separators=(',', ':'), default=str));"
         "sys.exit(0 if rec['ok'] else 1)"],
        cwd=repo, env=env)
    if r.returncode != 0:
        print("CI: pallas warm link.replace FAILED", flush=True)
        failures += 1

    print("=== ci: runtime fault containment ===", flush=True)
    r = subprocess.run(
        [sys.executable, "-c",
         "import json, sys;"
         "from benchmarks.safety_suite import runtime_fault_section;"
         "rec = runtime_fault_section();"
         "print(json.dumps(rec, separators=(',', ':'), default=str));"
         "sys.exit(0 if rec['ok'] else 1)"],
        cwd=repo, env=env)
    if r.returncode != 0:
        print("CI: runtime fault containment FAILED", flush=True)
        failures += 1

    print("=== ci: tier-1 pytest ===", flush=True)
    known_path = os.path.join(repo, "benchmarks", "ci_known_failures.txt")
    known = set()
    if os.path.exists(known_path):
        with open(known_path) as f:
            known = {ln.strip() for ln in f
                     if ln.strip() and not ln.startswith("#")}
    r = subprocess.run([sys.executable, "-m", "pytest", "-q"],
                       cwd=repo, env=env, capture_output=True, text=True)
    sys.stdout.write(r.stdout)
    sys.stderr.write(r.stderr[-2000:])
    # collection/fixture ERRORs count like FAILEDs: both must be on the
    # known-baseline list or the gate trips
    failed = {ln.split()[1] for ln in r.stdout.splitlines()
              if ln.startswith(("FAILED ", "ERROR ")) and len(ln.split()) > 1}
    new = sorted(failed - known)
    if r.returncode != 0 and not failed:
        print("CI: pytest errored without reporting failures", flush=True)
        failures += 1
    if new:
        print(f"CI: {len(new)} NEW test failure(s) beyond the known "
              f"environment baseline:", flush=True)
        for t in new:
            print(f"  {t}", flush=True)
        failures += 1
    elif failed:
        print(f"CI: {len(failed)} failure(s), all on the known "
              f"environment baseline — gate passes", flush=True)

    print(f"=== ci: {'FAIL' if failures else 'OK'} ===", flush=True)
    return 1 if failures else 0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("suites", nargs="*", default=[])
    ap.add_argument("--out", default="results/bench.json")
    ap.add_argument("--ci", action="store_true",
                    help="run the CI guard (perf smoke + tier-1 pytest)")
    args = ap.parse_args()
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    if args.ci:
        sys.exit(run_ci())
    picks = args.suites or list(SUITES)

    failures = 0
    for key in picks:
        mod_name, desc = SUITES[key]
        print(f"\n=== {key}: {desc} ===", flush=True)
        t0 = time.time()
        try:
            mod = __import__(mod_name, fromlist=["run"])
            mod.run(report)
        except Exception:
            traceback.print_exc()
            failures += 1
            report(key, "SUITE_ERROR", error=traceback.format_exc()[-200:])
        print(f"--- {key} done in {time.time() - t0:.1f}s", flush=True)

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(RESULTS, f, indent=1, default=str)
    print(f"\nwrote {len(RESULTS)} records to {args.out}")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
