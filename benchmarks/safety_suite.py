"""§5.2 safety reproduction: 7 safe accepted / 7 unsafe rejected at load
time, with verification latency (paper: 1-5 ms one-time).

Extended with the RUNTIME fault-containment matrix
(:func:`runtime_fault_section`, wired into ``benchmarks.run --ci``):
load-time verification rejects unsafe *programs*; the runtime guards
contain unsafe *executions* — injected faults at every trust boundary
(helper calls, map read-modify-writes, bridge upload/flush, decide
itself) on every execution tier must never escape ``decide()``, and the
decision under fault must be either the healthy policy decision or the
cost-model default, never garbage.  Hot-reload atomicity rides along:
an injected compile failure during ``link.replace()`` must leave the
old chain attached and deciding."""

from __future__ import annotations

import time

from repro.core import PolicyRuntime, VerifierError, verify
from repro.core.vm import VM, VMError
from repro.core.context import make_ctx
from repro.policies import SAFE_POLICIES, UNSAFE_PROGRAMS


MiB = 1 << 20
# injection points exercised per tier (bridge points only exist on the
# in-graph tiers; host tiers hit helper/map_rmw inside the chain)
_MATRIX_POINTS = ("helper", "map_rmw", "decide", "bridge_upload",
                  "bridge_download", "bridge_flush")


def _fault_tiers():
    from repro.core.cc import have_cc
    tiers = ["interp", "jit", "jaxc", "pallas", "pallas32"]
    if have_cc():
        tiers.append("native")
    return tiers


def _mk_dispatcher(tier, policy=None):
    """Runtime + dispatcher tuned for per-call observation: breakers and
    safe mode disabled so every injected fault exercises the per-call
    fallback path rather than latching.  ``policy`` overrides the driven
    tuner (default: the loop-heavy argmin tuner)."""
    from repro.collectives.dispatch import (CollectiveDispatcher,
                                            DispatchConfig)
    from repro.core import BreakerConfig
    from repro.policies.loops import latency_argmin_tuner
    pol = policy if policy is not None else latency_argmin_tuner
    rt = PolicyRuntime(tier=tier, breaker=BreakerConfig(enabled=False))
    rt.load(pol.program)
    if "config_lat_map" in rt.maps.names():
        m = rt.maps.get("config_lat_map")
        for k in range(0, m.max_entries, 5):
            m.update_u64(k, 900 + 13 * k, slot=0)
    disp = CollectiveDispatcher(runtime=rt, config=DispatchConfig(
        enable_decision_cache=False, safe_mode_threshold=1 << 30))
    return disp


def _decide(disp):
    from repro.core.context import CollType
    return disp.decide(CollType.ALL_REDUCE, 8 * MiB, 8, axis_name="dp")


def runtime_fault_section() -> dict:
    """Tier x injection-point containment matrix (importable; CI leg).

    For every tier and every trust-boundary point, run decide() with a
    deterministic always-fire injector and assert the guard contract:
    no exception escapes, the decision stays in-domain, and it equals
    either the healthy policy decision or the policy-detached default.
    Then assert hot-reload atomicity under an injected compile fault."""
    from repro.core import FaultInjector
    from repro.core.context import Algo, Proto
    rec = {"suite": "runtime_faults", "rows": [], "ok": True}

    # policy-detached default: what a faulted decide must degrade to
    from repro.collectives.dispatch import (CollectiveDispatcher,
                                            DispatchConfig)
    base = CollectiveDispatcher(runtime=PolicyRuntime(),
                                config=DispatchConfig())
    default_key = _decide(base).key()

    def contain_row(name, disp, point, healthy_keys):
        baseline = set(healthy_keys) | {default_key}
        escaped = 0
        bad_domain = 0
        off_baseline = 0
        with FaultInjector(seed=7).plan(point, prob=1.0) as inj:
            for _ in range(8):
                try:
                    d = _decide(disp)
                except Exception:
                    escaped += 1
                    continue
                if (d.algo >= Algo.COUNT or d.proto >= Proto.COUNT
                        or not 1 <= d.channels <= 32):
                    bad_domain += 1
                if d.key() not in baseline:
                    off_baseline += 1
            fired = inj.stats()[point]["fires"]
        ok = escaped == bad_domain == off_baseline == 0
        rec["rows"].append({
            "name": name, "fired": fired,
            "escaped": escaped, "bad_domain": bad_domain,
            "off_baseline": off_baseline,
            "fallbacks": disp.fault_stats.total, "ok": ok})
        rec["ok"] = rec["ok"] and ok

    def healthy_trajectory(mk):
        """All decision keys a fault-free dispatcher produces across the
        8-decide run — stateful policies (the telemetry tuner's hash
        state evolves per decide) legitimately change their decision
        mid-run, so the containment baseline is the whole trajectory."""
        disp = mk()
        return {_decide(disp).key() for _ in range(8)}

    from repro.policies.telemetry import bucket_tuner
    for tier in _fault_tiers():
        healthy = healthy_trajectory(lambda: _mk_dispatcher(tier))
        for point in _MATRIX_POINTS:
            contain_row(f"{tier}/{point}", _mk_dispatcher(tier), point,
                        healthy)

        # the tentpole's two new trust-boundary points, driven by the
        # hash-keyed shared-subroutine telemetry tuner (the argmin tuner
        # has neither hash maps nor bpf-to-bpf calls).  Host tiers fire
        # at the Python boundary; in-graph tiers inline calls and lower
        # hash RMW into the kernel, so their fire counts are 0 by
        # design — the row still proves decide() stays contained
        healthy_ht = healthy_trajectory(
            lambda: _mk_dispatcher(tier, bucket_tuner))
        for point in ("hash_rmw", "call_fn"):
            contain_row(f"{tier}/{point}",
                        _mk_dispatcher(tier, bucket_tuner), point,
                        healthy_ht)

        # hot-reload atomicity: a compile fault during replace() must
        # leave the old chain attached, deciding, and epoch-coherent
        disp = _mk_dispatcher(tier)
        rt = disp.runtime
        link = rt.chain("tuner")[0]
        before = _decide(disp).key()
        epoch_before = rt.epoch
        raised = False
        try:
            with FaultInjector(seed=7).plan("compile", prob=1.0):
                from repro.policies.loops import latency_argmin_tuner
                link.replace(latency_argmin_tuner.program)
        except Exception:
            raised = True
        ok = (raised and rt.is_attached("tuner")
              and rt.epoch == epoch_before
              and _decide(disp).key() == before)
        rec["rows"].append({
            "name": f"{tier}/replace_atomic", "raised": raised,
            "epoch_stable": rt.epoch == epoch_before, "ok": ok})
        rec["ok"] = rec["ok"] and ok
    return rec


def run(report):
    accepted = rejected = 0
    for pol in SAFE_POLICIES:
        t0 = time.perf_counter()
        verify(pol.program)
        dt = (time.perf_counter() - t0) * 1e3
        accepted += 1
        report("safety", pol.__name__, verdict="ACCEPT", verify_ms=dt,
               insns=len(pol.program))

    for name, (prog, frag) in sorted(UNSAFE_PROGRAMS.items()):
        t0 = time.perf_counter()
        try:
            verify(prog)
            verdict = "ACCEPT(!!)"
        except VerifierError as e:
            verdict = "REJECT"
            rejected += 1
            msg = str(e)
        dt = (time.perf_counter() - t0) * 1e3
        report("safety", name, verdict=verdict, verify_ms=dt,
               message=msg[:120])

    # the paper's side-by-side: unverified null-deref faults at runtime
    from repro.policies.unsafe import null_deref
    rt = PolicyRuntime(use_interpreter=True)
    m = rt.maps.create("latency_map", "hash", key_size=4, value_size=16,
                       max_entries=64)
    vm = VM(null_deref.insns, {"latency_map": m})
    try:
        vm.run(make_ctx("tuner", comm_id=1).buf)
        fault = "none (!!)"
    except VMError as e:
        fault = f"runtime fault: {e}"
    report("safety", "native_equivalent_comparison",
           unverified_execution=fault,
           verified_path="rejected at load time (see null_deref row)")
    report("safety", "summary", accepted=accepted, rejected=rejected,
           expected="7 accepted / 7 rejected")

    # runtime fault containment (the execution-time counterpart)
    rec = runtime_fault_section()
    for row in rec["rows"]:
        report("safety_runtime", row["name"],
               **{k: v for k, v in row.items() if k != "name"})
    assert rec["ok"], f"runtime fault containment regression: {rec}"
