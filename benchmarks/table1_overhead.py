"""Table 1 reproduction: per-call policy-decision overhead.

Paper (x86, LLVM JIT): native 20 ns; eBPF policies +80..130 ns, decomposed
as base +80, +30/map-lookup, +10/map-update.

Our host tier JITs to Python closures (no LLVM on this container), so
absolute numbers are µs-scale; we reproduce the *decomposition* and the
tier comparison: native-python baseline vs interpreter vs host JIT vs the
in-graph jaxc tier (whose marginal host cost is zero — it fuses into XLA).

The ``table1_codegen`` section reports the legacy (v1 dispatcher-loop)
and specializing (v2) generators side by side on every policy, plus the
dispatch-layer decision cache (``table1_dispatch``).

The ``table1_native`` section benches the machine-code tier (core/cc.py,
C compiled via the system toolchain) against the v2 JIT on every policy
and carries the ISSUE-8 acceptance summary: >= 5x median per-decision
speedup.  ``native_differential`` is the matching correctness gate.
"""

from __future__ import annotations

import time

import numpy as np

from repro.collectives.dispatch import CollectiveDispatcher, DispatchConfig
from repro.core import PolicyRuntime, make_ctx
from repro.core.context import CollType, POLICY_CONTEXT
from repro.core.jit import compile_program
from repro.policies import table1 as T

N_CALLS = 200_000
MiB = 1 << 20


def bench_fn(fn, ctx_buf, n=N_CALLS):
    # warmup
    for _ in range(2000):
        fn(ctx_buf)
    samples = []
    CHUNK = 5_000
    for _ in range(n // CHUNK):
        t0 = time.perf_counter_ns()
        for _ in range(CHUNK):
            fn(ctx_buf)
        samples.append((time.perf_counter_ns() - t0) / CHUNK)
    return float(np.percentile(samples, 50)), float(np.percentile(samples, 99))


def seed_maps(rt: PolicyRuntime):
    for name in rt.maps.names():
        m = rt.maps.get(name)
        m.update_u64(0, 1_000, slot=0)
        if m.value_size >= 16:
            m.update_u64(0, 8, slot=1)


def _seed_loop_maps(rt: PolicyRuntime) -> None:
    for name in rt.maps.names():
        m = rt.maps.get(name)
        for k in range(0, m.max_entries, 7):
            m.update_u64(k, 1_000 + 37 * k, slot=0)


def _run_loop_section(report, ctx) -> None:
    from repro.policies.loops import LOOP_POLICIES

    for pol in LOOP_POLICIES:
        name = pol.program.name
        tiers = {}
        bufs = {}
        for tier, kw in [("interp", dict(use_interpreter=True)),
                         ("jit_v2", {}), ("jit_v1", {})]:
            rt = PolicyRuntime(**kw)
            lp = rt.load(pol.program)
            _seed_loop_maps(rt)
            fn = lp.fn
            if tier == "jit_v1":
                resolved = {d.name: rt.maps.get(d.name)
                            for d in pol.program.maps}
                fn = compile_program(pol.program, resolved, codegen="v1")
            buf = bytearray(ctx.buf)
            ret = fn(buf)
            tiers[tier] = (fn, ret)
            bufs[tier] = bytes(buf)
        differential_ok = (len({r for _, r in tiers.values()}) == 1
                           and len(set(bufs.values())) == 1)

        jaxc_ok = None
        try:
            import jax

            from repro.core.jaxc import (compile_jax, ctx_to_vec,
                                         map_to_array)
            rt = PolicyRuntime(use_interpreter=True)
            rt.load(pol.program)
            _seed_loop_maps(rt)
            arrays = {d.name: map_to_array(rt.maps.get(d.name))
                      for d in pol.program.maps}
            fn, _ = compile_jax(pol.program)
            with jax.enable_x64(True):
                jret, vec_out, _ = fn(ctx_to_vec(bytearray(ctx.buf)),
                                      arrays)
            jaxc_ok = (int(jret) == tiers["interp"][1]
                       and np.asarray(vec_out).astype("<u8")
                       .tobytes() == bufs["interp"])
        except Exception:
            jaxc_ok = False

        # loop policies are ~100x costlier per call than Table 1's
        # straight-line ones; perf_smoke's light warm-then-mean timer
        # (shared, not a third implementation) keeps the section in
        # seconds where bench_fn's 2000-call warmup would take minutes
        from benchmarks.perf_smoke import _bench
        p50_i = _bench(tiers["interp"][0], bytearray(ctx.buf), n=60)
        p50_v1 = _bench(tiers["jit_v1"][0], bytearray(ctx.buf), n=600)
        p50_v2 = _bench(tiers["jit_v2"][0], bytearray(ctx.buf), n=2000)
        report("table1_loops", name,
               p50_interp_ns=p50_i, p50_v1_ns=p50_v1, p50_v2_ns=p50_v2,
               v2_vs_interp=p50_i / p50_v2, v2_vs_v1=p50_v1 / p50_v2,
               differential_ok=differential_ok, jaxc_ok=jaxc_ok)


def _seed_telemetry(rt: PolicyRuntime) -> None:
    """Seed the telemetry hash maps with a few (coll, bucket) keys so the
    lookup-hit paths (EMA update, channel pick) execute, not just the
    insert path."""
    for name in rt.maps.names():
        m = rt.maps.get(name)
        for coll in (0, 1):
            for bucket in (12, 20):
                key = (coll << 8) | bucket
                m.update_u64(key, 3, slot=0)
                m.update_u64(key, 1 << bucket, slot=1)


def _telemetry_rows():
    """(program, seeder, ctx) differential rows for the shared-subroutine
    hash-keyed telemetry pair — a tuner AND a profiler policy calling the
    same policy-library subprograms over open-addressing hash maps."""
    from repro.policies.telemetry import bucket_profiler, bucket_tuner
    tuner_ctx = make_ctx("tuner", coll_type=0, msg_size=8 * MiB, comm_id=0,
                         n_ranks=8, max_channels=32)
    prof_ctx = make_ctx("profiler", event_type=1, coll_type=1,
                        msg_size=1 << 20, comm_id=7, latency_ns=480_000,
                        n_channels=8, timestamp_ns=123_456_789)
    return [(bucket_tuner.program, _seed_telemetry, tuner_ctx),
            (bucket_profiler.program, _seed_telemetry, prof_ctx)]


def _decoded_device_state(prog, names, arrs_out, writeback):
    """Device map images -> the same per-key state shape the host tiers
    report.  Raw row comparison is wrong for hash maps (their device
    image is the open-addressing table: [values..., key, used] rows in
    probe order, plus the occupancy row), so decode through each map's
    ``from_device`` protocol and read back by key."""
    from repro.core.maps import MapRegistry
    reg = MapRegistry()
    state = {}
    for d in prog.maps:
        if d.name not in names:
            continue
        m = reg.create(d.name, d.kind, key_size=d.key_size,
                       value_size=d.value_size, max_entries=d.max_entries)
        writeback(arrs_out[d.name], m)
        state[d.name] = [m.lookup_u64(k) for k in range(m.max_entries)]
    return state


def _host_tier_results(prog, ctx, seed_fn):
    """(ret, ctx bytes, map state) for interp / JIT v1 / JIT v2."""
    results = {}
    for tier, kw in [("interp", dict(use_interpreter=True)),
                     ("jit_v2", {}), ("jit_v1", {})]:
        rt = PolicyRuntime(**kw)
        lp = rt.load(prog)
        seed_fn(rt)
        fn = lp.fn
        if tier == "jit_v1":
            resolved = {d.name: rt.maps.get(d.name) for d in prog.maps}
            fn = compile_program(prog, resolved, codegen="v1")
        buf = bytearray(ctx.buf)
        ret = fn(buf)
        state = {d.name: [rt.maps.get(d.name).lookup_u64(k)
                          for k in range(rt.maps.get(d.name).max_entries)]
                 for d in prog.maps}
        results[tier] = (ret, bytes(buf), state)
    return results


def pallas_differential(report=None):
    """``table1_pallas``: the four-tier ladder closes — interp == v1 ==
    v2 == jaxc == pallas (return value, ctx out, map state) on every
    in-graph-eligible Table-1 and loop policy, with ZERO retraces across
    decisions on the in-graph path.  Reused verbatim as a CI gate by
    ``benchmarks.run --ci``."""
    import jax

    from repro.core.jaxc import (JaxcError, check_supported, compile_jax,
                                 ctx_to_vec, map_to_array)
    from repro.core.pallasc import compile_pallas
    from repro.policies.loops import LOOP_POLICIES

    from repro.core.jaxc import array_to_map

    rec = {"suite": "table1_pallas", "ok": True, "n_ineligible": 0,
           "ineligible": [], "policies": {}}
    ctx = make_ctx("tuner", msg_size=8 * MiB, comm_id=0, n_ranks=8,
                   max_channels=32)
    table1 = [(p.program, seed_maps, ctx) for p in
              (T.noop, T.static_override, T.size_aware, T.adaptive_channels,
               T.latency_feedback, T.bandwidth_probe, T.slo_enforcer)]
    loops = [(p.program, _seed_loop_maps, ctx) for p in LOOP_POLICIES]
    for prog, seed_fn, ctx in table1 + loops + _telemetry_rows():
        row = {}
        try:
            check_supported(prog)
        except JaxcError as e:
            # an ineligible policy is a suite failure now: the tentpole
            # contract is that the FULL policy surface lowers in-graph
            # (hash maps + bpf-to-bpf calls included); the ladder still
            # closes across the three host tiers, but the suite reports
            # the reason and trips the CI gate
            row["eligible"] = False
            row["why"] = str(e)
            row["ok"] = False
            rec["n_ineligible"] += 1
            rec["ineligible"].append(prog.name)
        else:
            host = _host_tier_results(prog, ctx, seed_fn)
            want_ret, want_buf, want_state = host["interp"]
            host_ok = len(set(map(str, host.values()))) == 1
            rt = PolicyRuntime(use_interpreter=True)
            rt.load(prog)
            seed_fn(rt)
            arrays = {d.name: map_to_array(rt.maps.get(d.name))
                      for d in prog.maps}
            row["eligible"] = True
            row["ok"] = host_ok
            for tier, compiler in (("jaxc", compile_jax),
                                   ("pallas", compile_pallas)):
                fn, names = compiler(prog)
                traces = []

                def traced(vec, arrs, _fn=fn, _t=traces):
                    _t.append(1)
                    return _fn(vec, arrs)
                jfn = jax.jit(traced)
                with jax.enable_x64(True):
                    ret, vec_out, arrs_out = jfn(
                        ctx_to_vec(bytearray(ctx.buf)), arrays)
                    # second decision feeds the updated map state back in:
                    # closed-loop adaptation must not retrace
                    jfn(ctx_to_vec(bytearray(ctx.buf)),
                        {n: arrs_out[n] for n in names})
                    state = _decoded_device_state(prog, names, arrs_out,
                                                  array_to_map)
                tier_ok = (
                    int(ret) == want_ret
                    and np.asarray(vec_out).astype("<u8").tobytes()
                    == want_buf
                    and all(state[n] == want_state[n] for n in names)
                    and len(traces) == 1)
                row[tier + "_ok"] = tier_ok
                row[tier + "_retraces"] = len(traces) - 1
                row["ok"] = row["ok"] and tier_ok
        rec["policies"][prog.name] = row
        rec["ok"] = rec["ok"] and row["ok"]
        if report is not None:
            report("table1_pallas", prog.name, **row)
    return rec


def pallas32_differential(report=None):
    """``table1_pallas32``: the SIX-tier ladder closes — interp == v1 ==
    v2 == jaxc == pallas == pallas32 (return value, ctx out, map state)
    on every in-graph-eligible Table-1 and loop policy, with ZERO
    retraces across decisions, and the 32-bit-pair leg runs with jax's
    default 32-bit types (no ``enable_x64`` anywhere on its path — the
    Mosaic-compilable property).  Reused verbatim as a CI gate by
    ``benchmarks.run --ci``.

    Unlike :func:`pallas_differential`, this suite does NOT skip when
    the build's x64 scope is broken: the uint64 in-graph legs drop out,
    but the pair leg still gates (that is its reason to exist)."""
    import jax

    from repro.core.jaxc import (JaxcError, check_supported, compile_jax,
                                 ctx_to_vec, map_to_array)
    from repro.core.lower32 import (ctx_to_vec32, map_to_array32,
                                    ret32_to_int, vec32_to_bytes)
    from repro.core.pallasc import compile_pallas
    from repro.policies.loops import LOOP_POLICIES

    from repro.core.jaxc import array_to_map
    from repro.core.lower32 import array32_to_map

    rec = {"suite": "table1_pallas32", "ok": True, "n_ineligible": 0,
           "ineligible": [],
           "x64_free_32bit_path": not jax.config.jax_enable_x64,
           "policies": {}}
    ctx = make_ctx("tuner", msg_size=8 * MiB, comm_id=0, n_ranks=8,
                   max_channels=32)
    table1 = [(p.program, seed_maps, ctx) for p in
              (T.noop, T.static_override, T.size_aware, T.adaptive_channels,
               T.latency_feedback, T.bandwidth_probe, T.slo_enforcer)]
    loops = [(p.program, _seed_loop_maps, ctx) for p in LOOP_POLICIES]
    for prog, seed_fn, ctx in table1 + loops + _telemetry_rows():
        row = {}
        try:
            check_supported(prog, word_width=32)
        except JaxcError as e:
            # the tentpole contract: the FULL policy surface lowers on
            # the 32-bit-pair tier too (hash maps compare keys as
            # (lo, hi) pairs; calls inline) — ineligibility is a suite
            # failure, reported with its reason
            row["eligible"] = False
            row["why"] = str(e)
            row["ok"] = False
            rec["n_ineligible"] += 1
            rec["ineligible"].append(prog.name)
            rec["policies"][prog.name] = row
            rec["ok"] = False
            if report is not None:
                report("table1_pallas32", prog.name, **row)
            continue

        host = _host_tier_results(prog, ctx, seed_fn)
        want_ret, want_buf, want_state = host["interp"]
        row["eligible"] = True
        row["ok"] = len(set(map(str, host.values()))) == 1

        def fresh_arrays(to_array):
            rt = PolicyRuntime(use_interpreter=True)
            rt.load(prog)
            seed_fn(rt)
            return {d.name: to_array(rt.maps.get(d.name))
                    for d in prog.maps}

        # -- pallas32 leg: no x64, always runs -------------------------
        arrays = fresh_arrays(map_to_array32)
        fn32, names = compile_pallas(prog, word_width=32)
        traces = []

        def traced32(vec, arrs, _fn=fn32, _t=traces):
            _t.append(1)
            return _fn(vec, arrs)
        jfn = jax.jit(traced32)
        ret, vec_out, arrs_out = jfn(ctx_to_vec32(bytearray(ctx.buf)),
                                     arrays)
        # second decision feeds the updated map state back in:
        # closed-loop adaptation must not retrace
        jfn(ctx_to_vec32(bytearray(ctx.buf)),
            {n: arrs_out[n] for n in names})
        state32 = _decoded_device_state(prog, names, arrs_out,
                                        array32_to_map)
        ok32 = (ret32_to_int(ret) == want_ret
                and vec32_to_bytes(vec_out) == want_buf
                and all(state32[n] == want_state[n] for n in names)
                and len(traces) == 1)
        row["pallas32_ok"] = ok32
        row["pallas32_retraces"] = len(traces) - 1
        row["ok"] = row["ok"] and ok32

        # -- uint64 in-graph legs (need the x64 scope) -----------------
        for tier, compiler in (
                ("jaxc", compile_jax),
                ("pallas", lambda p: compile_pallas(p, word_width=64))):
            fn, names = compiler(prog)
            traces = []

            def traced(vec, arrs, _fn=fn, _t=traces):
                _t.append(1)
                return _fn(vec, arrs)
            jfn = jax.jit(traced)
            with jax.enable_x64(True):
                ret, vec_out, arrs_out = jfn(
                    ctx_to_vec(bytearray(ctx.buf)),
                    fresh_arrays(map_to_array))
                jfn(ctx_to_vec(bytearray(ctx.buf)),
                    {n: arrs_out[n] for n in names})
                state = _decoded_device_state(prog, names, arrs_out,
                                              array_to_map)
            tier_ok = (
                int(ret) == want_ret
                and np.asarray(vec_out).astype("<u8").tobytes()
                == want_buf
                and all(state[n] == want_state[n] for n in names)
                and len(traces) == 1)
            row[tier + "_ok"] = tier_ok
            row["ok"] = row["ok"] and tier_ok
        rec["policies"][prog.name] = row
        rec["ok"] = rec["ok"] and row["ok"]
        if report is not None:
            report("table1_pallas32", prog.name, **row)
    return rec


def native_differential(report=None):
    """``table1_native_diff``: the machine-code tier is bit-identical to
    the host ladder (return value, ctx out, map state) on EVERY Table-1
    and loop policy.  No eligibility gate — unlike the in-graph tiers,
    native walks the same CFG as the host JITs, so hash maps, bounded
    loops and host helpers all compile.  Reused verbatim as a CI gate by
    ``benchmarks.run --ci``; skips (ok) on compiler-less hosts."""
    from repro.core.cc import get_meta, have_cc
    from repro.policies.loops import LOOP_POLICIES

    rec = {"suite": "table1_native_diff", "ok": True, "policies": {}}
    if not have_cc():
        rec["skipped"] = "no C toolchain on this host (have_cc)"
        return rec
    ctx = make_ctx("tuner", msg_size=8 * MiB, comm_id=0, n_ranks=8,
                   max_channels=32)
    table1 = [(p.program, seed_maps, ctx) for p in
              (T.noop, T.static_override, T.size_aware, T.adaptive_channels,
               T.latency_feedback, T.bandwidth_probe, T.slo_enforcer)]
    loops = [(p.program, _seed_loop_maps, ctx) for p in LOOP_POLICIES]
    for prog, seed_fn, ctx in table1 + loops + _telemetry_rows():
        host = _host_tier_results(prog, ctx, seed_fn)
        rt = PolicyRuntime(tier="native")
        lp = rt.load(prog)
        seed_fn(rt)
        buf = bytearray(ctx.buf)
        ret = lp.fn(buf)
        state = {d.name: [rt.maps.get(d.name).lookup_u64(k)
                          for k in range(rt.maps.get(d.name).max_entries)]
                 for d in prog.maps}
        # pure programs bind the raw extension method (no attributes);
        # get_meta carries the codegen tag for those
        cg = (getattr(lp.fn, "__bpf_codegen__", None)
              or get_meta(lp.fn).get("codegen"))
        row = {"codegen": cg,
               "ok": ((ret, bytes(buf), state) == host["interp"]
                      and len(set(map(str, host.values()))) == 1
                      and cg == "native")}
        rec["policies"][prog.name] = row
        rec["ok"] = rec["ok"] and row["ok"]
        if report is not None:
            report("table1_native_diff", prog.name, **row)
    return rec


def _run_native_section(report, ctx) -> None:
    """``table1_native``: machine-code tier vs the v2 JIT per policy,
    ending in the ISSUE-8 acceptance summary (>= 5x median speedup).
    Direct-path policies (array maps, straight-line or loop code) run
    entirely in C; hash-map policies cross the C<->Python helper
    boundary per lookup and sit near parity — the median is carried by
    the direct path, which is the paper's 80-130 ns/decision regime."""
    from repro.core.cc import cache_stats, have_cc
    if not have_cc():
        report("table1_native", "summary",
               skipped="no C toolchain on this host (have_cc)")
        return
    from benchmarks.perf_smoke import _bench
    from repro.policies.loops import LOOP_POLICIES

    rows = [(p, seed_maps, 50_000, 20_000) for p in
            (T.noop, T.static_override, T.size_aware, T.adaptive_channels,
             T.latency_feedback, T.bandwidth_probe, T.slo_enforcer)]
    # loop policies: ~100x costlier under v2, so the v2 leg gets the
    # same reduced call count the loop section uses
    rows += [(p, _seed_loop_maps, 20_000, 2_000) for p in LOOP_POLICIES]
    speedups = []
    for pol, seed_fn, n_native, n_v2 in rows:
        fns = {}
        for tier in ("native", "jit"):
            rt = PolicyRuntime(tier=tier)
            lp = rt.load(pol.program)
            seed_fn(rt)
            fns[tier] = lp.fn
        p50_v2 = _bench(fns["jit"], bytearray(ctx.buf), n=n_v2)
        p50_nat = _bench(fns["native"], bytearray(ctx.buf), n=n_native)
        speedups.append(p50_v2 / p50_nat)
        report("table1_native", pol.program.name,
               p50_native_ns=p50_nat, p50_v2_ns=p50_v2,
               speedup=p50_v2 / p50_nat)
    report("table1_native", "summary",
           median_speedup=float(np.median(speedups)),
           min_speedup=float(np.min(speedups)),
           max_speedup=float(np.max(speedups)),
           target=">=5x median over JIT v2 (ISSUE 8)",
           paper_native_ns="80..130 ns/decision (x86 LLVM JIT)",
           **cache_stats())


def ci_table1(out="BENCH_table1.json"):
    """CI leg: ns/decision per tier per policy, written to ``out``.

    Uses perf_smoke's light warm-then-mean timer — the CI time budget
    can't pay bench_fn's chunked percentiles — and carries the
    ``table1_native`` acceptance section: >= 5x median per-decision
    speedup of the machine-code tier over the v2 JIT (ISSUE 8).  On
    compiler-less hosts the native column and its gate are skipped and
    the leg stays green."""
    import json as _json

    from benchmarks.perf_smoke import _bench
    from repro.core.cc import have_cc
    from repro.policies.loops import LOOP_POLICIES

    ctx = make_ctx("tuner", msg_size=8 * MiB, comm_id=0, n_ranks=8,
                   max_channels=32)
    rec = {"suite": "table1_ci",
           "timer": "perf_smoke._bench (light warm-then-mean)",
           "native_available": have_cc(),
           "policies": {}}
    # (policy, seeder, n_fast, n_v1, n_interp): loop policies are ~100x
    # costlier on the slow tiers, so those legs get reduced call counts
    rows = [(p, seed_maps, 20_000, 5_000, 2_000) for p in
            (T.noop, T.static_override, T.size_aware, T.adaptive_channels,
             T.latency_feedback, T.bandwidth_probe, T.slo_enforcer)]
    rows += [(p, _seed_loop_maps, 2_000, 600, 60) for p in LOOP_POLICIES]
    speedups = []
    for pol, seed_fn, n_fast, n_v1, n_interp in rows:
        row = {}
        tiers = [("interp_ns", dict(use_interpreter=True), n_interp),
                 ("jit_v2_ns", {}, n_fast)]
        if have_cc():
            tiers.append(("native_ns", dict(tier="native"), n_fast))
        for col, kw, n in tiers:
            rt = PolicyRuntime(**kw)
            lp = rt.load(pol.program)
            seed_fn(rt)
            row[col] = _bench(lp.fn, bytearray(ctx.buf), n=n)
        rt = PolicyRuntime()
        rt.load(pol.program)
        seed_fn(rt)
        resolved = {d.name: rt.maps.get(d.name) for d in pol.program.maps}
        fn_v1 = compile_program(pol.program, resolved, codegen="v1")
        row["jit_v1_ns"] = _bench(fn_v1, bytearray(ctx.buf), n=n_v1)
        if have_cc():
            row["native_speedup_vs_v2"] = row["jit_v2_ns"] / row["native_ns"]
            speedups.append(row["native_speedup_vs_v2"])
        rec["policies"][pol.program.name] = row

    # tentpole eligibility audit: every suite policy (Table 1 + loops +
    # the shared-subroutine telemetry pair) must lower in-graph on BOTH
    # word widths; an ineligible entry records the compiler's reason and
    # trips the --ci gate (no unexplained — or any — ineligibles)
    from repro.core.jaxc import JaxcError, check_supported
    from repro.policies.telemetry import TELEMETRY_POLICIES
    elig = {}
    n_inelig = 0
    for pol in [r[0] for r in rows] + TELEMETRY_POLICIES:
        prog = pol.program
        entry = {}
        for width in (64, 32):
            try:
                check_supported(prog, word_width=width)
                entry[f"w{width}"] = {"eligible": True}
            except JaxcError as e:
                entry[f"w{width}"] = {"eligible": False, "why": str(e)}
                n_inelig += 1
        elig[prog.name] = entry
    rec["eligibility"] = {"policies": elig, "n_ineligible": n_inelig,
                          "ok": n_inelig == 0}

    if have_cc():
        med = float(np.median(speedups))
        rec["table1_native"] = {
            "median_speedup_vs_v2": med,
            "min_speedup_vs_v2": float(np.min(speedups)),
            "max_speedup_vs_v2": float(np.max(speedups)),
            "target": ">=5x median over JIT v2 (ISSUE 8)",
            "paper_native_ns": "80..130 ns/decision (x86 LLVM JIT)",
            "ok": med >= 5.0}
        rec["ok"] = rec["table1_native"]["ok"] and rec["eligibility"]["ok"]
    else:
        rec["table1_native"] = {"skipped":
                                "no C toolchain on this host (have_cc)"}
        rec["ok"] = rec["eligibility"]["ok"]
    with open(out, "w") as f:
        _json.dump(rec, f, indent=1)
    return rec


def run(report):
    ctx = make_ctx("tuner", msg_size=8 * MiB, comm_id=0, n_ranks=8,
                   max_channels=32)

    p50n, p99n = bench_fn(T.native_baseline, ctx.buf)
    report("table1", "native_baseline", p50_ns=p50n, p99_ns=p99n,
           delta_p50_ns=0.0, lookups=0, updates=0)

    rows = [("noop", T.noop, 0, 0),
            ("static_override", T.static_override, 0, 0),
            ("size_aware", T.size_aware, 1, 0),
            ("adaptive_channels", T.adaptive_channels, 1, 0),
            ("latency_feedback", T.latency_feedback, 1, 1),
            ("bandwidth_probe", T.bandwidth_probe, 1, 1),
            ("slo_enforcer", T.slo_enforcer, 2, 1)]

    jit_rows = []
    codegen_speedups = []
    for name, pol, nl, nu in rows:
        rt = PolicyRuntime()
        lp = rt.load(pol.program)
        seed_maps(rt)
        p50, p99 = bench_fn(lp.fn, ctx.buf)
        jit_rows.append((name, p50))
        report("table1", name, p50_ns=p50, p99_ns=p99,
               delta_p50_ns=p50 - p50n, lookups=nl, updates=nu,
               verify_ms=lp.verify_ms, jit_ms=lp.jit_ms)

        # old (v1) vs new (v2) codegen, same resolved maps & map state
        resolved = {d.name: rt.maps.get(d.name) for d in pol.program.maps}
        fn_v1 = compile_program(pol.program, resolved, codegen="v1")
        p50_v1, p99_v1 = bench_fn(fn_v1, ctx.buf, n=N_CALLS // 4)
        codegen_speedups.append(p50_v1 / p50)
        report("table1_codegen", name, p50_v1_ns=p50_v1, p50_v2_ns=p50,
               speedup=p50_v1 / p50, mode=lp.fn.__bpf_mode__,
               structured=lp.fn.__bpf_structured__)

        rt_vm = PolicyRuntime(use_interpreter=True)
        lp_vm = rt_vm.load(pol.program)
        seed_maps(rt_vm)
        p50v, p99v = bench_fn(lp_vm.fn, ctx.buf, n=N_CALLS // 10)
        report("table1_interp", name, p50_ns=p50v, p99_ns=p99v,
               jit_speedup=p50v / p50)

    report("table1_codegen", "summary",
           median_speedup=float(np.median(codegen_speedups)),
           min_speedup=float(np.min(codegen_speedups)),
           target=">=2x median (ISSUE 1)")

    # bounded-loop policies (inexpressible pre-loop-support): differential
    # check across interpreter / JIT v1 / JIT v2 (+ jaxc where the build
    # allows), then per-tier timings — the loop-heavy analogue of Table 1
    _run_loop_section(report, ctx)

    # the full tier ladder: interp == v1 == v2 == jaxc == pallas on
    # every in-graph-eligible policy, zero retraces across decisions,
    # then the six-tier ladder including the Mosaic-ready 32-bit-pair
    # lowering (table1_pallas32; its pair leg runs without enable_x64)
    pallas_differential(report)
    pallas32_differential(report)

    # the machine-code tier: correctness gate, then ns/decision vs v2
    # with the ISSUE-8 >=5x-median acceptance summary
    native_differential(report)
    _run_native_section(report, ctx)

    # dispatch layer: cold full path vs epoch-keyed decision-cache hits
    rt = PolicyRuntime()
    rt.load(T.static_override.program)
    for cached in (False, True):
        disp = CollectiveDispatcher(
            runtime=rt,
            config=DispatchConfig(enable_decision_cache=cached))
        disp.decide(CollType.ALL_REDUCE, 8 * MiB, 8, axis_name="dp")
        n = 20_000
        t0 = time.perf_counter_ns()
        for _ in range(n):
            disp.decide(CollType.ALL_REDUCE, 8 * MiB, 8, axis_name="dp")
        per_call = (time.perf_counter_ns() - t0) / n
        if cached:
            report("table1_dispatch", "decide_cached", p50_ns=per_call,
                   cache_speedup=uncached_ns / per_call)
        else:
            uncached_ns = per_call
            report("table1_dispatch", "decide_uncached", p50_ns=per_call)

    # decomposition fit: delta ~= base + a*lookups + b*updates
    A = np.array([[1, nl, nu] for (_, _, nl, nu) in rows], float)
    y = np.array([p - p50n for (_, p) in jit_rows], float)
    coef, *_ = np.linalg.lstsq(A, y, rcond=None)
    report("table1_fit", "decomposition",
           base_ns=float(coef[0]), per_lookup_ns=float(coef[1]),
           per_update_ns=float(coef[2]),
           paper_model="80 + 30*n_lookup + 10*n_update (ns, x86 LLVM JIT)")
