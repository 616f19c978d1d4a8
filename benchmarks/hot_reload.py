"""§5.2 hot-reload reproduction: swap latency + zero lost calls under
continuous invocation (paper: 1.07 µs swap, ~9.4 ms total, 0 lost/400k),
extended to the link API: ``link.replace()`` verify-then-CAS latency and
transactional ``load_bundle`` whole-chain swap latency."""

from __future__ import annotations

import threading
import time

import numpy as np

from repro.core import PolicyRuntime, make_ctx
from repro.policies import (adapt_profiler, adapt_tuner, bad_channels,
                            ring_mid_v2, static_override)

N_CALLS = 400_000
N_THREADS = 4


def pallas_reload_section(report=None):
    """``hot_reload_pallas``: warm ``link.replace()`` on the pallas tier
    with a hash-map + subroutine policy (the telemetry bucket tuner).

    Asserts the T3 flush contract end-to-end: under ``deferred`` bridge
    sync the device-resident hash table is NOT visible in host maps
    between calls, and the first ``link.replace()`` — an attachment
    boundary — flushes it back, so the successor policy (any tier)
    starts from the state the outgoing one accumulated.  Also checks
    the swap stays atomic (one epoch bump per replace, depth-1 chain
    throughout) and reports warm swap latency.  Reused verbatim as a CI
    gate by ``benchmarks.run --ci``."""
    from repro.policies.telemetry import bucket_tuner

    rec = {"suite": "hot_reload_pallas", "ok": True}

    rt = PolicyRuntime(tier="pallas", bridge_sync="deferred")
    link = rt.attach(bucket_tuner.program, priority=0)

    def drive(n):
        for _ in range(n):
            ctx = make_ctx("tuner", coll_type=0, msg_size=4096, n_ranks=8,
                           max_channels=32)
            rt.invoke("tuner", ctx)

    drive(5)
    m = rt.maps.get("bucket_tune_state")
    key = (0 << 8) | 12            # bucket_key(coll=0, log2(4096)=12)
    stale = m.lookup_u64(key)      # deferred sync: host must be stale
    rec["deferred_host_stale"] = stale is None

    epoch0 = rt.epoch
    swaps, totals = [], []
    n_swaps = 10
    for i in range(n_swaps):
        prog = (static_override.program if i % 2 == 0
                else bucket_tuner.program)
        t0 = time.perf_counter_ns()
        link.replace(prog)
        totals.append((time.perf_counter_ns() - t0) / 1e3)
        swaps.append(rt.stats.swap_ns_last / 1e3)
        if i == 0:
            # the first replace is a T3 boundary: the 5 warm-up decisions
            # (insert count=1, then 4 hash-RMW hits) must have flushed
            # from device hash state into the host map
            rec["flushed_count"] = m.lookup_u64(key)
            rec["flush_at_t3_ok"] = rec["flushed_count"] == 5
        if prog is bucket_tuner.program:
            drive(2)
    # the last drive(2) is still device-resident (deferred sync); an
    # explicit flush reconciles: 5 warm-up + 5 reattachments x 2 = 15
    rt.flush_bridges("tuner")
    rec["final_count"] = m.lookup_u64(key)
    rec["final_count_ok"] = rec["final_count"] == 15
    rec["atomic_ok"] = (rt.epoch - epoch0 == n_swaps
                        and len(rt.chain("tuner")) == 1
                        and rt.stats.replaces == n_swaps
                        and rt.stats.flush_failures == 0)
    rec["swap_us_p50"] = float(np.percentile(swaps, 50))
    rec["total_replace_us_p50"] = float(np.percentile(totals, 50))
    rec["ok"] = (rec["deferred_host_stale"] and rec["flush_at_t3_ok"]
                 and rec["final_count_ok"] and rec["atomic_ok"])
    if report is not None:
        report("hot_reload", "pallas_link_replace_warm", **rec)
    return rec


def run(report):
    rt = PolicyRuntime()
    rt.load(static_override.program)

    # swap latency distribution over 200 reloads
    swaps = []
    totals = []
    for i in range(200):
        prog = bad_channels.program if i % 2 == 0 else ring_mid_v2.program
        t0 = time.perf_counter_ns()
        rt.reload(prog)
        totals.append((time.perf_counter_ns() - t0) / 1e3)
        swaps.append(rt.stats.swap_ns_last / 1e3)
    report("hot_reload", "swap_latency",
           swap_us_p50=float(np.percentile(swaps, 50)),
           swap_us_p99=float(np.percentile(swaps, 99)),
           total_reload_us_p50=float(np.percentile(totals, 50)),
           paper="swap 1.07 us, total ~9.4 ms (verify+LLVM JIT)")

    # zero lost calls across 400k invocations with concurrent reloads
    rt2 = PolicyRuntime()
    rt2.load(static_override.program)
    per_thread = N_CALLS // N_THREADS
    lost = [0] * N_THREADS
    stop = threading.Event()

    def invoker(t):
        bad = 0
        for _ in range(per_thread):
            ctx = make_ctx("tuner", msg_size=8 << 20)
            r = rt2.invoke("tuner", ctx)
            if r is None or ctx["n_channels"] not in (8, 1, 32):
                bad += 1
        lost[t] = bad

    def reloader():
        i = 0
        while not stop.is_set():
            rt2.reload(bad_channels.program if i % 2 == 0
                       else static_override.program)
            i += 1
            time.sleep(0.001)

    threads = [threading.Thread(target=invoker, args=(t,))
               for t in range(N_THREADS)]
    rl = threading.Thread(target=reloader)
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    rl.start()
    for t in threads:
        t.join()
    stop.set()
    rl.join()
    dt = time.perf_counter() - t0
    report("hot_reload", "lost_calls",
           invocations=N_CALLS, lost=sum(lost),
           reloads_during=rt2.stats.reloads, wall_s=round(dt, 2),
           paper="0 lost across 400k")

    # ---- link.replace(): verify-then-CAS on one chain position ----------
    rt3 = PolicyRuntime()
    link = rt3.attach(static_override.program, priority=0)
    rt3.attach(ring_mid_v2.program, priority=1)   # chain survives replaces
    rswaps, rtotals = [], []
    for i in range(200):
        prog = bad_channels.program if i % 2 == 0 else static_override.program
        t0 = time.perf_counter_ns()
        link.replace(prog)
        rtotals.append((time.perf_counter_ns() - t0) / 1e3)
        rswaps.append(rt3.stats.swap_ns_last / 1e3)
    report("hot_reload", "link_replace_latency",
           swap_us_p50=float(np.percentile(rswaps, 50)),
           swap_us_p99=float(np.percentile(rswaps, 99)),
           total_replace_us_p50=float(np.percentile(rtotals, 50)),
           chain_depth=len(rt3.chain("tuner")),
           note="CAS of one link inside a depth-2 chain; verify+JIT "
                "dominates, the published-chain swap is the tail")

    # ---- native tier: warm link.replace() through the object cache ------
    from repro.core.cc import cache_stats, have_cc
    if have_cc():
        rt5 = PolicyRuntime(tier="native")
        link5 = rt5.attach(static_override.program, priority=0)
        rt5.attach(ring_mid_v2.program, priority=1)
        # warm the compiled-object cache: the first replace of each
        # program pays the cc round trip (~10-100 ms), every later swap
        # rebinds the cached .so — that warm path is what a production
        # tuner loop alternating between known-good policies would pay
        link5.replace(bad_channels.program)
        link5.replace(static_override.program)
        before = cache_stats()
        nswaps, ntotals = [], []
        for i in range(200):
            prog = (bad_channels.program if i % 2 == 0
                    else static_override.program)
            t0 = time.perf_counter_ns()
            link5.replace(prog)
            ntotals.append((time.perf_counter_ns() - t0) / 1e3)
            nswaps.append(rt5.stats.swap_ns_last / 1e3)
        after = cache_stats()
        p50 = float(np.percentile(nswaps, 50))
        report("hot_reload", "native_link_replace_warm",
               swap_us_p50=p50,
               swap_us_p99=float(np.percentile(nswaps, 99)),
               total_replace_us_p50=float(np.percentile(ntotals, 50)),
               compiles_during=after["compiles"] - before["compiles"],
               cache_hits_during=after["cache_hits"] - before["cache_hits"],
               swap_vs_paper=round(p50 / 1.07, 2),
               paper="swap 1.07 us (verify+LLVM JIT, warm)",
               note="200 warm swaps on the machine-code tier: every "
                    "replace rebinds a cached .so, zero recompiles")
    else:
        report("hot_reload", "native_link_replace_warm",
               skipped="no C toolchain on this host (have_cc)")

    # ---- load_bundle(): whole-chain transactional swap ------------------
    rt4 = PolicyRuntime()
    rt4.load_bundle([adapt_profiler.program, adapt_tuner.program])
    bswaps, btotals = [], []
    for _ in range(100):
        t0 = time.perf_counter_ns()
        rt4.load_bundle([adapt_profiler.program, adapt_tuner.program])
        btotals.append((time.perf_counter_ns() - t0) / 1e3)
        bswaps.append(rt4.stats.swap_ns_last / 1e3)
    report("hot_reload", "bundle_swap_latency",
           swap_us_p50=float(np.percentile(bswaps, 50)),
           swap_us_p99=float(np.percentile(bswaps, 99)),
           total_bundle_us_p50=float(np.percentile(btotals, 50)),
           programs_per_bundle=2, sections_per_bundle=2,
           epoch_bumps_per_bundle=1,
           note="verify-everything-then-swap-everything: two sections "
                "(profiler+tuner) republish under a single epoch bump")

    # ---- pallas tier: warm replace of a hash+subroutine policy ----------
    pallas_reload_section(report)
