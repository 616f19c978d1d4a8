"""Smoke run of the system's main path on TPU chips.

  python3 chip_smoke.py              # one chip
  python3 chip_smoke.py --chips 4    # the cross-chip path on four chips

One process, phases in order; each prints one line, and a failing phase
exits non-zero.  There is no CPU fallback: a host whose first JAX device
is not a TPU fails the device phase.  The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

One chip:
  * decision kernel — every shipped tuner policy runs on
    ``PolicyRuntime(tier="pallas32")`` (a Mosaic kernel) behind a
    ``CollectiveDispatcher``, bit-identical to ``tier="interp"`` on
    return value, ctx and map state, with zero host fallbacks, compile
    failures or dispatcher faults; then one ``link.replace()`` on a
    deferred bridge whose T3 flush must land in the host maps.
  * training — tinyllama-1.1b at its published widths, 8 of its 22
    layers (22 do not fit one chip's 16 GB with float32 AdamW state),
    B=4, S=2048, remat, through the Trainer ``launch/train.py`` builds:
    a verified policy attached with one hot reload mid-run, against the
    same steps detached.  On one chip no collective is dispatched, so
    the losses must be equal bit for bit.

Four chips (``--chips 4``), and no other phase:
  * training — all 22 layers on a (data=2 FSDP, model=2) mesh, B=8,
    S=2048, ``size_aware`` attached (the dispatcher's ring/tree
    algorithms) against detached (XLA's collectives); losses agree
    within ``LOSS_TOL`` (reduction order and bf16 wires differ).
  * collectives — every algorithm of the dispatcher's table at
    ``Proto.SIMPLE`` against ``lax.psum`` / ``psum_scatter`` /
    ``all_gather`` / ``all_to_all`` at 4 KiB, 1 MiB and 64 MiB per chip.
  * in-graph selector — ``InGraphSelector(tier="pallas32").all_reduce``
    inside ``shard_map`` against ``lax.psum``, then the per-chip states
    merged into the host maps.

Timings, compile seconds and memory lines are informational.  Details
go to ``chiprun_out/chip_smoke_<chips>.json``.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

MiB = 1 << 20
LOSS_TOL = 5e-2          # |attached - detached| per step, four chips
# launch/train.py arguments of the two training phases
TRAIN_1 = ["--arch", "tinyllama-1.1b", "--layers", "8", "--batch", "4",
           "--seq", "2048"]
TRAIN_4 = ["--arch", "tinyllama-1.1b", "--batch", "8", "--seq", "2048",
           "--tp", "2"]
COLL_BYTES = (4096, MiB, 64 * MiB)     # per chip, float32
RECORD: dict = {}


def fail(phase: str, msg: str) -> None:
    print(f"[{phase}] FAIL {msg}", flush=True)
    sys.exit(1)


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def phase_device(chips: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    say("device", f"platform={d.platform} kind={d.device_kind} "
                  f"count={len(devs)}")
    if d.platform != "tpu":
        fail("device", f"found platform {d.platform!r}, not a TPU; "
                       "there is no CPU fallback")
    if len(devs) < chips:
        fail("device", f"needs {chips} chip(s), found {len(devs)}")
    RECORD["device"] = {"platform": d.platform, "kind": d.device_kind,
                        "count": len(devs)}
    return devs[:chips]


def cache_counter():
    """Count persistent compilation cache hits and misses."""
    import jax
    counts = {"hits": 0, "misses": 0}
    names = {"/jax/compilation_cache/cache_hits": "hits",
             "/jax/compilation_cache/cache_misses": "misses"}

    def listener(event, **_):
        if event in names:
            counts[names[event]] += 1
    jax.monitoring.register_event_listener(listener)
    return counts


# ---------------------------------------------------------------------------
# one chip: the decision kernel
# ---------------------------------------------------------------------------

def _seed(maps, cid: int) -> None:
    """Telemetry that sends the Table 1 policies down their deep
    branches (same values on every runtime under comparison)."""
    from repro.core.context import CollType
    for name in maps.names():
        m = maps.get(name)
        if name == "chan_map":
            m.update_u64(0, 6)
        elif name == "latency_map":
            m.update(cid.to_bytes(4, "little"),
                     (2_000_000).to_bytes(8, "little")
                     + (5).to_bytes(8, "little"))
        elif name == "slo_map":
            m.update(cid.to_bytes(4, "little"),
                     (1_000_000).to_bytes(8, "little"))
        elif name == "probe_map":
            m.update_u64(CollType.ALL_REDUCE, 98, slot=0)
            m.update_u64(CollType.ALL_REDUCE, 3, slot=1)
        elif name == "config_lat_map":
            for k in range(0, m.max_entries, 7):
                m.update_u64(k, 5000 + 37 * ((k * 13) % 41))


def _drive(prog, tier: str, grid, **rt_kw):
    """Attach ``prog`` on ``tier`` and run the grid through decide();
    returns (per-decision (ret, ctx), map images, runtime, dispatcher,
    link)."""
    from repro.collectives.dispatch import (CollectiveDispatcher,
                                            DispatchConfig, _comm_id)
    from repro.core import PolicyRuntime
    rt = PolicyRuntime(tier=tier, **rt_kw)
    link = rt.attach(prog)
    disp = CollectiveDispatcher(
        runtime=rt, config=DispatchConfig(enable_decision_cache=False))
    _seed(rt.maps, _comm_id("?", 8))
    seen = []
    invoke = rt.invoke

    def recording_invoke(section, ctx):
        ret = invoke(section, ctx)
        seen.append((ret, bytes(ctx.buf)))
        return ret
    rt.invoke = recording_invoke
    for coll, size, n in grid:
        disp.decide(coll, size, n)
    return seen, rt, disp, link


def _map_images(rt) -> dict:
    rt.flush_bridges()
    return {n: rt.maps.get(n).to_device().tobytes() for n in rt.maps.names()}


def phase_decisions() -> None:
    from repro.core.context import CollType
    from repro.policies.loops import (LOOP_POLICIES, histogram_bucket_tuner,
                                      latency_argmin_tuner)
    from repro.policies.table1 import SAFE_POLICIES
    from repro.policies.telemetry import bucket_tuner

    sizes = [1, 4096, 32 * 1024, 32 * 1024 + 1, MiB, 8 * MiB, 64 * MiB,
             1 << 30]
    grid = [(c, s, n) for c in (CollType.ALL_REDUCE, CollType.ALL_GATHER)
            for n in (4, 8) for s in sizes] * 2
    rows = {}
    t0 = time.perf_counter()
    for pol in SAFE_POLICIES + LOOP_POLICIES + [bucket_tuner]:
        prog = pol.program
        want, rt_i, _, _ = _drive(prog, "interp", grid)
        got, rt, disp, link = _drive(prog, "pallas32", grid)
        bridge = link.fn
        bad = [k for k, (a, b) in enumerate(zip(want, got)) if a != b]
        if len(got) != len(want) or bad:
            fail("decide", f"{prog.name}: pallas32 != interp at decision "
                           f"{bad[:1] or len(got)}")
        if _map_images(rt) != _map_images(rt_i):
            fail("decide", f"{prog.name}: map state differs from interp")
        counts = (bridge.stats.host_fallbacks, rt.stats.compile_failures,
                  disp.fault_stats.total)
        if counts != (0, 0, 0):
            fail("decide", f"{prog.name}: host_fallbacks, compile_failures, "
                           f"dispatcher faults = {counts}")
        if "tpu_custom_call" not in bridge.compiled_text():
            fail("decide", f"{prog.name}: no Mosaic kernel in the compiled "
                           "decision")
        rows[prog.name] = {"decisions": len(got),
                           "distinct_ctx": len({c for _, c in got})}
    say("decide", f"ok {len(rows)} policies x {len(grid)} decisions: "
                  f"pallas32 (Mosaic) == interp on ret, ctx and maps; "
                  f"({time.perf_counter() - t0:.1f}s informational)")
    RECORD["decide"] = rows

    # hot reload on the deferred bridge: the T3 flush must reach the host
    hist = histogram_bucket_tuner.program
    small = [(CollType.ALL_REDUCE, 4096, 8)] * 5
    _, rt, disp, link = _drive(hist, "pallas32", small,
                               bridge_sync="deferred")
    m = rt.maps.get("size_hist_map")
    before = sum(m.lookup_u64(k) for k in range(m.max_entries))
    link.replace(latency_argmin_tuner.program)
    after = sum(m.lookup_u64(k) for k in range(m.max_entries))
    if (before, after) != (0, len(small)):
        fail("reload", f"deferred histogram before/after replace = "
                       f"{before}/{after}, want 0/{len(small)}")
    disp.decide(CollType.ALL_REDUCE, MiB, 8)
    stats = link.fn.stats
    if (stats.host_fallbacks, rt.stats.compile_failures,
            disp.fault_stats.total) != (0, 0, 0):
        fail("reload", "faults after link.replace()")
    say("reload", f"ok link.replace() flushed {after} deferred kernel "
                  "writes into the host map")


# ---------------------------------------------------------------------------
# training (one chip and four)
# ---------------------------------------------------------------------------

def _train(argv, *, steps: int, reload_at: int = 0):
    """Build the Trainer as launch/train.py does and run ``steps``;
    with ``reload_at`` the attached policy is replaced after that many
    steps (the epoch bump retraces the step).  Returns per-step losses,
    step seconds and the trainer."""
    from repro.collectives.dispatch import dispatcher
    from repro.launch import train
    from repro.policies import static_override
    args = train.parse_args(argv + ["--steps", str(steps),
                                    "--log-every", "1"])
    tr = train.build_trainer(args)
    if reload_at:
        tr.run(steps=reload_at)
        epoch = dispatcher().epoch
        dispatcher().runtime.chain("tuner")[0].replace(
            static_override.program)
        if dispatcher().epoch == epoch:
            fail("train", "link.replace() did not bump the policy epoch, "
                          "so the step would not retrace")
        steps -= reload_at
    log = tr.run(steps=steps)          # the whole log, from step 1
    return ([m["loss"] for m in log], [m["step_time_s"] for m in log], tr)


def _peak_bytes(devs) -> list:
    return [d.memory_stats().get("peak_bytes_in_use", 0) for d in devs]


def phase_train_one(devs) -> None:
    import math
    base = TRAIN_1
    steps, reload_at = 6, 3
    att, t_att, tr = _train(base + ["--policy", "size_aware"],
                            steps=steps, reload_at=reload_at)
    del tr
    gc.collect()
    det, t_det, tr = _train(base, steps=steps)
    del tr
    gc.collect()
    if not all(math.isfinite(x) for x in att + det):
        fail("train", f"non-finite loss: attached {att} detached {det}")
    if att != det:
        fail("train", f"attached {att} != detached {det} (one chip "
                      "dispatches no collective)")
    steady = sorted(t_att[1:reload_at] + t_att[reload_at + 1:])
    say("train", f"ok {' '.join(base)}: {steps} steps, losses "
                 f"{att[0]:.6f} -> {att[-1]:.6f}, bit-identical attached "
                 f"(hot reload after step {reload_at}) vs detached")
    say("train", f"informational: first step incl. compile "
                 f"{t_att[0]:.2f}s, step after reload incl. retrace "
                 f"{t_att[reload_at]:.2f}s, steady step median "
                 f"{steady[len(steady) // 2]:.4f}s, peak_bytes_in_use "
                 f"{_peak_bytes(devs)}")
    RECORD["train"] = {"loss_attached": att, "loss_detached": det,
                       "step_s_attached": t_att, "step_s_detached": t_det,
                       "peak_bytes_in_use": _peak_bytes(devs)}


def _check_placement(tr, devs) -> list:
    """Every chip holds a shard of the parameters, and no chip holds
    most of them."""
    import jax
    want = set(devs)
    per_dev = {d: 0 for d in devs}
    for leaf in jax.tree.leaves(tr.params):
        if leaf.sharding.device_set != want:
            fail("place", f"a parameter lives on {leaf.sharding.device_set}")
        for s in leaf.addressable_shards:
            per_dev[s.device] += s.data.nbytes
    held = [per_dev[d] for d in devs]
    if min(held) == 0 or max(held) > 1.5 * min(held):
        fail("place", f"parameter bytes per chip {held}")
    return held


def phase_train_four(devs) -> None:
    import math
    base = TRAIN_4
    steps = 3
    att, t_att, tr = _train(base + ["--policy", "size_aware"], steps=steps)
    from repro.collectives.dispatch import dispatcher
    n_decisions = len(dispatcher().decisions)
    held = _check_placement(tr, devs)
    del tr
    gc.collect()
    det, t_det, tr = _train(base, steps=steps)
    del tr
    gc.collect()
    diff = [abs(a - b) for a, b in zip(att, det)]
    if not all(math.isfinite(x) for x in att + det):
        fail("train4", f"non-finite loss: attached {att} detached {det}")
    if n_decisions == 0:
        fail("train4", "the attached run dispatched no collective")
    if max(diff) > LOSS_TOL:
        fail("train4", f"attached {att} vs detached {det}: max |diff| "
                       f"{max(diff)} > {LOSS_TOL}")
    say("place", f"ok parameter bytes per chip {held}")
    say("train4", f"ok {' '.join(base)}, {steps} steps: attached {att} "
                  f"vs detached {det}, max |diff| {max(diff):.3e} <= "
                  f"{LOSS_TOL} ({n_decisions} dispatcher decisions)")
    say("train4", f"informational: step s attached {t_att}, detached "
                  f"{t_det}, peak_bytes_in_use {_peak_bytes(devs)}")
    RECORD["train4"] = {"loss_attached": att, "loss_detached": det,
                        "max_abs_diff": max(diff), "tolerance": LOSS_TOL,
                        "step_s_attached": t_att, "step_s_detached": t_det,
                        "decisions": n_decisions, "param_bytes": held}


# ---------------------------------------------------------------------------
# four chips: collectives and the in-graph selector
# ---------------------------------------------------------------------------

def phase_collectives(devs) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.collectives.dispatch import _algo_fn
    from repro.core.context import Algo, CollType, Proto

    mesh = Mesh(np.array(devs), ("x",))
    refs = {
        CollType.ALL_REDUCE: lambda v: lax.psum(v, "x"),
        CollType.REDUCE_SCATTER: lambda v: lax.psum_scatter(v, "x",
                                                            tiled=True),
        CollType.ALL_GATHER: lambda v: lax.all_gather(v, "x", tiled=True),
        CollType.ALL_TO_ALL: lambda v: lax.all_to_all(v, "x", 0, 0,
                                                      tiled=True),
    }
    exact = (CollType.ALL_GATHER, CollType.ALL_TO_ALL)
    algos = (Algo.DEFAULT, Algo.RING, Algo.TREE, Algo.BIDIR_RING)
    rows = []
    for nbytes in COLL_BYTES:
        per_chip = (nbytes // 4 // 256, 256)
        x = jax.device_put(
            jax.random.normal(jax.random.PRNGKey(nbytes),
                              (len(devs) * per_chip[0], 256), jnp.float32),
            NamedSharding(mesh, P("x")))
        for coll, ref in refs.items():
            def body(v, coll=coll, ref=ref):
                want = ref(v)
                errs = []
                for a in algos:
                    got = _algo_fn(coll, a)(v, "x", n_channels=4,
                                           protocol=Proto.SIMPLE)
                    errs.append(jnp.max(jnp.abs(got - want)))
                scale = jnp.max(jnp.abs(want))
                return lax.pmax(jnp.stack(errs + [scale]), "x")
            out = np.asarray(jax.jit(jax.shard_map(
                body, mesh=mesh, in_specs=P("x"), out_specs=P()))(x))
            errs, scale = out[:-1], float(out[-1])
            for a, e in zip(algos, errs):
                tol = 0.0 if coll in exact else 1e-5 * (scale + 1.0)
                rows.append({"coll": int(coll), "algo": int(a),
                             "bytes_per_chip": nbytes, "max_err": float(e),
                             "tol": tol})
                if not e <= tol:
                    fail("coll", f"{CollType.NAMES[coll]} "
                                 f"{Algo.NAMES[a]} at {nbytes} B/chip: max "
                                 f"|err| {e} > {tol}")
    worst = max(r["max_err"] for r in rows)
    say("coll", f"ok {len(rows)} (collective, algorithm, size) cases match "
                f"their lax reference (worst |err| {worst:.3e})")
    RECORD["coll"] = rows


def phase_selector(devs) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.collectives.ingraph import InGraphSelector
    from repro.core.maps import MapRegistry
    from repro.policies.telemetry import bucket_tuner

    mesh = Mesh(np.array(devs), ("x",))
    sel = InGraphSelector(bucket_tuner.program, tier="pallas32")
    reg = MapRegistry()
    base = sel.init_state(reg)
    x = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(7), (len(devs) * 512, 512)),
        NamedSharding(mesh, P("x")))

    def step(v, state):
        want = lax.psum(v, "x")
        errs, algos = [], []
        for _ in range(2):
            y, algo, state = sel.all_reduce(v, "x", state)
            errs.append(jnp.max(jnp.abs(y - want)))
            algos.append(algo)
        err = lax.pmax(jnp.stack(errs), "x")
        return err, jnp.stack(algos)[None], \
            {k: s[None] for k, s in state.items()}

    spec = {k: P() for k in base}
    fn = jax.jit(jax.shard_map(step, mesh=mesh, in_specs=(P("x"), spec),
                               out_specs=(P(), P("x"),
                                          {k: P("x") for k in base}),
                               check_vma=False))
    text = fn.lower(x, base).compile().as_text()
    if "tpu_custom_call" not in text:
        fail("select", "no Mosaic kernel in the selector step")
    err, algos, out = fn(x, base)
    err = np.asarray(err)
    if not (err <= 1e-4 * len(devs)).all():
        fail("select", f"selector all_reduce vs lax.psum max |err| {err}")
    shards = sel.unstack_sharded(out)
    merged = sel.merge_shard_states(reg, shards, base)
    m = reg.get("bucket_tune_state")
    counts = [int(np.frombuffer(bytes(m.lookup_ref(k)), "<u8")[0])
              for k in m.keys()]
    if merged != 1 or counts != [2 * len(devs)]:
        fail("select", f"merge: {merged} maps, per-key counts {counts}, "
                       f"want [{2 * len(devs)}]")
    say("select", f"ok InGraphSelector(pallas32) all_reduce == lax.psum "
                  f"(max |err| {float(err.max()):.3e}), algos per chip "
                  f"{np.asarray(algos).tolist()}, merged count "
                  f"{counts[0]} into the host map")
    RECORD["select"] = {"max_err": err.tolist(),
                        "algos": np.asarray(algos).tolist(),
                        "merged_count": counts[0]}


# ---------------------------------------------------------------------------

def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    devs = phase_device(args.chips)

    from repro.launch.cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    counts = cache_counter()
    t0 = time.perf_counter()
    if args.chips == 1:
        phase_decisions()
        phase_train_one(devs)
    else:
        phase_train_four(devs)
        phase_collectives(devs)
        phase_selector(devs)
    say("cache", f"informational: {cache_dir} hits={counts['hits']} "
                 f"misses={counts['misses']}; wall "
                 f"{time.perf_counter() - t0:.1f}s")
    RECORD["cache"] = dict(counts, dir=cache_dir)
    out = ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    (out / f"chip_smoke_{args.chips}.json").write_text(
        json.dumps(RECORD, indent=1, default=str))
    print(json.dumps({"ok": True, "device": RECORD["device"]}), flush=True)


if __name__ == "__main__":
    main()
