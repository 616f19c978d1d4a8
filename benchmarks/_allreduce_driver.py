"""Subprocess: real wall-clock 8-device AllReduce sweeps.

Two entry points, selected by argv[1]:

``legs`` — the original open-loop sweep: default (XLA psum) vs the
    verified ``ring_mid_v2`` policy's dispatch vs the deliberately-bad
    policy.  Prints one JSON per row.
``closed`` — the closed-loop sweep (ISSUE 10): per-device telemetry
    shards accumulate in a multi-shard :class:`DeviceBridge` (one shard
    per mesh device, round-robin — the host stand-in for in-kernel
    per-rank writes), ``dispatcher.sync_telemetry()`` runs the
    deterministic shard merge back into the pinned host maps, and the
    ``bucket_tuner`` telemetry policy flips from deferring (cold) to a
    per-size algorithm choice (warm) — tree/LL below its 256 KiB EMA
    threshold, ring/simple at and above it.  Each row records the cold
    and warm decisions plus measured default-vs-policy wall clock and
    bus bandwidth on the real 8-device host-CPU mesh.
``all`` (default) — both.

Run with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.  This
is a CPU rehearsal: it forces 8 host devices and never runs on the chip.
"""

import json
import os
import sys
import time

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jax.sharding import Mesh, PartitionSpec as P

from repro.collectives.dispatch import DispatchConfig, reset_dispatcher
from repro.core.context import Algo, CollType, Proto, make_ctx
from repro.core.pallasc import compile_host
from repro.core.runtime import PolicyRuntime
from repro.policies import bad_channels, bucket_tuner, ring_mid_v2

SIZES_MIB = [1, 4, 8, 16, 32]
REPS = 20

# closed-loop sizes chosen to straddle bucket_tuner's 256 KiB EMA
# threshold: the two below decide tree/LL, the two above ring/simple
CLOSED_SIZES_KIB = [64, 128, 1024, 4096]
CLOSED_REPS = 10
N_DEV = 8


def timeit(fn, x, reps=REPS):
    fn(x).block_until_ready()          # compile+warm
    fn(x).block_until_ready()
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(x).block_until_ready()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)), float(np.std(ts) / np.mean(ts))


def legs():
    devs = jax.devices()
    mesh = Mesh(np.array(devs).reshape(8), ("x",))
    rng = np.random.RandomState(0)

    for mib in SIZES_MIB:
        n_elems = mib * (1 << 20) // 4
        x = rng.randn(8, n_elems).astype(np.float32)
        busbytes = 2 * 7 / 8 * (mib << 20)

        def spmd(fn):
            return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P("x"),
                                     out_specs=P("x")))

        t_def, cv_def = timeit(spmd(lambda v: lax.psum(v, "x")), x)

        rt = PolicyRuntime()
        rt.load(ring_mid_v2.program)
        disp = reset_dispatcher(runtime=rt)
        t_pol, cv_pol = timeit(spmd(lambda v: disp.all_reduce(v, "x")), x)
        d = disp.decisions[-1]

        rt.reload(bad_channels.program)
        disp2 = reset_dispatcher(runtime=rt)
        t_bad, _ = timeit(spmd(lambda v: disp2.all_reduce(v, "x")), x)

        print(json.dumps({
            "name": f"{mib}MiB",
            "default_ms": round(t_def * 1e3, 3),
            "policy_ms": round(t_pol * 1e3, 3),
            "bad_policy_ms": round(t_bad * 1e3, 3),
            "policy_choice": f"algo={d.algo} proto={d.proto} ch={d.channels}",
            "policy_vs_default_pct": round(100 * (t_def / t_pol - 1), 1),
            "bad_degradation_pct": round(100 * (1 - t_def / t_bad), 1),
            "default_busbw_gbs": round(busbytes / t_def / 1e9, 2),
            "cv_default": round(cv_def, 4), "cv_policy": round(cv_pol, 4),
        }), flush=True)


def closed_loop():
    devs = jax.devices()
    mesh = Mesh(np.array(devs).reshape(N_DEV), ("x",))
    rng = np.random.RandomState(0)

    rt = PolicyRuntime(tier="jit")
    rt.load(bucket_tuner.program)
    disp = reset_dispatcher(runtime=rt,
                            config=DispatchConfig(decision_log_max=4096))
    n_nodes, rpn = disp.set_topology(mesh)

    # the per-device telemetry plane: one bridge shard per mesh device,
    # sharing the SAME host maps the dispatcher's tuner chain reads
    # (the registry hands back existing maps by name)
    prog = bucket_tuner.program
    resolved = {d.name: rt.maps.create(d.name, d.kind, key_size=d.key_size,
                                       value_size=d.value_size,
                                       max_entries=d.max_entries)
                for d in prog.maps}
    bridge = compile_host(prog, resolved, tier="pallas32", mode="jit",
                          sync="deferred", n_shards=N_DEV)
    disp.register_mesh_sync(bridge.flush)

    def spmd(fn):
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P("x"),
                                 out_specs=P("x")))

    for kib in CLOSED_SIZES_KIB:
        size = kib << 10
        n_elems = size // 4
        x = rng.randn(N_DEV, n_elems).astype(np.float32)
        busbytes = 2 * (N_DEV - 1) / N_DEV * size

        # cold: no telemetry for this size bucket yet -> the tuner
        # defers and dispatch runs the framework default
        d_cold = disp.decide(CollType.ALL_REDUCE, size, N_DEV,
                             axis_name="x")

        # per-device in-kernel telemetry: every device observes this
        # size a few times in its OWN shard (sizes are constant per
        # bucket, so the EMA is a fixed point and the merged cell is
        # bit-identical to any single shard's)
        for rep in range(3):
            for shard in range(N_DEV):
                bridge.set_shard(shard)
                ctx = make_ctx("tuner", coll_type=CollType.ALL_REDUCE,
                               msg_size=size, n_ranks=N_DEV,
                               max_channels=32)
                bridge(ctx.buf)

        # the all-gather merge step: shard deltas -> pinned host maps
        disp.sync_telemetry()

        # warm: the tuner now sees the merged (count, ema) and decides
        d_warm = disp.decide(CollType.ALL_REDUCE, size, N_DEV,
                             axis_name="x")

        t_def, cv_def = timeit(spmd(lambda v: lax.psum(v, "x")), x,
                               reps=CLOSED_REPS)
        t_pol, cv_pol = timeit(spmd(lambda v: disp.all_reduce(v, "x")), x,
                               reps=CLOSED_REPS)

        print(json.dumps({
            "name": f"closed_{kib}KiB",
            "size_bytes": size,
            "topology": {"n_nodes": n_nodes, "ranks_per_node": rpn},
            "cold_choice": {
                "algo": Algo.NAMES[d_cold.algo],
                "proto": Proto.NAMES[d_cold.proto],
                "channels": d_cold.channels,
                "from_policy": d_cold.from_policy,
            },
            "warm_choice": {
                "algo": Algo.NAMES[d_warm.algo],
                "proto": Proto.NAMES[d_warm.proto],
                "channels": d_warm.channels,
                "from_policy": d_warm.from_policy,
            },
            "default_ms": round(t_def * 1e3, 3),
            "policy_ms": round(t_pol * 1e3, 3),
            "policy_vs_default_pct": round(100 * (t_def / t_pol - 1), 1),
            "default_busbw_gbs": round(busbytes / t_def / 1e9, 3),
            "policy_busbw_gbs": round(busbytes / t_pol / 1e9, 3),
            "telemetry_syncs": disp.telemetry_syncs,
            "shard_merges": bridge.stats.shard_merges,
            "cv_default": round(cv_def, 4), "cv_policy": round(cv_pol, 4),
        }), flush=True)


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    if which in ("legs", "all"):
        legs()
    if which in ("closed", "all"):
        closed_loop()


if __name__ == "__main__":
    main()
