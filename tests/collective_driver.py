"""Subprocess driver: validates collective algorithms on a real 8-device
(host CPU) mesh.  Run by tests/test_collectives.py with
XLA_FLAGS=--xla_force_host_platform_device_count=8 in the child env only
(the main test process keeps 1 device, per the harness rules).

Prints one line per check: ``OK <name>`` or ``FAIL <name> <detail>``.
"""

import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.context import Algo, Proto
from repro.collectives import algorithms as alg
from repro.collectives.dispatch import reset_dispatcher
from repro.core.runtime import PolicyRuntime


def check(name, got, want, atol=1e-5):
    ok = np.allclose(np.asarray(got), np.asarray(want), atol=atol, rtol=1e-5)
    print(("OK " if ok else "FAIL ") + name, flush=True)
    if not ok:
        print("  max err:", float(np.max(np.abs(np.asarray(got) - np.asarray(want)))))
    return ok


def main():
    devs = jax.devices()
    assert len(devs) == 8, f"need 8 devices, got {len(devs)}"
    mesh = Mesh(np.array(devs).reshape(8), ("x",))
    rng = np.random.RandomState(0)
    failures = 0

    def run_spmd(fn, x):
        m = jax.shard_map(fn, mesh=mesh, in_specs=P("x"), out_specs=P("x"))
        return jax.jit(m)(x)

    # ---- allreduce variants vs psum ------------------------------------
    for size in (64, 1000, 8 * 1024):
        x = rng.randn(8, size).astype(np.float32)
        want = run_spmd(lambda v: lax.psum(v, "x"), x)
        for name, fn, kw in [
            ("ring_c1", alg.allreduce_ring, dict(n_channels=1)),
            ("ring_c4", alg.allreduce_ring, dict(n_channels=4)),
            ("ring_ll128", alg.allreduce_ring,
             dict(n_channels=2, protocol=Proto.LL128)),
            ("bidir", alg.allreduce_bidir_ring, dict(n_channels=2)),
            ("tree", alg.allreduce_tree, dict()),
            ("tree_ll128", alg.allreduce_tree, dict(protocol=Proto.LL128)),
        ]:
            tol = 0.5 if "ll" in name else 1e-5
            got = run_spmd(lambda v: fn(v, "x", **kw), x)
            failures += not check(f"allreduce_{name}_{size}", got, want,
                                  atol=tol)

    # ---- reduce-scatter --------------------------------------------------
    x = rng.randn(64, 5).astype(np.float32)  # per-device (8,5)
    want = run_spmd(lambda v: lax.psum_scatter(v, "x", tiled=True), x)
    got = run_spmd(lambda v: alg.reduce_scatter_ring(v, "x"), x)
    failures += not check("reduce_scatter_ring", got, want)

    # ---- all-gather --------------------------------------------------------
    x = rng.randn(8, 3, 4).astype(np.float32)
    want = run_spmd(lambda v: lax.all_gather(v, "x", tiled=True), x)
    got = run_spmd(lambda v: alg.all_gather_ring(v, "x"), x)
    failures += not check("all_gather_ring", got, want)

    # ---- all-to-all ----------------------------------------------------------
    x = rng.randn(64, 6).astype(np.float32)  # per-device (8,6)
    want = run_spmd(
        lambda v: lax.all_to_all(v, "x", split_axis=0, concat_axis=0,
                                 tiled=True), x)
    got = run_spmd(lambda v: alg.all_to_all_chunked(v, "x"), x)
    failures += not check("all_to_all_chunked", got, want)

    # ---- policy-driven dispatch end-to-end ----------------------------------
    from repro.policies import ring_mid_v2, bad_channels
    rt = PolicyRuntime()
    rt.load(ring_mid_v2.program)
    disp = reset_dispatcher(runtime=rt)
    x = rng.randn(8, 1 << 19).astype(np.float32)  # 2 MiB/dev < 4 MiB: defer
    want = run_spmd(lambda v: lax.psum(v, "x"), x)
    got = run_spmd(lambda v: disp.all_reduce(v, "x"), x)
    failures += not check("dispatch_small_defers_to_default", got, want)
    d = disp.decisions[-1]
    assert d.algo == Algo.DEFAULT, d
    x = rng.randn(8, 2 << 20).astype(np.float32)  # 8 MiB/dev: ring/ll128
    want = run_spmd(lambda v: lax.psum(v, "x"), x)
    got = run_spmd(lambda v: disp.all_reduce(v, "x"), x)
    failures += not check("dispatch_mid_uses_ring", got, want, atol=0.5)
    d = disp.decisions[-1]
    assert d.algo == Algo.RING and d.proto == Proto.LL128 and d.channels == 32, d

    # hot-reload swaps decisions at the dispatch layer
    rt.reload(bad_channels.program)
    got = run_spmd(lambda v: disp.all_reduce(v, "x"), x)
    failures += not check("dispatch_after_reload", got, want, atol=0.5)
    d = disp.decisions[-1]
    assert d.channels == 1 and d.algo == Algo.RING, d

    # ---- fault containment: an injected decide()-path fault must be
    # invisible to the collective — BIT-identical to running with the
    # policy detached (both degrade to the framework-default algorithm)
    from repro.core import FaultInjector
    from repro.collectives.dispatch import CollectiveDispatcher
    base = CollectiveDispatcher(runtime=PolicyRuntime())   # detached
    rt2 = PolicyRuntime()
    rt2.load(ring_mid_v2.program)
    disp2 = CollectiveDispatcher(runtime=rt2)
    x = rng.randn(8, 2 << 20).astype(np.float32)
    want = run_spmd(lambda v: base.all_reduce(v, "x"), x)
    with FaultInjector(seed=3).plan("decide", prob=1.0):
        got = run_spmd(lambda v: disp2.all_reduce(v, "x"), x)
    ok = np.array_equal(np.asarray(got), np.asarray(want))
    print(("OK " if ok else "FAIL ") + "fault_contained_bit_identical",
          flush=True)
    failures += not ok
    d = disp2.decisions[-1]
    assert not d.from_policy and d.algo == Algo.DEFAULT, d
    assert disp2.fault_stats.policy_exceptions > 0

    print(f"DONE failures={failures}", flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
