"""In-graph adaptive dispatch: verified policy drives lax.switch across
collective algorithm branches inside ONE compiled program — decisions
change step-to-step with live map state, zero retraces.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.collectives.ingraph import InGraphSelector
from repro.core import map_decl, policy
from repro.core.context import Algo

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

lat_map = map_decl("lat_map", kind="array", value_size=16, max_entries=4)
# [0]=ema latency, [1]=decision count


@policy(section="tuner", maps=[lat_map])
def adaptive_ingraph(ctx):
    """Telemetry arrives via ctx.dtype_bytes (see InGraphSelector.decide);
    EMA it in the map; pick tree when slow, default when fast."""
    st = lat_map.lookup(0)
    if st is None:
        ctx.algorithm = 0
        return 0
    if st[0] == 0:
        st[0] = ctx.dtype_bytes
    else:
        st[0] = (st[0] * 3 + ctx.dtype_bytes) // 4
    st[1] = st[1] + 1
    if st[0] > 1000000:
        ctx.algorithm = 2          # tree: latency-optimized
        ctx.n_channels = 2
    else:
        ctx.algorithm = 0          # default
        ctx.n_channels = 8
    return 0


def test_decisions_adapt_without_retrace():
    sel = InGraphSelector(adaptive_ingraph.program)
    state = sel.init_state()

    traces = []

    @jax.jit
    def step(state, latency_ns):
        traces.append(1)           # count retraces
        algo, ch, state = sel.decide(
            state, coll=0, msg_bytes=1 << 20, n=8, latency_ns=latency_ns)
        return algo, state

    # fast regime -> default(0); slow regime -> tree(2); recovery -> default
    # (x64 scope wraps the jit calls so uint64 inputs are not canonicalized)
    seen = []
    with jax.enable_x64(True):
        for lat in [1_000] * 4 + [5_000_000] * 6 + [1_000] * 8:
            algo, state = step(state, jnp.uint32(lat))
            seen.append(int(algo))
    assert len(traces) == 1, "must not retrace"
    assert seen[0] == 0 and 2 in seen, seen
    assert seen[-1] == 0, f"should recover: {seen}"
    # the map recorded every decision
    assert int(np.asarray(state["lat_map"])[0, 1]) == len(seen)


@pytest.mark.slow
def test_ingraph_allreduce_correct_on_8_devices():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    code = """
import jax, jax.numpy as jnp, numpy as np, sys
sys.path.insert(0, %r)
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from test_ingraph_dispatch import adaptive_ingraph
from repro.collectives.ingraph import InGraphSelector

sel = InGraphSelector(adaptive_ingraph.program)
mesh = Mesh(np.array(jax.devices()).reshape(8), ("x",))
x = np.random.RandomState(0).randn(8, 4096).astype(np.float32)
state = sel.init_state()

def f(v, state, lat):
    y, algo, state = sel.all_reduce(v, "x", state, latency_ns=lat)
    return y, algo, state

sm = jax.jit(jax.shard_map(f, mesh=mesh,
                       in_specs=(P("x"), P(), P()), out_specs=(P("x"), P(), P()),
                       check_vma=False))
want = jax.jit(jax.shard_map(lambda v: lax.psum(v, "x"), mesh=mesh,
                         in_specs=P("x"), out_specs=P("x")))(x)
algos = []
with jax.enable_x64(True):
    for lat in [1000]*3 + [5_000_000]*4:
        y, algo, state = sm(x, state, jnp.uint32(lat))
        assert np.allclose(np.asarray(y), np.asarray(want), atol=1e-4), "wrong result"
        algos.append(int(np.asarray(algo)))
assert algos[0] == 0 and algos[-1] == 2, algos
print("INGRAPH_OK", algos)
"""
    out = subprocess.run(
        [sys.executable, "-c", code % os.path.join(REPO, "tests")],
        env=env, capture_output=True, text=True, timeout=600,
        cwd=os.path.join(REPO, "tests"))
    sys.stdout.write(out.stdout)
    sys.stderr.write(out.stderr[-1500:])
    assert "INGRAPH_OK" in out.stdout
