"""In-graph tier (jaxc): verified bytecode -> pure JAX, equivalent to the VM.

The flagship beyond-paper capability: the SAME verified bytecode that runs
on the host tier runs inside a jitted XLA program, with array maps threaded
as device state.
"""

import jax
import numpy as np
import pytest

from repro.core import PolicyRuntime, make_ctx
from repro.core.context import POLICY_CONTEXT
from repro.core.jaxc import (JaxcError, compile_jax, ctx_to_vec,
                             map_to_array)
from repro.policies import (adapt_map, adapt_tuner, bad_channels,
                            ring_mid_v2, size_aware)
from repro.policies.table1 import chan_map

MiB = 1 << 20


def _run_both(pol, ctx_kwargs, seed_maps=None):
    """Run host-JIT tier and jaxc tier; return (host_ctx, jax_ctx, rets)."""
    rt = PolicyRuntime()
    rt.load(pol.program)
    if seed_maps:
        for mname, entries in seed_maps.items():
            m = rt.maps.get(mname)
            for k, slots in entries.items():
                for si, v in enumerate(slots):
                    m.update_u64(k, v, slot=si)

    hctx = make_ctx("tuner", **ctx_kwargs)
    hret = rt.invoke("tuner", hctx)

    fn, names = compile_jax(pol.program)
    jctx = make_ctx("tuner", **ctx_kwargs)
    vec = ctx_to_vec(jctx.buf)
    arrays = {n: map_to_array(rt2_map(pol, n, seed_maps)) for n in names}
    # hold the x64 scope across the jit boundary: a scope *inside* the
    # trace cannot re-widen inputs the outer bind canonicalized to 32-bit
    with jax.enable_x64(True):
        jret, vec_out, arrays_out = jax.jit(fn)(vec, arrays)
        return hctx, np.asarray(vec_out), int(hret), int(jret)


def rt2_map(pol, name, seed_maps):
    """Build a fresh host map seeded identically (pre-invocation state)."""
    from repro.core.maps import MapRegistry
    reg = MapRegistry()
    d = pol.program.map_decl(name)
    m = reg.create(name, d.kind, key_size=d.key_size,
                   value_size=d.value_size, max_entries=d.max_entries)
    if seed_maps and name in seed_maps:
        for k, slots in seed_maps[name].items():
            for si, v in enumerate(slots):
                m.update_u64(k, v, slot=si)
    return m


FIELDS = list(POLICY_CONTEXT.fields)


@pytest.mark.parametrize("msg_size", [1 * MiB, 8 * MiB, 64 * MiB, 256 * MiB])
def test_ring_mid_v2_matches_host(msg_size):
    hctx, jvec, hret, jret = _run_both(ring_mid_v2, dict(msg_size=msg_size))
    assert hret == jret
    for i, f in enumerate(FIELDS):
        assert int(jvec[i]) == hctx[f], f"field {f} differs"


def test_bad_channels_matches_host():
    hctx, jvec, hret, jret = _run_both(bad_channels, dict(msg_size=MiB))
    assert hret == jret
    assert int(jvec[FIELDS.index("n_channels")]) == 1


def test_array_map_policy_matches_host():
    seed = {"chan_map": {0: [12]}}
    hctx, jvec, hret, jret = _run_both(
        size_aware, dict(msg_size=16 * 1024, comm_id=0), seed)
    assert hret == jret
    assert int(jvec[FIELDS.index("n_channels")]) == hctx["n_channels"] == 12


def test_adaptive_policy_state_evolves_in_graph():
    """Run adapt_tuner 3 times in-graph, threading map state — the closed
    loop without host round-trips."""
    fn, names = compile_jax(adapt_tuner.program)
    jit_fn = jax.jit(fn)

    rt = PolicyRuntime()
    rt.load(adapt_tuner.program)
    m = rt.maps.get("adapt_map")
    # comm 5: ema latency high (contention), channels 10, count 1
    m.update_u64(5, 2_000_000, slot=0)
    m.update_u64(5, 10, slot=1)
    m.update_u64(5, 1, slot=2)

    arrays = {"adapt_map": map_to_array(m)}
    # x64 scope wraps the jit calls so uint64 inputs are not canonicalized
    with jax.enable_x64(True):
        for step in range(3):
            ctx = make_ctx("tuner", comm_id=5)
            vec = ctx_to_vec(ctx.buf)
            ret, vec, arrays = jit_fn(vec, arrays)
            # host tier on a parallel copy
            hctx = make_ctx("tuner", comm_id=5)
            rt.invoke("tuner", hctx)
            nch = int(np.asarray(vec)[FIELDS.index("n_channels")])
            assert nch == hctx["n_channels"], f"step {step}"
        # contention backoff: 10 -> 8 -> 6 -> 4
        assert int(np.asarray(arrays["adapt_map"])[5, 1]) == 4


def test_hash_map_policy_runs_in_graph():
    """Hash-keyed policies compile in-graph now (the old rejection is
    gone): adaptive_channels' latency_map lookup lowers to a probe loop
    over the device hash table, matching the host tier on both the
    seeded-hit and the miss path."""
    from repro.policies import adaptive_channels  # uses a hash map
    seed = {"latency_map": {5: [2_000_000, 7]}}
    hctx, jvec, hret, jret = _run_both(
        adaptive_channels, dict(msg_size=MiB, comm_id=5), seed_maps=seed)
    assert hret == jret
    for i, f in enumerate(FIELDS):
        assert int(jvec[i]) == hctx[f], f"field {f} differs"
    assert hctx["n_channels"] == 8          # st[1] + 1 on the hit path

    hctx2, jvec2, hret2, jret2 = _run_both(
        adaptive_channels, dict(msg_size=MiB, comm_id=9), seed_maps=seed)
    assert hret2 == jret2
    assert int(jvec2[FIELDS.index("n_channels")]) \
        == hctx2["n_channels"] == 2         # unseeded key: miss path


def test_jaxc_composes_with_outer_jit_32bit():
    """jaxc must be embeddable in a 32-bit-default outer program."""
    import jax.numpy as jnp
    fn, _ = compile_jax(bad_channels.program)

    def step(x, vec):
        ret, vec_out, _ = fn(vec, {})
        nch = vec_out[FIELDS.index("n_channels")].astype(jnp.uint32)
        return x * nch, vec_out

    vec = ctx_to_vec(make_ctx("tuner", msg_size=MiB).buf)
    # the x64 scope wraps the outer jit so the uint64 ctx stays 64-bit;
    # the outer program still computes in explicit 32-bit dtypes throughout
    with jax.enable_x64(True):
        y, _ = jax.jit(step)(jnp.uint32(3), vec)
    assert int(y) == 3
