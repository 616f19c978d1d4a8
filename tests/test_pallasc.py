"""The pallas in-graph tier: single-kernel lowering of verified policies.

Covers loop lowering (pallas == interpreter incl. map state), the
explicit pure-JAX ``mode="jit"`` body, the backend-chosen word width,
load-time compile rejection, verifier-artifact
reuse (one static pass per load, never two), the runtime's
``tier="pallas"`` selection, and the dispatcher's in-graph routing with
zero retraces across decisions.
"""

import jax
import numpy as np
import pytest

from repro.core import PolicyRuntime, assemble, make_ctx, map_decl, pallasc
from repro.core.vm import VM
from repro.policies.loops import LOOP_POLICIES, latency_argmin_tuner


def _seed_maps(rt):
    for name in rt.maps.names():
        m = rt.maps.get(name)
        for k in range(0, m.max_entries, 3):
            m.update_u64(k, 100 + 17 * k, slot=0)


def _interp_results(prog, ctx_kw):
    rt = PolicyRuntime(use_interpreter=True)
    lp = rt.load(prog)
    _seed_maps(rt)
    ctx = make_ctx("tuner", **ctx_kw)
    ret = lp.fn(ctx.buf)
    state = {d.name: [rt.maps.get(d.name).lookup_u64(k)
                      for k in range(rt.maps.get(d.name).max_entries)]
             for d in prog.maps}
    return ret, bytes(ctx.buf), state


CTX_KW = dict(msg_size=8 << 20, comm_id=2, n_ranks=8, max_channels=32)


# ---------------------------------------------------------------------------
# Loop lowering + differential vs the interpreter
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pol", LOOP_POLICIES, ids=lambda p: p.program.name)
@pytest.mark.parametrize("mode", ["pallas", "jit"])
def test_loop_policy_matches_interpreter(pol, mode):
    from repro.core.jaxc import ctx_to_vec, map_to_array

    prog = pol.program
    want_ret, want_buf, want_state = _interp_results(prog, CTX_KW)

    rt = PolicyRuntime(use_interpreter=True)
    rt.load(prog)
    _seed_maps(rt)
    arrays = {d.name: map_to_array(rt.maps.get(d.name)) for d in prog.maps}
    fn, names = pallasc.compile_pallas(prog, mode=mode)
    ctx = make_ctx("tuner", **CTX_KW)
    with jax.enable_x64(True):
        ret, vec_out, arrays_out = jax.jit(fn)(ctx_to_vec(ctx.buf), arrays)
    assert int(ret) == want_ret
    assert np.asarray(vec_out).astype("<u8").tobytes() == want_buf
    for n in names:
        got = [int(x) for x in np.asarray(arrays_out[n])[:, 0]]
        assert got == want_state[n], n


def test_jit_fallback_equals_pallas_kernel():
    """The pure-JAX body (``mode="jit"``) and the pallas_call kernel are
    the same lowering — byte-identical outputs on the same inputs."""
    from repro.core.jaxc import ctx_to_vec, map_to_array

    prog = latency_argmin_tuner.program
    rt = PolicyRuntime(use_interpreter=True)
    rt.load(prog)
    _seed_maps(rt)
    arrays = {d.name: map_to_array(rt.maps.get(d.name)) for d in prog.maps}
    outs = {}
    for mode in ("pallas", "jit"):
        fn, names = pallasc.compile_pallas(prog, mode=mode)
        with jax.enable_x64(True):
            ret, vec, arrs = jax.jit(fn)(
                ctx_to_vec(make_ctx("tuner", **CTX_KW).buf), arrays)
        outs[mode] = (int(ret), np.asarray(vec).tobytes(),
                      {n: np.asarray(arrs[n]).tobytes() for n in names})
    assert outs["pallas"] == outs["jit"]


def test_unknown_mode_rejected():
    with pytest.raises(pallasc.PallascError, match="mode"):
        pallasc.compile_pallas(latency_argmin_tuner.program, mode="mosaic")


def test_default_mode_is_the_kernel_never_a_silent_fallback():
    """``mode`` defaults to the Pallas kernel; the pure-JAX body runs
    only when asked for by name."""
    import inspect
    sig = inspect.signature(pallasc.compile_pallas)
    assert sig.parameters["mode"].default == "pallas"
    assert not hasattr(pallasc, "HAVE_PALLAS")


@pytest.mark.parametrize("on_tpu,want", [(True, 32), (False, 64)])
def test_word_width_follows_backend(monkeypatch, on_tpu, want):
    """On TPU ``word_width=None`` and ``tier="pallas"`` take the pair
    form — Mosaic has no 64-bit integer units; elsewhere uint64."""
    from repro.collectives.ingraph import InGraphSelector
    monkeypatch.setattr(pallasc, "on_tpu", lambda: on_tpu)
    assert pallasc.resolve_word_width(None) == want
    sel = InGraphSelector(latency_argmin_tuner.program, tier="pallas")
    assert sel.word_width == want
    assert pallasc.resolve_word_width(32) == 32
    with pytest.raises(pallasc.PallascError, match="word_width"):
        pallasc.resolve_word_width(16)


def test_uint64_kernel_never_handed_to_mosaic():
    with pytest.raises(pallasc.PallascError, match="Mosaic"):
        pallasc.compile_pallas(latency_argmin_tuner.program, word_width=64,
                               interpret=False)


def test_bridge_compile_failure_rejected_at_load(monkeypatch):
    """A program the backend cannot compile is a load-time rejection
    (counted in ``compile_failures``, old chain untouched) — never a
    first-decision failure the guarded dispatcher would hide."""
    real = pallasc.compile_pallas

    def mosaic_only(prog, vinfo=None, **kw):
        return real(prog, vinfo, **dict(kw, interpret=False))
    monkeypatch.setattr(pallasc, "compile_pallas", mosaic_only)
    rt = PolicyRuntime(tier="pallas32")
    with pytest.raises(pallasc.PallascError, match="does not compile"):
        rt.attach(latency_argmin_tuner.program)
    assert rt.stats.compile_failures == 1
    assert rt.attached("tuner") is None


def test_bridge_exposes_compiled_text():
    rt = PolicyRuntime(tier="pallas32")
    lp = rt.load(latency_argmin_tuner.program)
    text = lp.fn.compiled_text()
    assert "HloModule" in text or "module" in text


def test_hash_map_policy_runs_in_kernel():
    """Hash-keyed policies lower into the kernel now (the old actionable
    rejection is gone): latency_feedback's probe-loop hash table runs
    device-resident and matches the interpreter — return value, ctx
    writeback, and decoded per-key state (insert on first sight, then
    in-place RMW on the warm key)."""
    from repro.core.maps import MapRegistry
    from repro.core.verifier import verify_with_info
    from repro.policies import table1 as T

    prog = T.latency_feedback.program
    vinfo = verify_with_info(prog)

    def mk_maps():
        reg = MapRegistry()
        return {d.name: reg.create(d.name, d.kind, key_size=d.key_size,
                                   value_size=d.value_size,
                                   max_entries=d.max_entries)
                for d in prog.maps}

    kw = dict(msg_size=8 << 20, comm_id=5, n_ranks=8, max_channels=32)
    maps_i = mk_maps()
    maps_p = mk_maps()
    fn = pallasc.compile_host(prog, maps_p, vinfo, tier="pallas")
    for _ in range(2):                  # insert path, then RMW-hit path
        ctx_p = make_ctx("tuner", **kw)
        ret = fn(ctx_p.buf)
        ctx_i2 = make_ctx("tuner", **kw)
        want = VM(prog.insns, maps_i, subprogs=prog.subprogs).run(ctx_i2.buf)
        assert ret == want
        assert bytes(ctx_p.buf) == bytes(ctx_i2.buf)
    fn.flush()
    for name, m in maps_p.items():
        mi = maps_i[name]
        for k in (5, 6):
            assert (m.lookup_u64(k, 0), m.lookup_u64(k, 1)) == \
                (mi.lookup_u64(k, 0), mi.lookup_u64(k, 1)), (name, k)


# ---------------------------------------------------------------------------
# Verifier-artifact reuse
# ---------------------------------------------------------------------------

def test_compile_reuses_provided_verifier_artifacts(monkeypatch):
    """With a vinfo handed in, compile_pallas must not re-verify — the
    runtime's load path pays for exactly one static pass."""
    from repro.core import verifier as verifier_mod
    from repro.core.jaxc import ctx_to_vec

    prog = assemble("""
        mov64 r6, 0
    loop:
        jge   r6, 100, done
        add64i r6, 1
        ja    loop
    done:
        mov64 r0, r6
        exit
    """, section="tuner")
    vinfo = verifier_mod.verify_with_info(prog)

    def boom(_prog):
        raise AssertionError("re-verified despite provided artifacts")
    monkeypatch.setattr(pallasc, "verify_with_info", boom)
    fn, _ = pallasc.compile_pallas(prog, vinfo)
    with jax.enable_x64(True):
        ret, _, _ = jax.jit(fn)(ctx_to_vec(make_ctx("tuner").buf), {})
    assert int(ret) == 100


def test_runtime_load_verifies_exactly_once(monkeypatch):
    import repro.core.runtime as runtime_mod
    calls = []
    real = runtime_mod.verify_with_info

    def counted(prog):
        calls.append(prog.name)
        return real(prog)
    monkeypatch.setattr(runtime_mod, "verify_with_info", counted)
    rt = PolicyRuntime(tier="pallas")
    rt.load(latency_argmin_tuner.program)
    assert calls == [latency_argmin_tuner.program.name]


# ---------------------------------------------------------------------------
# Runtime tier selection
# ---------------------------------------------------------------------------

def test_runtime_rejects_unknown_tier():
    with pytest.raises(ValueError, match="tier"):
        PolicyRuntime(tier="llvm")


@pytest.mark.parametrize("tier", ["jaxc", "pallas"])
def test_runtime_tier_matches_interpreter(tier):
    prog = latency_argmin_tuner.program
    want_ret, want_buf, want_state = _interp_results(prog, CTX_KW)
    rt = PolicyRuntime(tier=tier)
    lp = rt.load(prog)
    _seed_maps(rt)
    ctx = make_ctx("tuner", **CTX_KW)
    assert lp.fn(ctx.buf) == want_ret
    assert bytes(ctx.buf) == want_buf
    state = {d.name: [rt.maps.get(d.name).lookup_u64(k)
                      for k in range(rt.maps.get(d.name).max_entries)]
             for d in prog.maps}
    assert state == want_state


def test_runtime_pallas_tier_writes_map_state_back():
    """Closed loop through the host bridge: a map-writing policy's state
    lands back in the host maps (the cross-plugin source of truth)."""
    from repro.policies.loops import histogram_bucket_tuner
    rt = PolicyRuntime(tier="pallas")
    rt.load(histogram_bucket_tuner.program)
    m = rt.maps.get("size_hist_map")
    before = m.lookup_u64(23)
    rt.invoke("tuner", make_ctx("tuner", msg_size=8 << 20, max_channels=32))
    assert m.lookup_u64(23) == before + 1   # 8 MiB -> log2 bucket 23


def test_runtime_pallas_hot_reload_keeps_t3():
    """Verify-then-swap semantics hold on the pallas tier too: a rejected
    replacement leaves the old kernel attached."""
    from repro.policies.unsafe import unbounded_loop
    rt = PolicyRuntime(tier="pallas")
    rt.load(latency_argmin_tuner.program)
    epoch = rt.epoch
    assert rt.try_reload(unbounded_loop) is not None
    assert rt.epoch == epoch
    ctx = make_ctx("tuner", msg_size=8 << 20, max_channels=32)
    rt.invoke("tuner", ctx)
    assert ctx["n_channels"] == 8          # old policy still deciding


# ---------------------------------------------------------------------------
# In-graph routing: dispatcher -> InGraphSelector(tier="pallas")
# ---------------------------------------------------------------------------

def test_ingraph_selector_pallas_zero_retraces():
    import jax.numpy as jnp

    from repro.collectives.ingraph import InGraphSelector
    from tests.test_ingraph_dispatch import adaptive_ingraph

    sel = InGraphSelector(adaptive_ingraph.program, tier="pallas")
    state = sel.init_state()
    traces = []

    @jax.jit
    def step(state, latency_ns):
        traces.append(1)
        algo, ch, state = sel.decide(
            state, coll=0, msg_bytes=1 << 20, n=8, latency_ns=latency_ns)
        return algo, state

    seen = []
    with jax.enable_x64(True):
        for lat in [1_000] * 4 + [5_000_000] * 6 + [1_000] * 8:
            algo, state = step(state, jnp.uint32(lat))
            seen.append(int(algo))
    assert len(traces) == 1, "must not retrace"
    assert seen[0] == 0 and 2 in seen and seen[-1] == 0, seen
    assert int(np.asarray(state["lat_map"])[0, 1]) == len(seen)


def test_dispatcher_routes_ingraph_with_live_state():
    from repro.collectives.dispatch import CollectiveDispatcher

    rt = PolicyRuntime()
    rt.load(latency_argmin_tuner.program)
    m = rt.maps.get("config_lat_map")
    m.update_u64(11, 50)                   # config 11 fastest
    m.update_u64(3, 900)
    disp = CollectiveDispatcher(runtime=rt)
    sel, state = disp.make_ingraph(tier="pallas")
    assert sel.tier == "pallas"
    # host-accumulated telemetry moved in-graph with the policy
    assert int(np.asarray(state["config_lat_map"])[11, 0]) == 50
    with jax.enable_x64(True):
        algo, ch, state = jax.jit(
            lambda s: sel.decide(s, coll=0, msg_bytes=1 << 20, n=8))(state)
    assert int(ch) == 12                   # argmin config + 1


def test_dispatcher_ingraph_requires_attached_tuner():
    from repro.collectives.dispatch import CollectiveDispatcher
    disp = CollectiveDispatcher(runtime=PolicyRuntime())
    with pytest.raises(RuntimeError, match="no tuner policy attached"):
        disp.make_ingraph(tier="pallas")


# ---------------------------------------------------------------------------
# Hand-assembled loop program with in-loop map writes
# ---------------------------------------------------------------------------

accum_map = map_decl("pallas_accum", kind="array", value_size=8,
                     max_entries=4)


def test_loop_with_map_writeback_matches_vm():
    from repro.core.jaxc import ctx_to_vec, map_to_array
    from repro.core.maps import MapRegistry

    prog = assemble("""
        stw    [r10-4], 1
        ldmap  r1, pallas_accum
        mov64  r2, r10
        add64i r2, -4
        call   map_lookup_elem
        jeqi   r0, 0, out
        mov64  r9, r0
        mov64  r6, 0
    loop:
        jge    r6, 70, out
        ldxdw  r7, [r9+0]
        add64  r7, r6
        stxdw  [r9+0], r7
        add64i r6, 1
        ja     loop
    out:
        mov64  r0, 0
        exit
    """, section="tuner", maps=(accum_map,))

    reg = MapRegistry()
    m = reg.create("pallas_accum", "array", value_size=8, max_entries=4)
    m.update_u64(1, 7)
    want = VM(prog.insns, {"pallas_accum": m}).run(make_ctx("tuner").buf)
    want_cell = m.lookup_u64(1)
    assert want_cell == 7 + sum(range(70))

    reg2 = MapRegistry()
    m2 = reg2.create("pallas_accum", "array", value_size=8, max_entries=4)
    m2.update_u64(1, 7)
    fn, _ = pallasc.compile_pallas(prog)
    with jax.enable_x64(True):
        ret, _, arrs = jax.jit(fn)(ctx_to_vec(make_ctx("tuner").buf),
                                   {"pallas_accum": map_to_array(m2)})
    assert int(ret) == want
    assert int(np.asarray(arrs["pallas_accum"])[1, 0]) == want_cell
