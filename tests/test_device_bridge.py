"""Regression tests for the device-resident host bridge (pallasc.DeviceBridge).

The old bridge round-tripped full map state in both directions on every
call; these tests pin the new contract:

  * repeated ``decide()``/invoke calls perform ZERO map uploads while
    host maps are clean (asserted via the bridge's dirty-counter
    introspection, not timing),
  * a host mutation between calls IS picked up (version-gated upload),
  * lookup-only maps never sync back,
  * kernel-written EMA state reaches the host maps per-call in ``step``
    mode, and in ``deferred`` mode exactly at ``flush()`` / detach /
    ``link.replace()`` / bundle reload — the T3 boundaries where the
    runtime guarantees host maps are the source of truth.
"""

import pytest

from repro.core import PolicyRuntime, make_ctx
from repro.policies.loops import histogram_bucket_tuner, latency_argmin_tuner

CTX_KW = dict(msg_size=8 << 20, comm_id=0, n_ranks=8, max_channels=32)


def _seed_argmin(rt):
    m = rt.maps.get("config_lat_map")
    for k in range(0, m.max_entries, 5):
        m.update_u64(k, 900 + 13 * k, slot=0)


@pytest.mark.parametrize("tier", ["pallas", "pallas32"])
def test_warm_repeat_calls_zero_uploads(tier):
    rt = PolicyRuntime(tier=tier)
    lp = rt.load(latency_argmin_tuner.program)
    _seed_argmin(rt)
    bridge = lp.fn
    n_maps = len(latency_argmin_tuner.program.maps)
    for _ in range(3):
        rt.invoke("tuner", make_ctx("tuner", **CTX_KW))
    # first call seeded every map; the two warm repeats uploaded nothing
    assert bridge.stats.calls == 3
    assert bridge.stats.map_uploads == n_maps
    # the argmin policy only LOOKS UP its latency map -> never synced back
    assert bridge.stats.map_downloads == 0


@pytest.mark.parametrize("tier", ["pallas", "pallas32"])
def test_host_mutation_between_calls_is_picked_up(tier):
    rt = PolicyRuntime(tier=tier)
    rt.load(latency_argmin_tuner.program)
    bridge = rt.attached("tuner").fn
    m = rt.maps.get("config_lat_map")
    m.update_u64(11, 50)                 # config 11 fastest
    m.update_u64(3, 900)
    ctx = make_ctx("tuner", **CTX_KW)
    rt.invoke("tuner", ctx)
    assert ctx["n_channels"] == 12       # argmin config + 1
    ups = bridge.stats.map_uploads
    # clean repeat: no upload, same decision
    ctx = make_ctx("tuner", **CTX_KW)
    rt.invoke("tuner", ctx)
    assert ctx["n_channels"] == 12 and bridge.stats.map_uploads == ups
    # host mutation: config 4 becomes fastest; next call must re-upload
    m.update_u64(4, 7)
    ctx = make_ctx("tuner", **CTX_KW)
    rt.invoke("tuner", ctx)
    assert ctx["n_channels"] == 5
    assert bridge.stats.map_uploads == ups + 1


def test_step_sync_written_state_visible_immediately():
    rt = PolicyRuntime(tier="pallas")        # default sync="step"
    rt.load(histogram_bucket_tuner.program)
    m = rt.maps.get("size_hist_map")
    before = m.lookup_u64(23)
    rt.invoke("tuner", make_ctx("tuner", **CTX_KW))
    assert m.lookup_u64(23) == before + 1    # 8 MiB -> log2 bucket 23


@pytest.mark.parametrize("tier", ["pallas", "pallas32"])
def test_deferred_sync_state_lands_at_flush(tier):
    rt = PolicyRuntime(tier=tier, bridge_sync="deferred")
    lp = rt.load(histogram_bucket_tuner.program)
    bridge = lp.fn
    m = rt.maps.get("size_hist_map")
    for _ in range(4):
        rt.invoke("tuner", make_ctx("tuner", **CTX_KW))
    # kernel wrote device-resident state; nothing synced back yet
    assert m.lookup_u64(23) == 0
    assert bridge.stats.map_downloads == 0
    n = bridge.flush()
    assert n >= 1
    assert m.lookup_u64(23) == 4             # all four decisions visible


def test_deferred_sync_flushes_on_detach():
    rt = PolicyRuntime(tier="pallas", bridge_sync="deferred")
    lp = rt.load(histogram_bucket_tuner.program)
    m = rt.maps.get("size_hist_map")
    rt.invoke("tuner", make_ctx("tuner", **CTX_KW))
    assert m.lookup_u64(23) == 0
    rt.detach("tuner")
    assert m.lookup_u64(23) == 1
    assert lp.fn.stats.flushes == 1


def test_deferred_sync_flushes_on_hot_reload():
    """reload() (legacy single-slot swap) is a T3 boundary: the outgoing
    kernel's accumulated state must land in the host maps the incoming
    program starts from."""
    rt = PolicyRuntime(tier="pallas", bridge_sync="deferred")
    old = rt.load(histogram_bucket_tuner.program)
    m = rt.maps.get("size_hist_map")
    rt.invoke("tuner", make_ctx("tuner", **CTX_KW))
    rt.invoke("tuner", make_ctx("tuner", **CTX_KW))
    assert m.lookup_u64(23) == 0
    rt.reload(histogram_bucket_tuner.program)
    assert m.lookup_u64(23) == 2
    assert old.fn.stats.flushes == 1
    # and the successor seeded its device state from the flushed maps
    rt.invoke("tuner", make_ctx("tuner", **CTX_KW))
    rt.attached("tuner").fn.flush()
    assert m.lookup_u64(23) == 3


def test_deferred_sync_flushes_on_link_replace():
    rt = PolicyRuntime(tier="pallas", bridge_sync="deferred")
    link = rt.attach(histogram_bucket_tuner.program)
    m = rt.maps.get("size_hist_map")
    rt.invoke("tuner", make_ctx("tuner", **CTX_KW))
    assert m.lookup_u64(23) == 0
    link.replace(latency_argmin_tuner.program)
    assert m.lookup_u64(23) == 1


def test_deferred_sync_flushes_on_bundle_reload():
    rt = PolicyRuntime(tier="pallas", bridge_sync="deferred")
    rt.load_bundle([histogram_bucket_tuner.program])
    m = rt.maps.get("size_hist_map")
    rt.invoke("tuner", make_ctx("tuner", **CTX_KW))
    assert m.lookup_u64(23) == 0
    rt.load_bundle([latency_argmin_tuner.program])
    assert m.lookup_u64(23) == 1


def test_invalidate_forces_reupload():
    rt = PolicyRuntime(tier="pallas")
    lp = rt.load(latency_argmin_tuner.program)
    bridge = lp.fn
    rt.invoke("tuner", make_ctx("tuner", **CTX_KW))
    ups = bridge.stats.map_uploads
    bridge.invalidate()
    rt.invoke("tuner", make_ctx("tuner", **CTX_KW))
    assert bridge.stats.map_uploads == ups + len(
        latency_argmin_tuner.program.maps)


def test_flush_never_writes_back_lookup_only_maps():
    """flush() (and therefore every T3 boundary) must not revert host
    mutations to maps the kernel can only read — the kernel cannot have
    changed them, so their stale device copy must never win."""
    rt = PolicyRuntime(tier="pallas")
    lp = rt.load(latency_argmin_tuner.program)
    _seed_argmin(rt)
    rt.invoke("tuner", make_ctx("tuner", **CTX_KW))   # device copy exists
    m = rt.maps.get("config_lat_map")
    m.update_u64(11, 777)                # host mutation after the upload
    assert lp.fn.flush() == 0            # nothing kernel-writable to sync
    assert m.lookup_u64(11) == 777       # host write survived
    rt.detach("tuner")                   # T3 boundary: same guarantee
    assert m.lookup_u64(11) == 777


def test_pointer_store_bumps_version_on_runtime_tiers():
    """The most common map-write pattern — lookup then store through the
    value pointer — must bump the version on both runtime host tiers
    (interp and JIT v2), or a bridge sharing the pinned map would keep
    deciding on stale telemetry forever."""
    from repro.core import assemble, map_decl
    decl = map_decl("ptr_store", kind="array", value_size=8, max_entries=4)
    prog = assemble("""
        stw    [r10-4], 1
        ldmap  r1, ptr_store
        mov64  r2, r10
        add64i r2, -4
        call   map_lookup_elem
        jeqi   r0, 0, out
        lddw   r8, 12345
        stxdw  [r0+0], r8
    out:
        mov64  r0, 0
        exit
    """, section="tuner", maps=(decl,))
    for kw in (dict(use_interpreter=True), {}):
        rt = PolicyRuntime(**kw)
        rt.load(prog)
        m = rt.maps.get("ptr_store")
        v0 = m.version
        rt.invoke("tuner", make_ctx("tuner", **CTX_KW))
        assert m.lookup_u64(1) == 12345
        assert m.version > v0, f"tier {kw} missed the pointer store"


def test_runtime_rejects_unknown_bridge_sync():
    with pytest.raises(ValueError, match="bridge_sync"):
        PolicyRuntime(tier="pallas", bridge_sync="eager")


def test_bridge_rejects_unknown_sync():
    from repro.core.maps import MapRegistry
    from repro.core.pallasc import PallascError, compile_host
    with pytest.raises(PallascError, match="sync"):
        compile_host(latency_argmin_tuner.program, {}, tier="pallas32",
                     sync="lazy")


def test_ema_helper_bumps_map_version_on_every_host_tier():
    """The dirty tracking the bridge depends on: EMA writebacks through
    the VM and through the host JIT (closure path AND the v2 inline fast
    path) all bump the map version — they write through live refs, not
    update(), so the version counter must be bumped explicitly or a
    host-tier profiler sharing a map with a device-resident bridge would
    leave the device copy stale forever."""
    from repro.core import assemble, map_decl
    from repro.core.jit import compile_program

    decl = map_decl("ver_ema", kind="array", value_size=8, max_entries=4)
    prog = assemble("""
        stw    [r10-4], 1
        ldmap  r1, ver_ema
        mov64  r2, r10
        add64i r2, -4
        mov64  r3, 500
        mov64  r4, 4
        call   ema_update
        mov64  r0, 0
        exit
    """, section="tuner", maps=(decl,))
    for kw in (dict(use_interpreter=True), {}):
        rt = PolicyRuntime(**kw)
        rt.load(prog)
        m = rt.maps.get("ver_ema")
        m.update_u64(1, 1_000)
        v0 = m.version
        rt.invoke("tuner", make_ctx("tuner", **CTX_KW))
        assert m.version > v0, f"tier {kw} did not bump the map version"
    # v1 codegen (the closure path) as well
    rt = PolicyRuntime()
    rt.load(prog)
    m = rt.maps.get("ver_ema")
    m.update_u64(1, 1_000)
    fn = compile_program(prog, {"ver_ema": m}, codegen="v1")
    v0 = m.version
    fn(make_ctx("tuner", **CTX_KW).buf)
    assert m.version > v0
