"""pallasc — verified policy bytecode lowered to ONE Pallas kernel.

The in-kernel execution tier.  The ladder so far: the interpreter (ground
truth), the host JIT (v1/v2 Python closures), and jaxc (pure-JAX
if-conversion fused into the step program).  jaxc already removed host
round-trips, but its lowering emits free-floating jnp ops that XLA may
schedule anywhere; this tier packages the whole verified decision —
including PR 3's bounded loops — into a single :func:`pl.pallas_call`
kernel, so on TPU the policy runs as one Mosaic kernel whose operands
(ctx vector + map state) stay in on-chip memory (SMEM) for the duration
of the decision.  Host marginal cost per decision is zero: the host
neither computes nor copies anything once the step is dispatched.

Two word widths share the entry point:

  * ``word_width=64`` — the uint64 lowering
    (:class:`repro.core.jaxc._Lowerer`).  Runs in interpret mode only
    (Mosaic has no 64-bit integer ops); needs the scoped x64 context.
  * ``word_width=32`` — the Mosaic-ready pair lowering
    (:class:`repro.core.lower32._Lowerer32`): every u64 register, stack
    slot, ctx field, and map slot is a ``(lo, hi)`` uint32 pair with
    explicit carry/borrow, widening multiply, pair shifts, and pairwise
    compare chains.  No 64-bit integer op ever reaches the kernel, and
    no x64 scope is needed anywhere on the path.

``word_width=None`` follows the backend: 32 on TPU, 64 elsewhere.

Lowering path (shared with jaxc by construction): the verifier's
artifacts — shared CFG, proven ``loop_bounds``, per-insn region info —
drive the same predicated block-by-block lowering; forward regions
if-convert, each natural loop becomes one ``lax.fori_loop`` running
exactly ``bound + 1`` header visits.  ``compile_*(prog, vinfo)`` reuses
the runtime's single verify pass.

Backends: on TPU the kernel compiles through Mosaic; on CPU (CI) the
same ``pallas_call`` runs in interpret mode — identical lowering path,
executed by the Pallas interpreter.  ``mode="jit"``, asked for by name,
bypasses the kernel harness and jits the bare lowering body.

The host bridge (:class:`DeviceBridge`, returned by
:func:`compile_host`) keeps map state DEVICE-RESIDENT across calls:
uploads are version-gated (a clean host map is never re-uploaded),
writebacks cover only the maps the program can statically write, and
``flush()`` forces a full device->host sync — the runtime calls it at
every T3 boundary (detach / ``link.replace()`` / bundle reload) so host
maps remain the cross-plugin source of truth exactly when attachment
changes hands.
"""

from __future__ import annotations

import dataclasses
import math
import threading
import time
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import faults as _faults
from .context import Algo, Proto
from .jaxc import (JaxcError, _Lowerer, array_to_map, check_supported,
                   compile_jax, ctx_to_vec, map_to_array, maybe_x64,
                   written_map_names)
from .lower32 import (FRAME_WORDS, array32_to_map, compile_jax32,
                      ctx_to_vec32, map_to_array32, n_frames, ret32_to_int,
                      run_on_refs, vec32_to_bytes)
from .maps import BpfMap, device_shape
from .program import Program
from .verifier import verify_with_info

class PallascError(Exception):
    pass


def on_tpu() -> bool:
    """True when the default backend is a TPU.  Kernels then compile
    through Mosaic, and the in-graph tiers take the pair form."""
    return jax.default_backend() == "tpu"


def resolve_word_width(word_width: Optional[int]) -> int:
    """``None`` picks from the backend: the pair form (32) on TPU, whose
    Mosaic backend has no 64-bit integer units, and uint64 elsewhere."""
    if word_width is None:
        return 32 if on_tpu() else 64
    if word_width not in (32, 64):
        raise PallascError(f"unknown word_width {word_width!r}; use 64 "
                           "(uint64 state, needs x64) or 32 (Mosaic-ready "
                           "(lo, hi) uint32 pairs)")
    return word_width


def compile_pallas(prog: Program, vinfo=None, *, mode: str = "pallas",
                   interpret: Optional[bool] = None,
                   word_width: Optional[int] = None):
    """Return (fn, map_names) — the in-graph calling convention.

    With ``word_width=64``: ``fn(ctx_vec, map_arrays) ->
    (ret, ctx_vec_out, map_arrays_out)``, ``ctx_vec`` uint64[n_fields],
    maps uint64[rows, cols] — requires the x64 scope.

    With ``word_width=32`` (the Mosaic-ready pair form): ``ctx_vec`` is
    uint32[n_fields, 2], maps are uint32[rows, cols, 2] (trailing axis =
    [lo, hi]), ``ret`` is uint32[2]; no x64 anywhere.

    ``vinfo`` reuses a prior :func:`verify_with_info` result (shared
    cfg / loop_bounds / max_steps / region info) — the runtime's load
    path verifies once and hands the artifacts down.  ``mode="jit"``
    jits the bare lowering body instead of the kernel harness.
    ``interpret=None`` compiles through Mosaic on TPU and the Pallas
    interpreter elsewhere (same lowering path either way);
    ``word_width=None`` follows :func:`resolve_word_width`.  The uint64
    kernel is never handed to Mosaic.
    """
    if mode not in ("pallas", "jit"):
        raise PallascError(f"unknown pallasc mode {mode!r}; "
                           "use 'pallas' or 'jit'")
    word_width = resolve_word_width(word_width)
    try:
        check_supported(prog, word_width=word_width)
    except JaxcError as e:
        raise PallascError(
            f"policy '{prog.name}' cannot lower to the pallas tier: {e}"
        ) from e
    if vinfo is None:
        vinfo = verify_with_info(prog)
    names = [d.name for d in prog.maps]

    if mode == "jit":
        # the identical lowering body, no kernel harness
        if word_width == 32:
            return compile_jax32(prog, vinfo)

        def fn(ctx_vec, map_arrays: Dict[str, jnp.ndarray]):
            with jax.enable_x64(True):
                return _Lowerer(prog, vinfo,
                                jnp.asarray(ctx_vec, jnp.uint64),
                                map_arrays).run()
        return fn, names

    if interpret is None:
        interpret = not on_tpu()
    if word_width == 32:
        return _build_pallas_fn32(prog, vinfo, interpret), names
    if not interpret:
        raise PallascError(
            f"policy '{prog.name}': the uint64 kernel cannot lower through "
            "Mosaic (no 64-bit integer units); use word_width=32")
    return _build_pallas_fn(prog, vinfo, interpret), names


def _build_pallas_fn(prog: Program, vinfo, interpret: bool) -> Callable:
    """One ``pl.pallas_call``: ctx + every array map in, (ret, ctx, maps)
    out, all as full-block VMEM tiles (house style: explicit BlockSpecs
    with an index map per operand; grid=(1,) — the whole decision state
    fits one grid step's VMEM by the verifier's bounded-state guarantee:
    ctx is n_fields*8 bytes, maps are bounded by their declarations)."""
    decls = list(prog.maps)
    names = [d.name for d in decls]
    n_maps = len(names)
    n_fields = prog.ctx_type.size // 8

    def kernel(*refs):
        ctx_ref = refs[0]
        map_refs = refs[1:1 + n_maps]
        ret_ref = refs[1 + n_maps]
        ctx_out_ref = refs[2 + n_maps]
        out_map_refs = refs[3 + n_maps:]
        ctx = ctx_ref[...]
        maps = {n: r[...] for n, r in zip(names, map_refs)}
        ret, ctx_out, maps_out = _Lowerer(prog, vinfo, ctx, maps).run()
        ret_ref[...] = jnp.reshape(ret, (1,))
        ctx_out_ref[...] = ctx_out
        for n, r in zip(names, out_map_refs):
            r[...] = maps_out[n]

    vec_spec = pl.BlockSpec((n_fields,), lambda i: (0,))
    # device_shape appends control/metadata rows (ringbuf cursors, LRU
    # key/recency/clock) to the value rows — one rectangular VMEM tile
    # per map regardless of kind
    map_shapes = [device_shape(d.kind, d.value_size, d.max_entries)
                  for d in decls]
    map_specs = [pl.BlockSpec(s, lambda i: (0, 0)) for s in map_shapes]
    call = pl.pallas_call(
        kernel,
        grid=(1,),
        in_specs=[vec_spec] + map_specs,
        out_specs=(pl.BlockSpec((1,), lambda i: (0,)), vec_spec,
                   *map_specs),
        out_shape=(jax.ShapeDtypeStruct((1,), jnp.uint64),
                   jax.ShapeDtypeStruct((n_fields,), jnp.uint64),
                   *[jax.ShapeDtypeStruct(s, jnp.uint64)
                     for s in map_shapes]),
        interpret=interpret,
    )

    def fn(ctx_vec, map_arrays: Dict[str, jnp.ndarray]):
        with jax.enable_x64(True):
            args = [jnp.asarray(ctx_vec, jnp.uint64)]
            args += [jnp.asarray(map_arrays[n], jnp.uint64) for n in names]
            out = call(*args)
            return out[0][0], out[1], dict(zip(names, out[2:]))
    return fn


def _build_pallas_fn32(prog: Program, vinfo, interpret: bool) -> Callable:
    """The pair-form kernel: ctx, every map and the return value as flat
    uint32 ``[lo, hi]`` word arrays in SMEM, the stack frames in SMEM
    scratch.  The body is scalar control code — loads and stores at
    static or clamped dynamic indices, 32-bit arithmetic, ``scf.for`` /
    ``scf.while`` loops — which is what Mosaic lowers on the TPU's
    scalar core.  Inputs are copied to the outputs once, then the
    decision updates the outputs in place."""
    decls = list(prog.maps)
    names = [d.name for d in decls]
    n_maps = len(names)
    n_fields = prog.ctx_type.size // 8
    shapes = [(*device_shape(d.kind, d.value_size, d.max_entries), 2)
              for d in decls]
    sizes = [n_fields * 2] + [math.prod(s) for s in shapes]

    def copy(src, dst, n: int) -> None:
        def body(i, c):
            dst[i] = src[i]
            return c
        lax.fori_loop(0, n, body, 0)

    def kernel(*refs):
        ins, outs = refs[:1 + n_maps], refs[1 + n_maps:]
        ret_ref, ctx_ref, map_refs = outs[0], outs[1], outs[2:2 + n_maps]
        for src, dst, n in zip(ins, (ctx_ref, *map_refs), sizes):
            copy(src, dst, n)
        run_on_refs(prog, vinfo, ctx_ref, dict(zip(names, map_refs)),
                    outs[-1], ret_ref)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    call = pl.pallas_call(
        kernel,
        in_specs=[smem] * (1 + n_maps),
        out_specs=(smem,) * (2 + n_maps),
        out_shape=(jax.ShapeDtypeStruct((2,), jnp.uint32),
                   *[jax.ShapeDtypeStruct((n,), jnp.uint32) for n in sizes]),
        scratch_shapes=[pltpu.SMEM((n_frames(prog) * FRAME_WORDS,),
                                   jnp.uint32)],
        interpret=interpret,
        name=f"bpf_{prog.name}",
    )

    def fn(ctx_vec32, map_arrays32: Dict[str, jnp.ndarray]):
        args = [jnp.asarray(ctx_vec32, jnp.uint32).reshape(-1)]
        args += [jnp.asarray(map_arrays32[n], jnp.uint32).reshape(-1)
                 for n in names]
        out = call(*args)
        return (out[0], out[1].reshape(n_fields, 2),
                {n: o.reshape(s) for n, o, s in zip(names, out[2:], shapes)})
    return fn


# ---------------------------------------------------------------------------
# Host bridge — the PolicyRuntime load/invoke contract for in-graph tiers
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class BridgeStats:
    """Introspection counters for the device-resident bridge; the
    regression tests and perf benchmarks key their assertions off these
    (e.g. "N warm repeat calls perform zero map uploads")."""
    calls: int = 0
    map_uploads: int = 0
    map_downloads: int = 0
    flushes: int = 0
    # multi-shard bridges: merged flushes performed and hash keys dropped
    # to capacity (E2BIG) during a merge
    shard_merges: int = 0
    merge_dropped_keys: int = 0
    # fault containment: upload retries taken, calls served by the host
    # VM after retries ran dry, writebacks deferred after a download
    # failure, and out-of-domain tuner decisions observed device-side
    # (accumulated per call, drained into this counter at flush())
    upload_retries: int = 0
    host_fallbacks: int = 0
    download_failures: int = 0
    domain_faults: int = 0


class DeviceBridge:
    """``fn(ctx_buf) -> int`` host closure with device-resident map state.

    Replaces the old full-sync bridge that round-tripped EVERY map in
    both directions on EVERY call.  Sync now happens only at the edges
    that need it:

      * **upload** — version-gated: a map is (re-)uploaded only when the
        host mutated it since the bridge last saw it (``BpfMap.version``;
        first call seeds everything).  Two bridges sharing a pinned map
        stay coherent through the host copy: one bridge's writeback
        bumps the version, the other re-uploads.
      * **download** — statically scoped: only maps the verified program
        can write (:func:`repro.core.jaxc.written_map_names`) ever sync
        back; lookup-only telemetry inputs never round-trip.  When they
        sync is the ``sync`` policy: ``"step"`` (default) writes them
        back after every call, so host maps remain the observable source
        of truth after every decision; ``"deferred"`` keeps them
        device-resident across calls — zero per-call sync in BOTH
        directions — and writes back only on :meth:`flush` (which the
        runtime triggers at every T3 boundary).
      * **flush()** — full device->host writeback.  The runtime invokes
        it at T3 boundaries (detach, ``link.replace()``, bundle reload);
        host code that mutates map values through raw ``lookup_ref``
        pointers (outside the versioned ``update``/``update_u64``/helper
        surface) should call :meth:`invalidate` to force a re-upload.

    Deferred-mode conflict rule: between flushes the device owns the
    kernel-written maps.  A host write to such a map while unflushed
    kernel writes exist cannot be merged slot-wise; the bridge keeps the
    device copy and the racing host write is DISCARDED at the next
    flush (which overwrites the whole map with device state).  Host
    code that must mutate a kernel-written map under ``"deferred"``
    coordinates explicitly: call :meth:`flush` first, then write.  Host
    writes to lookup-only maps are always picked up on the next call,
    in either mode.

    On accelerator backends the map operands are donated to the kernel
    (``donate_argnums``) so repeat calls alias device buffers instead of
    copying; CPU/interpret CI skips donation (unsupported there, and
    jax would warn on every call).

    Mesh mode (``n_shards > 1``): the bridge keeps one device-resident
    state copy PER SHARD (device/rank index, selected with
    :meth:`set_shard`), each seeded from the host maps at its own upload
    time and carrying a per-map **write cursor** (kernel calls that
    wrote the map on that shard).  Per-call writeback is meaningless
    across shards, so mesh mode requires ``sync="deferred"``; ``flush()``
    runs the versioned, conflict-free merge instead of a one-shard
    overwrite: counter slots land as the sum of per-shard deltas, EMA
    (``merge="max"``) slots go to the shard with the highest cursor, and
    hash maps reconcile per key (:mod:`repro.core.shardmerge`).  The
    merge result is bit-deterministic in shard count and order, and host
    mutations made while shards were accumulating are never lost — each
    shard contributes only deltas against its own seed snapshot.
    """

    def __init__(self, prog: Program, resolved_maps: Dict[str, BpfMap],
                 vinfo=None, *, tier: str = "pallas",
                 mode: str = "pallas", sync: str = "step",
                 n_shards: int = 1):
        if sync not in ("step", "deferred"):
            raise PallascError(f"unknown bridge sync policy {sync!r}; "
                               "use 'step' or 'deferred'")
        if n_shards < 1:
            raise PallascError(f"n_shards must be >= 1, got {n_shards}")
        if n_shards > 1 and sync != "deferred":
            raise PallascError(
                "multi-shard bridges accumulate per-shard deltas and merge "
                "at flush(); per-call writeback cannot reconcile shards — "
                "use sync='deferred'")
        if vinfo is None:
            vinfo = verify_with_info(prog)
        if tier in ("pallas", "pallas32"):
            ww = 32 if tier == "pallas32" else resolve_word_width(None)
            fn, names = compile_pallas(prog, vinfo, mode=mode,
                                       word_width=ww)
        elif tier == "jaxc":
            ww = 64
            fn, names = compile_jax(prog, vinfo)
        else:
            raise PallascError(f"unknown in-graph tier {tier!r}")
        self.tier = tier
        self.word_width = ww
        self.sync = sync
        self._names = names
        self._maps = resolved_maps
        self._prog = prog
        self._written = written_map_names(prog, vinfo) & set(names)
        # fault containment: a failed upload retries with bounded
        # backoff, then the call runs on the host VM instead of raising
        self.upload_retries = 2
        self.retry_backoff_s = 0.001
        self._host_fn: Optional[Callable[[bytearray], int]] = None
        # the kernel cannot throw, so out-of-domain tuner decisions are
        # detected host-side per call and drained into stats at flush()
        self._pending_domain_faults = 0
        self._domain_offs = None
        if prog.section == "tuner":
            ct = prog.ctx_type
            try:
                self._domain_offs = (ct.offset_of("algorithm"),
                                     ct.offset_of("protocol"),
                                     ct.offset_of("n_channels"))
            except KeyError:  # pragma: no cover — tuner ctx has them
                pass
        self._jfn = self._compile(fn)
        self.n_shards = n_shards
        if n_shards > 1:
            from .shardmerge import MERGEABLE_KINDS
            bad = sorted(n for n in self._written
                         if prog.map_decl(n).kind not in MERGEABLE_KINDS)
            if bad:
                kinds = ", ".join(f"{n} ({prog.map_decl(n).kind})"
                                  for n in bad)
                raise PallascError(
                    f"policy '{prog.name}' writes map(s) with no order-free "
                    f"shard merge: {kinds}; mergeable kinds: "
                    f"{', '.join(MERGEABLE_KINDS)}")
        self._shard = 0
        # one device-resident state copy per shard; single-shard bridges
        # see the exact pre-mesh behavior through the property aliases
        self._devs = [dict() for _ in range(n_shards)]
        self._seens = [dict() for _ in range(n_shards)]
        self._dirtys = [set() for _ in range(n_shards)]
        # mesh mode only: per-shard seed snapshots (u64 host layout, for
        # delta merges) and per-map write cursors
        self._bases = [dict() for _ in range(n_shards)]
        self._cursors = [dict() for _ in range(n_shards)]
        self._lock = threading.Lock()
        self.stats = BridgeStats()

    def _compile(self, fn):
        """Compile the decision for its operand shapes at load, so a
        program the backend cannot lower is rejected by the runtime's
        load path instead of failing on the first decision."""
        prog, ww = self._prog, self.word_width
        n_fields = prog.ctx_type.size // 8
        dt = jnp.uint32 if ww == 32 else jnp.uint64
        lane = (2,) if ww == 32 else ()
        ctx = jax.ShapeDtypeStruct((n_fields, *lane), dt)
        maps = {d.name: jax.ShapeDtypeStruct(
            (*device_shape(d.kind, d.value_size, d.max_entries), *lane), dt)
            for d in prog.maps}
        donate = jax.default_backend() in ("tpu", "gpu")
        jfn = jax.jit(fn, donate_argnums=(1,)) if donate else jax.jit(fn)
        try:
            with maybe_x64(ww == 64):
                return jfn.lower(ctx, maps).compile()
        except Exception as e:
            raise PallascError(
                f"policy '{prog.name}' does not compile for the {self.tier} "
                f"tier on {jax.default_backend()}: {type(e).__name__}: "
                f"{str(e).splitlines()[0] if str(e) else ''}") from e

    def compiled_text(self) -> str:
        """The compiled decision program (``tpu_custom_call`` marks a
        Mosaic kernel)."""
        return self._jfn.as_text()

    # per-shard state, addressed through the currently-selected shard so
    # the call path reads identically in single- and multi-shard mode
    @property
    def _dev(self) -> Dict[str, jnp.ndarray]:
        return self._devs[self._shard]

    @_dev.setter
    def _dev(self, value: Dict[str, jnp.ndarray]) -> None:
        self._devs[self._shard] = value

    @property
    def _seen(self) -> Dict[str, int]:
        return self._seens[self._shard]

    @property
    def _device_dirty(self) -> set:
        return self._dirtys[self._shard]

    @_device_dirty.setter
    def _device_dirty(self, value: set) -> None:
        self._dirtys[self._shard] = value

    def set_shard(self, shard: int) -> None:
        """Select which shard (device/rank index) subsequent calls run
        against.  Multi-process launches call this with their rank; the
        closed-loop benchmark round-robins it to simulate per-device
        in-kernel telemetry on a single host."""
        if not 0 <= shard < self.n_shards:
            raise PallascError(
                f"shard {shard} out of range for n_shards={self.n_shards}")
        with self._lock:
            self._shard = shard

    # -- host map -> device ------------------------------------------------
    def _upload_dirty(self) -> None:
        _faults.fire("bridge_upload", self.tier)
        for n in self._names:
            m = self._maps[n]
            if n not in self._dev or self._seen.get(n) != m.version:
                if n in self._device_dirty:
                    # unflushed kernel writes: the device copy wins (see
                    # the class docstring's deferred-mode conflict rule)
                    continue
                with m.lock:
                    # snapshot + version read under ONE critical section:
                    # recording a version observed after a lock-per-entry
                    # snapshot would permanently mask a host write that
                    # landed mid-copy
                    self._dev[n] = (map_to_array32(m)
                                    if self.word_width == 32
                                    else map_to_array(m))
                    self._seen[n] = m.version
                    if self.n_shards > 1 and n in self._written:
                        # merge base: the u64 state THIS shard was seeded
                        # from — its flush contribution is a delta (or a
                        # changed-cell set) against exactly this snapshot
                        self._bases[self._shard][n] = m.to_device()
                        self._cursors[self._shard][n] = 0
                self.stats.map_uploads += 1

    # -- device -> host map ------------------------------------------------
    def _writeback(self, names) -> None:
        _faults.fire("bridge_download", self.tier)
        for n in names:
            arr = self._dev.get(n)
            if arr is None:
                continue
            m = self._maps[n]
            with m.lock:
                # our own writeback must not read as a host mutation, or
                # the next call would re-upload state the device already
                # has — record the post-writeback version under the map
                # lock so a concurrent host write is never masked
                if self.word_width == 32:
                    array32_to_map(arr, m)
                else:
                    array_to_map(arr, m)
                self._seen[n] = m.version
            self._device_dirty.discard(n)
            self.stats.map_downloads += 1

    # -- fault containment -------------------------------------------------
    def _retry_upload(self) -> bool:
        """Bounded-backoff retry of the dirty-map upload."""
        for attempt in range(self.upload_retries):
            time.sleep(self.retry_backoff_s * (attempt + 1))
            self.stats.upload_retries += 1
            try:
                self._upload_dirty()
                return True
            except Exception:
                continue
        return False

    def _host_tier_fn(self) -> Callable[[bytearray], int]:
        """Lazily-built host-VM fallback for calls whose upload failed.

        Runs against the HOST maps — the source of truth for everything
        the kernel hasn't written since the last flush.  Under
        ``sync="deferred"`` unflushed kernel writes are invisible to the
        fallback call (they reach host maps at the next healthy flush);
        that staleness is the documented deferred-mode window, not a new
        one."""
        if self._host_fn is None:
            from .vm import VM
            self._host_fn = VM(self._prog.insns, self._maps,
                               subprogs=self._prog.subprogs).run
        return self._host_fn

    # -- the runtime host-closure contract ---------------------------------
    def __call__(self, ctx_buf: bytearray) -> int:
        with self._lock:
            self.stats.calls += 1
            try:
                self._upload_dirty()
            except Exception:
                if not self._retry_upload():
                    # retries exhausted: contain the fault by running
                    # this one decision on the host tier instead of
                    # raising into the collective path
                    self.stats.host_fallbacks += 1
                    return self._host_tier_fn()(ctx_buf)
            with maybe_x64(self.word_width == 64):
                if self.word_width == 32:
                    ret, ctx_out, maps_out = self._jfn(
                        ctx_to_vec32(ctx_buf), self._dev)
                    self._dev = dict(maps_out)
                    ctx_buf[:] = vec32_to_bytes(ctx_out)
                    rv = ret32_to_int(ret)
                else:
                    import numpy as np
                    ret, ctx_out, maps_out = self._jfn(
                        ctx_to_vec(ctx_buf), self._dev)
                    self._dev = dict(maps_out)
                    ctx_buf[:] = np.asarray(ctx_out).astype("<u8").tobytes()
                    rv = int(ret)
            if self._domain_offs is not None:
                ao, po, co = self._domain_offs
                a = int.from_bytes(ctx_buf[ao:ao + 8], "little")
                p = int.from_bytes(ctx_buf[po:po + 8], "little")
                c = int.from_bytes(ctx_buf[co:co + 8], "little")
                if (a or p or c) and (a >= Algo.COUNT or p >= Proto.COUNT
                                      or c > 0xFFFFFFFF):
                    self._pending_domain_faults += 1
            if self.sync == "step":
                try:
                    self._writeback(self._written)
                except Exception:
                    # contained: host sync is deferred — keep the maps
                    # marked device-dirty so flush() retries later
                    self.stats.download_failures += 1
                    self._device_dirty |= self._written
            else:
                self._device_dirty |= self._written
                if self.n_shards > 1:
                    cur = self._cursors[self._shard]
                    for n in self._written:
                        cur[n] = cur.get(n, 0) + 1
            return rv

    def flush(self) -> int:
        """Sync every device-resident KERNEL-WRITABLE map back to the
        host maps; returns how many were written.  Called by the runtime
        at every T3 boundary (detach / replace / bundle reload).
        Lookup-only maps are never flushed — the kernel cannot have
        changed them, and writing their device copy back would silently
        revert host mutations made since the last upload."""
        with self._lock:
            _faults.fire("bridge_flush", self.tier)
            if self.n_shards > 1:
                synced = self._merged_flush()
            else:
                names = [n for n in self._names
                         if n in self._dev and n in self._written]
                self._writeback(names)
                synced = len(names)
            self.stats.flushes += 1
            # drain the per-call out-of-domain observations so the host
            # side sees kernel-tier fault events at T3 boundaries
            self.stats.domain_faults += self._pending_domain_faults
            self._pending_domain_faults = 0
            return synced

    def _merged_flush(self) -> int:
        """Mesh-mode flush: reconcile every shard's copy of each written
        map against the CURRENT host state with the deterministic shard
        merge, then drop all shard copies so the next call per shard
        re-seeds from the merged view.  Returns maps merged."""
        import numpy as np
        from . import shardmerge as _sm
        synced = 0
        for n in self._names:
            if n not in self._written:
                continue
            decl = self._prog.map_decl(n)
            shards = []
            for s in range(self.n_shards):
                arr = self._devs[s].get(n)
                if arr is None or self._cursors[s].get(n, 0) == 0:
                    continue  # never seeded, or seeded but never written
                a64 = (_sm.pairs_to_u64(arr) if self.word_width == 32
                       else np.asarray(jax.device_get(arr), dtype="<u8"))
                shards.append(_sm.Shard(s, a64, self._cursors[s][n],
                                        self._bases[s][n]))
            if not shards:
                continue
            mstats: dict = {}
            m = self._maps[n]
            with m.lock:
                merged = _sm.merge_map_shards(decl, m.to_device(), shards,
                                              mstats)
                m.from_device(merged)
            self.stats.merge_dropped_keys += mstats.get("dropped_keys", 0)
            self.stats.map_downloads += 1
            synced += 1
            # every shard copy is now stale relative to the merged host
            # state; drop them so the next per-shard call re-seeds
            for s in range(self.n_shards):
                self._devs[s].pop(n, None)
                self._seens[s].pop(n, None)
                self._dirtys[s].discard(n)
                self._bases[s].pop(n, None)
                self._cursors[s].pop(n, None)
        if synced:
            self.stats.shard_merges += 1
        return synced

    def invalidate(self, name: Optional[str] = None) -> None:
        """Drop the device copy of ``name`` (or all maps) so the next
        call re-uploads from the host — the escape hatch for host writes
        that bypass the versioned map mutation surface."""
        with self._lock:
            for s in range(self.n_shards):
                if name is None:
                    self._devs[s].clear()
                    self._seens[s].clear()
                    self._dirtys[s].clear()
                    self._bases[s].clear()
                    self._cursors[s].clear()
                else:
                    self._devs[s].pop(name, None)
                    self._seens[s].pop(name, None)
                    self._dirtys[s].discard(name)
                    self._bases[s].pop(name, None)
                    self._cursors[s].pop(name, None)


def compile_host(prog: Program, resolved_maps: Dict[str, BpfMap],
                 vinfo=None, *, tier: str = "pallas",
                 mode: str = "pallas",
                 sync: str = "step", n_shards: int = 1) -> DeviceBridge:
    """Wrap an in-graph tier (pallas / pallas32 / jaxc) behind the host
    closure signature ``fn(ctx_buf) -> int`` the runtime invokes.

    Returns a :class:`DeviceBridge`: map state stays device-resident
    across calls with version-gated uploads and statically-scoped
    writebacks, and the function is jitted once at load — repeat
    decisions replay the compiled kernel with zero retraces and, when
    host maps are clean, zero map uploads (``sync="deferred"`` also
    skips the per-call writeback of kernel-written maps; the state then
    reaches host maps at ``flush()``/T3 boundaries).

    ``n_shards > 1`` builds a mesh-mode bridge (one device-resident
    state copy per shard, selected with :meth:`DeviceBridge.set_shard`;
    ``flush()`` runs the deterministic shard merge) — requires
    ``sync="deferred"``."""
    return DeviceBridge(prog, resolved_maps, vinfo, tier=tier, mode=mode,
                        sync=sync, n_shards=n_shards)
