"""jaxc — verified policy bytecode compiled to pure JAX (the in-graph tier).

This tier goes beyond the paper: NCCLbpf's policies execute on the host
around each collective launch; on TPU the step function is one fused XLA
program, so we *if-convert* the verified policy into jnp ops and run it
INSIDE the compiled program.  Closed-loop adaptation (profiler map ->
tuner decision -> ``lax.switch`` branch) then happens per step with zero
host round-trips and zero retraces.

Why verification makes this possible:
  * the CFG decomposes into forward regions plus *natural loops with
    proven trip bounds* (shared :mod:`repro.core.cfg` layer) -> forward
    regions if-convert classically (execute every block under a
    predicate, writes select via ``jnp.where``), and each loop lowers to
    one ``lax.fori_loop`` running exactly ``bound + 1`` iterations with
    the machine state (regs / stack / ctx / maps / exit predicates)
    functionally threaded through the carry — early exits simply drop
    the ``active`` predicate so remaining iterations are no-ops
  * every memory insn has a statically known region (ctx / stack / one
    specific map)  -> each load/store lowers to a typed gather/scatter
  * bounded stack, bounded loops -> fixed-size traced state, zero
    retraces across decisions

Supported surface (JaxcError otherwise): ALU64/32, jumps, bounded loops,
bpf-to-bpf calls (``call_fn`` — callees are inlined under the caller's
predicate with a fresh frame, so zero-retrace and single-``fori_loop``
structure survive), ctx loads/stores (8-byte fields), stack loads/stores
(static or dynamic offset), ARRAY-family maps (u64-slot granularity;
``perdev_array`` exposes its current shard), RINGBUF maps
(reserve/submit/discard on the control words appended to the device
array — see :func:`repro.core.maps.device_shape`), HASH maps
(fixed-capacity open-addressing table, linear probing via a masked
probe-distance scan; inserts fail with E2BIG when full, deletes stay
host-side), LRU_HASH maps (masked-scan lookup/update over ``[value,
key, recency]`` rows plus a clock cell), helpers map_lookup_elem /
map_update_elem / ema_update / ringbuf_reserve / ringbuf_submit /
ringbuf_discard.  Wall-clock helpers are host-tier-only.

We pass ctx and maps as uint64 arrays under the scoped 64-bit context
(``jax.enable_x64``); the surrounding model code stays 32-bit.
"""

from __future__ import annotations

import contextlib
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from . import helpers as H
from .cfg import CFG, Loop
from .isa import (FP_REG, Insn, STACK_SIZE, alu_base, alu_width, is_alu,
                  is_imm_form, is_jump_cond, is_load, is_store, jump_base,
                  mem_size)
from .maps import BpfMap
from .program import Program
from .verifier import verify_with_info

M64 = (1 << 64) - 1


class JaxcError(Exception):
    pass


# pointer encoding (mirrors the host JIT):
#   stack: 1<<32 | byte_off
#   ctx:   2<<32 | byte_off
#   map value (array map mi): (16+mi)<<56 | key<<24 | byte_off
_STACK_TAG = 1 << 32
_CTX_TAG = 2 << 32


def _map_tag(mi: int):
    return (16 + mi) << 56


_INGRAPH_KINDS = ("array", "perdev_array", "ringbuf", "hash", "lru_hash")
_INGRAPH_HIDS = (1, 2, 64, 65, 66, 67)


def check_supported(prog: Program, *, word_width: int = 64) -> None:
    """Raise JaxcError if ``prog`` cannot lower in-graph.

    ``word_width=32`` additionally applies the 32-bit-pair tier's
    restriction (no LRU recency/clock lowering), mirroring
    :mod:`repro.core.pallasc`'s compile-time rejection so eligibility
    probes agree with the compiler."""
    if word_width == 32:
        lru = [d.name for d in prog.maps if d.kind == "lru_hash"]
        if lru:
            raise JaxcError(
                f"policy '{prog.name}' uses lru_hash map(s) "
                f"{', '.join(repr(n) for n in lru)}; the 32-bit-pair tier "
                "does not lower LRU recency/clock metadata.  Workarounds: "
                "declare the map with kind=\"hash\" or run on a host tier "
                "(interp/jit/native); off the TPU, word_width=64 lowers it")
    for d in prog.maps:
        if d.kind not in _INGRAPH_KINDS:
            raise JaxcError(
                f"map '{d.name}' is {d.kind}; in-graph tier supports "
                f"{'/'.join(_INGRAPH_KINDS)} maps only")
        if d.value_size % 8:
            raise JaxcError(f"map '{d.name}': value_size must be 8-aligned")
        if d.kind == "hash" and d.key_size not in (4, 8):
            raise JaxcError(
                f"hash map '{d.name}': in-graph probing needs a 4- or "
                f"8-byte key (got {d.key_size})")
    bodies = [("main", prog.insns)]
    bodies += [(sp.name, sp.insns) for sp in prog.subprogs]
    for fname, insns in bodies:
        for pc, insn in enumerate(insns):
            if insn.op == "call" and insn.imm not in _INGRAPH_HIDS:
                hname = H.HELPERS[insn.imm].name
                if hname == "map_delete_elem":
                    raise JaxcError(
                        f"map_delete_elem (insn {pc} in {fname}) is not "
                        "available in-graph: deleting from a linear-"
                        "probing table would need tombstones; delete "
                        "from the host side instead (the bridge repacks "
                        "the table canonically on the next upload)")
                raise JaxcError(
                    f"helper {hname} (insn {pc} in {fname}) is not "
                    "available in-graph")


def _fn_infos(vinfo):
    """Per-function analysis artifacts: ``vinfo.fns`` when the verifier
    ran multi-function, else the top-level object (which quacks the
    same) as the sole entry."""
    fns = getattr(vinfo, "fns", None)
    return list(fns) if fns else [vinfo]


def written_map_names(prog: Program, vinfo) -> frozenset:
    """Maps the program can mutate, from the verifier's region facts.

    A map is written iff some store's proven region is a value cell of it,
    or a mutating helper (``map_update_elem`` / ``ema_update`` / any
    ringbuf helper — the control words advance) statically binds to it,
    or a ``map_lookup_elem`` binds to an LRU map (a hit refreshes
    recency; plain-hash lookups mutate nothing).  Subprogram bodies
    count: a map a callee writes is written.  The host bridge uses this
    to sync back ONLY these maps after a device call — lookup-only
    telemetry inputs never round-trip."""
    kinds = {d.name: d.kind for d in prog.maps}
    out = set()
    for fi in _fn_infos(vinfo):
        for pc, insn in enumerate(fi.insns):
            if is_store(insn.op):
                info = fi.mem_info.get(pc)
                if info is not None and info[0] not in ("ctx", "stack"):
                    out.add(info[1])
            elif insn.op == "call" and insn.imm in (2, 64, 65, 66, 67):
                mname = fi.call_map.get(pc)
                if mname is not None:
                    out.add(mname)
            elif insn.op == "call" and insn.imm == 1:
                mname = fi.call_map.get(pc)
                if mname is not None and kinds.get(mname) == "lru_hash":
                    out.add(mname)
    return frozenset(out)


def maybe_x64(needed: bool = True):
    """``jax.enable_x64(True)`` scope when ``needed``, a no-op otherwise:
    callers that serve both word widths scope with
    ``maybe_x64(word_width == 64)``, so the 32-bit-pair path never drags
    the x64 machinery in."""
    return jax.enable_x64(True) if needed else contextlib.nullcontext()


def _u64(x):
    return jnp.asarray(x, jnp.uint64)


def _pred_or(ps):
    p = ps[0]
    for q in ps[1:]:
        p = jnp.logical_or(p, q)
    return p


def _sel(p, new, old):
    return jnp.where(p, new, old)


class _Lowerer:
    """One policy invocation lowered block-by-block under predicates.

    Machine state lives in attributes (regs/stack/ctx/maps/done/ret) so
    straight-line emission stays imperative; loops snapshot the state
    into a ``fori_loop`` carry and restore from the final carry.

    The CFG walk (regions, predicates, loop carries) is representation-
    agnostic: every place a 64-bit machine value is materialized,
    selected, computed, or compared goes through the ``_imm`` / ``_coerce``
    / ``_sel`` / ``_alu`` / ``_cmp`` hooks plus the memory/helper methods.
    The base class keeps the native-uint64 representation; the 32-bit-pair
    lowering (:mod:`repro.core.lower32`, for Mosaic's 32-bit-only integer
    units) subclasses it and swaps only those hooks."""

    def __init__(self, prog: Program, vinfo, ctx_vec, map_arrays):
        self.prog = prog
        self.vinfo = vinfo
        # per-function analysis artifacts: bpf-to-bpf callees are
        # *inlined* at lowering time (`_inline_call`), retargeting
        # fninfo/cfg/insns at the callee for the duration of its body
        self.fns = _fn_infos(vinfo)
        self.fninfo = self.fns[0]
        self.cfg: CFG = self.fninfo.cfg
        self.insns = list(prog.insns)
        self.decls = list(prog.maps)
        self.map_index = {d.name: i for i, d in enumerate(self.decls)}
        self.map_names = [d.name for d in self.decls]
        self._init_state(ctx_vec, map_arrays)

    # ---- representation hooks (overridden by the 32-bit-pair lowerer) ----
    def _init_state(self, ctx_vec, map_arrays) -> None:
        self.ctx = jnp.asarray(ctx_vec, jnp.uint64)
        self.maps = {k: jnp.asarray(v, jnp.uint64)
                     for k, v in map_arrays.items()}
        self.regs: List[jnp.ndarray] = [_u64(0)] * 11
        self.regs[1] = self._imm(_CTX_TAG)
        self.regs[FP_REG] = self._imm(_STACK_TAG | STACK_SIZE)
        self.stack = self._fresh_stack()
        self.done = jnp.asarray(False)
        self.ret = self._imm(0)

    def _fresh_stack(self):
        """A zeroed frame in the machine representation (u64 slots)."""
        return jnp.zeros(STACK_SIZE // 8, jnp.uint64)

    def _enter_frame(self):
        """Give an inlined callee a fresh zeroed frame; returns the token
        :meth:`_leave_frame` restores the caller's frame from."""
        saved, self.stack = self.stack, self._fresh_stack()
        return saved

    def _leave_frame(self, saved) -> None:
        self.stack = saved

    def _imm(self, imm: int):
        """Materialize a 64-bit immediate in the machine representation."""
        return jnp.uint64(imm & M64)

    def _coerce(self, val):
        """Coerce a helper/ALU result into the machine representation."""
        return jnp.asarray(val, jnp.uint64)

    def _sel(self, p, new, old):
        """Predicated select over machine values."""
        return _sel(p, new, old)

    def _alu(self, base: str, width: int, a, b):
        return _alu_jax(base, width, a, b)

    def _cmp(self, base: str, a, b):
        return _cmp_jax(base, a, b)

    # ---- entry -----------------------------------------------------------
    def run(self):
        top = {h for h, L in self.cfg.loops.items() if L.parent is None}
        out = self._exec_region(
            list(range(self.cfg.n)), {0: [jnp.asarray(True)]}, expand=top)
        if out:
            # every top-level out-edge is an exit (routed via done/ret);
            # residue is a CFG bug — raise even under python -O
            raise JaxcError(f"unrouted edges at top level: {sorted(out)}")
        return self.ret, self.ctx, self.maps

    # ---- region execution ------------------------------------------------
    def _exec_region(self, block_list: List[int],
                     incoming: Dict[int, list], expand) -> Dict[int, list]:
        region = set(block_list)
        inc: Dict[int, list] = {b: list(ps) for b, ps in incoming.items()}
        out: Dict[int, list] = {}
        consumed = set()

        def route(src: int, tgt: int, p) -> None:
            if tgt == CFG.EXIT:
                return  # exit insns route through done/ret directly
            if tgt in region and tgt > src:
                inc.setdefault(tgt, []).append(p)
            else:
                # leaves the region, or is a back edge to its header
                out.setdefault(tgt, []).append(p)

        for b in block_list:
            if b in consumed:
                continue
            ps = inc.get(b)
            if b in expand:
                L = self.cfg.loops[b]
                consumed |= L.body
                if ps is None:
                    continue  # statically unreachable loop
                self._lower_loop(L, _pred_or(ps),
                                 lambda tgt, p, b=b: route(b, tgt, p))
                continue
            if ps is None:
                continue  # statically unreachable
            self._exec_block(b, _pred_or(ps),
                             lambda tgt, p, b=b: route(b, tgt, p))
        return out

    def _exec_block(self, b: int, P, route) -> None:
        insns = self.insns
        start, end = self.cfg.ranges[b]
        for pc in range(start, end):
            insn = insns[pc]
            op = insn.op
            if op == "exit":
                take = jnp.logical_and(P, jnp.logical_not(self.done))
                self.ret = self._sel(take, self.regs[0], self.ret)
                self.done = jnp.logical_or(self.done, P)
                return
            if op == "ja":
                route(self.cfg.succs[b][0], P)
                return
            if is_jump_cond(op):
                a = self.regs[insn.dst]
                v = self._imm(insn.imm) if is_imm_form(op) \
                    else self.regs[insn.src]
                c = self._cmp(jump_base(op), a, v)
                taken, fall = self.cfg.succs[b]
                route(taken, jnp.logical_and(P, c))
                route(fall, jnp.logical_and(P, jnp.logical_not(c)))
                return
            self._exec_straight(pc, insn, P)
        route(self.cfg.succs[b][0], P)  # fall-through block

    # ---- straight-line instructions --------------------------------------
    def _wreg(self, P, idx: int, val) -> None:
        self.regs[idx] = self._sel(P, self._coerce(val), self.regs[idx])

    def _exec_straight(self, pc: int, insn: Insn, P) -> None:
        op = insn.op
        if op == "lddw":
            self._wreg(P, insn.dst, self._imm(insn.imm))
            return
        if op == "ldmap":
            mi = self.map_index[insn.map_name]
            self._wreg(P, insn.dst, self._imm(_map_tag(mi)))
            return
        if op == "call":
            ret = self._call(pc, insn, P)
            self._wreg(P, 0, ret)
            for r in (1, 2, 3, 4, 5):
                self._wreg(P, r, self._imm(0))
            return
        if op == "call_fn":
            self._inline_call(insn.imm, P)
            return
        if is_alu(op):
            a = self.regs[insn.dst]
            b = self._imm(insn.imm) if is_imm_form(op) \
                else self.regs[insn.src]
            self._wreg(P, insn.dst,
                       self._alu(alu_base(op), alu_width(op), a, b))
            return
        if is_load(op):
            self._exec_load(pc, insn, P)
            return
        if is_store(op):
            self._exec_store(pc, insn, P)
            return
        raise JaxcError(f"unhandled op {op}")

    # ---- memory -----------------------------------------------------------
    def _stack_load(self, ptr, size: int):
        slot = ((ptr & jnp.uint64(0xFFFFFFFF)) >> 3).astype(jnp.int32)
        word = self.stack[slot]
        if size == 8:
            return word
        sh = ((ptr & jnp.uint64(7)) * 8).astype(jnp.uint64)
        mask = jnp.uint64((1 << (8 * size)) - 1)
        return (word >> sh) & mask

    def _stack_store(self, P, ptr, size: int, val) -> None:
        off = ptr & jnp.uint64(0xFFFFFFFF)
        slot = (off >> 3).astype(jnp.int32)
        word = self.stack[slot]
        if size == 8:
            new = jnp.asarray(val, jnp.uint64)
        else:
            sh = ((off & jnp.uint64(7)) * 8).astype(jnp.uint64)
            mask = jnp.uint64((1 << (8 * size)) - 1)
            new = ((word & ~(mask << sh))
                   | ((jnp.asarray(val, jnp.uint64) & mask) << sh))
        self.stack = self.stack.at[slot].set(_sel(P, new, word))

    @staticmethod
    def _mapval_decode(ptr):
        mi = ((ptr >> jnp.uint64(56)) - 16).astype(jnp.int32)
        key = ((ptr >> jnp.uint64(24)) & jnp.uint64(0xFFFFFFFF)).astype(
            jnp.int32)
        off = ptr & jnp.uint64(0xFFFFFF)
        return mi, key, off

    def _exec_load(self, pc: int, insn: Insn, P) -> None:
        size = mem_size(insn.op)
        region, mname, base = self.fninfo.mem_info[pc]
        ptr = self.regs[insn.src] + jnp.uint64(insn.off & M64)
        if region == "ctx":
            off = base + insn.off  # static (verified)
            val = self.ctx[off // 8]
            if size < 8:
                val = val & jnp.uint64((1 << (8 * size)) - 1)
        elif region == "stack":
            val = self._stack_load(ptr, size)
        else:  # mapval
            _, key, off = self._mapval_decode(ptr)
            slot = (off >> jnp.uint64(3)).astype(jnp.int32)
            val = self.maps[mname][key, slot]
            if size < 8:
                val = val & jnp.uint64((1 << (8 * size)) - 1)
        self._wreg(P, insn.dst, val)

    def _exec_store(self, pc: int, insn: Insn, P) -> None:
        size = mem_size(insn.op)
        region, mname, base = self.fninfo.mem_info[pc]
        val = jnp.uint64(insn.imm & M64) if not insn.op.startswith("stx") \
            else self.regs[insn.src]
        ptr = self.regs[insn.dst] + jnp.uint64(insn.off & M64)
        if region == "ctx":
            slot = (base + insn.off) // 8
            self.ctx = self.ctx.at[slot].set(_sel(P, val, self.ctx[slot]))
        elif region == "stack":
            self._stack_store(P, ptr, size, val)
        else:  # mapval
            _, key, off = self._mapval_decode(ptr)
            slot = (off >> jnp.uint64(3)).astype(jnp.int32)
            old = self.maps[mname][key, slot]
            self.maps[mname] = self.maps[mname].at[key, slot].set(
                _sel(P, val, old))

    # ---- helpers -----------------------------------------------------------
    def _call(self, pc: int, insn: Insn, P):
        hid = insn.imm
        # the verifier proved exactly which map reaches this call site
        mname = self.fninfo.call_map.get(pc)
        if mname is None:
            raise JaxcError(f"helper at insn {pc} has no static map binding")
        mi = self.map_index[mname]
        d = self.decls[mi]
        if d.kind == "ringbuf":
            return self._call_ringbuf(hid, mi, d, P)
        if d.kind == "lru_hash":
            return self._call_lru(hid, mi, d, P)
        if d.kind == "hash":
            return self._call_hash(hid, mi, d, P)
        key = self._stack_load(self.regs[2], d.key_size).astype(jnp.uint64)
        valid = key < jnp.uint64(d.max_entries)
        ki = jnp.minimum(key, jnp.uint64(d.max_entries - 1)).astype(jnp.int32)
        if hid == 1:  # map_lookup_elem(map, key*)
            enc = (jnp.uint64(_map_tag(mi))
                   | ((key & jnp.uint64(0xFFFFFFFF)) << jnp.uint64(24)))
            return jnp.where(valid, enc, jnp.uint64(0))
        if hid == 2:  # map_update_elem(map, key*, value*, flags)
            n_slots = d.value_size // 8
            row = [self._stack_load(self.regs[3] + jnp.uint64(8 * s), 8)
                   for s in range(n_slots)]
            newrow = jnp.stack(row)
            old = self.maps[d.name][ki]
            take = jnp.logical_and(P, valid)
            self.maps[d.name] = self.maps[d.name].at[ki].set(
                jnp.where(take, newrow, old))
            return jnp.where(valid, jnp.uint64(0), jnp.uint64(M64))
        if hid == 64:  # ema_update(map, key*, sample, weight)
            w = jnp.maximum(self.regs[4], jnp.uint64(1))
            old = self.maps[d.name][ki, 0]
            new = (old * (w - jnp.uint64(1)) + self.regs[3]) // w
            take = jnp.logical_and(P, valid)
            self.maps[d.name] = self.maps[d.name].at[ki, 0].set(
                jnp.where(take, new, old))
            return new
        raise JaxcError(f"helper {hid} not supported in-graph")

    def _call_ringbuf(self, hid: int, mi: int, d, P):
        """reserve/submit/discard on the control words the device layout
        appends to the record rows (``maps.device_shape``): head / tail /
        drops / pending, mirroring :class:`repro.core.maps.RingBufMap`
        cursor-for-cursor so vm differentials stay bit-identical."""
        arr = self.maps[d.name]
        slots = d.value_size // 8
        ctl = lambda w: (d.max_entries + w // slots, w % slots)  # noqa: E731
        (hr, hc), (pr, pc2) = ctl(0), ctl(3)
        head, pend = arr[hr, hc], arr[pr, pc2]
        if hid == 66:  # ringbuf_submit: publish the pending record
            head2 = head + pend
            arr = arr.at[hr, hc].set(jnp.where(P, head2, head))
            arr = arr.at[pr, pc2].set(jnp.where(P, jnp.uint64(0), pend))
            self.maps[d.name] = arr
            return jnp.uint64(0)
        if hid == 67:  # ringbuf_discard: abandon the pending record
            arr = arr.at[pr, pc2].set(jnp.where(P, jnp.uint64(0), pend))
            self.maps[d.name] = arr
            return jnp.uint64(0)
        if hid != 65:
            raise JaxcError(f"helper {hid} on ringbuf map '{d.name}'")
        # ringbuf_reserve: implicitly commit a still-pending reservation,
        # then NULL (+1 drop) on full, else mark the next row pending
        (tr, tc), (dr, dc) = ctl(1), ctl(2)
        tail, drops = arr[tr, tc], arr[dr, dc]
        head1 = head + pend
        full = (head1 - tail) >= jnp.uint64(d.max_entries)
        arr = arr.at[hr, hc].set(jnp.where(P, head1, head))
        arr = arr.at[pr, pc2].set(jnp.where(
            P, jnp.where(full, jnp.uint64(0), jnp.uint64(1)), pend))
        arr = arr.at[dr, dc].set(jnp.where(
            jnp.logical_and(P, full), drops + jnp.uint64(1), drops))
        self.maps[d.name] = arr
        row = (head1 % jnp.uint64(d.max_entries)) & jnp.uint64(0xFFFFFFFF)
        enc = jnp.uint64(_map_tag(mi)) | (row << jnp.uint64(24))
        return jnp.where(full, jnp.uint64(0), enc)

    def _call_lru(self, hid: int, mi: int, d, P):
        """lookup/update on the LRU device layout: ``max_entries`` rows of
        ``[value slots..., key, recency]`` plus the clock cell at
        ``[max_entries, 0]`` (``maps.device_shape``).  Victim selection is
        ``argmin(recency)`` — first minimum, so free rows (recency 0) win
        and ties break to the lowest index, matching the host map."""
        arr = self.maps[d.name]
        slots = d.value_size // 8
        kcol, rcol = slots, slots + 1
        key = self._stack_load(self.regs[2], d.key_size).astype(jnp.uint64)
        keys = arr[:d.max_entries, kcol]
        recs = arr[:d.max_entries, rcol]
        match = jnp.logical_and(recs > jnp.uint64(0), keys == key)
        found = jnp.any(match)
        idx = jnp.argmax(match).astype(jnp.int32)
        clock = arr[d.max_entries, 0]
        clock1 = clock + jnp.uint64(1)
        if hid == 1:  # map_lookup_elem: a hit refreshes recency
            take = jnp.logical_and(P, found)
            arr = arr.at[d.max_entries, 0].set(
                jnp.where(take, clock1, clock))
            arr = arr.at[idx, rcol].set(
                jnp.where(take, clock1, arr[idx, rcol]))
            self.maps[d.name] = arr
            enc = (jnp.uint64(_map_tag(mi))
                   | (idx.astype(jnp.uint64) << jnp.uint64(24)))
            return jnp.where(found, enc, jnp.uint64(0))
        # the remaining helpers claim a row: the hit, else the LRU victim
        victim = jnp.argmin(recs).astype(jnp.int32)
        tgt = jnp.where(found, idx, victim)
        oldrow = lax.dynamic_slice(
            arr, (tgt, jnp.int32(0)), (1, arr.shape[1]))[0]
        if hid == 2:  # map_update_elem: overwrite hit else evict victim
            newrow = jnp.stack(
                [self._stack_load(self.regs[3] + jnp.uint64(8 * s), 8)
                 for s in range(slots)])
            ret = jnp.uint64(0)
        elif hid == 64:  # ema_update: RMW slot 0 (miss seeds from old=0)
            w = jnp.maximum(self.regs[4], jnp.uint64(1))
            old = jnp.where(found, oldrow[0], jnp.uint64(0))
            new = (old * (w - jnp.uint64(1)) + self.regs[3]) // w
            keep = jnp.where(found, oldrow[:slots],
                             jnp.zeros(slots, jnp.uint64))
            newrow = keep.at[0].set(new)
            ret = new
        else:
            raise JaxcError(f"helper {hid} on lru_hash map '{d.name}'")
        full_new = jnp.concatenate([newrow, jnp.stack([key, clock1])])
        sel = jnp.where(P, full_new, oldrow)
        arr = lax.dynamic_update_slice(
            arr, sel[None, :], (tgt, jnp.int32(0)))
        arr = arr.at[d.max_entries, 0].set(jnp.where(P, clock1, clock))
        self.maps[d.name] = arr
        return ret

    def _hash_probe(self, arr, d, key):
        """Open-addressing probe over the hash device layout
        (``max_entries`` rows of ``[value slots..., key, used]`` plus the
        occupancy cell at ``[max_entries, 0]`` — ``maps.device_shape``).

        Linear probing in probe-distance order from ``hash_slot(key)``:
        the scan stops at the first row that is a key match or empty,
        exactly the sequential probe's termination — so the selected row
        matches the host map's packing (``HashMap.to_device`` inserts by
        the same probe sequence, and in-graph deletion is rejected, so
        no tombstone can sit between the home slot and the key).

        Returns ``(first, hit, can_claim)``: the stopping row index, a
        key-match predicate, and whether a miss may claim ``first`` as a
        fresh slot (False when the table is full and the key absent)."""
        slots = d.value_size // 8
        kcol, ucol = slots, slots + 1
        cap = d.max_entries
        keys = arr[:cap, kcol]
        used = arr[:cap, ucol] > jnp.uint64(0)
        h = ((key & jnp.uint64(0xFFFFFFFF)) ^ (key >> jnp.uint64(32))) \
            % jnp.uint64(cap)
        dist = (jnp.arange(cap, dtype=jnp.uint64) - h) % jnp.uint64(cap)
        is_match = jnp.logical_and(used, keys == key)
        stop = jnp.logical_or(is_match, jnp.logical_not(used))
        first = jnp.argmin(
            jnp.where(stop, dist, jnp.uint64(cap))).astype(jnp.int32)
        has_stop = jnp.any(stop)
        hit = jnp.logical_and(has_stop, is_match[first])
        can_claim = jnp.logical_and(has_stop, jnp.logical_not(hit))
        return first, hit, can_claim

    def _call_hash(self, hid: int, mi: int, d, P):
        """lookup/update/ema on the open-addressing hash layout.  A full
        table rejects inserts with -1 (E2BIG), matching the host map;
        lookups mutate nothing (unlike LRU there is no recency)."""
        arr = self.maps[d.name]
        slots = d.value_size // 8
        cap = d.max_entries
        key = self._stack_load(self.regs[2], d.key_size).astype(jnp.uint64)
        first, hit, can_claim = self._hash_probe(arr, d, key)
        if hid == 1:  # map_lookup_elem: encode the physical row index
            enc = (jnp.uint64(_map_tag(mi))
                   | (first.astype(jnp.uint64) << jnp.uint64(24)))
            return jnp.where(hit, enc, jnp.uint64(0))
        ok = jnp.logical_or(hit, can_claim)
        oldrow = lax.dynamic_slice(
            arr, (first, jnp.int32(0)), (1, arr.shape[1]))[0]
        if hid == 2:  # map_update_elem: overwrite hit else claim a slot
            newvals = jnp.stack(
                [self._stack_load(self.regs[3] + jnp.uint64(8 * s), 8)
                 for s in range(slots)])
            ret = jnp.where(ok, jnp.uint64(0), jnp.uint64(M64))
        elif hid == 64:  # ema_update: RMW slot 0 (miss seeds from old=0)
            w = jnp.maximum(self.regs[4], jnp.uint64(1))
            old = jnp.where(hit, oldrow[0], jnp.uint64(0))
            new = (old * (w - jnp.uint64(1)) + self.regs[3]) // w
            keep = jnp.where(hit, oldrow[:slots],
                             jnp.zeros(slots, jnp.uint64))
            newvals = keep.at[0].set(new)
            ret = new
        else:
            raise JaxcError(f"helper {hid} on hash map '{d.name}'")
        take = jnp.logical_and(P, ok)
        full_new = jnp.concatenate(
            [newvals, jnp.stack([key, jnp.uint64(1)])])
        sel = jnp.where(take, full_new, oldrow)
        arr = lax.dynamic_update_slice(
            arr, sel[None, :], (first, jnp.int32(0)))
        occ = arr[cap, 0]
        arr = arr.at[cap, 0].set(jnp.where(
            jnp.logical_and(P, can_claim), occ + jnp.uint64(1), occ))
        self.maps[d.name] = arr
        return ret

    # ---- bpf-to-bpf calls ---------------------------------------------------
    def _inline_call(self, idx: int, P) -> None:
        """``call_fn``: inline the callee's lowered body under the
        caller's predicate.  The callee gets a fresh frame — zeroed
        stack, fresh regs with r1-r5 copied in — while ctx and maps stay
        shared (writes inside the callee are already gated on ``P``
        through its block predicates).  done/ret are callee-local, so a
        callee ``exit`` returns to the caller's continuation instead of
        ending the program.  Inlining (vs an out-of-line call) keeps the
        whole program one straight trace: zero retraces, and loops
        containing calls still lower to a single ``fori_loop``."""
        callee = self.fns[1 + idx]
        saved = (self.fninfo, self.cfg, self.insns, self._enter_frame(),
                 self.regs, self.done, self.ret)
        self.fninfo = callee
        self.cfg = callee.cfg
        self.insns = list(callee.insns)
        cregs = [self._imm(0)] * 11
        for r in (1, 2, 3, 4, 5):
            cregs[r] = saved[4][r]
        cregs[FP_REG] = self._imm(_STACK_TAG | STACK_SIZE)
        self.regs = cregs
        self.done = jnp.asarray(False)
        self.ret = self._imm(0)

        top = {h for h, L in self.cfg.loops.items() if L.parent is None}
        out = self._exec_region(list(range(self.cfg.n)), {0: [P]},
                                expand=top)
        if out:
            raise JaxcError(
                f"unrouted edges in subprogram '{callee.name}': "
                f"{sorted(out)}")
        ret = self.ret
        (self.fninfo, self.cfg, self.insns, frame,
         self.regs, self.done, self.ret) = saved
        self._leave_frame(frame)
        self._wreg(P, 0, ret)
        for r in (1, 2, 3, 4, 5):
            self._wreg(P, r, self._imm(0))

    # ---- loops -------------------------------------------------------------
    def _snapshot(self, active, exit_preds):
        return (active, tuple(self.regs), self.stack, self.ctx,
                tuple(self.maps[n] for n in self.map_names),
                self.done, self.ret, tuple(exit_preds))

    def _restore(self, carry):
        active, regs, stack, ctx, maps_t, done, ret, exps = carry
        self.regs = list(regs)
        self.stack = stack
        self.ctx = ctx
        self.maps = {n: m for n, m in zip(self.map_names, maps_t)}
        self.done = done
        self.ret = ret
        return active, list(exps)

    def _lower_loop(self, L: Loop, entry_pred, route) -> None:
        """One natural loop -> one ``lax.fori_loop``.

        The carry threads (active, regs, stack, ctx, maps, done, ret,
        per-exit-target predicates).  Each iteration executes header +
        body under ``active``; taking an exit edge latches that target's
        predicate and drops out of ``active``, so later iterations leave
        the state untouched.  The verifier's trip bound caps the counter:
        ``bound`` body passes plus one final header visit that takes the
        exit test."""
        h = L.header
        bound = self.fninfo.loop_bounds[h]
        body_blocks = sorted(L.body)
        exit_targets = list(L.exit_targets)
        inner = {M.header for M in self.cfg.inner_loops(L)}

        false_ = jnp.asarray(False)
        init = self._snapshot(entry_pred, [false_] * len(exit_targets))

        def body(_k, carry):
            active, exps = self._restore(carry)
            out = self._exec_region(body_blocks, {h: [active]},
                                    expand=inner)
            next_active = _pred_or(out.pop(h, [false_]))
            new_exps = []
            for tgt, e in zip(exit_targets, exps):
                new_exps.append(jnp.logical_or(
                    e, _pred_or(out.pop(tgt, [false_]))))
            if out:
                raise JaxcError(
                    f"loop at block {h}: unrouted edges {sorted(out)}")
            return self._snapshot(next_active, new_exps)

        final = lax.fori_loop(0, bound + 1, body, init)
        _, exps = self._restore(final)
        for tgt, e in zip(exit_targets, exps):
            route(tgt, e)


def compile_jax(prog: Program, vinfo=None):
    """Return (fn, map_names).

    ``fn(ctx_vec, map_arrays) -> (ret, ctx_vec_out, map_arrays_out)`` where
    ``ctx_vec`` is uint64[n_fields] and ``map_arrays`` is a dict
    name -> uint64[max_entries, value_slots].  Pure; jit/vmap/scan-safe.

    ``vinfo`` reuses a prior :func:`verify_with_info` result (the shared
    cfg / loop_bounds / mem_info artifacts) so callers that already
    verified — the runtime's load path, the pallas tier — pay for one
    static pass, not two.
    """
    check_supported(prog)
    if vinfo is None:
        vinfo = verify_with_info(prog)

    def run(ctx_vec, map_arrays: Dict[str, jnp.ndarray]):
        with jax.enable_x64(True):
            return _Lowerer(prog, vinfo, ctx_vec, map_arrays).run()

    return run, [d.name for d in prog.maps]


def _alu_jax(base: str, width: int, a, b):
    mask32 = jnp.uint64(0xFFFFFFFF)
    if width == 32:
        a = a & mask32
        b = b & mask32

    def fin(x):
        return (x & mask32) if width == 32 else x

    if base == "mov":
        return fin(b)
    if base == "add":
        return fin(a + b)
    if base == "sub":
        return fin(a - b)
    if base == "mul":
        return fin(a * b)
    if base == "div":
        return fin(a // jnp.maximum(b, jnp.uint64(1)))  # b!=0 verified
    if base == "mod":
        return fin(a % jnp.maximum(b, jnp.uint64(1)))
    if base == "and":
        return a & b
    if base == "or":
        return fin(a | b)
    if base == "xor":
        return fin(a ^ b)
    sh = b & jnp.uint64(width - 1)
    if base == "lsh":
        return fin(a << sh)
    if base == "rsh":
        return fin(a >> sh)
    if base == "arsh":
        sa = a.astype(jnp.int64) if width == 64 else \
            (a & mask32).astype(jnp.uint32).astype(jnp.int32)
        return fin((sa >> sh.astype(sa.dtype)).astype(jnp.int64)
                   .astype(jnp.uint64))
    if base == "neg":
        return fin(jnp.uint64(0) - a)
    raise JaxcError(f"ALU base {base}")


def _cmp_jax(base: str, a, b):
    if base in ("jeq",):
        return a == b
    if base == "jne":
        return a != b
    if base == "jgt":
        return a > b
    if base == "jge":
        return a >= b
    if base == "jlt":
        return a < b
    if base == "jle":
        return a <= b
    if base == "jset":
        return (a & b) != 0
    sa, sb = a.astype(jnp.int64), b.astype(jnp.int64)
    return {"jsgt": sa > sb, "jsge": sa >= sb,
            "jslt": sa < sb, "jsle": sa <= sb}[base]


# ---------------------------------------------------------------------------
# Host <-> device map state conversion
# ---------------------------------------------------------------------------

def map_to_array(m: BpfMap) -> jnp.ndarray:
    """Host map -> uint64[rows, cols] device image.

    Delegates to the map's own ``to_device`` protocol (``maps.py``):
    array-family maps export their slots, ringbufs append control words,
    LRU maps append key/recency columns and the clock row.  Raises for
    kinds with no device representation (plain hash)."""
    from .maps import MapError
    try:
        out = m.to_device()
    except MapError as e:
        raise JaxcError(str(e)) from None
    with jax.enable_x64(True):
        return jnp.asarray(out)


def array_to_map(arr, m: BpfMap) -> None:
    """Write device map state back into the host map (after a step)."""
    import numpy as np
    m.from_device(np.asarray(arr, dtype=np.uint64))


def ctx_to_vec(ctx_buf: bytearray) -> jnp.ndarray:
    import numpy as np
    with jax.enable_x64(True):
        return jnp.asarray(np.frombuffer(bytes(ctx_buf), dtype="<u8"))


def compile_jax_jit(prog: Program):
    fn, names = compile_jax(prog)
    return jax.jit(fn), names
