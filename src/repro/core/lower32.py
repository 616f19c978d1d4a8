"""lower32 — the 32-bit-pair lowering of the u64 policy machine.

Mosaic (the TPU Pallas backend) has no 64-bit integer ops, so the uint64
lowering in :mod:`repro.core.jaxc` cannot become a TPU kernel (it runs
as plain XLA ops, or in interpret mode).  This module re-represents EVERY u64
machine value — registers, stack slots, ctx fields, array-map slots, the
return value — as a ``(lo, hi)`` pair of uint32, with the full u64
semantics synthesized from 32-bit ops:

  * add/sub carry/borrow chains (``lo`` wraps, the carry feeds ``hi``),
  * widening multiply from 16-bit limbs (the classic mulhi synthesis —
    every partial product and carry provably fits uint32),
  * pair shifts/rotates split into the in-lane (< 32) and cross-lane
    (>= 32) half-planes with all shift amounts clamped to [0, 31] so no
    lane ever sees an out-of-range shift,
  * 64-bit div/mod as a 64-step shift-subtract long division (statically
    unrolled; the verifier proves divisors non-zero, zero is defensively
    treated as one exactly like the uint64 tier),
  * pairwise compare chains for every signed/unsigned jump condition
    (hi decides, lo breaks ties — lo compares stay unsigned even for
    signed conditions).

The control-flow machinery (predicated regions, ``lax.fori_loop`` loop
carries, exit-predicate routing) is inherited unchanged from
:class:`repro.core.jaxc._Lowerer`; only the representation hooks are
overridden, and ctx, stack and map state live in flat uint32 refs (see
:class:`_Lowerer32`).  Loads verify exactly once: ``compile_jax32``
reuses the same ``verify_with_info`` artifacts as every other tier.

Array layout convention (host <-> device, little-endian friendly):
the trailing axis holds ``[lo, hi]`` — a uint64 array viewed as ``<u4``
yields exactly this layout, so host conversion is a reinterpret, not a
shuffle.  ctx is uint32[n_fields, 2], maps are uint32[rows, cols, 2]
(:func:`repro.core.maps.device_shape`), the return value is uint32[2].

None of this path touches the x64 scope: it traces, jits, and executes
with jax's default 32-bit types enabled only.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax.numpy as jnp
import numpy as np
from jax import lax

from .isa import FP_REG, STACK_SIZE, Insn, mem_size
from .jaxc import (JaxcError, _CTX_TAG, _Lowerer, _STACK_TAG, _map_tag,
                   check_supported)
from .maps import BpfMap, device_shape
from .program import Program
from .verifier import verify_with_info

M64 = (1 << 64) - 1
M32 = 0xFFFFFFFF

Pair = Tuple[jnp.ndarray, jnp.ndarray]  # (lo, hi), both uint32


# ---------------------------------------------------------------------------
# Pair primitives — u64 semantics from uint32 lanes
# ---------------------------------------------------------------------------

def _u32(x) -> jnp.ndarray:
    return jnp.uint32(x & M32)


def pair_const(v: int) -> Pair:
    v &= M64
    return (_u32(v), _u32(v >> 32))


def pair_select(p, a: Pair, b: Pair) -> Pair:
    return (jnp.where(p, a[0], b[0]), jnp.where(p, a[1], b[1]))


def pair_add(a: Pair, b: Pair) -> Pair:
    lo = a[0] + b[0]
    carry = (lo < a[0]).astype(jnp.uint32)
    return (lo, a[1] + b[1] + carry)


def pair_sub(a: Pair, b: Pair) -> Pair:
    lo = a[0] - b[0]
    borrow = (a[0] < b[0]).astype(jnp.uint32)
    return (lo, a[1] - b[1] - borrow)


def mul32_wide(a, b) -> Pair:
    """uint32 x uint32 -> full 64-bit product as (lo, hi).

    16-bit-limb schoolbook multiply; the carry accumulator ``t`` is at
    most ``0xFFFF + 2*0xFFFF`` and the hi sum equals the true high word
    (< 2**32), so nothing wraps."""
    a0, a1 = a & 0xFFFF, a >> 16
    b0, b1 = b & 0xFFFF, b >> 16
    p00, p01 = a0 * b0, a0 * b1
    p10, p11 = a1 * b0, a1 * b1
    t = (p00 >> 16) + (p01 & 0xFFFF) + (p10 & 0xFFFF)
    lo = (t << 16) | (p00 & 0xFFFF)
    hi = p11 + (p01 >> 16) + (p10 >> 16) + (t >> 16)
    return (lo, hi)


def pair_mul(a: Pair, b: Pair) -> Pair:
    """a * b mod 2**64: lo64(a_lo*b_lo) + ((a_lo*b_hi + a_hi*b_lo) << 32)."""
    lo, hi00 = mul32_wide(a[0], b[0])
    return (lo, hi00 + a[0] * b[1] + a[1] * b[0])


def pair_lsh(a: Pair, b: Pair) -> Pair:
    lo, hi = a
    s = b[0] & 63
    s31 = s & 31
    cross = (32 - s31) & 31          # 0 exactly when s31 == 0 (discarded)
    lo_small = lo << s31
    hi_small = (hi << s31) | jnp.where(s31 == 0, jnp.uint32(0), lo >> cross)
    big = s >= 32                    # then s - 32 == s31
    return (jnp.where(big, jnp.uint32(0), lo_small),
            jnp.where(big, lo << s31, hi_small))


def pair_rsh(a: Pair, b: Pair) -> Pair:
    lo, hi = a
    s = b[0] & 63
    s31 = s & 31
    cross = (32 - s31) & 31
    lo_small = (lo >> s31) | jnp.where(s31 == 0, jnp.uint32(0), hi << cross)
    hi_small = hi >> s31
    big = s >= 32
    return (jnp.where(big, hi >> s31, lo_small),
            jnp.where(big, jnp.uint32(0), hi_small))


def pair_arsh(a: Pair, b: Pair) -> Pair:
    lo, hi = a
    shi = hi.astype(jnp.int32)
    s = b[0] & 63
    s31 = s & 31
    s31i = s31.astype(jnp.int32)
    cross = (32 - s31) & 31
    lo_small = (lo >> s31) | jnp.where(s31 == 0, jnp.uint32(0), hi << cross)
    hi_small = (shi >> s31i).astype(jnp.uint32)
    sign_fill = (shi >> 31).astype(jnp.uint32)
    big = s >= 32
    return (jnp.where(big, (shi >> s31i).astype(jnp.uint32), lo_small),
            jnp.where(big, sign_fill, hi_small))


def pair_cmp(base: str, a: Pair, b: Pair):
    """Every jump condition as a pairwise compare chain: the hi lane
    decides (signed for js* — only hi carries the sign), equal-hi ties
    break on an UNSIGNED lo compare in both cases."""
    al, ah = a
    bl, bh = b
    if base == "jeq":
        return jnp.logical_and(ah == bh, al == bl)
    if base == "jne":
        return jnp.logical_not(jnp.logical_and(ah == bh, al == bl))
    if base == "jset":
        return ((ah & bh) | (al & bl)) != 0
    hi_eq = ah == bh
    signed = base in ("jsgt", "jsge", "jslt", "jsle")
    ha = ah.astype(jnp.int32) if signed else ah
    hb = bh.astype(jnp.int32) if signed else bh
    if base in ("jgt", "jsgt"):
        return (ha > hb) | (hi_eq & (al > bl))
    if base in ("jge", "jsge"):
        return (ha > hb) | (hi_eq & (al >= bl))
    if base in ("jlt", "jslt"):
        return (ha < hb) | (hi_eq & (al < bl))
    if base in ("jle", "jsle"):
        return (ha < hb) | (hi_eq & (al <= bl))
    raise JaxcError(f"compare base {base}")


def pair_divmod(a: Pair, b: Pair) -> Tuple[Pair, Pair]:
    """(a // b, a % b) by 64-step shift-subtract long division.

    The step index is static, so every per-bit shift amount is a
    compile-time constant in [0, 31] — nothing here needs a 64-bit lane.
    b == 0 is defensively treated as 1 (matching the uint64 tier; the
    verifier proves policy divisors non-zero)."""
    bz = jnp.logical_and(b[0] == 0, b[1] == 0)
    b = pair_select(bz, pair_const(1), b)
    q_lo = q_hi = jnp.uint32(0)
    r: Pair = pair_const(0)
    for i in range(63, -1, -1):
        bit = (a[1] >> (i - 32)) & 1 if i >= 32 else (a[0] >> i) & 1
        r = ((r[0] << 1) | bit, (r[1] << 1) | (r[0] >> 31))
        ge = pair_cmp("jge", r, b)
        r = pair_select(ge, pair_sub(r, b), r)
        g = ge.astype(jnp.uint32)
        if i >= 32:
            q_hi = q_hi | (g << (i - 32))
        else:
            q_lo = q_lo | (g << i)
    return (q_lo, q_hi), r


def _alu64_pair(base: str, a: Pair, b: Pair) -> Pair:
    if base == "mov":
        return b
    if base == "add":
        return pair_add(a, b)
    if base == "sub":
        return pair_sub(a, b)
    if base == "mul":
        return pair_mul(a, b)
    if base == "div":
        return pair_divmod(a, b)[0]
    if base == "mod":
        return pair_divmod(a, b)[1]
    if base == "and":
        return (a[0] & b[0], a[1] & b[1])
    if base == "or":
        return (a[0] | b[0], a[1] | b[1])
    if base == "xor":
        return (a[0] ^ b[0], a[1] ^ b[1])
    if base == "lsh":
        return pair_lsh(a, b)
    if base == "rsh":
        return pair_rsh(a, b)
    if base == "arsh":
        return pair_arsh(a, b)
    if base == "neg":
        return pair_sub(pair_const(0), a)
    raise JaxcError(f"ALU base {base}")


def _alu32_pair(base: str, a: Pair, b: Pair) -> Pair:
    """eBPF 32-bit ALU: operate on the lo lanes, zero the hi lane."""
    al, bl = a[0], b[0]
    z = jnp.uint32(0)
    if base == "mov":
        return (bl, z)
    if base == "add":
        return (al + bl, z)
    if base == "sub":
        return (al - bl, z)
    if base == "mul":
        return (al * bl, z)
    if base == "div":
        return (al // jnp.maximum(bl, jnp.uint32(1)), z)
    if base == "mod":
        return (al % jnp.maximum(bl, jnp.uint32(1)), z)
    if base == "and":
        return (al & bl, z)
    if base == "or":
        return (al | bl, z)
    if base == "xor":
        return (al ^ bl, z)
    if base == "lsh":
        return (al << (bl & 31), z)
    if base == "rsh":
        return (al >> (bl & 31), z)
    if base == "arsh":
        return ((al.astype(jnp.int32)
                 >> (bl & 31).astype(jnp.int32)).astype(jnp.uint32), z)
    if base == "neg":
        return (z - al, z)
    raise JaxcError(f"ALU base {base}")


# ---------------------------------------------------------------------------
# The lowerer: jaxc's CFG walk over the pair representation, state in refs
# ---------------------------------------------------------------------------

FRAME_WORDS = STACK_SIZE // 8 * 2     # one stack frame: 64 slots x [lo, hi]


def _get(ref, i) -> Pair:
    """The pair at flat word index ``i`` (lo at ``i``, hi at ``i + 1``)."""
    return (ref[i], ref[i + 1])


def _put(ref, i, p, val: Pair) -> None:
    """Predicated pair store: the cell keeps its old value where ``p`` is
    false, so a write in a block the program did not take is a no-op."""
    lo, hi = ref[i], ref[i + 1]
    ref[i] = jnp.where(p, val[0], lo)
    ref[i + 1] = jnp.where(p, val[1], hi)


def _clip(i, n: int):
    """Clamp a traced index into ``[0, n)``.  Blocks run under a false
    predicate still compute their addresses from whatever the registers
    hold; clamping keeps those dead accesses inside the buffer (the
    selects discard what they read, and their stores write the old
    value back)."""
    return jnp.clip(i, 0, n - 1)


def n_frames(prog: Program) -> int:
    """Stack frames the inlined call tree can hold live at once: the main
    frame plus one per subprogram (the verifier rejects recursion, so no
    call path visits a subprogram twice)."""
    return 1 + len(prog.subprogs)


class _Lowerer32(_Lowerer):
    """`_Lowerer` with every machine value as a (lo, hi) uint32 pair.

    Registers, ``done`` and ``ret`` are traced scalars threaded through
    the ``fori_loop`` carries exactly as in the base class.  ctx, stack
    frames and map state live in flat uint32 REFS (``[lo, hi]`` word
    pairs): every access is a scalar load or store at a static or
    clamped dynamic index.  That is the form Mosaic lowers (SMEM refs,
    scalar-core arithmetic, ``scf.for``/``scf.while``) — no scatter, no
    gather, no dynamic slice, no 64-bit lane.  Outside a kernel,
    :func:`compile_jax32` runs the same body under ``pl.run_state``,
    which turns the ref effects back into pure array updates."""

    def __init__(self, prog: Program, vinfo, ctx_ref, map_refs, stack_ref):
        self._refs = (ctx_ref, dict(map_refs), stack_ref)
        super().__init__(prog, vinfo, None, None)

    # ---- representation hooks -------------------------------------------
    def _init_state(self, ctx_vec, map_arrays) -> None:
        self.ctx, self.maps, self.stack = self._refs
        self.shapes = {d.name: device_shape(d.kind, d.value_size,
                                            d.max_entries)
                       for d in self.decls}
        self.frame = 0
        self._zero_frame()
        self.regs = [pair_const(0)] * 11
        self.regs[1] = pair_const(_CTX_TAG)
        self.regs[FP_REG] = pair_const(_STACK_TAG | STACK_SIZE)
        self.done = jnp.asarray(False)
        self.ret = pair_const(0)

    def _zero_frame(self) -> None:
        base, ref = self.frame * FRAME_WORDS, self.stack

        def body(i, c):
            ref[base + i] = jnp.uint32(0)
            return c
        lax.fori_loop(0, FRAME_WORDS, body, 0)

    def _enter_frame(self):
        self.frame += 1
        self._zero_frame()
        return None

    def _leave_frame(self, saved) -> None:
        self.frame -= 1

    def _snapshot(self, active, exit_preds):
        # ctx / stack / maps are refs, updated in place by the loop body
        return (active, tuple(self.regs), self.done, self.ret,
                tuple(exit_preds))

    def _restore(self, carry):
        active, regs, done, ret, exps = carry
        self.regs = list(regs)
        self.done = done
        self.ret = ret
        return active, list(exps)

    def _imm(self, imm: int) -> Pair:
        return pair_const(imm)

    def _coerce(self, val) -> Pair:
        if not (isinstance(val, tuple) and len(val) == 2):
            raise JaxcError("pair lowering produced a non-pair value")
        return val

    def _sel(self, p, new: Pair, old: Pair) -> Pair:
        return pair_select(p, new, old)

    def _alu(self, base: str, width: int, a: Pair, b: Pair) -> Pair:
        return _alu64_pair(base, a, b) if width == 64 \
            else _alu32_pair(base, a, b)

    def _cmp(self, base: str, a: Pair, b: Pair):
        return pair_cmp(base, a, b)

    # ---- addressing --------------------------------------------------------
    def _cell(self, name: str, row, col):
        """Flat word index of map ``name``'s cell ``(row, col)``."""
        return (row * self.shapes[name][1] + col) * 2

    def _slot(self, ptr: Pair):
        """Flat word index of the stack slot ``ptr`` addresses."""
        slot = _clip((ptr[0] >> 3).astype(jnp.int32), STACK_SIZE // 8)
        return self.frame * FRAME_WORDS + slot * 2

    # ---- memory ----------------------------------------------------------
    def _stack_load(self, ptr: Pair, size: int):
        word = _get(self.stack, self._slot(ptr))
        if size == 8:
            return word
        sh = (ptr[0] & 7) * 8
        shifted = pair_rsh(word, (sh, jnp.uint32(0)))
        return (shifted[0] & _u32((1 << (8 * size)) - 1), jnp.uint32(0))

    def _stack_store(self, P, ptr: Pair, size: int, val: Pair) -> None:
        i = self._slot(ptr)
        if size == 8:
            new = val
        else:
            word = _get(self.stack, i)
            mask = (1 << (8 * size)) - 1
            sh: Pair = ((ptr[0] & 7) * 8, jnp.uint32(0))
            hole = pair_lsh(pair_const(mask), sh)
            piece = pair_lsh((val[0] & _u32(mask), jnp.uint32(0)), sh)
            new = ((word[0] & ~hole[0]) | piece[0],
                   (word[1] & ~hole[1]) | piece[1])
        _put(self.stack, i, P, new)

    def _mapval_cell(self, mname: str, ptr: Pair):
        """Flat word index of the map value slot a value pointer names:
        ``ptr`` encodes ``(16 + mi) << 56 | row << 24 | byte_off``."""
        lo, hi = ptr
        rows, cols = self.shapes[mname]
        row = _clip(((hi << 8) | (lo >> 24)).astype(jnp.int32), rows)
        col = _clip(((lo & 0xFFFFFF) >> 3).astype(jnp.int32), cols)
        return self._cell(mname, row, col)

    def _exec_load(self, pc: int, insn: Insn, P) -> None:
        size = mem_size(insn.op)
        region, mname, base = self.fninfo.mem_info[pc]
        ptr = pair_add(self.regs[insn.src], pair_const(insn.off & M64))
        if region == "ctx":
            off = base + insn.off            # static (verified)
            val = _get(self.ctx, off // 8 * 2)
        elif region == "stack":
            val = self._stack_load(ptr, size)
        else:  # mapval
            val = _get(self.maps[mname], self._mapval_cell(mname, ptr))
        if region != "stack" and size < 8:
            val = (val[0] & _u32((1 << (8 * size)) - 1), jnp.uint32(0))
        self._wreg(P, insn.dst, val)

    def _exec_store(self, pc: int, insn: Insn, P) -> None:
        size = mem_size(insn.op)
        region, mname, base = self.fninfo.mem_info[pc]
        val: Pair = pair_const(insn.imm & M64) \
            if not insn.op.startswith("stx") else self.regs[insn.src]
        ptr = pair_add(self.regs[insn.dst], pair_const(insn.off & M64))
        if region == "ctx":
            _put(self.ctx, (base + insn.off) // 8 * 2, P, val)
        elif region == "stack":
            self._stack_store(P, ptr, size, val)
        else:  # mapval
            _put(self.maps[mname], self._mapval_cell(mname, ptr), P, val)

    # ---- helpers ---------------------------------------------------------
    def _ema(self, old: Pair) -> Pair:
        """``ema_update``'s ``(old * (w - 1) + sample) // w``, w >= 1."""
        one = pair_const(1)
        w = pair_select(pair_cmp("jgt", self.regs[4], one),
                        self.regs[4], one)
        acc = pair_add(pair_mul(old, pair_sub(w, one)), self.regs[3])
        return pair_divmod(acc, w)[0]

    def _value_row(self, n_slots: int):
        """The ``n_slots`` u64 words ``map_update_elem``'s value* (r3)
        points at on the stack."""
        return [self._stack_load(pair_add(self.regs[3], pair_const(8 * s)), 8)
                for s in range(n_slots)]

    def _call(self, pc: int, insn: Insn, P) -> Pair:
        hid = insn.imm
        mname = self.fninfo.call_map.get(pc)
        if mname is None:
            raise JaxcError(f"helper at insn {pc} has no static map binding")
        mi = self.map_index[mname]
        d = self.decls[mi]
        if d.kind == "ringbuf":
            return self._call_ringbuf32(hid, mi, d, P)
        if d.kind == "hash":
            return self._call_hash32(hid, mi, d, P)
        ref = self.maps[d.name]
        key = self._stack_load(self.regs[2], d.key_size)   # hi lane is 0
        valid = key[0] < jnp.uint32(d.max_entries)
        ki = jnp.minimum(key[0], jnp.uint32(d.max_entries - 1)).astype(
            jnp.int32)
        if hid == 1:  # map_lookup_elem(map, key*)
            tag = pair_const(_map_tag(mi))
            shifted = pair_lsh(key, pair_const(24))
            enc: Pair = (tag[0] | shifted[0], tag[1] | shifted[1])
            return pair_select(valid, enc, pair_const(0))
        take = jnp.logical_and(P, valid)
        if hid == 2:  # map_update_elem(map, key*, value*, flags)
            for s, val in enumerate(self._value_row(d.value_size // 8)):
                _put(ref, self._cell(d.name, ki, s), take, val)
            return pair_select(valid, pair_const(0), pair_const(M64))
        if hid == 64:  # ema_update(map, key*, sample, weight)
            i = self._cell(d.name, ki, 0)
            new = self._ema(_get(ref, i))
            _put(ref, i, take, new)
            return new
        raise JaxcError(f"helper {hid} not supported in-graph")

    def _hash_probe32(self, d, key: Pair):
        """Pair-form open-addressing probe (see ``_Lowerer._hash_probe``
        for the layout and termination argument), as a scalar scan in
        probe order from the home slot that stops at the first key match
        or empty row.  ``hash_slot`` folds the key to 32 bits
        (``lo ^ hi``), so locating the probe origin costs one uint32
        modulo, and key equality is a two-lane compare.

        Returns ``(first, hit, can_claim)``; a full table with the key
        absent scans every row and reports row 0, no hit, no claim."""
        ref, name = self.maps[d.name], d.name
        slots = d.value_size // 8
        cap = d.max_entries
        h = ((key[0] ^ key[1]) % jnp.uint32(cap)).astype(jnp.int32)

        def row_state(row):
            klo, khi = _get(ref, self._cell(name, row, slots))
            ulo, uhi = _get(ref, self._cell(name, row, slots + 1))
            used = (ulo | uhi) > 0
            return used, used & (klo == key[0]) & (khi == key[1])

        def cond(c):
            k, stopped, _ = c
            return jnp.logical_and(k < cap, jnp.logical_not(stopped))

        def body(c):
            k, _, _ = c
            row = h + k
            row = jnp.where(row >= cap, row - cap, row)
            used, match = row_state(row)
            return k + 1, match | jnp.logical_not(used), row

        _, has_stop, row = lax.while_loop(
            cond, body, (jnp.int32(0), jnp.asarray(False), jnp.int32(0)))
        first = jnp.where(has_stop, row, 0)
        hit = jnp.logical_and(has_stop, row_state(first)[1])
        return first, hit, jnp.logical_and(has_stop, jnp.logical_not(hit))

    def _call_hash32(self, hid: int, mi: int, d, P) -> Pair:
        ref, name = self.maps[d.name], d.name
        slots = d.value_size // 8
        key = self._stack_load(self.regs[2], d.key_size)   # Pair
        first, hit, can_claim = self._hash_probe32(d, key)
        if hid == 1:  # map_lookup_elem: encode the physical row index
            tag = pair_const(_map_tag(mi))
            row: Pair = (first.astype(jnp.uint32), jnp.uint32(0))
            sh = pair_lsh(row, pair_const(24))
            enc: Pair = (tag[0] | sh[0], tag[1] | sh[1])
            return pair_select(hit, enc, pair_const(0))
        ok = jnp.logical_or(hit, can_claim)
        if hid == 2:  # map_update_elem: overwrite hit else claim a slot
            newvals = self._value_row(slots)
            ret = pair_select(ok, pair_const(0), pair_const(M64))
        elif hid == 64:  # ema_update: RMW slot 0 (miss seeds from old=0)
            zero = pair_const(0)
            old = [pair_select(hit, _get(ref, self._cell(name, first, s)),
                               zero) for s in range(slots)]
            ret = self._ema(old[0])
            newvals = [ret] + old[1:]
        else:
            raise JaxcError(f"helper {hid} on hash map '{d.name}'")
        take = jnp.logical_and(P, ok)
        for s, val in enumerate(newvals + [key, pair_const(1)]):
            _put(ref, self._cell(name, first, s), take, val)
        occ_i = self._cell(name, d.max_entries, 0)
        _put(ref, occ_i, jnp.logical_and(P, can_claim),
             pair_add(_get(ref, occ_i), pair_const(1)))
        return ret

    def _call_ringbuf32(self, hid: int, mi: int, d, P) -> Pair:
        """reserve/submit/discard over the device layout's control words,
        with the free-running u64 cursors held as (lo, hi) pairs — the
        carry chains keep cursor arithmetic exact past 2**32 events."""
        ref = self.maps[d.name]
        slots = d.value_size // 8

        def ctl(w):
            return self._cell(d.name, d.max_entries + w // slots, w % slots)

        hi_, ti_, di_, pi_ = ctl(0), ctl(1), ctl(2), ctl(3)
        head, tail = _get(ref, hi_), _get(ref, ti_)
        drops, pend = _get(ref, di_), _get(ref, pi_)
        if hid == 66:  # ringbuf_submit
            _put(ref, hi_, P, pair_add(head, pend))
            _put(ref, pi_, P, pair_const(0))
            return pair_const(0)
        if hid == 67:  # ringbuf_discard
            _put(ref, pi_, P, pair_const(0))
            return pair_const(0)
        if hid != 65:
            raise JaxcError(f"helper {hid} on ringbuf map '{d.name}'")
        # ringbuf_reserve: implicit commit, then NULL (+1 drop) on full
        head1 = pair_add(head, pend)
        full = pair_cmp("jge", pair_sub(head1, tail),
                        pair_const(d.max_entries))
        _put(ref, hi_, P, head1)
        _put(ref, pi_, P, pair_select(full, pair_const(0), pair_const(1)))
        _put(ref, di_, jnp.logical_and(P, full),
             pair_add(drops, pair_const(1)))
        row = pair_divmod(head1, pair_const(d.max_entries))[1]
        tag = pair_const(_map_tag(mi))
        sh = pair_lsh(row, pair_const(24))
        enc: Pair = (tag[0] | sh[0], tag[1] | sh[1])
        return pair_select(full, pair_const(0), enc)


def run_on_refs(prog: Program, vinfo, ctx_ref, map_refs, stack_ref,
                ret_ref) -> None:
    """Run one decision against flat uint32 refs: ``ctx_ref`` and each
    ``map_refs[name]`` are updated in place, ``ret_ref[0:2]`` receives
    the return value, ``stack_ref`` is scratch of
    ``n_frames(prog) * FRAME_WORDS`` words.  The kernel body of the
    ``pallas32`` tier and of :func:`compile_jax32`."""
    ret = _Lowerer32(prog, vinfo, ctx_ref, map_refs, stack_ref).run()[0]
    ret_ref[0] = ret[0]
    ret_ref[1] = ret[1]


def compile_jax32(prog: Program, vinfo=None):
    """Return (fn, map_names) in the pair calling convention.

    ``fn(ctx_vec32, map_arrays32) -> (ret32, ctx32_out, map_arrays32_out)``
    where ``ctx_vec32`` is uint32[n_fields, 2], each map array is
    uint32[rows, cols, 2] (trailing axis = [lo, hi], rows/cols from
    :func:`repro.core.maps.device_shape`) and ``ret32`` is uint32[2].
    Pure and jit-safe: the ref-based body runs under ``pl.run_state``.
    Runs with jax's default 32-bit types — no x64 scope anywhere.

    ``vinfo`` reuses a prior :func:`verify_with_info` result so the
    runtime's load path verifies exactly once across every tier."""
    from jax.experimental import pallas as pl
    check_supported(prog, word_width=32)
    if vinfo is None:
        vinfo = verify_with_info(prog)
    names = [d.name for d in prog.maps]

    def run(ctx_vec32, map_arrays32: Dict[str, jnp.ndarray]):
        ctx = jnp.asarray(ctx_vec32, jnp.uint32)
        arrs = {n: jnp.asarray(map_arrays32[n], jnp.uint32) for n in names}

        def body(refs):
            run_on_refs(prog, vinfo, *refs)

        init = (ctx.reshape(-1), {n: a.reshape(-1) for n, a in arrs.items()},
                jnp.zeros(n_frames(prog) * FRAME_WORDS, jnp.uint32),
                jnp.zeros(2, jnp.uint32))
        ctx_o, maps_o, _, ret = pl.run_state(body)(init)
        return (ret, ctx_o.reshape(ctx.shape),
                {n: maps_o[n].reshape(arrs[n].shape) for n in names})

    return run, names


# ---------------------------------------------------------------------------
# Host <-> device conversion (pure numpy reinterprets — no x64 scope)
# ---------------------------------------------------------------------------

def map_to_array32(m: BpfMap) -> jnp.ndarray:
    """Host map -> uint32[rows, cols, 2]; a ``<u4`` view of the map's
    little-endian u64 device image (``to_device``), so [..., 0] is lo and
    [..., 1] is hi.  Control/metadata rows ride along untranslated."""
    from .maps import MapError
    try:
        a64 = m.to_device()
    except MapError as e:
        raise JaxcError(str(e)) from None
    rows, cols = a64.shape
    return jnp.asarray(
        np.ascontiguousarray(a64).view("<u4").reshape(rows, cols, 2))


def array32_to_map(arr, m: BpfMap) -> None:
    """Write pair-form device map state back into the host map."""
    host = np.ascontiguousarray(np.asarray(arr, dtype=np.uint32))
    m.from_device(host.view("<u8").reshape(host.shape[0], host.shape[1]))


def ctx_to_vec32(ctx_buf: bytearray) -> jnp.ndarray:
    return jnp.asarray(
        np.frombuffer(bytes(ctx_buf), dtype="<u4").reshape(-1, 2))


def vec32_to_bytes(arr) -> bytes:
    return np.asarray(arr).astype("<u4").tobytes()


def ret32_to_int(ret) -> int:
    r = np.asarray(ret)
    return int(r[0]) | (int(r[1]) << 32)
