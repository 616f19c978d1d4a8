"""Golden battery for the Mosaic-ready 32-bit-pair lowering (lower32).

Every u64 machine value is a (lo, hi) uint32 pair on this path, which is
exactly where synthesized 64-bit semantics can go subtly wrong: carry and
borrow propagation across the 32-bit boundary, widening multiplies,
shifts that straddle the lane split (0/31/32/33/63), long division, and
pairwise compare chains in both signed half-planes.  Each case is a
hand-written program asserted BIT-EXACT against the reference
interpreter (vm.py) — the repo's differential ground truth.

The whole file runs with jax's default 32-bit types: no ``enable_x64``
anywhere, by construction (that is the point of the tier).
"""

import random

import numpy as np
import pytest

from repro.core import assemble, make_ctx, map_decl
from repro.core.lower32 import (compile_jax32, ctx_to_vec32, map_to_array32,
                                pair_const, ret32_to_int, vec32_to_bytes)
from repro.core.maps import MapRegistry
from repro.core.vm import VM

# 32-bit-boundary-heavy constant pool (includes negative-signed encodings)
BOUNDARY = [0, 1, 3, 2**31 - 1, 2**31, 2**31 + 1, 2**32 - 1, 2**32,
            2**32 + 1, 2**48 + 12345, 2**63 - 1, 2**63, 2**63 + 1,
            2**64 - 1, -1, -2, -(2**31), -(2**32), -(2**63)]

CTX_KW = dict(msg_size=8 << 20, comm_id=2, n_ranks=8, max_channels=32)


def _vm_run(prog, maps=None):
    maps = maps or {}
    ctx = make_ctx(prog.section, **CTX_KW)
    ret = VM(prog.insns, maps).run(ctx.buf)
    return ret, bytes(ctx.buf)


def _pair_run(prog, map_arrays=None, jit=False):
    """Run through the pair lowering (eager by default — tiny programs
    compile faster that way; jit=True exercises the traced path)."""
    import jax
    fn, names = compile_jax32(prog)
    if jit:
        fn = jax.jit(fn)
    ctx = make_ctx(prog.section, **CTX_KW)
    ret, vec_out, arrs = fn(ctx_to_vec32(ctx.buf), map_arrays or {})
    return ret32_to_int(ret), vec32_to_bytes(vec_out), arrs


def _assert_match(prog, maps_vm=None, map_arrays=None, jit=False):
    want_ret, want_buf = _vm_run(prog, maps_vm)
    got_ret, got_buf, arrs = _pair_run(prog, map_arrays, jit=jit)
    assert got_ret == want_ret, \
        f"ret {got_ret:#x} != vm {want_ret:#x}\n{prog.source}"
    assert got_buf == want_buf, f"ctx mismatch\n{prog.source}"
    return arrs


def test_runs_without_x64():
    """The battery's premise: jax is in its default 32-bit mode, and the
    pair path neither needs nor enables x64."""
    import jax
    import jax.numpy as jnp
    assert not jax.config.jax_enable_x64
    prog = assemble("lddw r0, 0xFFFFFFFFFFFFFFFF\n exit")
    fn, _ = compile_jax32(prog)
    ret, vec, _ = fn(ctx_to_vec32(make_ctx("tuner").buf), {})
    assert ret.dtype == jnp.uint32 and vec.dtype == jnp.uint32
    assert ret32_to_int(ret) == 2**64 - 1


def test_pair_const_layout():
    lo, hi = pair_const(0x123456789ABCDEF0)
    assert int(lo) == 0x9ABCDEF0 and int(hi) == 0x12345678


# ---------------------------------------------------------------------------
# Carry / borrow
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("a", [0xFFFFFFFF, 2**32 - 2, 2**64 - 1,
                               2**63 - 1, 2**31 - 1, 0])
@pytest.mark.parametrize("b", [1, 0xFFFFFFFF, 2**63, 2**64 - 1])
def test_add_with_carry(a, b):
    _assert_match(assemble(f"""
        lddw  r6, {a}
        lddw  r7, {b}
        add64 r6, r7
        mov64 r0, r6
        exit
    """))


@pytest.mark.parametrize("a", [0, 1, 2**32, 2**32 - 1, 2**63, 5])
@pytest.mark.parametrize("b", [1, 2, 0xFFFFFFFF, 2**63 + 1, 2**64 - 1])
def test_sub_with_borrow(a, b):
    _assert_match(assemble(f"""
        lddw  r6, {a}
        lddw  r7, {b}
        sub64 r6, r7
        mov64 r0, r6
        exit
    """))


def test_neg64_and_imm_add_carry():
    _assert_match(assemble("""
        lddw   r6, 0xFFFFFFFF
        add64i r6, 1
        neg64  r6
        lddw   r7, -1
        add64  r6, r7
        mov64  r0, r6
        exit
    """))


# ---------------------------------------------------------------------------
# Widening multiply
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("a,b", [
    (0xFFFFFFFF, 0xFFFFFFFF),            # max 32x32 partial products
    (0x123456789, 0x987654321),          # carries through every limb
    (2**63 + 12345, 3),                  # hi-lane wraparound
    (2**32, 2**32),                      # lo product exactly zero
    (2**64 - 1, 2**64 - 1),              # full wrap: (2^64-1)^2 mod 2^64
    (0x1234_5678_9ABC_DEF0, 0x0FED_CBA9_8765_4321),
])
def test_widening_mul(a, b):
    _assert_match(assemble(f"""
        lddw  r6, {a}
        lddw  r7, {b}
        mul64 r6, r7
        mov64 r0, r6
        exit
    """))


# ---------------------------------------------------------------------------
# Shifts across the lane boundary
# ---------------------------------------------------------------------------

SHIFT_VALS = [0x8000000000000001, 0xDEADBEEFCAFEBABE, 1, 2**63, 2**32 + 7]


@pytest.mark.parametrize("op", ["lsh64i", "rsh64i", "arsh64i"])
@pytest.mark.parametrize("s", [0, 1, 31, 32, 33, 63])
@pytest.mark.parametrize("v", SHIFT_VALS)
def test_shift_imm(op, s, v):
    _assert_match(assemble(f"""
        lddw  r6, {v}
        {op}  r6, {s}
        mov64 r0, r6
        exit
    """))


@pytest.mark.parametrize("op", ["lsh64", "rsh64", "arsh64"])
@pytest.mark.parametrize("s", [0, 31, 32, 33, 63])
def test_shift_reg_dynamic_amount(op, s):
    # amount arrives in a register (the dynamic pair-shift path)
    _assert_match(assemble(f"""
        lddw  r6, 0x8123456789ABCDEF
        mov64 r7, {s}
        {op}  r6, r7
        mov64 r0, r6
        exit
    """))


# ---------------------------------------------------------------------------
# Pair compares: every jump condition, both signed half-planes
# ---------------------------------------------------------------------------

JUMPS = ["jeq", "jne", "jgt", "jge", "jlt", "jle",
         "jsgt", "jsge", "jslt", "jsle", "jset"]
CMP_PAIRS = [
    (5, 2**63 + 3),                  # positive vs negative half-plane
    (2**63 + 3, 5),                  # negative vs positive
    (2**63 + 5, 2**63 + 3),          # both negative
    (7, 7),                          # equality
    (2**32 + 1, 2**32 + 2),          # equal hi, lo breaks the tie
    (2**32 + 2, 2**32 + 1),
    (0, 2**64 - 1),                  # 0 vs -1
    (2**31, 2**31 - 1),              # the 32-bit signed boundary
]


@pytest.mark.parametrize("op", JUMPS)
@pytest.mark.parametrize("a,b", CMP_PAIRS)
def test_pair_compare_reg(op, a, b):
    _assert_match(assemble(f"""
        lddw  r6, {a}
        lddw  r7, {b}
        {op}  r6, r7, yes
        mov64 r0, 0
        exit
    yes:
        mov64 r0, 1
        exit
    """))


@pytest.mark.parametrize("op", JUMPS)
@pytest.mark.parametrize("imm", [0, 1, -1, 2**31 - 1, -(2**31), 1000])
def test_pair_compare_imm(op, imm):
    # imm form: the immediate sign-extends to 64 bits before comparing
    _assert_match(assemble(f"""
        lddw  r6, 0xFFFFFFFF80000000
        {op}  r6, {imm}, yes
        mov64 r0, 0
        exit
    yes:
        mov64 r0, 1
        exit
    """))


# ---------------------------------------------------------------------------
# Long division / modulo
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op", ["div64", "mod64"])
@pytest.mark.parametrize("a,b", [
    (2**64 - 1, 3),
    (2**63, 2**32 + 1),              # divisor wider than one lane
    (12345, 997),
    (2**64 - 1, 2**64 - 1),
    (5, 2**63 + 9),                  # divisor > dividend
    (0xDEADBEEFCAFEBABE, 0x12345),
])
def test_long_division(op, a, b):
    _assert_match(assemble(f"""
        lddw  r6, {a}
        lddw  r7, {b}
        {op}  r6, r7
        mov64 r0, r6
        exit
    """))


# ---------------------------------------------------------------------------
# 32-bit ALU ops zero the hi lane
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op,arg", [
    ("add32", "r7"), ("sub32", "r7"), ("mul32", "r7"), ("xor32", "r7"),
    ("lsh32i", "5"), ("rsh32i", "7"), ("arsh32i", "3"), ("mov32", "r7"),
    ("div32", "r7"), ("mod32", "r7"), ("neg32", None),
])
def test_alu32_zeroes_upper(op, arg):
    line = f"{op} r6" if arg is None else f"{op} r6, {arg}"
    _assert_match(assemble(f"""
        lddw  r6, 0xFFFFFFFF8000000F
        lddw  r7, 0x10000000B
        {line}
        mov64 r0, r6
        exit
    """))


# ---------------------------------------------------------------------------
# Stack sub-word stores/loads within a u64 slot
# ---------------------------------------------------------------------------

def test_subword_stack_rmw():
    _assert_match(assemble("""
        lddw   r6, 0x1122334455667788
        stxdw  [r10-8], r6
        stb    [r10-3], 0xAB        ; byte at offset 5 within the slot
        sth    [r10-8], 0xCDEF
        ldxw   r7, [r10-8]
        ldxb   r8, [r10-3]
        ldxdw  r0, [r10-8]
        add64  r0, r7
        add64  r0, r8
        exit
    """))


def test_ctx_writeback_bit_exact():
    _assert_match(assemble("""
        ldxdw  r6, [r1+msg_size]
        rsh64i r6, 20
        stxdw  [r1+n_channels], r6
        lddw   r7, 0xFFFFFFFF00000002
        stxdw  [r1+algorithm], r7
        mov64  r0, 0
        exit
    """))


# ---------------------------------------------------------------------------
# In-loop EMA map writeback (div + mul + carry per iteration)
# ---------------------------------------------------------------------------

ema_map = map_decl("p32_ema", kind="array", value_size=8, max_entries=4)


def _ema_loop_prog():
    return assemble("""
        stw    [r10-4], 2
        lddw   r7, 0xFFFFFFF0
        mov64  r6, 0
    loop:
        jge    r6, 65, out
        ldmap  r1, p32_ema
        mov64  r2, r10
        add64i r2, -4
        mov64  r3, r7
        add64  r3, r6
        mov64  r4, 4
        call   ema_update
        add64i r6, 1
        ja     loop
    out:
        mov64  r0, 0
        exit
    """, section="tuner", maps=(ema_map,))


@pytest.mark.parametrize("jit", [False, True])
def test_inloop_ema_writeback_matches_vm(jit):
    prog = _ema_loop_prog()
    reg = MapRegistry()
    m = reg.create("p32_ema", "array", value_size=8, max_entries=4)
    m.update_u64(2, 0xFFFFFFFFFF)        # EMA seed crosses the lane split
    want_ret, _ = _vm_run(prog, {"p32_ema": m})
    want = [m.lookup_u64(k) for k in range(4)]

    reg2 = MapRegistry()
    m2 = reg2.create("p32_ema", "array", value_size=8, max_entries=4)
    m2.update_u64(2, 0xFFFFFFFFFF)
    got_ret, _, arrs = _pair_run(prog, {"p32_ema": map_to_array32(m2)},
                                 jit=jit)
    assert got_ret == want_ret
    got = np.asarray(arrs["p32_ema"])
    got_cells = [int(got[k, 0, 0]) | (int(got[k, 0, 1]) << 32)
                 for k in range(4)]
    assert got_cells == want


def test_map_update_elem_full_row_pairs():
    row_map = map_decl("p32_row", kind="array", value_size=16, max_entries=3)
    prog = assemble("""
        stw    [r10-4], 1
        lddw   r6, 0xAABBCCDDEEFF0011
        stxdw  [r10-24], r6
        lddw   r7, 0x1234567890ABCDEF
        stxdw  [r10-16], r7
        ldmap  r1, p32_row
        mov64  r2, r10
        add64i r2, -4
        mov64  r3, r10
        add64i r3, -24
        mov64  r4, 0
        call   map_update_elem
        exit
    """, section="tuner", maps=(row_map,))
    reg = MapRegistry()
    m = reg.create("p32_row", "array", value_size=16, max_entries=3)
    want_ret, _ = _vm_run(prog, {"p32_row": m})
    want = [(m.lookup_u64(k, slot=0), m.lookup_u64(k, slot=1))
            for k in range(3)]

    reg2 = MapRegistry()
    m2 = reg2.create("p32_row", "array", value_size=16, max_entries=3)
    got_ret, _, arrs = _pair_run(prog, {"p32_row": map_to_array32(m2)})
    assert got_ret == want_ret
    got = np.asarray(arrs["p32_row"])
    got_rows = [(int(got[k, 0, 0]) | (int(got[k, 0, 1]) << 32),
                 int(got[k, 1, 0]) | (int(got[k, 1, 1]) << 32))
                for k in range(3)]
    assert got_rows == want


# ---------------------------------------------------------------------------
# The pallas_call kernel harness (interpret mode) agrees with the body
# ---------------------------------------------------------------------------

def test_pallas32_kernel_equals_jit_body():
    import jax
    from repro.core import pallasc
    prog = _ema_loop_prog()
    reg = MapRegistry()
    m = reg.create("p32_ema", "array", value_size=8, max_entries=4)
    m.update_u64(2, 54321)
    arrays = {"p32_ema": map_to_array32(m)}
    outs = {}
    for mode in ("pallas", "jit"):
        fn, names = pallasc.compile_pallas(prog, mode=mode, word_width=32)
        ret, vec, arrs = jax.jit(fn)(
            ctx_to_vec32(make_ctx("tuner", **CTX_KW).buf), arrays)
        outs[mode] = (ret32_to_int(ret), vec32_to_bytes(vec),
                      {n: np.asarray(arrs[n]).tobytes() for n in names})
    assert outs["pallas"] == outs["jit"]


# ---------------------------------------------------------------------------
# Seeded mixed-op fuzz over the boundary constant pool (no maps)
# ---------------------------------------------------------------------------

_FUZZ_OPS = ["add64", "sub64", "mul64", "and64", "or64", "xor64",
             "add32", "sub32", "mul32", "xor32"]


@pytest.mark.parametrize("seed", range(8))
def test_boundary_constant_soup(seed):
    rng = random.Random(0x32B17 + seed)
    lines = [f"    lddw r{r}, {rng.choice(BOUNDARY)}" for r in (6, 7, 8)]
    for _ in range(rng.randint(6, 14)):
        k = rng.random()
        if k < 0.5:
            dst, src = rng.sample([6, 7, 8], 2)
            lines.append(f"    {rng.choice(_FUZZ_OPS)} r{dst}, r{src}")
        elif k < 0.8:
            op = rng.choice(["lsh64i", "rsh64i", "arsh64i"])
            lines.append(f"    {op} r{rng.choice([6, 7, 8])}, "
                         f"{rng.choice([0, 1, 31, 32, 33, 63])}")
        else:
            op = rng.choice(["jgt", "jslt", "jge", "jne"])
            lines.append(f"    {op} r{rng.choice([6, 7, 8])}, "
                         f"r{rng.choice([6, 7, 8])}, skip{len(lines)}")
            lines.append(f"    add64i r{rng.choice([6, 7, 8])}, "
                         f"{rng.randint(1, 1 << 20)}")
            lines.append(f"skip{len(lines) - 2}:")
    lines += ["    xor64 r6, r7", "    add64 r6, r8",
              "    mov64 r0, r6", "    exit"]
    _assert_match(assemble("\n".join(lines)))


# ---------------------------------------------------------------------------
# lru_hash stays off this tier — actionable rejection with workarounds
# ---------------------------------------------------------------------------

def test_lru_hash_rejected_with_concrete_workarounds():
    """lru_hash recency metadata does not lower to pair form: selecting
    the 32-bit tier for such a policy must fail at load with the maps
    named and every documented workaround spelled out (plain hash kind,
    word_width=64, host tier) — plain `hash` maps on the same path load
    fine."""
    from repro.core.jaxc import JaxcError, check_supported
    from repro.core.pallasc import PallascError, compile_pallas
    from repro.core.verifier import verify_with_info
    from repro.policies.profiler import straggler_trap

    prog = straggler_trap.program
    with pytest.raises(PallascError) as ei:
        compile_pallas(prog, verify_with_info(prog), mode="jit",
                       word_width=32)
    msg = str(ei.value)
    assert "lru_hash" in msg and "'ema_map'" in msg
    assert 'kind="hash"' in msg              # workaround 1: plain hash
    assert "word_width=64" in msg            # workaround 2: off the TPU
    assert "host tier" in msg                # workaround 3: interp/jit/native
    # the eligibility probe agrees (it drives the BENCH audit + CI gate)
    with pytest.raises(JaxcError, match="lru_hash"):
        check_supported(prog, word_width=32)
    # same policy, 64-bit path: eligible (no exception)
    check_supported(prog, word_width=64)

    plain = map_decl("plain_ok32", kind="hash", key_size=8, value_size=8,
                     max_entries=4)
    prog2 = assemble("""
        stdw  [r10-8], 3
        ldmap r1, plain_ok32
        mov64 r2, r10
        add64i r2, -8
        call  map_lookup_elem
        mov64 r0, 0
        exit
    """, section="tuner", maps=(plain,))
    fn, names = compile_jax32(prog2)         # loads cleanly on the pair tier
    assert "plain_ok32" in names
