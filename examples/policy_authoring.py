"""Policy authoring tour: all three execution tiers of one verified policy.

    PYTHONPATH=src python examples/policy_authoring.py

Shows: bytecode + disassembly, the verifier's abstract interpretation
catching each bug class, and the same program running on (a) the
interpreter, (b) the host JIT, (c) jaxc — INSIDE a jitted XLA program
with map state threaded functionally (the beyond-paper tier).
"""

import jax
import jax.numpy as jnp

from repro.core import (PolicyRuntime, VerifierError, assemble, make_ctx,
                        map_decl, policy, verify)
from repro.core.jaxc import compile_jax, ctx_to_vec, map_to_array
from repro.core.context import POLICY_CONTEXT

MiB = 1 << 20
hist = map_decl("hist", kind="array", value_size=8, max_entries=4)


@policy(section="tuner", maps=[hist])
def bucketizer(ctx):
    """Count decisions per size bucket; pick channels by bucket."""
    b = 0
    if ctx.msg_size > 1 * MiB:
        b = 1
    if ctx.msg_size > 32 * MiB:
        b = 2
    if ctx.msg_size > 256 * MiB:
        b = 3
    st = hist.lookup(b)
    if st is not None:
        st[0] = st[0] + 1
    ctx.n_channels = min(4 + b * 8, 32)
    return 0


def main():
    prog = bucketizer.program
    print(f"== compiled to {len(prog)} bytecode insns; disassembly head:")
    print("\n".join(prog.disasm().splitlines()[:8]), "\n   ...")

    verify(prog)
    print("== verifier: ACCEPTED")

    print("\n== hand-written unsafe bytecode is still caught:")
    evil = assemble("""
        mov64  r2, 1
        stxdw  [r10-520], r2
        mov64  r0, 0
        exit
    """, section="tuner")
    try:
        verify(evil)
    except VerifierError as e:
        print(f"   REJECT: {e}")

    # tier A+B: interpreter vs host JIT
    for tier, interp in [("interpreter", True), ("host JIT", False)]:
        rt = PolicyRuntime(use_interpreter=interp)
        rt.load(prog)
        ctx = make_ctx("tuner", msg_size=64 * MiB)
        rt.invoke("tuner", ctx)
        print(f"== {tier:12s}: 64 MiB -> channels={ctx['n_channels']}")

    # tier C: in-graph (jaxc) — runs inside jit with functional map state
    fn, names = compile_jax(prog)
    fields = list(POLICY_CONTEXT.fields)

    @jax.jit
    def training_step_with_policy(map_state, msg_bytes):
        vec = ctx_to_vec(make_ctx("tuner").buf)
        with jax.enable_x64(True):
            vec = vec.at[fields.index("msg_size")].set(
                msg_bytes.astype(jnp.uint64))
        ret, vec_out, maps_out = fn(vec, {"hist": map_state})
        nch = vec_out[fields.index("n_channels")].astype(jnp.int32)
        return nch, maps_out["hist"]

    rt = PolicyRuntime()
    rt.load(prog)
    state = map_to_array(rt.maps.get("hist"))
    # x64 scope wraps the jit calls so uint64 inputs are not canonicalized
    with jax.enable_x64(True):
        for mib in (0.5, 8, 64, 512):
            nch, state = training_step_with_policy(
                state, jnp.uint32(int(mib * MiB) & 0xFFFFFFFF))
            print(f"== in-graph (jaxc): {mib:>5} MiB -> channels={int(nch)}")
    import numpy as np
    print(f"   bucket histogram carried as device state: "
          f"{[int(x) for x in np.asarray(state)[:, 0]]}")


if __name__ == "__main__":
    main()
