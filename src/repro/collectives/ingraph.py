"""In-graph adaptive dispatch: a verified policy selects the collective
algorithm per STEP, inside one compiled XLA program.

The paper's host-side model decides per call; under jit, host decisions
freeze at trace time, and hot behavior changes need a retrace.  This module
removes that limit: the jaxc-compiled policy reads live telemetry from a
functionally-threaded eBPF array map and drives ``lax.switch`` over
pre-lowered algorithm branches — closed-loop adaptation with ZERO retraces
and ZERO host round-trips.

Three in-graph tiers share this entry point: ``tier="jaxc"`` (pure-JAX
if-conversion), ``tier="pallas"`` (the same CFG lowering packaged as one
``pl.pallas_call`` kernel, at the backend's word width), and
``tier="pallas32"`` (the kernel in the 32-bit-pair representation: every
u64 as a (lo, hi) uint32 pair with its state in SMEM, no x64 scope
anywhere — the form Mosaic lowers on TPU).  All carry the map state as
operands, so closed-loop adaptation keeps zero retraces either way.

Usage:
    sel = InGraphSelector(policy_program, tier="pallas32")
    state = sel.init_state()
    ...inside your jitted step:
    y, state = sel.all_reduce(x, "model", state, latency_ns=obs)
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..core.context import Algo, CollType, POLICY_CONTEXT, Proto
from ..core.jaxc import compile_jax, map_to_array, maybe_x64
from ..core.lower32 import map_to_array32
from ..core.maps import MapRegistry
from ..core.program import Program
from ..core.verifier import verify_with_info
from . import algorithms as alg

_FIELDS = list(POLICY_CONTEXT.fields)
_IDX = {name: i for i, name in enumerate(_FIELDS)}

# branch table: algorithm id -> implementation (uniform signature)
_BRANCHES = [
    ("default", lambda x, a: alg.allreduce_native(x, a)),
    ("ring", lambda x, a: alg.allreduce_ring(x, a, n_channels=4)),
    ("tree", lambda x, a: alg.allreduce_tree(x, a)),
    ("bidir_ring", lambda x, a: alg.allreduce_bidir_ring(x, a,
                                                         n_channels=2)),
]

TIERS = ("jaxc", "pallas", "pallas32")

# extra state leaf carrying the in-graph fault flag: compiled policies
# cannot throw, so out-of-domain decisions are clamped IN the graph and
# counted here (a uint32[1] accumulator threaded with the map state);
# hosts drain it at flush boundaries via :meth:`InGraphSelector.drain_faults`
FAULT_KEY = "__fault_flags__"

# per-shard write cursor: how many decide() calls have run against this
# state copy (a uint32[1] leaf bumped in-graph).  Under ``shard_map``
# every device threads its OWN state, so after a step each device's copy
# diverged; the cursor is the version the deterministic shard merge
# (:meth:`InGraphSelector.merge_shard_states`) uses for its
# max-version-wins cells
CURSOR_KEY = "__write_cursor__"


class InGraphSelector:
    def __init__(self, program: Program, *, tier: str = "jaxc"):
        if tier not in TIERS:
            raise ValueError(f"unknown in-graph tier {tier!r}; "
                             f"use one of {', '.join(TIERS)}")
        vinfo = verify_with_info(program)
        self.program = program
        self.tier = tier
        if tier in ("pallas", "pallas32"):
            from ..core.pallasc import compile_pallas, resolve_word_width
            # "pallas" takes the backend's word width: the pair form on
            # TPU, where Mosaic has no 64-bit integer units
            self.word_width = 32 if tier == "pallas32" \
                else resolve_word_width(None)
            self._fn, self.map_names = compile_pallas(
                program, vinfo, word_width=self.word_width)
        else:
            self._fn, self.map_names = compile_jax(program, vinfo)
            self.word_width = 64
        from ..core.jaxc import written_map_names
        # maps the verified program can write — the only leaves the
        # shard merge ever reconciles (lookup-only state can't diverge)
        self.written_names = written_map_names(program, vinfo) \
            & set(self.map_names)

    def init_state(self, registry: Optional[MapRegistry] = None
                   ) -> Dict[str, jnp.ndarray]:
        """Device-resident map state (thread through your step fn).

        With ``registry`` (e.g. a live runtime's ``maps``), the state is
        seeded from the existing host maps — telemetry a profiler
        already accumulated moves in-graph instead of starting cold.
        The array layout follows the tier's word width: uint64 slots for
        the 64-bit tiers, uint32 [lo, hi] pairs for ``pallas32``."""
        reg = registry or MapRegistry()
        to_array = map_to_array32 if self.word_width == 32 else map_to_array
        out = {}
        for d in self.program.maps:
            m = reg.create(d.name, d.kind, key_size=d.key_size,
                           value_size=d.value_size,
                           max_entries=d.max_entries)
            out[d.name] = to_array(m)
        out[FAULT_KEY] = jnp.zeros((1,), jnp.uint32)
        out[CURSOR_KEY] = jnp.zeros((1,), jnp.uint32)
        return out

    def _ctx_vec(self, fields: Dict[str, object]) -> jnp.ndarray:
        """Build the ctx vector in the tier's representation.

        On the 32-bit path, Python ints split into both lanes exactly.
        Traced integer operands are at most 32 bits wide here (without
        x64 jax has no wider integer dtype), so they ride the lo lane
        losslessly; traced FLOATS (e.g. a float32 latency observation
        that can exceed 2**32 ns) are split into hi/lo so the policy
        sees the same value the uint64 tiers would."""
        if self.word_width == 32:
            vec = jnp.zeros((len(_FIELDS), 2), jnp.uint32)
            for name, v in fields.items():
                i = _IDX[name]
                if isinstance(v, int):
                    vec = vec.at[i, 0].set(jnp.uint32(v & 0xFFFFFFFF))
                    vec = vec.at[i, 1].set(
                        jnp.uint32((v >> 32) & 0xFFFFFFFF))
                    continue
                arr = jnp.asarray(v)
                if jnp.issubdtype(arr.dtype, jnp.floating):
                    hi = jnp.floor(arr / (2.0**32))
                    lo = arr - hi * (2.0**32)
                    vec = vec.at[i, 0].set(lo.astype(jnp.uint32))
                    vec = vec.at[i, 1].set(hi.astype(jnp.uint32))
                else:
                    vec = vec.at[i, 0].set(arr.astype(jnp.uint32))
            return vec
        vec = jnp.zeros((len(_FIELDS),), jnp.uint64)
        for name, v in fields.items():
            vec = vec.at[_IDX[name]].set(jnp.asarray(v, jnp.uint64))
        return vec

    def decide(self, state: Dict, *, coll: int, msg_bytes: int, n: int,
               comm_id: int = 0, latency_ns=None
               ) -> Tuple[jnp.ndarray, jnp.ndarray, Dict]:
        """Run the verified policy in-graph.

        Returns (algo_idx int32, channels int32, new_state)."""
        with maybe_x64(self.word_width == 64):
            fields: Dict[str, object] = {
                "coll_type": int(coll), "msg_size": int(msg_bytes),
                "n_ranks": int(n), "comm_id": int(comm_id),
                "max_channels": 32,
            }
            if latency_ns is not None:
                # live telemetry rides the ctx 'topo_links' slot? no —
                # policies read it from the map; feed it there via the
                # profiler program or pass through dtype_bytes-free field
                fields["dtype_bytes"] = latency_ns
            vec = self._ctx_vec(fields)
            flags = state.get(FAULT_KEY)
            cursor = state.get(CURSOR_KEY)
            prog_state = {k: v for k, v in state.items()
                          if k not in (FAULT_KEY, CURSOR_KEY)}
            _, vec_out, prog_state = self._fn(vec, prog_state)
            if self.word_width == 32:
                raw_algo = vec_out[_IDX["algorithm"], 0].astype(jnp.int32)
                raw_ch = vec_out[_IDX["n_channels"], 0].astype(jnp.int32)
            else:
                raw_algo = vec_out[_IDX["algorithm"]].astype(jnp.int32)
                raw_ch = vec_out[_IDX["n_channels"]].astype(jnp.int32)
        # the kernel cannot throw, so the domain guard is a clamp lowered
        # INTO the graph; any clamp that changed the value bumps the
        # fault-flag leaf (drained host-side at flush boundaries)
        algo = jnp.clip(raw_algo, 0, len(_BRANCHES) - 1)
        ch = jnp.clip(raw_ch, 0, 32)
        state = dict(prog_state)
        if flags is not None:
            bad = ((raw_algo != algo) | (raw_ch != ch)).astype(jnp.uint32)
            state[FAULT_KEY] = flags + bad
        if cursor is not None:
            state[CURSOR_KEY] = cursor + jnp.uint32(1)
        return algo, ch, state

    def drain_faults(self, state: Dict) -> Tuple[int, Dict]:
        """Read-and-zero the in-graph fault counter (host sync point —
        call at the same cadence as ``DeviceBridge.flush``).  Returns
        ``(n_faults, state_with_cleared_flag)``; states built before the
        flag leaf existed drain as 0."""
        flags = state.get(FAULT_KEY)
        if flags is None:
            return 0, state
        n = int(jax.device_get(flags)[0])
        state = dict(state)
        state[FAULT_KEY] = jnp.zeros((1,), jnp.uint32)
        return n, state

    # ------------------------------------------------------------------
    # mesh-scale state: per-device shards -> one merged host view
    # ------------------------------------------------------------------
    @staticmethod
    def unstack_sharded(state: Dict) -> list:
        """Split a state whose leaves carry a leading DEVICE axis (the
        shape ``shard_map``/``jax.device_get`` hands back when every
        device threads its own copy) into one per-device state list for
        :meth:`merge_shard_states`."""
        import numpy as np
        leaves = {k: np.asarray(jax.device_get(v))
                  for k, v in state.items()}
        counts = {v.shape[0] for v in leaves.values()}
        if len(counts) != 1:
            raise ValueError(
                f"inconsistent leading device axis across state leaves: "
                f"{sorted(counts)}")
        n = counts.pop()
        return [{k: v[i] for k, v in leaves.items()} for i in range(n)]

    def merge_shard_states(self, registry: MapRegistry,
                           shard_states, base_state: Dict,
                           stats: Optional[dict] = None) -> int:
        """Publish per-device state shards back into the host maps.

        ``shard_states`` is one state dict per device (use
        :meth:`unstack_sharded` on a stacked state), each carrying the
        diverged map leaves plus its ``CURSOR_KEY`` write count;
        ``base_state`` is the state they were ALL seeded from (what
        :meth:`init_state` returned).  Each written map reconciles via
        the deterministic shard merge (:mod:`repro.core.shardmerge`):
        counter slots sum per-shard deltas, ``merge="max"`` cells go to
        the shard with the highest cursor, hash maps merge per key —
        bit-identical for any device count and shard order.  Returns
        the number of maps merged."""
        import numpy as np
        from ..core import shardmerge as _sm

        def to64(arr):
            a = np.asarray(jax.device_get(arr))
            return _sm.pairs_to_u64(a) if self.word_width == 32 \
                else a.astype("<u8", copy=False)

        merged_maps = 0
        for d in self.program.maps:
            if d.name not in self.written_names:
                continue
            base64 = to64(base_state[d.name])
            shards = []
            for sid, st in enumerate(shard_states):
                cur = st.get(CURSOR_KEY)
                cur = int(np.asarray(jax.device_get(cur)).reshape(-1)[0]) \
                    if cur is not None else 1
                if cur == 0:
                    continue
                shards.append(_sm.Shard(sid, to64(st[d.name]), cur, base64))
            if not shards:
                continue
            m = registry.create(d.name, d.kind, key_size=d.key_size,
                                value_size=d.value_size,
                                max_entries=d.max_entries)
            with m.lock:
                m.from_device(_sm.merge_map_shards(d, m.to_device(),
                                                   shards, stats))
            merged_maps += 1
        return merged_maps

    def all_reduce(self, x, axis_name: str, state: Dict, *,
                   comm_id: int = 0, latency_ns=None):
        """Policy-selected all-reduce via lax.switch (all branches lowered
        once; selection is a runtime scalar)."""
        n = lax.axis_size(axis_name)
        algo, ch, state = self.decide(
            state, coll=CollType.ALL_REDUCE,
            msg_bytes=int(x.size) * x.dtype.itemsize, n=n,
            comm_id=comm_id, latency_ns=latency_ns)
        y = lax.switch(algo, [lambda v, f=f: f(v, axis_name)
                              for _, f in _BRANCHES], x)
        return y, algo, state
