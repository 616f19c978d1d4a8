"""Collective algorithm implementations (shard_map context).

The NCCL algorithm/protocol/channel space mapped to TPU-native constructs:

  algorithm DEFAULT     -> XLA's built-in lowering (lax.psum / all_to_all);
                           the "NVLS" analogue: opaque, hardware-offloaded,
                           best at large sizes
  algorithm RING        -> explicit reduce-scatter + all-gather rings built
                           from lax.ppermute (n-1 + n-1 hops)
  algorithm BIDIR_RING  -> two half-size counter-rotating rings
  algorithm TREE        -> recursive halving/doubling (2 log2 n hops),
                           latency-optimal for small messages
  protocol SIMPLE       -> full-precision wire
  protocol LL           -> bf16 wire, bf16 accumulation (latency analogue)
  protocol LL128        -> bf16 wire, f32 accumulation
  n_channels            -> the tensor is split into `c` independent chunk
                           rings whose ppermute chains are data-independent,
                           letting XLA overlap them across ICI links —
                           NCCL's channel parallelism, TPU-style

All functions must be called inside shard_map with `axis_name` a mesh axis.
Every implementation is numerically validated against `allreduce_native`
in tests/test_collectives.py on a real 8-device (host) mesh.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..core.context import Proto


def _axis_size(axis_name: str) -> int:
    return lax.axis_size(axis_name)


def wire_dtypes(protocol: int, dtype) -> Tuple[object, object]:
    """(wire_dtype, acc_dtype) for a protocol knob."""
    if protocol == Proto.SIMPLE or dtype == jnp.bfloat16:
        return dtype, dtype
    if protocol == Proto.LL:
        return jnp.bfloat16, jnp.bfloat16
    if protocol == Proto.LL128:
        return jnp.bfloat16, jnp.float32
    return dtype, dtype


# ---------------------------------------------------------------------------
# native (DEFAULT / "NVLS analogue")
# ---------------------------------------------------------------------------

def allreduce_native(x, axis_name: str, **_):
    return lax.psum(x, axis_name)


# ---------------------------------------------------------------------------
# ring
# ---------------------------------------------------------------------------

def _ring_chunk_allreduce(flat, axis_name: str, n: int, i, wire_dtype,
                          acc_dtype, reverse: bool = False):
    """AllReduce one 1-D chunk via RS+AG rings.  flat.size % n == 0."""
    blocks = flat.reshape(n, -1).astype(acc_dtype)
    step = -1 if reverse else 1
    perm = [(d, (d + step) % n) for d in range(n)]

    # ---- reduce-scatter ----------------------------------------------------
    # at hop k (1-based), device i receives the partial sum of block
    # (i - k*step) and adds its local copy; after n-1 hops it owns the
    # fully-reduced block (i + step) % n.
    cur = lax.dynamic_index_in_dim(blocks, i % n, axis=0, keepdims=False)
    for k in range(1, n):
        sent = lax.ppermute(cur.astype(wire_dtype), axis_name, perm)
        recv_block = (i - k * step) % n
        local = lax.dynamic_index_in_dim(blocks, recv_block, axis=0,
                                         keepdims=False)
        cur = local + sent.astype(acc_dtype)
    # now cur = fully-reduced block (i - (n-1)*step) % n == (i + step) % n
    owned = (i + step) % n

    # ---- all-gather ring ----------------------------------------------------
    out = jnp.zeros_like(blocks)
    out = lax.dynamic_update_index_in_dim(out, cur, owned, axis=0)
    for k in range(1, n):
        cur = lax.ppermute(cur.astype(wire_dtype), axis_name, perm
                           ).astype(acc_dtype)
        # the block received at hop k was owned by device (i - k*step)
        blk = (i - k * step + step) % n
        out = lax.dynamic_update_index_in_dim(out, cur, blk, axis=0)
    return out.reshape(-1)


def _chunked(flat, n_channels: int, n: int):
    """Split into n_channels independent chunks, each n-divisible."""
    c = max(1, min(n_channels, 32))
    quantum = n * c
    pad = (-flat.size) % quantum
    flat = jnp.pad(flat, (0, pad))
    return flat.reshape(c, -1), pad


@partial(jax.named_call, name="allreduce_ring")
def allreduce_ring(x, axis_name: str, *, n_channels: int = 1,
                   protocol: int = Proto.SIMPLE, **_):
    n = _axis_size(axis_name)
    if n == 1:
        return x
    wire, acc = wire_dtypes(protocol, x.dtype)
    i = lax.axis_index(axis_name)
    flat = x.reshape(-1)
    chunks, pad = _chunked(flat, n_channels, n)
    outs = [_ring_chunk_allreduce(chunks[c], axis_name, n, i, wire, acc)
            for c in range(chunks.shape[0])]
    out = jnp.concatenate(outs)
    if pad:
        out = out[:flat.size]
    return out.reshape(x.shape).astype(x.dtype)


@partial(jax.named_call, name="allreduce_bidir_ring")
def allreduce_bidir_ring(x, axis_name: str, *, n_channels: int = 1,
                         protocol: int = Proto.SIMPLE, **_):
    """Two counter-rotating rings, each carrying half the payload —
    doubles effective link utilization on bidirectional ICI."""
    n = _axis_size(axis_name)
    if n == 1:
        return x
    wire, acc = wire_dtypes(protocol, x.dtype)
    i = lax.axis_index(axis_name)
    flat = x.reshape(-1)
    c = max(1, min(n_channels, 32))
    chunks, pad = _chunked(flat, 2 * c, n)
    half = chunks.shape[0] // 2
    outs = []
    for ci in range(chunks.shape[0]):
        outs.append(_ring_chunk_allreduce(
            chunks[ci], axis_name, n, i, wire, acc, reverse=(ci >= half)))
    out = jnp.concatenate(outs)
    if pad:
        out = out[:flat.size]
    return out.reshape(x.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# tree (recursive halving-doubling)
# ---------------------------------------------------------------------------

@partial(jax.named_call, name="allreduce_tree")
def allreduce_tree(x, axis_name: str, *, n_channels: int = 1,
                   protocol: int = Proto.SIMPLE, **_):
    n = _axis_size(axis_name)
    if n == 1:
        return x
    if n & (n - 1):
        # non-power-of-two axis: fall back to ring (NCCL does similar)
        return allreduce_ring(x, axis_name, n_channels=n_channels,
                              protocol=protocol)
    wire, acc = wire_dtypes(protocol, x.dtype)
    i = lax.axis_index(axis_name)
    flat = x.reshape(-1)
    pad = (-flat.size) % n
    flat = jnp.pad(flat, (0, pad)).astype(acc)

    cur = flat
    # halving reduce-scatter: distances n/2 ... 1
    d = n // 2
    while d >= 1:
        pairs = [(j, j ^ d) for j in range(n)]
        bit = (i & d) != 0
        lo, hi = jnp.split(cur, 2)
        keep = jnp.where(bit, hi, lo)
        send = jnp.where(bit, lo, hi)
        recv = lax.ppermute(send.astype(wire), axis_name, pairs)
        cur = keep + recv.astype(keep.dtype)
        d //= 2
    # doubling all-gather: distances 1 ... n/2
    d = 1
    while d < n:
        pairs = [(j, j ^ d) for j in range(n)]
        bit = (i & d) != 0
        recv = lax.ppermute(cur.astype(wire), axis_name, pairs
                            ).astype(cur.dtype)
        cur = jnp.where(bit,
                        jnp.concatenate([recv, cur]),
                        jnp.concatenate([cur, recv]))
        d *= 2
    if pad:
        cur = cur[:x.size]
    return cur.reshape(x.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# reduce-scatter / all-gather (FSDP building blocks)
# ---------------------------------------------------------------------------

@partial(jax.named_call, name="reduce_scatter_ring")
def reduce_scatter_ring(x, axis_name: str, *, protocol: int = Proto.SIMPLE,
                        **_):
    """Ring reduce-scatter along leading dim; returns x.shape[0]//n shard."""
    n = _axis_size(axis_name)
    if n == 1:
        return x
    wire, acc = wire_dtypes(protocol, x.dtype)
    i = lax.axis_index(axis_name)
    assert x.shape[0] % n == 0, "leading dim must divide the axis"
    blocks = x.reshape(n, x.shape[0] // n, *x.shape[1:]).astype(acc)
    perm = [(d, (d + 1) % n) for d in range(n)]
    cur = lax.dynamic_index_in_dim(blocks, i, axis=0, keepdims=False)
    for k in range(1, n):
        sent = lax.ppermute(cur.astype(wire), axis_name, perm)
        blk = (i - k) % n
        local = lax.dynamic_index_in_dim(blocks, blk, axis=0, keepdims=False)
        cur = local + sent.astype(acc)
    # device i owns block (i+1)%n; rotate so device i owns block i
    cur = lax.ppermute(cur.astype(wire), axis_name, perm).astype(acc)
    return cur.astype(x.dtype)


@partial(jax.named_call, name="all_gather_ring")
def all_gather_ring(x, axis_name: str, *, protocol: int = Proto.SIMPLE, **_):
    n = _axis_size(axis_name)
    if n == 1:
        return x
    wire, _ = wire_dtypes(protocol, x.dtype)
    i = lax.axis_index(axis_name)
    perm = [(d, (d + 1) % n) for d in range(n)]
    out = jnp.zeros((n,) + x.shape, x.dtype)
    out = lax.dynamic_update_index_in_dim(out, x, i, axis=0)
    cur = x
    for k in range(1, n):
        cur = lax.ppermute(cur.astype(wire), axis_name, perm).astype(x.dtype)
        out = lax.dynamic_update_index_in_dim(out, cur, (i - k) % n, axis=0)
    return out.reshape((n * x.shape[0],) + x.shape[1:])


# ---------------------------------------------------------------------------
# all-to-all (MoE dispatch path)
# ---------------------------------------------------------------------------

def all_to_all_native(x, axis_name: str, *, split_axis: int = 0,
                      concat_axis: int = 0, tiled: bool = True, **_):
    return lax.all_to_all(x, axis_name, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=tiled)


@partial(jax.named_call, name="all_to_all_chunked")
def all_to_all_chunked(x, axis_name: str, *, n_channels: int = 1,
                       protocol: int = Proto.SIMPLE, **_):
    """ppermute-composed all-to-all over the leading dim (tiled semantics):
    x.shape[0] split into n slots; slot j goes to device j.  Chunking splits
    each slot payload for channel pipelining."""
    n = _axis_size(axis_name)
    if n == 1:
        return x
    wire, _ = wire_dtypes(protocol, x.dtype)
    i = lax.axis_index(axis_name)
    assert x.shape[0] % n == 0
    blocks = x.reshape(n, x.shape[0] // n, *x.shape[1:])
    out = jnp.zeros_like(blocks)
    # keep own slot
    own = lax.dynamic_index_in_dim(blocks, i, axis=0, keepdims=False)
    out = lax.dynamic_update_index_in_dim(out, own, i, axis=0)
    for k in range(1, n):
        # send slot (i+k)%n with rotation k
        perm = [(d, (d + k) % n) for d in range(n)]
        send = lax.dynamic_index_in_dim(blocks, (i + k) % n, axis=0,
                                        keepdims=False)
        recv = lax.ppermute(send.astype(wire), axis_name, perm
                            ).astype(x.dtype)
        out = lax.dynamic_update_index_in_dim(out, recv, (i - k) % n, axis=0)
    return out.reshape(x.shape)
