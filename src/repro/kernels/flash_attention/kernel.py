"""Flash attention Pallas TPU kernel (causal / sliding-window).

TPU adaptation (vs the CUDA original): the TPU grid is *sequential* over
the trailing axis, so instead of one thread-block owning a q-tile and
looping over kv in shared memory, the kernel walks kv-tiles as grid steps
and carries the online-softmax state (m, l, acc) in VMEM scratch across
steps.  MXU alignment: block shapes are multiples of 128 in the lane dim;
the f32 accumulator lives in VMEM for the whole q-tile (bq × d floats —
the BlockSpec budget is bq·d + 2·(bq·bk) + 2·bk·d floats ≤ ~2 MiB VMEM
for the default 128/512 tiles).

Grid: (B·H, S/bq, T/bk) — kv innermost so scratch carries across it.

No model calls this kernel: it has no backward pass, computes every tile
whether masked or not, and casts its operands to float32.  Latent
attention trains with JAX's splash-attention kernel (``models/mla.py``)
and GQA attention with the plain softmax (``models/attention.py``);
``tests/test_kernels.py`` checks this kernel against ``ref.py``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BQ = 128
DEFAULT_BK = 128


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 scale: float, causal: bool, window: int, bq: int, bk: int,
                 seq_q: int, seq_k: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)              # (bq, d)
    k = k_ref[0].astype(jnp.float32)              # (bk, d)
    v = v_ref[0].astype(jnp.float32)              # (bk, d)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    # global positions (queries aligned to the END of the key range)
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) \
        + (seq_k - seq_q)
    k_pos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask = mask & (k_pos <= q_pos)
    if window > 0:
        mask = mask & (k_pos > q_pos - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                            # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked rows (m_new == NEG_INF): keep weights at 0
    alpha = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)

    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kj == nk - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention_tpu(q, k, v, *, causal: bool = True, window: int = 0,
                        scale=None, bq: int = DEFAULT_BQ,
                        bk: int = DEFAULT_BK, interpret: bool = True):
    """q: (BH, S, d); k/v: (BH, T, d) — heads pre-flattened/kv-expanded."""
    BH, S, d = q.shape
    T = k.shape[1]
    scale = scale if scale is not None else d ** -0.5
    bq = min(bq, S)
    bk = min(bk, T)
    assert S % bq == 0 and T % bk == 0, (S, bq, T, bk)

    kern = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        bq=bq, bk=bk, seq_q=S, seq_k=T)

    return pl.pallas_call(
        kern,
        grid=(BH, S // bq, T // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),     # m
            pltpu.VMEM((bq, 1), jnp.float32),     # l
            pltpu.VMEM((bq, d), jnp.float32),     # acc
        ],
        interpret=interpret,
    )(q, k, v)
