"""GQA attention: full / sliding-window / chunked-local, train + decode.

Tensor layout (inside shard_map):
  activations x: (B_local, S, D)          — batch over data axis, D full
  wq:  (D, Hp*hd // tp)                   — column-parallel (pad heads)
  wk/wv: (D, KV*hd // tp) if n_kv >= tp else (D, KV*hd) replicated
  wo:  (Hp*hd // tp, D)                   — row-parallel + psum(model)

When tp > n_kv, each device keeps ALL kv heads (the standard KV-replication
scheme for GQA under wide TP) and uses the group its local q heads map to.

Every backend computes the softmax here in plain jnp; the flash-attention
Pallas kernel (src/repro/kernels/flash_attention) is not called.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .config import ModelConfig
from .layers import (MeshAxes, apply_rope, col_linear, fsdp_gather,
                     rms_norm, rope_freqs, row_linear, tp_psum)

NEG_INF = -1e30


def kv_split(cfg: ModelConfig, ax: MeshAxes) -> bool:
    """KV heads are TP-split only when they divide evenly; otherwise the
    standard KV-replication scheme for GQA under wide TP."""
    return ax.tp > 1 and cfg.n_kv_heads % ax.tp == 0


def _local_heads(cfg: ModelConfig, ax: MeshAxes) -> Tuple[int, int]:
    """(q heads per device, kv heads per device)."""
    hp = cfg.padded_heads(ax.tp)
    h_loc = hp // ax.tp
    kv_loc = cfg.n_kv_heads // ax.tp if kv_split(cfg, ax) else cfg.n_kv_heads
    return h_loc, kv_loc


def _kv_map(cfg: ModelConfig, ax: MeshAxes):
    """(h_loc,) int32: local q head -> local kv head index (traced by rank)."""
    h_loc, kv_loc = _local_heads(cfg, ax)
    g = max(1, cfg.n_heads // cfg.n_kv_heads)
    j = jnp.arange(h_loc, dtype=jnp.int32)
    r = lax.axis_index(ax.model) if ax.tp > 1 else 0
    gq = jnp.minimum(r * h_loc + j, cfg.n_heads - 1)   # clamp padded heads
    gkv = gq // g
    if kv_split(cfg, ax):
        return jnp.clip(gkv - r * kv_loc, 0, kv_loc - 1)
    return gkv


def qkv_project(p, x, cfg: ModelConfig, ax: MeshAxes, positions,
                *, use_rope: bool = True):
    """Returns q (B,S,h_loc,hd), k/v (B,S,kv_loc,hd)."""
    hd = cfg.hd
    h_loc, kv_loc = _local_heads(cfg, ax)
    q = col_linear(x, p["wq"], ax, bias=p.get("bq"), fsdp_dim=0)
    k = col_linear(x, p["wk"], ax, bias=p.get("bk"), fsdp_dim=0)
    v = col_linear(x, p["wv"], ax, bias=p.get("bv"), fsdp_dim=0)
    B, S = x.shape[0], x.shape[1]
    q = q.reshape(B, S, h_loc, hd)
    k = k.reshape(B, S, kv_loc, hd)
    v = v.reshape(B, S, kv_loc, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    if use_rope:
        ang = rope_freqs(hd, cfg.rope_theta, positions)
        q = apply_rope(q, ang)
        k = apply_rope(k, ang)
    return q, k, v


def _sdpa(q, k, v, mask, *, scale, kv_map):
    """(B,S,h,hd) x (B,T,kv,hd) -> (B,S,h,hd).

    ``kv_map`` (h,) maps each local q head to its local kv head (GQA under
    TP; may be rank-dependent and traced)."""
    B, S, H, hd = q.shape
    k = jnp.take(k, kv_map, axis=2)   # (B, T, H, hd)
    v = jnp.take(v, kv_map, axis=2)
    logits = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    logits = jnp.where(mask[:, None, :, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", w.astype(v.dtype), v)
    return out.reshape(B, S, H, hd)


def causal_mask(S: int, positions, kv_positions, *, window: int = 0):
    """(B|1, S, T) boolean mask; window > 0 = sliding window."""
    pq = positions[..., :, None]          # (B|1, S, 1)
    pk = kv_positions[..., None, :]       # (B|1, 1, T)
    m = pk <= pq
    if window > 0:
        m = m & (pk > pq - window)
    return m


def attention_train(p, x, cfg: ModelConfig, ax: MeshAxes, *,
                    use_rope: bool = True, causal: bool = True):
    """Training/prefill path, no cache.  Sliding window per cfg.attention."""
    B, S, D = x.shape
    positions = jnp.arange(S, dtype=jnp.int32)[None]  # (1, S)
    q, k, v = qkv_project(p, x, cfg, ax, positions[0], use_rope=use_rope)
    window = cfg.window if cfg.attention in ("sliding", "chunked") else 0
    if causal:
        mask = causal_mask(S, positions, positions, window=window)
    else:
        mask = jnp.ones((1, S, S), bool)
    out = _sdpa(q, k, v, mask, scale=cfg.hd ** -0.5,
                kv_map=_kv_map(cfg, ax))
    out = out.reshape(B, S, -1)
    return row_linear(out, p["wo"], ax, fsdp_dim=1)


def attention_decode(p, x, cache, cfg: ModelConfig, ax: MeshAxes, pos,
                     *, use_rope: bool = True):
    """One-token decode against a KV cache.

    cache: dict(k=(B, C, kv_loc, hd), v=..., idx=scalar int32 write index)
    For sliding-window configs C == window (ring buffer); for full
    attention C == max context.  pos: (B,) absolute positions.
    """
    B, S, D = x.shape
    assert S == 1
    q, k, v = qkv_project(p, x, cfg, ax, pos[:, None], use_rope=use_rope)
    C = cache["k"].shape[1]
    slot = (cache["idx"] % C).astype(jnp.int32)
    # scatter the new kv at the ring slot
    ck = cache["k"].at[:, slot].set(k[:, 0])
    cv = cache["v"].at[:, slot].set(v[:, 0])
    # kv positions for masking: ring buffer holds absolute positions
    kpos = cache["pos"].at[:, slot].set(pos)
    window = cfg.window if cfg.attention in ("sliding", "chunked") else 0
    mask = causal_mask(1, pos[:, None], kpos, window=window)
    mask = mask & (kpos[:, None, :] >= 0)
    out = _sdpa(q, ck, cv, mask, scale=cfg.hd ** -0.5,
                kv_map=_kv_map(cfg, ax))
    out = out.reshape(B, 1, -1)
    y = row_linear(out, p["wo"], ax, fsdp_dim=1)
    new_cache = dict(k=ck, v=cv, pos=kpos, idx=cache["idx"] + 1)
    return y, new_cache


def cross_attention(p, x, enc_kv, cfg: ModelConfig, ax: MeshAxes):
    """Encoder-decoder cross attention (whisper). enc_kv: (k, v) tensors."""
    B, S, D = x.shape
    hd = cfg.hd
    h_loc, kv_loc = _local_heads(cfg, ax)
    q = col_linear(x, p["wq"], ax, fsdp_dim=0).reshape(B, S, h_loc, hd)
    k, v = enc_kv
    T = k.shape[1]
    mask = jnp.ones((1, S, T), bool)
    out = _sdpa(q, k, v, mask, scale=hd ** -0.5, kv_map=_kv_map(cfg, ax))
    return row_linear(out.reshape(B, S, -1), p["wo"], ax, fsdp_dim=1)


def encode_kv(p, enc_out, cfg: ModelConfig, ax: MeshAxes):
    """Precompute cross-attention K/V from encoder output."""
    B, T, D = enc_out.shape
    _, kv_loc = _local_heads(cfg, ax)
    k = col_linear(enc_out, p["wk"], ax, fsdp_dim=0).reshape(B, T, kv_loc,
                                                             cfg.hd)
    v = col_linear(enc_out, p["wv"], ax, fsdp_dim=0).reshape(B, T, kv_loc,
                                                             cfg.hd)
    return k, v


def init_cache(cfg: ModelConfig, B: int, ctx: int, ax: MeshAxes, dtype):
    """KV cache pytree for one attention layer."""
    _, kv_loc = _local_heads(cfg, ax)
    window = cfg.window if cfg.attention in ("sliding", "chunked") else 0
    C = min(ctx, window) if window else ctx
    return dict(
        k=jnp.zeros((B, C, kv_loc, cfg.hd), dtype),
        v=jnp.zeros((B, C, kv_loc, cfg.hd), dtype),
        pos=jnp.full((B, C), -1, jnp.int32),
        idx=jnp.zeros((), jnp.int32),
    )
