"""Multi-head latent attention (MLA), as DeepSeek-V2 (arXiv:2405.04434
§2.1) defines it and DeepSeek-V3 and Moonlight use it, with no query
compression:

  q              = x W_q                 D -> H x (dn + dr), [q_nope | q_pe]
  [c | k_r]      = x W_kv_a              D -> r + dr
  c_kv           = RMSNorm(c)            the latent a cache holds
  [k_nope | v]   = c_kv W_kv_b           r -> H x (dn + dv)
  k_pe           = rope(k_r)             one rope key shared by the H heads
  o              = softmax([q_nope, rope(q_pe)] . [k_nope, k_pe]^T
                           / sqrt(dn + dr)) v
  y              = o W_o                 H x dv -> D

Rope rotates the halves of the ``dr`` columns (``layers.apply_rope``);
DeepSeek's checkpoints interleave them, which for seeded weights is a
fixed permutation of the rope columns of ``W_q`` and ``W_kv_a``.

Training computes the causal softmax in one of two ways, chosen by what
the code can observe.  On the TPU, when ``FLASH_BLOCK`` divides S,
JAX's splash-attention kernel (:func:`causal_flash`) computes it in
tiles of ``FLASH_BLOCK`` x ``FLASH_BLOCK``: it skips the tiles above the
diagonal, keeps the logits and the online softmax's statistics in the
core's fast memory, and has its own dq and dkv kernels.  Elsewhere
:func:`blockwise_attention` computes it in blocks of query rows against
every key, each block under ``jax.checkpoint`` so that the S x S square
of logits is never held whole; it is the kernel's oracle in the tests.
Each training call counts, at trace time, the tiles of one head's square
(``repro.mla.kv_tiles_total``) and those the kernel computes
(``repro.mla.kv_tiles_computed``, none on the blockwise path).  Decode
attends over the latent cache with the plain masked softmax.  The heads
are not split over the model axis (every rank computes all of them);
FSDP gathers the weights along their input dimension.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental.pallas.ops.tpu import splash_attention as splash
from jax.sharding import PartitionSpec as P

from ..obs import spans
from .config import ModelConfig
from .layers import MeshAxes, apply_rope, fsdp_gather, rms_norm, rope_freqs

NEG_INF = -1e30
Q_BLOCK = 512           # query rows per block of the blockwise softmax
# query and key rows per tile of the TPU kernel: on a TPU v5e, MLA's
# forward and backward at S=8192, H=16 took 15.8 ms in tiles of 1024,
# 17.4 in tiles of 512 and 27.0 in tiles of 256
FLASH_BLOCK = 1024


def init_params(keys, cfg: ModelConfig, n: int, dense):
    """The weights of ``n`` stacked layers from ``keys`` (at least 4)."""
    D, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    p = {"wq": dense(keys[0], (n, D, H * (dn + dr))),
         "wkv_a": dense(keys[1], (n, D, r + dr)),
         "kv_norm": jnp.ones((n, r), jnp.float32),
         "wkv_b": dense(keys[2], (n, r, H * (dn + dv))),
         "wo": dense(keys[3], (n, H * dv, D))}
    s = {"wq": P(None, "data", None), "wkv_a": P(None, "data", None),
         "kv_norm": P(None, None), "wkv_b": P(None, "data", None),
         "wo": P(None, None, "data")}
    return p, s


def _latent(p, x, cfg: ModelConfig, ax: MeshAxes, positions):
    """q (B,S,H,dn+dr) with rope applied, the normed latent (B,S,r) and the
    roped shared key (B,S,dr)."""
    B, S, _ = x.shape
    H, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr = cfg.qk_nope_dim, cfg.qk_rope_dim
    wq = fsdp_gather(p["wq"], ax, 0).astype(x.dtype)
    wa = fsdp_gather(p["wkv_a"], ax, 0).astype(x.dtype)
    q = jnp.einsum("bsd,df->bsf", x, wq).reshape(B, S, H, dn + dr)
    ckr = jnp.einsum("bsd,df->bsf", x, wa)
    c = rms_norm(ckr[..., :r], p["kv_norm"], cfg.norm_eps)
    ang = rope_freqs(dr, cfg.rope_theta, positions)
    q = jnp.concatenate([q[..., :dn], apply_rope(q[..., dn:], ang)], -1)
    k_pe = apply_rope(ckr[..., None, r:], ang)[:, :, 0]
    return q, c, k_pe


def _keys_values(p, c, k_pe, cfg: ModelConfig, ax: MeshAxes):
    """k (B,T,H,dn+dr) and v (B,T,H,dv) from the latent and the rope key."""
    B, T, _ = c.shape
    H, dn, dv = cfg.n_heads, cfg.qk_nope_dim, cfg.v_head_dim
    wb = fsdp_gather(p["wkv_b"], ax, 0).astype(c.dtype)
    kv = jnp.einsum("btr,rf->btf", c, wb).reshape(B, T, H, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_pe[:, :, None], (B, T, H,
                                                             k_pe.shape[-1]))],
        -1)
    return k, kv[..., dn:]


def _softmax_av(q, k, v, mask, scale):
    """(B,S,H,dq) x (B,T,H,dq) x (B,T,H,dv) -> (B,S,H,dv), float32
    logits; ``mask`` (B|1, S, T)."""
    s = jnp.einsum("bshd,bthd->bhst", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask[:, None], s, NEG_INF)
    # the row max stays out of its consumers' fusion: fused, XLA's TPU
    # compiler turns the max and its broadcast into a reduce-window over
    # 2T - 1 columns, a pass of O(T) per element
    m = lax.optimization_barrier(
        lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True)))
    e = jnp.exp(s - m)
    w = e / jnp.sum(e, axis=-1, keepdims=True)
    return jnp.einsum("bhst,bthd->bshd", w.astype(v.dtype), v)


def _out(p, o, ax: MeshAxes):
    B, S = o.shape[:2]
    wo = fsdp_gather(p["wo"], ax, 1).astype(o.dtype)
    return jnp.einsum("bsf,fd->bsd", o.reshape(B, S, -1), wo)


def blockwise_attention(q, k, v, *, block: int = Q_BLOCK):
    """Causal attention over query blocks of ``block`` rows, each under
    ``jax.checkpoint``: a block's (B, H, block, T) logits are the most
    that is ever held.  A ``block`` that does not divide S is the whole
    square."""
    B, S, H, dq = q.shape
    Q = block if S % block == 0 else S
    scale = dq ** -0.5
    cols = jnp.arange(S)

    @jax.checkpoint
    def one(args):
        qb, rows = args
        return _softmax_av(qb, k, v, (cols[None, :] <= rows[:, None])[None],
                           scale)

    qs = jnp.moveaxis(q.reshape(B, S // Q, Q, H, dq), 1, 0)
    o = lax.map(one, (qs, jnp.arange(S).reshape(S // Q, Q)))
    return jnp.moveaxis(o, 0, 1).reshape(B, S, H, v.shape[-1])


@functools.lru_cache(maxsize=None)
def _splash_kernel(S: int, H: int, block: int, interpret: bool):
    """The causal splash kernel over H heads of S rows, and the tiles of
    one head's square it computes (its mask info's non-empty blocks).
    Built out of any trace, so that the cached mask info holds arrays and
    no tracers."""
    with jax.ensure_compile_time_eval():
        kernel = splash.make_splash_mha(
            splash.MultiHeadMask([splash.CausalMask((S, S))] * H),
            head_shards=1, q_seq_shards=1, interpret=interpret,
            block_sizes=splash.BlockSizes(
                block_q=block, block_kv=block, block_kv_compute=block,
                block_q_dkv=block, block_kv_dkv=block,
                block_kv_dkv_compute=block, block_q_dq=block,
                block_kv_dq=block))
    block_mask = np.asarray(kernel.fwd_mask_info.block_mask)
    return kernel, int(np.count_nonzero(block_mask[0]))


def causal_flash(q, k, v, *, block: int = FLASH_BLOCK):
    """Causal attention by the splash kernel, compiled on the TPU and
    interpreted elsewhere: (B,S,H,dq) x (B,S,H,dq) x (B,S,H,dv) ->
    (B,S,H,dv); ``block`` divides S.  Splash applies no scale, so q is
    scaled in float32 before it is cast back to its dtype."""
    B, S, H, dq = q.shape
    kernel, _ = _splash_kernel(S, H, block, jax.default_backend() != "tpu")
    q = (q.astype(jnp.float32) * dq ** -0.5).astype(q.dtype)
    heads = functools.partial(jnp.swapaxes, axis1=1, axis2=2)
    return heads(jax.vmap(kernel)(heads(q), heads(k), heads(v)))


def causal_attention(q, k, v):
    """:func:`causal_flash` on the TPU when ``FLASH_BLOCK`` divides S, else
    :func:`blockwise_attention`; counts the tiles of one head's square
    and those the kernel computes."""
    S, H = q.shape[1:3]
    n = -(-S // FLASH_BLOCK)
    spans.count("repro.mla.kv_tiles_total", n * n)
    if jax.default_backend() == "tpu" and S % FLASH_BLOCK == 0:
        spans.count("repro.mla.kv_tiles_computed",
                    _splash_kernel(S, H, FLASH_BLOCK, False)[1])
        return causal_flash(q, k, v)
    return blockwise_attention(q, k, v)


def attention_train(p, x, cfg: ModelConfig, ax: MeshAxes):
    with jax.named_scope("repro.mla"):
        S = x.shape[1]
        q, c, k_pe = _latent(p, x, cfg, ax, jnp.arange(S, dtype=jnp.int32))
        k, v = _keys_values(p, c, k_pe, cfg, ax)
        return _out(p, causal_attention(q, k, v), ax)


def init_cache(cfg: ModelConfig, B: int, ctx: int, dtype):
    """The latent cache of one layer: c_kv and the roped shared key."""
    return dict(c=jnp.zeros((B, ctx, cfg.kv_lora_rank), dtype),
                k_pe=jnp.zeros((B, ctx, cfg.qk_rope_dim), dtype),
                pos=jnp.full((B, ctx), -1, jnp.int32),
                idx=jnp.zeros((), jnp.int32))


def attention_decode(p, x, cache, cfg: ModelConfig, ax: MeshAxes, pos):
    """One token (x: (B, 1, D)) at absolute positions ``pos`` (B,)
    against the latent cache; keys and values are expanded from it."""
    with jax.named_scope("repro.mla"):
        q, c, k_pe = _latent(p, x, cfg, ax, pos[:, None])
        slot = (cache["idx"] % cache["c"].shape[1]).astype(jnp.int32)
        cc = cache["c"].at[:, slot].set(c[:, 0].astype(cache["c"].dtype))
        ck = cache["k_pe"].at[:, slot].set(
            k_pe[:, 0].astype(cache["k_pe"].dtype))
        kpos = cache["pos"].at[:, slot].set(pos)
        k, v = _keys_values(p, cc.astype(x.dtype), ck.astype(x.dtype), cfg,
                            ax)
        mask = (kpos[:, None, :] <= pos[:, None, None]) & \
            (kpos[:, None, :] >= 0)
        o = _softmax_av(q, k, v, mask, q.shape[-1] ** -0.5)
        y = _out(p, o, ax)
    return y, dict(c=cc, k_pe=ck, pos=kpos, idx=cache["idx"] + 1)
