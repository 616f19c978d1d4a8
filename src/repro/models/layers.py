"""Sharded layer primitives (explicit Megatron-style SPMD).

Everything here executes inside shard_map over the production mesh.  The
'model' axis carries tensor parallelism; the 'data' axis carries batch +
FSDP parameter sharding; the optional 'pod' axis carries cross-pod data
parallelism.  Every collective goes through the policy dispatcher.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
from jax import lax

from ..collectives.dispatch import dispatcher
from ..core.context import AxisKind


@dataclasses.dataclass(frozen=True)
class MeshAxes:
    """Which mesh axes exist for this step and how params are laid out."""
    data: str = "data"
    model: str = "model"
    pod: Optional[str] = None
    fsdp: bool = True          # params sharded over `data` (gathered on use)
    gather_bf16: bool = False  # FSDP gathers on the bf16 wire (halves bytes)
    tp: int = 1                # static size of the model axis
    dp: int = 1                # static size of the data axis (per pod)
    n_pods: int = 1

    @property
    def world(self) -> int:
        return self.tp * self.dp * self.n_pods


# ---------------------------------------------------------------------------
# collectives (policy-dispatched)
# ---------------------------------------------------------------------------

def tp_psum(x, ax: MeshAxes):
    """Row-parallel reduction over the model axis.  Tagged so the
    save_psum remat policy keeps the result: the rematerialized forward
    then re-runs only local compute — zero collectives in recompute."""
    if ax.tp == 1:
        return x
    out = dispatcher().all_reduce(x, ax.model, axis_kind=AxisKind.MODEL)
    return jax.ad_checkpoint.checkpoint_name(out, "tp_psum")


import functools


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _ag_bf16_wire(w, axis_name: str):
    """all-gather with a guaranteed-bf16 wire.

    XLA's float-normalization pass rewrites bf16 collectives to f32 on
    backends without native bf16 (this CPU container), hiding the savings
    from the dry-run HLO.  Bitcasting to u16 defeats the pass — on TPU a
    plain bf16 all-gather lowers identically."""
    return _ag_bf16_fwd(w, axis_name)[0]


def _ag_bf16_fwd(w, axis_name):
    wb = w.astype(jnp.bfloat16)
    wu = lax.bitcast_convert_type(wb, jnp.uint16)
    gu = dispatcher().all_gather(wu, axis_name, axis_kind=AxisKind.DATA)
    g = lax.bitcast_convert_type(gu, jnp.bfloat16)
    return g, ()


def _ag_bf16_bwd(axis_name, res, ct):
    # reduce-scatter of the cotangent (bf16 accumulate; f32-normalized on
    # CPU backends — halves too on TPU's native bf16 reduce-scatter)
    g = dispatcher().reduce_scatter(ct.astype(jnp.bfloat16), axis_name,
                                    axis_kind=AxisKind.DATA)
    return (g.astype(jnp.float32),)


_ag_bf16_wire.defvjp(_ag_bf16_fwd, _ag_bf16_bwd)


def fsdp_gather(w, ax: MeshAxes, dim: int):
    """Gather an FSDP-sharded parameter along `dim` over the data axis.

    AD transposes lax.all_gather to psum_scatter, so gradients are
    automatically reduce-scattered back to the shards (ZeRO-3).  With
    ``ax.gather_bf16`` the gather rides a bf16 wire (half the bytes)."""
    if not ax.fsdp or ax.dp == 1:
        return w
    if dim != 0:
        w = jnp.moveaxis(w, dim, 0)
    if ax.gather_bf16 and w.dtype == jnp.float32:
        w = _ag_bf16_wire(w, ax.data)
    else:
        w = dispatcher().all_gather(w, ax.data, axis_kind=AxisKind.DATA)
    if dim != 0:
        w = jnp.moveaxis(w, 0, dim)
    return w


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rms_norm(x, scale, eps: float = 1e-6):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * lax.rsqrt(var + eps)).astype(dt) * scale.astype(dt)


def layer_norm(x, scale, bias, eps: float = 1e-5):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * lax.rsqrt(var + eps)
    return y.astype(dt) * scale.astype(dt) + bias.astype(dt)


def apply_norm(cfg, x, p):
    """The configuration's norm (``cfg.norm``) with its parameters ``p``."""
    if cfg.norm == "layernorm":
        return layer_norm(x, p["scale"], p["bias"])
    return rms_norm(x, p["scale"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float, positions):
    """positions: (...,) int32 -> (..., head_dim//2) angles."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                      dtype=jnp.float32) / head_dim))
    return positions[..., None].astype(jnp.float32) * inv


def apply_rope(x, angles):
    """x: (B, S, H, head_dim); angles: (S, hd//2) or (B, S, hd//2)."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    if angles.ndim == 2:          # (S, hd//2)
        angles = angles[None]     # (1, S, hd//2)
    angles = angles[:, :, None, :]  # (B|1, S, 1, hd//2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# linear helpers (TP-aware)
# ---------------------------------------------------------------------------

def col_linear(x, w, ax: MeshAxes, *, bias=None, fsdp_dim: int = 0):
    """Column-parallel: w per-device (D, out/tp); x replicated in D."""
    w = fsdp_gather(w, ax, fsdp_dim)
    y = jnp.einsum("...d,df->...f", x, w.astype(x.dtype))
    if bias is not None:
        y = y + bias.astype(x.dtype)
    return y


def row_linear(x, w, ax: MeshAxes, *, bias=None, fsdp_dim: int = 1,
               reduce: bool = True):
    """Row-parallel: w per-device (in/tp, D); psum over model after."""
    w = fsdp_gather(w, ax, fsdp_dim)
    y = jnp.einsum("...f,fd->...d", x, w.astype(x.dtype))
    if reduce:
        y = tp_psum(y, ax)
    if bias is not None:
        y = y + bias.astype(x.dtype)
    return y


# ---------------------------------------------------------------------------
# vocab-parallel embedding + distributed cross-entropy
# ---------------------------------------------------------------------------

def vp_embed(ids, emb, ax: MeshAxes, vocab_padded: int):
    """emb per-device (Vp/tp, D) -> (..., D) via masked lookup + psum."""
    emb = fsdp_gather(emb, ax, 1)
    vloc = vocab_padded // ax.tp if ax.tp > 1 else vocab_padded
    if ax.tp > 1:
        r = lax.axis_index(ax.model)
        lo = r * vloc
        local = jnp.clip(ids - lo, 0, vloc - 1)
        hit = (ids >= lo) & (ids < lo + vloc)
        out = emb[local] * hit[..., None].astype(emb.dtype)
        return tp_psum(out, ax)
    return emb[ids]


def vp_logits_loss(x, emb_or_head, labels, ax: MeshAxes, vocab: int,
                   vocab_padded: int, *, fsdp_dim: int = 1):
    """Distributed cross-entropy over a vocab-parallel head.

    Never materializes the full (T, V) logits on one device: computes the
    softmax normalizer with psum-max / psum-sum over the model axis.
    x: (..., D); head per-device (Vp/tp, D); labels (...,) int32.
    Returns mean loss (scalar, f32).
    """
    head = fsdp_gather(emb_or_head, ax, fsdp_dim)
    logits = jnp.einsum("...d,vd->...v", x, head.astype(x.dtype)
                        ).astype(jnp.float32)
    vloc = logits.shape[-1]
    if ax.tp > 1:
        r = lax.axis_index(ax.model)
        lo = r * vloc
    else:
        lo = 0
    # mask padded vocab entries
    col = lo + jnp.arange(vloc)
    logits = jnp.where(col[None, :] < vocab, logits, -1e30)

    # stabilizer only — gradient-free (pmax has no JVP rule, and none is
    # needed: subtracting any constant leaves the softmax loss unchanged)
    m_loc = jnp.max(lax.stop_gradient(logits), axis=-1)
    m = lax.stop_gradient(tp_psum_max(m_loc, ax))
    se = jnp.sum(jnp.exp(logits - m[..., None]), axis=-1)
    se = tp_psum(se, ax)
    lse = jnp.log(se) + m

    local_lab = jnp.clip(labels - lo, 0, vloc - 1)
    hit = (labels >= lo) & (labels < lo + vloc)
    lab_logit = jnp.take_along_axis(logits, local_lab[..., None],
                                    axis=-1)[..., 0]
    lab_logit = tp_psum(lab_logit * hit.astype(jnp.float32), ax)
    return jnp.mean(lse - lab_logit)


def tp_psum_max(x, ax: MeshAxes):
    if ax.tp == 1:
        return x
    return lax.pmax(x, ax.model)


def vp_logits(x, head, ax: MeshAxes, vocab: int):
    """Full logits (gathered over model) — serving-time only, small T."""
    head = head.astype(x.dtype)
    logits = jnp.einsum("...d,vd->...v", x, head)
    if ax.tp > 1:
        logits = dispatcher().all_gather(
            jnp.moveaxis(logits, -1, 0), ax.model,
            axis_kind=AxisKind.MODEL)
        logits = jnp.moveaxis(logits, 0, -1)
    return logits[..., :vocab]
