"""Expert-parallel Mixture-of-Experts with capacity-based token dispatch.

The richest policy target in the framework: the token exchange is an
all-to-all over the 'model' axis, routed through the policy dispatcher
(the tuner's algorithm/protocol/channel decisions apply to it exactly as
to NCCL's MoE traffic).

Layout:
  router w: (D, E)                      — replicated (tiny)
  expert w1/w3: (E/tp, D, Fe), w2: (E/tp, Fe, D)   — expert-parallel
  dispatch buffer: (E, C, D) per device -> all_to_all(model) ->
  (E_loc, tp*C, D) per device -> grouped matmul -> reverse

Capacity C = ceil(T·k / E · capacity_factor); overflow tokens are dropped
(standard top-k capacity routing).  Aux losses: load-balance (Switch) +
router z-loss.

A configuration with ``capacity_factor`` 0 takes :func:`dropless_block`
instead: DeepSeek-V3's routing (sigmoid scores, a selection-only bias,
routed scaling, the sequence-wise balance loss) over all ``n_experts``,
computed for the ``experts_here`` held on this chip, with no pair dropped.
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..collectives.dispatch import dispatcher
from ..core.context import AxisKind
from .config import ModelConfig
from .layers import MeshAxes, col_linear, fsdp_gather, row_linear

F32 = jnp.float32


def router_topk(logits, k: int):
    """logits (T, E) -> (gates (T,k), idx (T,k), aux metrics)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, idx = lax.top_k(probs, k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    return gates, idx, probs


def _positions_in_expert(idx, E: int, k: int):
    """Priority-ordered position of each (token, choice) in its expert."""
    T = idx.shape[0]
    pos = []
    counts = jnp.zeros((E,), jnp.int32)
    for c in range(k):
        oh = jax.nn.one_hot(idx[:, c], E, dtype=jnp.int32)        # (T, E)
        pic = jnp.cumsum(oh, axis=0) - 1 + counts[None, :]
        counts = counts + jnp.sum(oh, axis=0)
        pos.append(jnp.sum(pic * oh, axis=-1))                    # (T,)
    return jnp.stack(pos, axis=1)                                 # (T, k)


def moe_block(p, x, cfg: ModelConfig, ax: MeshAxes
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) -> (out, aux_loss)."""
    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    xt_full = x.reshape(B * S, D)

    # Activations are replicated across the model axis (Megatron TP), so
    # each expert-parallel rank routes a disjoint 1/tp slice of the tokens;
    # outputs are all-gathered back afterwards.  Without this split every
    # rank would dispatch identical copies -> tp x duplicate expert compute.
    tp = ax.tp
    token_split = tp > 1 and xt_full.shape[0] % tp == 0 \
        and xt_full.shape[0] >= tp
    if token_split:
        r = lax.axis_index(ax.model)
        Tl = xt_full.shape[0] // tp
        xt = lax.dynamic_slice_in_dim(xt_full, r * Tl, Tl, axis=0)
    else:
        # tiny token counts (decode): all ranks route identical copies;
        # each combines its own copy back — correct, duplicated compute
        xt = xt_full
    T = xt.shape[0]

    logits = xt @ p["router"].astype(xt.dtype)                    # (T, E)
    gates, idx, probs = router_topk(logits, k)

    # --- aux losses ----------------------------------------------------------
    me = jnp.mean(probs, axis=0)                                   # (T,E)->(E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=1), axis=0)
    aux = cfg.router_aux_coef * E * jnp.sum(me * ce)
    zloss = 1e-3 * jnp.mean(
        jnp.square(jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)))
    aux = aux + zloss

    # --- capacity + dispatch ---------------------------------------------------
    C = max(1, math.ceil(T * k / E * cfg.capacity_factor))
    pos = _positions_in_expert(idx, E, k)                          # (T, k)
    keep = (pos < C)
    e_flat = idx.reshape(-1)                                       # (T*k,)
    p_flat = jnp.clip(pos.reshape(-1), 0, C - 1)
    w_flat = (gates * keep).reshape(-1)

    buf = jnp.zeros((E, C, D), xt.dtype)
    src = jnp.repeat(xt, k, axis=0) * keep.reshape(-1, 1).astype(xt.dtype)
    buf = buf.at[e_flat, p_flat].add(src)

    # --- all_to_all over the model axis (expert parallel) ----------------------
    if tp > 1:
        e_loc = E // tp
        buf = buf.reshape(tp, e_loc, C, D)
        buf = dispatcher().all_to_all(buf, ax.model,
                                      axis_kind=AxisKind.EXPERT)
        # now buf[s, e, c, :] = tokens from source device s for local expert e
        buf = buf.transpose(1, 0, 2, 3).reshape(e_loc, tp * C, D)
    else:
        e_loc = E

    # --- grouped expert FFN (Pallas grouped-matmul target) ---------------------
    w1 = fsdp_gather(p["w1"], ax, 1).astype(buf.dtype)  # (e_loc, D, Fe)
    w3 = fsdp_gather(p["w3"], ax, 1).astype(buf.dtype)
    w2 = fsdp_gather(p["w2"], ax, 2).astype(buf.dtype)  # (e_loc, Fe, D)
    h = jnp.einsum("ecd,edf->ecf", buf, w1)
    u = jnp.einsum("ecd,edf->ecf", buf, w3)
    h = jax.nn.silu(h.astype(jnp.float32)).astype(buf.dtype) * u
    out = jnp.einsum("ecf,efd->ecd", h, w2)

    # --- reverse all_to_all -----------------------------------------------------
    if tp > 1:
        out = out.reshape(e_loc, tp, C, D).transpose(1, 0, 2, 3)
        out = dispatcher().all_to_all(out, ax.model,
                                      axis_kind=AxisKind.EXPERT)
        out = out.reshape(E, C, D)

    # --- combine -----------------------------------------------------------------
    gathered = out[e_flat, p_flat]                                  # (T*k, D)
    y = jnp.sum((gathered * w_flat[:, None].astype(gathered.dtype)
                 ).reshape(T, k, D), axis=1)

    # restore replication across the model axis
    if token_split:
        y = dispatcher().all_gather(y, ax.model, axis_kind=AxisKind.MODEL)

    # --- shared experts (llama4): dense TP path over the FULL token set --------
    if cfg.n_shared_experts:
        y = y + _shared(p, xt_full, ax)

    return y.reshape(B, S, D), aux


def _shared(p, xt, ax: MeshAxes):
    """The shared experts, one SwiGLU over every token."""
    hs = col_linear(xt, p["shared_w1"], ax, fsdp_dim=0)
    us = col_linear(xt, p["shared_w3"], ax, fsdp_dim=0)
    hs = jax.nn.silu(hs.astype(F32)).astype(xt.dtype) * us
    return row_linear(hs, p["shared_w2"], ax, fsdp_dim=1)


@jax.custom_vjp
def _permute(x, idx, inv):
    """``x[idx]`` for a permutation ``idx`` whose inverse is ``inv``.  Its
    transpose is the gather by ``inv``, where AD would scatter-add."""
    return x[idx]


def _permute_fwd(x, idx, inv):
    return x[idx], (idx, inv)


def _permute_bwd(res, g):
    return g[res[1]], None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


def _grouped(x, w, sizes, rows):
    """``lax.ragged_dot`` over the groups ``sizes``, with the rows past the
    groups' end (``rows`` False) zero, in the value and in every gradient:
    the TPU kernel leaves them unwritten in both directions."""
    return jnp.where(rows, lax.ragged_dot(x, w, sizes), 0)


def route_scores(p, xt, cfg: ModelConfig):
    """DeepSeek-V3 routing of tokens ``xt`` (T, D) over all ``n_experts``:
    float32 sigmoid scores ``s``, the top-k chosen on ``s + e_bias`` (the
    bias selects and never weights), and the gates ``s_chosen /
    sum(s_chosen) * routed_scale``.  Returns (s, idx, gates)."""
    s = jax.nn.sigmoid(jnp.einsum("td,de->te", xt.astype(F32), p["router"],
                                  precision=lax.Precision.HIGHEST))
    _, idx = lax.top_k(s + lax.stop_gradient(p["e_bias"]), cfg.top_k)
    sel = jnp.take_along_axis(s, idx, axis=-1)
    gates = sel / jnp.sum(sel, -1, keepdims=True) * cfg.routed_scale
    return s, idx, gates


def dropless_block(p, x, cfg: ModelConfig, ax: MeshAxes):
    """x: (B, S, D) -> (out, aux_loss, stats).

    Routes every token over all ``n_experts`` and computes the part of the
    layer that the experts held here (ids ``0 .. n_held - 1``) give: the
    (token, choice) pairs routed to them are sorted by expert into a
    buffer of the worst case, T x top_k rows, and the three expert
    matmuls are one ragged grouped product over the held groups.  No
    pair is dropped however uneven the routing.  The absent experts' part
    is left out; the shared experts run over every token.

    aux is the sequence-wise balance loss (DeepSeek-V3 §2.1.2), over all
    experts: ``alpha * sum_i f_i P_i`` per sequence, ``f_i`` the share of
    the sequence's choices that went to expert i times E / k, ``P_i`` the
    mean of i's score normalised over the experts; the mean over the
    batch.  stats: ``load`` (E,) choices per expert, which the step feeds
    to :func:`update_expert_bias`, the counters ``pairs_here`` and
    ``max_expert_pairs`` (the largest held group), and ``choices`` (T, k),
    the experts each token chose."""
    B, S, D = x.shape
    E, k, Eh = cfg.n_experts, cfg.top_k, cfg.n_held
    T = B * S
    xt = x.reshape(T, D)
    with jax.named_scope("repro.moe.route"):
        s, idx, gates = route_scores(p, xt, cfg)
        chosen = jnp.sum(jax.nn.one_hot(idx, E, dtype=F32), axis=1)  # (T, E)
        f = jnp.sum(chosen.reshape(B, S, E), axis=1) * (E / (k * S))
        P = jnp.mean((s / jnp.sum(s, -1, keepdims=True)).reshape(B, S, E),
                     axis=1)
        aux = cfg.seq_aux_coef * jnp.mean(jnp.sum(f * P, axis=-1))

        e = idx.reshape(-1)                                       # (T*k,)
        group = jnp.where(e < Eh, e, Eh)          # Eh: not held, sorts last
        order = jnp.argsort(group, stable=True)
        inv = jnp.zeros_like(order).at[order].set(
            jnp.arange(T * k, dtype=order.dtype), unique_indices=True)
        sizes = jnp.sum(jax.nn.one_hot(group, Eh, dtype=jnp.int32), axis=0)
        held = (group[order] < Eh)[:, None]       # rows the groups cover
        xs = _permute(jnp.repeat(xt, k, axis=0), order, inv)     # (T*k, D)
        xs = jnp.where(held, xs, 0)
    with jax.named_scope("repro.moe.experts"):
        w1 = fsdp_gather(p["w1"], ax, 1).astype(x.dtype)          # (Eh, D, Fe)
        w3 = fsdp_gather(p["w3"], ax, 1).astype(x.dtype)
        w2 = fsdp_gather(p["w2"], ax, 2).astype(x.dtype)          # (Eh, Fe, D)
        h = _grouped(xs, w1, sizes, held)
        u = _grouped(xs, w3, sizes, held)
        a = jax.nn.silu(h.astype(F32)).astype(x.dtype) * u
        o = _grouped(a, w2, sizes, held)
    with jax.named_scope("repro.moe.route"):
        w = gates.reshape(-1)[order][:, None].astype(o.dtype)
        ow = _permute(o * w, inv, order)                  # pair order again
        y = jnp.sum(ow.reshape(T, k, D).astype(F32), axis=1).astype(x.dtype)
    if cfg.n_shared_experts:
        with jax.named_scope("repro.moe.shared"):
            y = y + _shared(p, xt, ax)
    here = jnp.sum(e < Eh).astype(jnp.int32)
    stats = {"load": jnp.sum(chosen, axis=0),
             "pairs_here": here,
             "max_expert_pairs": jnp.max(sizes),
             "choices": idx}
    return y.reshape(B, S, D), aux, stats


def update_expert_bias(new_params, old_params, loads, cfg: ModelConfig):
    """DeepSeek-V3's bias update, after the optimizer: each MoE layer's
    ``e_bias`` becomes its value before the step plus ``bias_rate *
    sign(mean load - load_i)``, over the loads of all experts as routed.
    ``loads`` mirrors the params' ``blocks`` and ``tail`` lists (None for
    a block with no MoE); the optimizer never moves the bias."""
    out = dict(new_params)
    for part in ("blocks", "tail"):
        blocks = list(new_params[part])
        for j, load in enumerate(loads[part]):
            if load is None:
                continue
            old = old_params[part][j]["moe"]["e_bias"]
            step = cfg.bias_rate * jnp.sign(
                jnp.mean(load, axis=-1, keepdims=True) - load)
            moe = {**blocks[j]["moe"], "e_bias": old + step}
            blocks[j] = {**blocks[j], "moe": moe}
        out[part] = blocks
    return out
