"""Model assembly: init, forward, loss, prefill, decode — all families.

Layer stacks are scanned over a *period* of block kinds (e.g. RG-LRU's
(rec, rec, attn)); parameters are stacked (n_periods, ...) per position-in-
period so lax.scan keeps the HLO small for 48-layer configs while mixed
block patterns remain expressible.  Remainder layers (when n_layers is not
a multiple of the period) are unrolled.

Parameters are GLOBAL logical arrays; ``init_params`` also returns the
matching PartitionSpec tree consumed by shard_map/jit in the launcher.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from . import attention as att
from . import mla
from . import recurrent as rec
from .config import ModelConfig
from .layers import (MeshAxes, apply_norm, vp_embed, vp_logits,
                     vp_logits_loss)
from .mlp import mlp_block
from .moe import dropless_block, moe_block


# ===========================================================================
# init
# ===========================================================================

def _norm_params(key, cfg, n, with_bias=None):
    wb = cfg.norm == "layernorm" if with_bias is None else with_bias
    p = {"scale": jnp.ones((n, cfg.d_model), jnp.float32)}
    if wb:
        p["bias"] = jnp.zeros((n, cfg.d_model), jnp.float32)
    return p, {"scale": P(None, None), **({"bias": P(None, None)} if wb else {})}


def _dense(key, shape, scale=None):
    scale = scale or (1.0 / math.sqrt(shape[-2]))
    return jax.random.normal(key, shape, jnp.float32) * scale


def _attn_params(key, cfg: ModelConfig, ax: MeshAxes, n: int,
                 *, cross: bool = False):
    hp = cfg.padded_heads(ax.tp)
    hd = cfg.hd
    kvw = cfg.n_kv_heads * hd
    ks = jax.random.split(key, 8)
    qdim = hp * hd
    p = {
        "wq": _dense(ks[0], (n, cfg.d_model, qdim)),
        "wk": _dense(ks[1], (n, cfg.d_model, kvw)),
        "wv": _dense(ks[2], (n, cfg.d_model, kvw)),
        "wo": _dense(ks[3], (n, qdim, cfg.d_model)),
    }
    kv_spec = "model" if (ax.tp > 1 and cfg.n_kv_heads % ax.tp == 0) else None
    s = {
        "wq": P(None, "data", "model"),
        "wk": P(None, "data", kv_spec),
        "wv": P(None, "data", kv_spec),
        "wo": P(None, "model", "data"),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((n, qdim), jnp.float32)
        p["bk"] = jnp.zeros((n, kvw), jnp.float32)
        p["bv"] = jnp.zeros((n, kvw), jnp.float32)
        s["bq"] = P(None, "model")
        s["bk"] = P(None, kv_spec)
        s["bv"] = P(None, kv_spec)
    if cfg.qk_norm and not cross:
        p["q_norm"] = jnp.ones((n, hd), jnp.float32)
        p["k_norm"] = jnp.ones((n, hd), jnp.float32)
        s["q_norm"] = P(None, None)
        s["k_norm"] = P(None, None)
    return p, s


def _mlp_params(key, cfg: ModelConfig, n: int, *, d_ff: Optional[int] = None):
    F = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp == "swiglu":
        p = {"w_gate": _dense(ks[0], (n, cfg.d_model, F)),
             "w_up": _dense(ks[1], (n, cfg.d_model, F)),
             "w_down": _dense(ks[2], (n, F, cfg.d_model))}
        s = {"w_gate": P(None, "data", "model"),
             "w_up": P(None, "data", "model"),
             "w_down": P(None, "model", "data")}
    else:
        p = {"w_up": _dense(ks[0], (n, cfg.d_model, F)),
             "b_up": jnp.zeros((n, F), jnp.float32),
             "w_down": _dense(ks[2], (n, F, cfg.d_model)),
             "b_down": jnp.zeros((n, cfg.d_model), jnp.float32)}
        s = {"w_up": P(None, "data", "model"), "b_up": P(None, "model"),
             "w_down": P(None, "model", "data"), "b_down": P(None, None)}
    return p, s


def _moe_params(key, cfg: ModelConfig, n: int):
    """The router over all ``n_experts`` and the weights of the experts
    held here (all of them unless ``experts_here`` says otherwise)."""
    ks = jax.random.split(key, 7)
    E, Fe, D, Eh = cfg.n_experts, cfg.moe_d_ff, cfg.d_model, cfg.n_held
    p = {"router": _dense(ks[0], (n, D, E), scale=0.02),
         "w1": _dense(ks[1], (n, Eh, D, Fe)),
         "w3": _dense(ks[2], (n, Eh, D, Fe)),
         "w2": _dense(ks[3], (n, Eh, Fe, D))}
    s = {"router": P(None, None, None),
         "w1": P(None, "model", "data", None),
         "w3": P(None, "model", "data", None),
         "w2": P(None, "model", None, "data")}
    if cfg.dropless:      # the selection bias: updated by the step, not AdamW
        p["e_bias"] = jnp.zeros((n, E), jnp.float32)
        s["e_bias"] = P(None, None)
    if cfg.n_shared_experts:
        Fs = Fe * cfg.n_shared_experts
        p["shared_w1"] = _dense(ks[4], (n, D, Fs))
        p["shared_w3"] = _dense(ks[5], (n, D, Fs))
        p["shared_w2"] = _dense(ks[6], (n, Fs, D))
        s["shared_w1"] = P(None, "data", "model")
        s["shared_w3"] = P(None, "data", "model")
        s["shared_w2"] = P(None, "model", "data")
    return p, s


def _mlstm_params(key, cfg: ModelConfig, n: int):
    D, H = cfg.d_model, cfg.n_heads
    inner = 2 * D
    ks = jax.random.split(key, 7)
    p = {"w_q": _dense(ks[0], (n, D, inner)),
         "w_k": _dense(ks[1], (n, D, inner)),
         "w_v": _dense(ks[2], (n, D, inner)),
         "w_og": _dense(ks[3], (n, D, inner)),
         "w_down": _dense(ks[4], (n, inner, D)),
         "w_i": _dense(ks[5], (n, D, H), scale=0.02),
         "w_f": _dense(ks[6], (n, D, H), scale=0.02),
         "b_i": jnp.zeros((n, H), jnp.float32),
         "b_f": jnp.full((n, H), 3.0, jnp.float32)}
    s = {"w_q": P(None, "data", None), "w_k": P(None, "data", None),
         "w_v": P(None, "data", "model"), "w_og": P(None, "data", "model"),
         "w_down": P(None, "model", "data"),
         "w_i": P(None, "data", None), "w_f": P(None, "data", None),
         "b_i": P(None, None), "b_f": P(None, None)}
    return p, s


def _slstm_params(key, cfg: ModelConfig, n: int):
    D = cfg.d_model
    ks = jax.random.split(key, 5)
    p = {f"w_{g}": _dense(ks[i], (n, D, D))
         for i, g in enumerate(["z", "i", "f", "o"])}
    s = {f"w_{g}": P(None, "data", "model") for g in ["z", "i", "f", "o"]}
    for g in ["z", "i", "f", "o"]:
        p[f"r_{g}"] = jnp.zeros((n, D), jnp.float32)
        s[f"r_{g}"] = P(None, "model")
    p["w_down"] = _dense(ks[4], (n, D, D))
    s["w_down"] = P(None, "model", "data")
    return p, s


def _rglru_params(key, cfg: ModelConfig, n: int):
    D = cfg.d_model
    W = cfg.rglru_width or D
    K = cfg.conv1d_width
    ks = jax.random.split(key, 6)
    p = {"w_in": _dense(ks[0], (n, D, 2 * W)),
         "conv_w": _dense(ks[1], (n, K, W), scale=0.3),
         "conv_b": jnp.zeros((n, W), jnp.float32),
         "w_a": _dense(ks[2], (n, D, W), scale=0.02),
         "w_x": _dense(ks[3], (n, D, W), scale=0.02),
         "lam": jax.random.uniform(ks[4], (n, W), jnp.float32, 0.3, 0.8),
         "w_out": _dense(ks[5], (n, W, D))}
    s = {"w_in": P(None, "data", "model"), "conv_w": P(None, None, "model"),
         "conv_b": P(None, "model"), "w_a": P(None, "data", "model"),
         "w_x": P(None, "data", "model"), "lam": P(None, "model"),
         "w_out": P(None, "model", "data")}
    return p, s


def _block_params(key, kind: str, cfg: ModelConfig, ax: MeshAxes, n: int,
                  *, with_cross: bool = False, dense: bool = False):
    """``dense``: one of the leading dense layers of a MoE stack."""
    ks = jax.random.split(key, 6)
    p, s = {}, {}
    p["ln1"], s["ln1"] = _norm_params(ks[0], cfg, n)
    if kind == "attn":
        if cfg.is_mla:
            p["attn"], s["attn"] = mla.init_params(
                jax.random.split(ks[1], 4), cfg, n, _dense)
        else:
            p["attn"], s["attn"] = _attn_params(ks[1], cfg, ax, n)
        p["ln2"], s["ln2"] = _norm_params(ks[2], cfg, n)
        if cfg.is_moe and not dense:
            p["moe"], s["moe"] = _moe_params(ks[3], cfg, n)
        elif cfg.d_ff:
            p["mlp"], s["mlp"] = _mlp_params(ks[3], cfg, n)
        if with_cross:
            p["xattn"], s["xattn"] = _attn_params(ks[4], cfg, ax, n,
                                                  cross=True)
            p["ln_x"], s["ln_x"] = _norm_params(ks[5], cfg, n)
    elif kind == "mlstm":
        p["mlstm"], s["mlstm"] = _mlstm_params(ks[1], cfg, n)
    elif kind == "slstm":
        p["slstm"], s["slstm"] = _slstm_params(ks[1], cfg, n)
    elif kind == "rglru":
        p["rglru"], s["rglru"] = _rglru_params(ks[1], cfg, n)
        p["ln2"], s["ln2"] = _norm_params(ks[2], cfg, n)
        if cfg.d_ff:
            p["mlp"], s["mlp"] = _mlp_params(ks[3], cfg, n)
    else:
        raise ValueError(kind)
    return p, s


def _period(cfg: ModelConfig) -> Tuple[Tuple[str, ...], int, int]:
    """(kinds, period length, remainder) of the layers after the
    ``first_k_dense`` leading ones."""
    kinds = cfg.block_kinds()
    n = cfg.n_layers - cfg.first_k_dense
    if cfg.family == "ssm" and cfg.slstm_every:
        plen = cfg.slstm_every
    elif cfg.family == "hybrid" and cfg.rglru_pattern:
        plen = len(cfg.rglru_pattern)
    else:
        plen = 1
    if cfg.nope_every:
        plen = plen * cfg.nope_every // math.gcd(plen, cfg.nope_every)
    plen = min(plen, n)
    rem = n - n // plen * plen
    return kinds, plen, rem


def _n_full(cfg: ModelConfig, plen: int) -> int:
    return (cfg.n_layers - cfg.first_k_dense) // plen


def _layer_params(params, cfg: ModelConfig, li: int):
    """Layer ``li``'s parameters, unstacked."""
    kinds, plen, rem = _period(cfg)
    k, n_full = cfg.first_k_dense, _n_full(cfg, plen)
    if li < k:
        return jax.tree.map(lambda a: a[0], params["lead"][li])
    if li < k + n_full * plen:
        grp, pos_in = divmod(li - k, plen)
        return jax.tree.map(lambda a: a[grp], params["blocks"][pos_in])
    return jax.tree.map(lambda a: a[0],
                        params["tail"][li - k - n_full * plen])


def init_params(key, cfg: ModelConfig, ax: MeshAxes
                ) -> Tuple[Dict, Dict]:
    """Returns (params, partition_specs) — global logical arrays."""
    kinds, plen, rem = _period(cfg)
    n_full, k = _n_full(cfg, plen), cfg.first_k_dense
    keys = jax.random.split(key, plen + rem + 8)

    params: Dict[str, Any] = {}
    specs: Dict[str, Any] = {}

    vp = cfg.padded_vocab(ax.tp)
    params["embed"] = jax.random.normal(keys[-1], (vp, cfg.d_model),
                                        jnp.float32) * 0.02
    specs["embed"] = P("model", "data")
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(
            keys[-2], (vp, cfg.d_model), jnp.float32) * 0.02
        specs["lm_head"] = P("model", "data")
    params["final_norm"], specs["final_norm"] = _norm_params(
        keys[-3], cfg, 1)

    with_cross = cfg.family == "audio"
    if k:
        params["lead"], specs["lead"] = [], []
        for j, kj in enumerate(jax.random.split(keys[-6], k)):
            p, s = _block_params(kj, kinds[j], cfg, ax, 1, dense=True)
            params["lead"].append(p)
            specs["lead"].append(s)
    params["blocks"], specs["blocks"] = [], []
    for j in range(plen):
        p, s = _block_params(keys[j], kinds[k + j], cfg, ax, n_full,
                             with_cross=with_cross)
        params["blocks"].append(p)
        specs["blocks"].append(s)
    params["tail"], specs["tail"] = [], []
    for j in range(rem):
        p, s = _block_params(keys[plen + j], kinds[k + n_full * plen + j],
                             cfg, ax, 1, with_cross=with_cross)
        params["tail"].append(p)
        specs["tail"].append(s)

    if cfg.family == "audio":
        enc_cfg = dataclasses.replace(cfg, qk_norm=False, qkv_bias=False)
        pe, se = [], []
        k_enc = jax.random.split(keys[-4], 2)
        p, s = _block_params(k_enc[0], "attn",
                             dataclasses.replace(enc_cfg, n_experts=0),
                             ax, cfg.n_enc_layers)
        params["enc_blocks"], specs["enc_blocks"] = p, s
        params["enc_norm"], specs["enc_norm"] = _norm_params(
            k_enc[1], cfg, 1)
        params["enc_pos"] = jnp.zeros((cfg.n_audio_frames, cfg.d_model),
                                      jnp.float32)
        specs["enc_pos"] = P(None, None)

    if cfg.family == "vlm":
        params["proj"] = jax.random.normal(
            keys[-5], (cfg.d_model, cfg.d_model), jnp.float32) * 0.02
        specs["proj"] = P(None, None)

    if not ax.fsdp:
        specs = jax.tree.map(
            lambda sp: P(*(None if a == "data" else a for a in sp)),
            specs, is_leaf=lambda v: isinstance(v, P))
    return params, specs


# ===========================================================================
# block application
# ===========================================================================

def _apply_block(p, kind: str, x, cfg: ModelConfig, ax: MeshAxes, *,
                 use_rope: bool = True, causal: bool = True,
                 enc_kv=None, aux_acc=None):
    """Returns (x, aux_acc, stats): stats are a dropless MoE layer's
    (:func:`moe.dropless_block`), else None."""
    h = apply_norm(cfg, x, p["ln1"])
    stats = None
    if kind == "attn":
        if cfg.is_mla:
            y = mla.attention_train(p["attn"], h, cfg, ax)
        else:
            y = att.attention_train(p["attn"], h, cfg, ax,
                                    use_rope=use_rope, causal=causal)
        x = x + y
        if enc_kv is not None:
            hx = apply_norm(cfg, x, p["ln_x"])
            x = x + att.cross_attention(p["xattn"], hx, enc_kv, cfg, ax)
        h2 = apply_norm(cfg, x, p["ln2"])
        if "moe" in p:
            if cfg.dropless:
                y2, aux, stats = dropless_block(p["moe"], h2, cfg, ax)
            else:
                y2, aux = moe_block(p["moe"], h2, cfg, ax)
            if aux_acc is not None:
                aux_acc += aux
        elif "mlp" in p:
            y2 = mlp_block(p["mlp"], h2, cfg, ax)
        else:
            y2 = 0.0
        x = x + y2
    elif kind == "mlstm":
        x = x + rec.mlstm_block(p["mlstm"], h, cfg, ax)
    elif kind == "slstm":
        x = x + rec.slstm_block(p["slstm"], h, cfg, ax)
    elif kind == "rglru":
        x = x + rec.rglru_block(p["rglru"], h, cfg, ax)
        h2 = apply_norm(cfg, x, p["ln2"])
        if cfg.d_ff:
            x = x + mlp_block(p["mlp"], h2, cfg, ax)
    return x, aux_acc, stats


def _use_rope(cfg: ModelConfig, layer_idx: int) -> bool:
    """llama4 iRoPE: every nope_every-th layer skips rope; whisper uses
    learned/sinusoidal absolute positions, never rope."""
    if cfg.family == "audio":
        return False
    if cfg.nope_every and (layer_idx + 1) % cfg.nope_every == 0:
        return False
    return True


def _stack_forward(params, x, cfg: ModelConfig, ax: MeshAxes, *,
                   causal: bool = True, enc_kv=None):
    """The leading dense layers, then the scanned period-grouped stack,
    then the remainder.  Returns (x, aux_loss, stats): stats per block of
    ``params["blocks"]`` and ``params["tail"]`` (stacked over the scan),
    None where a block has no dropless MoE."""
    kinds, plen, rem = _period(cfg)
    k, n_full = cfg.first_k_dense, _n_full(cfg, plen)
    aux = jnp.zeros((), jnp.float32)
    for j, p in enumerate(params.get("lead", [])):
        pj = jax.tree.map(lambda a: a[0], p)
        x, aux, _ = _apply_block(pj, kinds[j], x, cfg, ax,
                                 use_rope=_use_rope(cfg, j), causal=causal,
                                 enc_kv=enc_kv, aux_acc=aux)
    stats = {"blocks": [None] * plen, "tail": []}

    if n_full > 0:
        def period_step(carry, xs):
            x, aux = carry
            st = []
            for j in range(plen):
                x, aux, sj = _apply_block(xs[j], kinds[k + j], x, cfg, ax,
                                          use_rope=_use_rope(cfg, k + j),
                                          causal=causal, enc_kv=enc_kv,
                                          aux_acc=aux)
                st.append(sj)
            return (x, aux), st

        if cfg.remat:
            if cfg.remat_policy == "save_psum":
                pol = jax.checkpoint_policies.save_only_these_names(
                    "tp_psum")
                period_step = jax.checkpoint(period_step,
                                             prevent_cse=False, policy=pol)
            else:
                period_step = jax.checkpoint(period_step, prevent_cse=False)
        xs = tuple(params["blocks"])
        (x, aux), stats["blocks"] = lax.scan(period_step, (x, aux), xs)
    for j, p in enumerate(params["tail"]):
        li = k + n_full * plen + j
        pj = jax.tree.map(lambda a: a[0], p)
        x, aux, sj = _apply_block(pj, kinds[li], x, cfg, ax,
                                  use_rope=_use_rope(cfg, li),
                                  causal=causal, enc_kv=enc_kv, aux_acc=aux)
        stats["tail"].append(None if sj is None else
                             jax.tree.map(lambda a: a[None], sj))
    return x, aux, stats


def _encode_audio(params, frames, cfg: ModelConfig, ax: MeshAxes):
    """frames: (B, T, D) stub conv-frontend output."""
    x = frames + params["enc_pos"][None, :frames.shape[1]].astype(frames.dtype)
    enc_cfg = dataclasses.replace(cfg, n_experts=0, qk_norm=False,
                                  qkv_bias=False, attention="full")

    def enc_step(x, p):
        x, _, _ = _apply_block(p, "attn", x, enc_cfg, ax, use_rope=False,
                               causal=False)
        return x, None

    x, _ = lax.scan(enc_step, x, params["enc_blocks"])
    return apply_norm(cfg, x, jax.tree.map(lambda a: a[0],
                                           params["enc_norm"]))


def embed_tokens(params, tokens, cfg: ModelConfig, ax: MeshAxes, dtype):
    vp = cfg.padded_vocab(ax.tp)
    x = vp_embed(tokens, params["embed"], ax, vp).astype(dtype)
    return x * (cfg.d_model ** 0.5) if cfg.family == "hybrid" else x


def forward_hidden(params, batch, cfg: ModelConfig, ax: MeshAxes):
    """batch: dict with 'tokens' (B,S) [+ 'frames' | 'patches'].
    Returns (hidden (B,S',D), aux)."""
    h, aux, _ = _forward_hidden(params, batch, cfg, ax)
    return h, aux


def _forward_hidden(params, batch, cfg: ModelConfig, ax: MeshAxes):
    dtype = cfg.jdtype
    tokens = batch["tokens"]
    x = embed_tokens(params, tokens, cfg, ax, dtype)

    enc_kv = None
    if cfg.family == "audio":
        enc_out = _encode_audio(params, batch["frames"].astype(dtype), cfg,
                                ax)
        # cross-attn K/V are computed per decoder layer inside the block;
        # here we precompute one shared projection (whisper ties none, but
        # per-layer K/V from a scanned stack needs per-layer params —
        # they live in p["xattn"]); pass the raw encoder output.
        enc_kv = enc_out
    if cfg.family == "vlm" and "patches" in batch:
        proj = params["proj"].astype(dtype)
        pat = batch["patches"].astype(dtype) @ proj
        x = jnp.concatenate([pat, x], axis=1)

    x, aux, stats = _stack_forward_dispatch(params, x, cfg, ax,
                                            enc_kv=enc_kv)
    fn = jax.tree.map(lambda a: a[0], params["final_norm"])
    return apply_norm(cfg, x, fn), aux, stats


def _stack_forward_dispatch(params, x, cfg, ax, *, enc_kv=None):
    if cfg.family == "audio":
        # per-layer cross-attention: compute K/V inside each block from the
        # shared encoder output
        kinds, plen, rem = _period(cfg)
        enc_out = enc_kv

        def dec_step(carry, p):
            x, aux = carry
            kv = att.encode_kv(p["xattn"], enc_out, cfg, ax)
            x, aux, _ = _apply_block(p, "attn", x, cfg, ax, enc_kv=kv,
                                     use_rope=False, aux_acc=aux)
            return (x, aux), None

        aux = jnp.zeros((), jnp.float32)
        (x, aux), _ = lax.scan(dec_step, (x, aux), params["blocks"][0])
        return x, aux, {"blocks": [None], "tail": []}
    return _stack_forward(params, x, cfg, ax, enc_kv=None)


def forward_logits(params, batch, cfg: ModelConfig, ax: MeshAxes):
    h, aux = forward_hidden(params, batch, cfg, ax)
    head = params.get("lm_head", params["embed"])
    return vp_logits(h, head, ax, cfg.vocab), aux


def loss_fn(params, batch, cfg: ModelConfig, ax: MeshAxes):
    """Mean next-token CE (+ MoE aux).  batch['labels'] aligned to tokens."""
    return loss_and_stats(params, batch, cfg, ax)[0]


def loss_and_stats(params, batch, cfg: ModelConfig, ax: MeshAxes):
    """(loss, stats): :func:`loss_fn` and, for a dropless MoE stack, the
    per-layer expert loads (``loads``, mirroring ``blocks`` and ``tail``)
    and the step's counters: ``pairs_here`` summed over layers and
    ``max_expert_pairs``, the largest held group of any layer.  Other
    stacks give empty stats."""
    h, aux, st = _forward_hidden(params, batch, cfg, ax)
    labels = batch["labels"]
    if h.shape[1] != labels.shape[1]:      # vlm: drop patch positions
        h = h[:, -labels.shape[1]:]
    head = params.get("lm_head", params["embed"])
    vpad = cfg.padded_vocab(ax.tp)
    ce = vp_logits_loss(h, head, labels, ax, cfg.vocab, vpad)
    layers = [x for x in st["blocks"] + st["tail"] if x is not None]
    if not layers:
        return ce + aux, {}
    stats = {"loads": {part: [None if x is None else x["load"]
                              for x in st[part]]
                       for part in ("blocks", "tail")},
             "pairs_here": sum(jnp.sum(x["pairs_here"]) for x in layers),
             "max_expert_pairs": jnp.max(jnp.stack(
                 [jnp.max(x["max_expert_pairs"]) for x in layers]))}
    return ce + aux, stats


def expert_choices(params, batch, cfg: ModelConfig, ax: MeshAxes):
    """The experts every token chose in each dropless MoE layer, in layer
    order: (MoE layers, tokens, top_k) ids over all ``n_experts``, from
    the forward pass of :func:`loss_and_stats`."""
    _, _, st = _forward_hidden(params, batch, cfg, ax)
    _, plen, _ = _period(cfg)
    k, n_full = cfg.first_k_dense, _n_full(cfg, plen)
    layers = [(k + i * plen + j, s["choices"][i])
              for j, s in enumerate(st["blocks"]) if s is not None
              for i in range(n_full)]
    layers += [(k + n_full * plen + j, s["choices"][0])
               for j, s in enumerate(st["tail"]) if s is not None]
    return jnp.stack([c for _, c in sorted(layers, key=lambda t: t[0])])


# ===========================================================================
# serving: prefill + decode
# ===========================================================================

def init_caches(params, cfg: ModelConfig, B: int, ctx: int, ax: MeshAxes):
    kinds = cfg.block_kinds()
    caches = []
    for k in kinds:
        if k == "attn" and cfg.is_mla:
            caches.append(mla.init_cache(cfg, B, ctx, cfg.jdtype))
        elif k == "attn":
            caches.append(att.init_cache(cfg, B, ctx, ax, cfg.jdtype))
        elif k == "mlstm":
            caches.append(rec.mlstm_init_state(cfg, B, ax))
        elif k == "slstm":
            caches.append(rec.slstm_init_state(cfg, B, ax))
        elif k == "rglru":
            caches.append(rec.rglru_init_state(cfg, B, ax))
    return caches


def decode_step(params, token, caches, pos, cfg: ModelConfig, ax: MeshAxes,
                *, enc_out=None):
    """token (B,1) int32; pos (B,) absolute positions; caches per layer.
    Returns (next_token (B,1), new_caches).  Layers unrolled (decode HLO is
    small: S=1)."""
    dtype = cfg.jdtype
    kinds = cfg.block_kinds()
    x = embed_tokens(params, token, cfg, ax, dtype)

    new_caches = []
    for li in range(cfg.n_layers):
        kind = kinds[li]
        p = _layer_params(params, cfg, li)
        c = caches[li]
        h = apply_norm(cfg, x, p["ln1"])
        if kind == "attn":
            if cfg.is_mla:
                y, c = mla.attention_decode(p["attn"], h, c, cfg, ax, pos)
            else:
                y, c = att.attention_decode(p["attn"], h, c, cfg, ax, pos,
                                            use_rope=_use_rope(cfg, li))
            x = x + y
            if cfg.family == "audio" and enc_out is not None:
                hx = apply_norm(cfg, x, p["ln_x"])
                kv = att.encode_kv(p["xattn"], enc_out, cfg, ax)
                x = x + att.cross_attention(p["xattn"], hx, kv, cfg, ax)
            h2 = apply_norm(cfg, x, p["ln2"])
            if "moe" in p and cfg.dropless:
                y2 = dropless_block(p["moe"], h2, cfg, ax)[0]
            elif "moe" in p:
                y2, _ = moe_block(p["moe"], h2, cfg, ax)
            elif "mlp" in p:
                y2 = mlp_block(p["mlp"], h2, cfg, ax)
            else:
                y2 = 0.0
            x = x + y2
        elif kind == "mlstm":
            y, c = rec.mlstm_decode(p["mlstm"], h, c, cfg, ax)
            x = x + y
        elif kind == "slstm":
            y, c = rec.slstm_block(p["slstm"], h, cfg, ax, state=c,
                                   return_state=True)
            x = x + y
        elif kind == "rglru":
            y, c = rec.rglru_block(p["rglru"], h, cfg, ax, state=c,
                                   return_state=True)
            x = x + y
            h2 = apply_norm(cfg, x, p["ln2"])
            if cfg.d_ff:
                x = x + mlp_block(p["mlp"], h2, cfg, ax)
        new_caches.append(c)

    fn = jax.tree.map(lambda a: a[0], params["final_norm"])
    x = apply_norm(cfg, x, fn)
    head = params.get("lm_head", params["embed"])
    logits = vp_logits(x, head, ax, cfg.vocab)
    nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    return nxt, new_caches


def prefill(params, batch, cfg: ModelConfig, ax: MeshAxes):
    """Prefill pass: full forward returning last-position logits.

    (Cache population for subsequent decode reuses decode_step in serving;
    the prefill *shape* exercises the full-sequence compute path.)"""
    h, _ = forward_hidden(params, batch, cfg, ax)
    head = params.get("lm_head", params["embed"])
    return vp_logits(h[:, -1:], head, ax, cfg.vocab)
