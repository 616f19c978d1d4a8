"""Plain float32 reference of a DeepSeek-V3-layer training step, as
Moonlight-16B-A3B publishes it, on one chip's share of its experts.

Written from the published equations (DeepSeek-V2 arXiv:2405.04434 §2.1,
DeepSeek-V3 arXiv:2412.19437 §2.1): RMSNorm; latent attention (MLA) with
no query compression, q/k heads of nope + rope columns, one rope key
shared by the heads, values of their own width, the causal softmax
computed in blocks of query rows; leading dense SwiGLU layers, then MoE
layers of shared SwiGLU experts and routed ones chosen by sigmoid scores
plus a selection-only bias, gated by the chosen scores normalised and
scaled; next-token cross-entropy plus the sequence-wise balance loss;
global-norm clipping and AdamW under a warm-up + cosine schedule (from
``transformer.py``); after each step the bias update
``b_i += gamma * sign(mean load - load_i)``.  It imports nothing of the
program.

The chip holds ``n_routed_experts`` of the router's ``n_routed_experts *
deployment.expert_parallel`` experts, ids 0 up.  The router scores all of
them and chooses the top-k among all; each held expert is a dense SwiGLU
over every token, weighted by its gate where it was chosen and by zero
elsewhere.  What the absent experts would add is left out.

The choice of experts is discrete: wherever two of a token's scores lie
closer than bfloat16's rounding, a bfloat16 program and this float32
reference choose differently, and that token's gradient moves by a whole
expert's share.  ``train`` can therefore take the program's choices:
it then routes each token to the experts the program chose, computes
their scores, gates and outputs itself, and counts the chosen pairs that
its own top-k lacks (``choice_gap``, the share of all pairs), so that
the choice is checked apart from the arithmetic.

Weights come from the seed by the key tree of the configuration's
deployment (``init``): every matrix ``normal(key) / sqrt(fan_in)``, the
router and the embeddings ``normal(key) * 0.02``, norms one, the bias
zero.  Every matmul goes through ``transformer._mm`` at float32
``HIGHEST``; ``quant`` is the fp8 control, as there.
"""

from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

from .transformer import _mm, _rms, _rope, adamw, leaves, norms

F32 = jnp.float32
NEG = -1e30
Q_BLOCK = 512           # query rows per block, for memory


def dims(c: dict) -> dict:
    """Sizes under short names, from a configuration's published keys and
    its deployment."""
    Eh = c["n_routed_experts"]
    return dict(
        D=c["hidden_size"], H=c["num_attention_heads"],
        r=c["kv_lora_rank"], dn=c["qk_nope_head_dim"],
        dr=c["qk_rope_head_dim"], dv=c["v_head_dim"],
        F=c["intermediate_size"], Fe=c["moe_intermediate_size"],
        V=c["vocab_size"], L=c["num_hidden_layers"],
        K=c["first_k_dense_replace"], Eh=Eh,
        E=Eh * c["deployment"]["expert_parallel"],
        k=c["num_experts_per_tok"], Fs=c["moe_intermediate_size"] *
        c["n_shared_experts"], theta=float(c["rope_theta"]),
        eps=float(c["rms_norm_eps"]), scale=float(c["routed_scaling_factor"]),
        alpha=float(c["assumed"]["seq_aux_alpha"]),
        gamma=float(c["assumed"]["bias_update_gamma"]))


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _dense(key, shape, scale=None):
    return jax.random.normal(key, shape, F32) * (scale or shape[-2] ** -0.5)


def _block(key, d: dict, n: int, moe: bool) -> Dict:
    """``n`` stacked layers; ``moe`` False for a leading dense one."""
    D, H = d["D"], d["H"]
    ks = jax.random.split(key, 6)
    a = jax.random.split(ks[1], 4)
    p = {"ln1": {"scale": jnp.ones((n, D), F32)},
         "attn": {"wq": _dense(a[0], (n, D, H * (d["dn"] + d["dr"]))),
                  "wkv_a": _dense(a[1], (n, D, d["r"] + d["dr"])),
                  "kv_norm": jnp.ones((n, d["r"]), F32),
                  "wkv_b": _dense(a[2], (n, d["r"], H * (d["dn"] + d["dv"]))),
                  "wo": _dense(a[3], (n, H * d["dv"], D))},
         "ln2": {"scale": jnp.ones((n, D), F32)}}
    if moe:
        m = jax.random.split(ks[3], 7)
        Eh, Fe, Fs = d["Eh"], d["Fe"], d["Fs"]
        p["moe"] = {"router": _dense(m[0], (n, D, d["E"]), 0.02),
                    "w1": _dense(m[1], (n, Eh, D, Fe)),
                    "w3": _dense(m[2], (n, Eh, D, Fe)),
                    "w2": _dense(m[3], (n, Eh, Fe, D)),
                    "e_bias": jnp.zeros((n, d["E"]), F32),
                    "shared_w1": _dense(m[4], (n, D, Fs)),
                    "shared_w3": _dense(m[5], (n, D, Fs)),
                    "shared_w2": _dense(m[6], (n, Fs, D))}
    else:
        m = jax.random.split(ks[3], 3)
        p["mlp"] = {"w_gate": _dense(m[0], (n, D, d["F"])),
                    "w_up": _dense(m[1], (n, D, d["F"])),
                    "w_down": _dense(m[2], (n, d["F"], D))}
    return p


def init(seed31: int, c: dict) -> Dict:
    """The weights of ``seed31``: the leading dense layers one by one, the
    MoE layers stacked, as a tree whose leaf paths name them."""
    d = dims(c)
    keys = jax.random.split(jax.random.PRNGKey(seed31), 9)
    return {"embed": _normal(keys[-1], (d["V"], d["D"])),
            "lm_head": _normal(keys[-2], (d["V"], d["D"])),
            "final_norm": {"scale": jnp.ones((1, d["D"]), F32)},
            "lead": [_block(k, d, 1, False)
                     for k in jax.random.split(keys[-6], d["K"])],
            "blocks": [_block(keys[0], d, d["L"] - d["K"], True)]}


def _normal(key, shape):
    return jax.random.normal(key, shape, F32) * 0.02


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _mla(p, x, d, quant):
    B, S, _ = x.shape
    H, r, dn, dr, dv = d["H"], d["r"], d["dn"], d["dr"], d["dv"]
    q = _mm("bsd,df->bsf", x, p["wq"], quant).reshape(B, S, H, dn + dr)
    ckr = _mm("bsd,df->bsf", x, p["wkv_a"], quant)
    c = _rms(ckr[..., :r], p["kv_norm"], d["eps"])
    k_pe = _rope(ckr[..., None, r:], d["theta"])                 # (B,S,1,dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], d["theta"])], -1)
    kv = _mm("bsr,rf->bsf", c, p["wkv_b"], quant).reshape(B, S, H, dn + dv)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_pe, (B, S, H, dr))], -1)
    v = kv[..., dn:]
    Q = Q_BLOCK if S % Q_BLOCK == 0 else S

    @jax.checkpoint
    def block(args):
        qb, rows = args
        s = _mm("bshd,bthd->bhst", qb, k, quant) * (dn + dr) ** -0.5
        causal = jnp.arange(S)[None, :] <= rows[:, None]
        w = jax.nn.softmax(jnp.where(causal, s, NEG), axis=-1)
        return _mm("bhst,bthd->bshd", w, v, quant)

    qs = jnp.moveaxis(q.reshape(B, S // Q, Q, H, dn + dr), 1, 0)
    o = lax.map(block, (qs, jnp.arange(S).reshape(S // Q, Q)))
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, H * dv)
    return _mm("bsf,fd->bsd", o, p["wo"], quant)


def _swiglu(x, wg, wu, wd, quant):
    g = _mm("...d,df->...f", x, wg, quant)
    u = _mm("...d,df->...f", x, wu, quant)
    return _mm("...f,fd->...d", jax.nn.silu(g) * u, wd, quant)


def route(p, xt, d, quant=None):
    """Scores of all experts, the chosen top-k (on score + bias) and their
    gates: (s (T, E), idx (T, k), gates (T, k))."""
    s = jax.nn.sigmoid(_mm("td,de->te", xt, p["router"], quant))
    _, idx = lax.top_k(s + lax.stop_gradient(p["e_bias"]), d["k"])
    sel = jnp.take_along_axis(s, idx, axis=-1)
    return s, idx, sel / jnp.sum(sel, -1, keepdims=True) * d["scale"]


def moe(p, x, d, quant=None, choice=None):
    """One MoE layer on one chip's share: (output, balance loss, loads of
    all experts, the pairs of ``choice`` that the own top-k lacks).  With
    ``choice`` (T, k), each token goes to those experts instead of its
    own top-k."""
    B, S, D = x.shape
    E, Eh, k = d["E"], d["Eh"], d["k"]
    xt = x.reshape(B * S, D)
    s, idx, gates = route(p, xt, d, quant)
    differ = jnp.zeros((), jnp.int32)
    if choice is not None:
        differ = B * S * k - jnp.sum(
            jnp.any(choice[:, :, None] == idx[:, None, :], -1))
        idx = choice
        sel = jnp.take_along_axis(s, idx, axis=-1)
        gates = sel / jnp.sum(sel, -1, keepdims=True) * d["scale"]
    chosen = jnp.sum(jax.nn.one_hot(idx, E, dtype=F32), axis=1)   # (T, E)
    f = jnp.sum(chosen.reshape(B, S, E), axis=1) * (E / (k * S))
    P = jnp.mean((s / jnp.sum(s, -1, keepdims=True)).reshape(B, S, E), 1)
    aux = d["alpha"] * jnp.mean(jnp.sum(f * P, axis=-1))
    # each held expert's gate per token: zero where it was not chosen
    G = jnp.sum(jax.nn.one_hot(idx, Eh, dtype=F32) * gates[..., None], 1)

    def expert(y, e):
        w1, w3, w2, g = e
        return y + g[:, None] * _swiglu(xt, w1, w3, w2, quant), None

    y, _ = lax.scan(expert, jnp.zeros_like(xt),
                    (p["w1"], p["w3"], p["w2"], G.T))
    y = y + _swiglu(xt, p["shared_w1"], p["shared_w3"], p["shared_w2"],
                    quant)
    return y.reshape(B, S, D), aux, jnp.sum(chosen, axis=0), differ


def loss_fn(params, tokens, labels, c, quant=None, weight=None,
            choices=None):
    """(mean next-token cross-entropy over the positions ``weight`` keeps,
    plus every MoE layer's balance loss; (the MoE layers' loads (L', E),
    their chosen pairs that the own top-k lacks (L',))).  ``choices``
    (L', T, k), if given, are the experts each MoE layer routes to."""
    d = dims(c)
    x = params["embed"][tokens]

    def attn(p, x):
        return x + _mla(p["attn"], _rms(x, p["ln1"]["scale"], d["eps"]), d,
                        quant)

    for p in params["lead"]:
        p = jax.tree.map(lambda a: a[0], p)
        x = attn(p, x)
        m = p["mlp"]
        x = x + _swiglu(_rms(x, p["ln2"]["scale"], d["eps"]), m["w_gate"],
                        m["w_up"], m["w_down"], quant)

    def layer(x, pc):
        p, choice = pc
        x = attn(p, x)
        y, aux, load, differ = moe(p["moe"], _rms(x, p["ln2"]["scale"],
                                                  d["eps"]), d, quant, choice)
        return x + y, (aux, load, differ)

    x, (aux, loads, differ) = lax.scan(jax.checkpoint(layer), x,
                                       (params["blocks"][0], choices))
    x = _rms(x, params["final_norm"]["scale"][0], d["eps"])
    B, S, D = x.shape
    xs = x.reshape(-1, D)
    lab = labels.reshape(-1)
    wt = jnp.ones_like(lab, F32) if weight is None else weight.reshape(-1)
    chunk = min(1024, xs.shape[0])
    n = xs.shape[0] // chunk

    @jax.checkpoint
    def ce_chunk(args):
        xc, lc, wc = args
        z = _mm("td,vd->tv", xc, params["lm_head"], quant)
        lse = jax.nn.logsumexp(z, axis=-1)
        tgt = jnp.take_along_axis(z, lc[:, None], axis=-1)[:, 0]
        return jnp.sum((lse - tgt) * wc)

    tot = lax.map(ce_chunk, (xs.reshape(n, chunk, D), lab.reshape(n, chunk),
                             wt.reshape(n, chunk)))
    return jnp.sum(tot) / jnp.sum(wt) + jnp.sum(aux), (loads, differ)


def update_bias(new, old, loads, gamma):
    """The bias after a step: its value before AdamW plus ``gamma *
    sign(mean load - load)``; AdamW never moves it."""
    step = gamma * jnp.sign(jnp.mean(loads, -1, keepdims=True) - loads)
    blk = new["blocks"][0]
    moe_p = {**blk["moe"], "e_bias": old["blocks"][0]["moe"]["e_bias"] + step}
    return {**new, "blocks": [{**blk, "moe": moe_p}]}


def train(seed31: int, c: dict, opt: dict, batches, *, quant=None,
          weights=None, choices=None) -> dict:
    """``len(batches)`` reference steps from the weights of ``seed31`` on
    the default device, each routed by its entry of ``choices`` if given
    (the program's (L', T, k) experts of that step).  Returns the losses,
    the per-leaf norms of the first step's clipped gradient and of the
    parameters' change over all the steps, the last step's loads, and
    ``choice_gap``: the share of the given chosen pairs, over every step
    and layer, that the reference's own top-k lacks (0 without
    ``choices``)."""
    d = dims(c)
    params = jax.jit(lambda s: init(s, c))(seed31)
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
    m, v = zeros(params), zeros(params)

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, m, v, i, tokens, labels, weight, choice):
        (loss, (loads, differ)), g = jax.value_and_grad(
            loss_fn, has_aux=True)(params, tokens, labels, c, quant, weight,
                                   choice)
        new, m, v, g = adamw(params, g, m, v, i, opt)
        return update_bias(new, params, loads, d["gamma"]), m, v, loss, \
            norms(g), loads, jnp.sum(differ)

    losses, grad_norms, differ, pairs = [], None, 0, 0
    for i, b in enumerate(batches):
        w = None if weights is None else weights[i]
        ch = None if choices is None else jnp.asarray(choices[i])
        params, m, v, loss, gn, loads, dif = step(
            params, m, v, jnp.int32(i), b["tokens"], b["labels"], w, ch)
        losses.append(float(loss))
        differ += int(dif)
        pairs += 0 if ch is None else ch.size
        if grad_norms is None:
            grad_norms = {k: float(x) for k, x in gn.items()}
    del m, v

    @jax.jit
    def change(params, s):
        return norms(jax.tree.map(lambda a, b: a - b, params, init(s, c)))
    ch = {k: float(x) for k, x in change(params, seed31).items()}
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": ch,
            "loads": loads, "choice_gap": differ / pairs if pairs else 0.0}


__all__ = ["dims", "init", "leaves", "loss_fn", "moe", "route", "train"]
