"""Plain float32 reference of a decoder-only transformer training step.

Written from the published architecture (Qwen3 and OLMoE: RMSNorm,
per-head q/k RMSNorm, rotary embeddings, causal grouped-query attention,
a SwiGLU MLP or top-k routed SwiGLU experts, an output head that may be
tied to the embedding) and the training recipe (next-token
cross-entropy, the MoE load-balance and router z losses, global-norm
clipping, AdamW under a warm-up + cosine schedule).  It imports nothing
of the program.  Where the program departs from the published model,
the configuration file lists the departure under ``departures`` and
this reference follows the listed value (``semantics`` below).

Weights come from the seed by the key tree described in the
configuration file's deployment (``init`` below): every matrix is
``normal(key) / sqrt(fan_in)``, the router and the embeddings
``normal(key) * 0.02``, norms start at one.

Every matmul goes through :func:`_mm`, at ``Precision.HIGHEST`` in
float32.  With ``quant`` set to a float8 type, its operands are rounded
to it first, as fp8 training does: that is the control, the same
reference computed in the precision below the configuration's bfloat16.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
NEG = -1e30


def dims(c: dict) -> dict:
    """Sizes under short names, from a configuration's published keys."""
    D = c["hidden_size"]
    H = c["num_attention_heads"]
    return dict(
        D=D, H=H, KV=c["num_key_value_heads"],
        hd=c.get("head_dim") or D // H,
        F=c["intermediate_size"], V=c["vocab_size"],
        L=c["num_hidden_layers"], theta=float(c["rope_theta"]),
        tied=bool(c.get("tie_word_embeddings", False)),
        E=c.get("num_experts", 0), k=c.get("num_experts_per_tok", 0),
        Fe=c.get("moe_intermediate_size") or c["intermediate_size"],
        aux_coef=float(c.get("router_aux_loss_coef", 0.0)))


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _normal(key, shape, scale):
    return jax.random.normal(key, shape, F32) * scale


def init(seed31: int, c: dict) -> Dict:
    """The weights of ``seed31``, as a tree whose leaf paths name them."""
    d = dims(c)
    D, H, KV, hd, L = d["D"], d["H"], d["KV"], d["hd"], d["L"]
    keys = jax.random.split(jax.random.PRNGKey(seed31), 9)
    p = {"embed": _normal(keys[-1], (d["V"], D), 0.02),
         "final_norm": {"scale": jnp.ones((1, D), F32)}}
    if not d["tied"]:
        p["lm_head"] = _normal(keys[-2], (d["V"], D), 0.02)
    ks = jax.random.split(keys[0], 6)
    a = jax.random.split(ks[1], 8)
    blk = {
        "ln1": {"scale": jnp.ones((L, D), F32)},
        "ln2": {"scale": jnp.ones((L, D), F32)},
        "attn": {"wq": _normal(a[0], (L, D, H * hd), D ** -0.5),
                 "wk": _normal(a[1], (L, D, KV * hd), D ** -0.5),
                 "wv": _normal(a[2], (L, D, KV * hd), D ** -0.5),
                 "wo": _normal(a[3], (L, H * hd, D), (H * hd) ** -0.5),
                 "q_norm": jnp.ones((L, hd), F32),
                 "k_norm": jnp.ones((L, hd), F32)},
    }
    if d["E"]:
        E, Fe = d["E"], d["Fe"]
        m = jax.random.split(ks[3], 7)
        blk["moe"] = {"router": _normal(m[0], (L, D, E), 0.02),
                      "w1": _normal(m[1], (L, E, D, Fe), D ** -0.5),
                      "w3": _normal(m[2], (L, E, D, Fe), D ** -0.5),
                      "w2": _normal(m[3], (L, E, Fe, D), Fe ** -0.5)}
    else:
        F = d["F"]
        m = jax.random.split(ks[3], 3)
        blk["mlp"] = {"w_gate": _normal(m[0], (L, D, F), D ** -0.5),
                      "w_up": _normal(m[1], (L, D, F), D ** -0.5),
                      "w_down": _normal(m[2], (L, F, D), F ** -0.5)}
    p["blocks"] = [blk]
    return p


def leaves(tree) -> Dict[str, jnp.ndarray]:
    """``{path: leaf}`` with JAX's key-path spelling."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k): v for k, v in flat}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _round(x, dtype):
    """``x`` rounded to ``dtype`` under a per-tensor scale that maps its
    largest magnitude to the type's largest finite value."""
    top = float(jnp.finfo(dtype).max)
    s = jnp.maximum(jnp.max(jnp.abs(lax.stop_gradient(x))), 1e-30) / top
    return (x / s).astype(dtype).astype(F32) * s


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _fp8(x, quant):
    return _round(x, quant)


def _fp8_fwd(x, quant):
    return _round(x, quant), None


def _fp8_bwd(quant, _, ct):
    return (_round(ct, jnp.float8_e5m2),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _mm(eq, a, b, quant):
    """A matmul at float32 ``HIGHEST``; with ``quant``, the fp8 training
    recipe: both operands rounded to ``quant`` under per-tensor scales,
    and their cotangents to float8_e5m2 in the backward pass."""
    if quant is not None:
        a, b = _fp8(a, quant), _fp8(b, quant)
    return jnp.einsum(eq, a, b, precision=lax.Precision.HIGHEST,
                      preferred_element_type=F32)


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(p, x, d, sem, quant):
    B, S, _ = x.shape
    H, KV, hd = d["H"], d["KV"], d["hd"]
    q = _mm("bsd,df->bsf", x, p["wq"], quant).reshape(B, S, H, hd)
    k = _mm("bsd,df->bsf", x, p["wk"], quant).reshape(B, S, KV, hd)
    v = _mm("bsd,df->bsf", x, p["wv"], quant).reshape(B, S, KV, hd)
    q = _rope(_rms(q, p["q_norm"], sem["rms_eps"]), d["theta"])
    k = _rope(_rms(k, p["k_norm"], sem["rms_eps"]), d["theta"])
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    Q = min(S, 1024)                      # query rows per block, for memory

    @jax.checkpoint
    def block(args):
        qb, rows = args                   # (B, Q, H, hd), (Q,)
        s = _mm("bshd,bthd->bhst", qb, k, quant) * hd ** -0.5
        causal = jnp.arange(S)[None, :] <= rows[:, None]
        w = jax.nn.softmax(jnp.where(causal, s, NEG), axis=-1)
        return _mm("bhst,bthd->bshd", w, v, quant)

    qs = jnp.moveaxis(q.reshape(B, S // Q, Q, H, hd), 1, 0)
    o = lax.map(block, (qs, jnp.arange(S).reshape(S // Q, Q)))
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, H * hd)
    return _mm("bsf,fd->bsd", o, p["wo"], quant)


def _mlp(p, x, quant):
    g = _mm("bsd,df->bsf", x, p["w_gate"], quant)
    u = _mm("bsd,df->bsf", x, p["w_up"], quant)
    return _mm("bsf,fd->bsd", jax.nn.silu(g) * u, p["w_down"], quant)


def _route_group(p, xt, d, sem, quant):
    """Top-k routing with per-expert capacity over one dispatch group of
    tokens ``xt`` (T, D): choices are served in priority order (every
    token's first choice, then every second choice, ...), tokens in
    order within a choice; a choice past an expert's capacity is
    dropped.  Returns (output (T, D), aux loss)."""
    T = xt.shape[0]
    E, k = d["E"], d["k"]
    logits = _mm("td,de->te", xt, p["router"], quant)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = lax.top_k(probs, k)
    if sem["norm_topk_prob"]:
        gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    onehot = jax.nn.one_hot(idx, E, dtype=F32)                   # (T, k, E)
    balance = d["aux_coef"] * E * jnp.sum(
        jnp.mean(probs, 0) * jnp.mean(jnp.sum(onehot, 1), 0))
    zloss = sem["router_z_coef"] * jnp.mean(
        jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    C = max(1, math.ceil(T * k / E * sem["capacity_factor"]))
    prio = jnp.transpose(onehot, (1, 0, 2)).reshape(k * T, E)    # choice-major
    slot = (jnp.cumsum(prio, 0) - 1) * prio
    pos = jnp.sum(slot, -1).reshape(k, T).T.astype(jnp.int32)    # (T, k)
    keep = pos < C
    e_f, p_f = idx.reshape(-1), jnp.clip(pos, 0, C - 1).reshape(-1)
    src = jnp.repeat(xt, k, axis=0) * keep.reshape(-1, 1)
    buf = jnp.zeros((E, C, xt.shape[1]), F32).at[e_f, p_f].add(src)
    h = _mm("ecd,edf->ecf", buf, p["w1"], quant)
    u = _mm("ecd,edf->ecf", buf, p["w3"], quant)
    out = _mm("ecf,efd->ecd", jax.nn.silu(h) * u, p["w2"], quant)
    w = (gates * keep).reshape(-1, 1)
    y = jnp.sum((out[e_f, p_f] * w).reshape(T, k, -1), axis=1)
    return y, balance + zloss


def _moe(p, x, d, sem, quant):
    """Tokens are routed in ``sem['dispatch_groups']`` groups, each a
    contiguous run of the row-major (B*S) token order."""
    B, S, D = x.shape
    G = sem["dispatch_groups"]
    xg = x.reshape(G, B * S // G, D)
    y, aux = jax.vmap(lambda xt: _route_group(p, xt, d, sem, quant))(xg)
    return y.reshape(B, S, D), aux


def loss_fn(params, tokens, labels, c, sem, quant=None, weight=None):
    """Mean next-token cross-entropy over the positions ``weight`` keeps
    (all by default), plus the MoE auxiliary losses, per layer the mean
    over the dispatch groups."""
    d = dims(c)
    x = params["embed"][tokens]
    blk = params["blocks"][0]

    def layer(x, p):
        h = _rms(x, p["ln1"]["scale"], sem["rms_eps"])
        x = x + _attention(p["attn"], h, d, sem, quant)
        h = _rms(x, p["ln2"]["scale"], sem["rms_eps"])
        if d["E"]:
            y, aux = _moe(p["moe"], h, d, sem, quant)
        else:
            y, aux = _mlp(p["mlp"], h, quant), jnp.zeros((1,), F32)
        return x + y, aux

    x, aux = lax.scan(jax.checkpoint(layer), x, blk)             # aux (L, G)
    x = _rms(x, params["final_norm"]["scale"][0], sem["rms_eps"])
    head = params.get("lm_head", params["embed"])
    B, S, D = x.shape
    xs = x.reshape(-1, D)
    lab = labels.reshape(-1)
    wt = jnp.ones_like(lab, F32) if weight is None else weight.reshape(-1)
    chunk = min(1024, xs.shape[0])
    n = xs.shape[0] // chunk

    @jax.checkpoint
    def ce_chunk(args):
        xc, lc, wc = args
        z = _mm("td,vd->tv", xc, head, quant)
        lse = jax.nn.logsumexp(z, axis=-1)
        tgt = jnp.take_along_axis(z, lc[:, None], axis=-1)[:, 0]
        return jnp.sum((lse - tgt) * wc)

    tot = lax.map(ce_chunk, (xs.reshape(n, chunk, D), lab.reshape(n, chunk),
                             wt.reshape(n, chunk)))
    ce = jnp.sum(tot) / jnp.sum(wt)
    if d["E"]:
        ce = ce + jnp.sum(jnp.mean(aux, axis=1))   # aux (L, groups)
    return ce


# ---------------------------------------------------------------------------
# the optimizer
# ---------------------------------------------------------------------------

def lr_at(step, opt):
    """Warm-up then cosine decay to ``final_frac`` of ``lr``; ``step`` counts
    finished steps."""
    w = jnp.minimum(1.0, (step + 1) / max(opt["warmup_steps"], 1))
    prog = jnp.clip((step - opt["warmup_steps"]) /
                    max(opt["total_steps"] - opt["warmup_steps"], 1), 0., 1.)
    return opt["lr"] * w * (opt["final_frac"] +
                            (1 - opt["final_frac"]) *
                            0.5 * (1 + jnp.cos(jnp.pi * prog)))


def adamw(params, grads, m, v, step, opt):
    """One AdamW step after global-norm clipping.  Returns (params, m, v,
    clipped grads)."""
    gn = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    clip = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gn, 1e-9))
    g = jax.tree.map(lambda x: x * clip, grads)
    t = (step + 1).astype(F32)
    lr = lr_at(step, opt)
    b1, b2 = opt["b1"], opt["b2"]
    m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
    v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
    bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree.map(
        lambda p, a, b: p - lr * (a / bc1 / (jnp.sqrt(b / bc2) + opt["eps"])
                                  + opt["weight_decay"] * p), params, m, v)
    return params, m, v, g


def norms(tree) -> Dict[str, jnp.ndarray]:
    return {k: jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
            for k, x in leaves(tree).items()}


def placement(c: dict, devices):
    """Shardings that split each weight along its largest dimension that
    the devices divide (XLA partitions the rest); None on one device."""
    if len(devices) == 1:
        return None
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(devices), ("x",))
    n = len(devices)

    def spec(x):
        dims = [i for i in np.argsort(x.shape)[::-1] if x.shape[i] % n == 0]
        p = [None] * x.ndim
        if dims:
            p[dims[0]] = "x"
        return NamedSharding(mesh, P(*p))
    return jax.tree.map(spec, jax.eval_shape(lambda: init(1, c)))


def train(seed31: int, c: dict, sem: dict, opt: dict, batches, *,
          quant=None, weights=None, devices=None) -> dict:
    """Three (``len(batches)``) reference steps from the weights of
    ``seed31``.  Returns the losses, the per-leaf norms of the first
    step's clipped gradient, and the per-leaf norms of the parameters'
    change over all the steps.  ``weights`` are per-position loss weights
    per step (a fault's input); the weights are spread over ``devices``."""
    shard = placement(c, devices) if devices else None
    params = jax.jit(lambda s: init(s, c), out_shardings=shard)(seed31)
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
    m, v = zeros(params), zeros(params)

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, m, v, i, tokens, labels, weight):
        loss, g = jax.value_and_grad(loss_fn)(params, tokens, labels, c, sem,
                                              quant, weight)
        params, m, v, g = adamw(params, g, m, v, i, opt)
        return params, m, v, loss, norms(g)

    losses, grad_norms = [], None
    for i, b in enumerate(batches):
        w = None if weights is None else weights[i]
        params, m, v, loss, gn = step(params, m, v, jnp.int32(i),
                                      b["tokens"], b["labels"], w)
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = {k: float(x) for k, x in gn.items()}
    del m, v

    @jax.jit
    def change(params, s):
        return norms(jax.tree.map(lambda a, b: a - b, params, init(s, c)))
    ch = {k: float(x) for k, x in change(params, seed31).items()}
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": ch}
