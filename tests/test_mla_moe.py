"""Latent attention and the dropless held-expert MoE layer (the
DeepSeek-V3 layers of moonlight-16b-a3b), at small sizes on the CPU in
float32."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.models import forward_logits, init_params, mla, moe
from repro.models.layers import MeshAxes
from repro.models.transformer import decode_step, init_caches

AX = MeshAxes(tp=1, dp=1, fsdp=False)
F32 = jnp.float32


def smoke(**kw):
    return get_smoke_config("moonlight-16b-a3b").with_overrides(
        dtype="float32", **kw)


def test_blockwise_attention_equals_the_full_square():
    """Blocks of query rows give the whole square's causal softmax; float32
    on both sides, so only summation order differs (1e-5)."""
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k[0], (2, 64, 3, 24), F32)
    kk = jax.random.normal(k[1], (2, 64, 3, 24), F32)
    v = jax.random.normal(k[2], (2, 64, 3, 16), F32)
    full = mla.blockwise_attention(q, kk, v, block=65)    # one block
    for block in (8, 16, 64):
        np.testing.assert_allclose(
            mla.blockwise_attention(q, kk, v, block=block), full,
            rtol=1e-5, atol=1e-5)


def test_selection_uses_the_bias_and_the_gates_do_not():
    cfg = smoke()
    p = {"router": jax.random.normal(jax.random.PRNGKey(1), (64, 8)) * 0.1,
         "e_bias": jnp.array([0., 0., 0., 0., 0., 0., 0., 5.])}
    x = jax.random.normal(jax.random.PRNGKey(2), (32, 64), F32)
    s, idx, gates = moe.route_scores(p, x, cfg)
    assert bool(jnp.all(idx[:, 0] == 7))        # the bias wins selection
    s_ref = jax.nn.sigmoid(x @ p["router"])
    want_idx = jax.lax.top_k(s_ref + p["e_bias"], cfg.top_k)[1]
    np.testing.assert_array_equal(idx, want_idx)
    sel = jnp.take_along_axis(s_ref, idx, -1)       # scores, not s + b
    np.testing.assert_allclose(
        gates, sel / sel.sum(-1, keepdims=True) * cfg.routed_scale,
        rtol=1e-6)
    np.testing.assert_allclose(gates.sum(-1), cfg.routed_scale, rtol=1e-6)


def test_bias_moves_by_gamma_as_the_loads_say():
    cfg = smoke(bias_rate=0.25)
    old = {"blocks": [{"moe": {"e_bias": jnp.ones((2, 4))}}], "tail": []}
    new = {"blocks": [{"moe": {"e_bias": jnp.full((2, 4), 9.0)}}],
           "tail": []}
    loads = {"blocks": [jnp.array([[3., 1., 2., 2.], [0., 0., 4., 4.]])],
             "tail": []}
    got = moe.update_expert_bias(new, old, loads, cfg)
    # mean 2: over-loaded experts fall, under-loaded rise, the rest stay;
    # the optimizer's value (9) is dropped
    np.testing.assert_allclose(got["blocks"][0]["moe"]["e_bias"],
                               [[0.75, 1.25, 1., 1.], [1.25, 1.25, .75, .75]])


def test_dropless_keeps_every_pair_when_all_choose_one_expert():
    """Every token's first choice forced to held expert 1: its group holds
    all T pairs, far past any capacity, and none is dropped."""
    cfg = smoke()
    params, _ = init_params(jax.random.PRNGKey(3), cfg, AX)
    p = jax.tree.map(lambda a: a[0], params["blocks"][0]["moe"])
    p["e_bias"] = p["e_bias"].at[1].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, cfg.d_model), F32)
    y, _, st = moe.dropless_block(p, x, cfg, AX)
    T = 32
    assert int(st["max_expert_pairs"]) == T
    assert float(st["load"][1]) == T
    # the same layer computed densely: every held expert over every token
    s, idx, gates = moe.route_scores(p, x.reshape(T, -1), cfg)
    xt = x.reshape(T, -1)
    want = jnp.zeros_like(xt)
    for e in range(cfg.n_held):
        g = jnp.sum(jnp.where(idx == e, gates, 0.), -1)[:, None]
        h = jax.nn.silu(xt @ p["w1"][e]) * (xt @ p["w3"][e])
        want = want + g * (h @ p["w2"][e])
    hs = jax.nn.silu(xt @ p["shared_w1"]) * (xt @ p["shared_w3"])
    want = want + hs @ p["shared_w2"]
    np.testing.assert_allclose(y.reshape(T, -1), want, rtol=2e-5,
                               atol=2e-5)


def test_decode_through_the_latent_cache_matches_the_full_forward():
    """Tokens fed one at a time through the latent cache give the next
    tokens of the full causal forward (float32, where only summation order
    differs, so no argmax flips)."""
    cfg = smoke()
    params, _ = init_params(jax.random.PRNGKey(5), cfg, AX)
    toks = jax.random.randint(jax.random.PRNGKey(6), (2, 8), 0, cfg.vocab)
    full, _ = forward_logits(params, {"tokens": toks}, cfg, AX)
    caches = init_caches(params, cfg, 2, 8, AX)
    # decode_step returns argmax tokens: compare the forward's argmax
    step = jax.jit(lambda p, t, c, q: decode_step(p, t, c, q, cfg, AX))
    got = []
    for i in range(8):
        nxt, caches = step(params, toks[:, i:i + 1], caches,
                           jnp.full((2,), i, jnp.int32))
        got.append(nxt[:, 0])
    np.testing.assert_array_equal(jnp.stack(got, 1),
                                  jnp.argmax(full, -1))


def test_param_count_of_one_chips_share():
    """Guide §4's sum for one chip of an 8-way expert-parallel stage: the
    dense layer 83 M + 5 MoE layers of 100.4 M + the head's and the
    embedding's slices 83.9 M, about 669 M (within 1 %)."""
    cfg = get_config("moonlight-16b-a3b").with_overrides(
        n_layers=6, experts_here=8, vocab=20480)
    n = cfg.param_count()
    assert abs(n - 669e6) / 669e6 < 0.01, n
    shapes = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg, AX)[0])
    # param_count leaves out the layer norms' scales, as for every family
    norms = (2 * cfg.n_layers + 1) * cfg.d_model
    assert n + norms == sum(x.size for x in jax.tree.leaves(shapes))


@pytest.mark.parametrize("held", [0, 2])
def test_smoke_step_counts_pairs(held):
    """The counters the step returns: every pair is held with all experts
    here; none is dropped either way."""
    cfg = smoke(experts_here=held)
    params, _ = init_params(jax.random.PRNGKey(7), cfg, AX)
    toks = jax.random.randint(jax.random.PRNGKey(8), (2, 16), 0, cfg.vocab)
    from repro.models import loss_and_stats
    _, st = loss_and_stats(params, {"tokens": toks, "labels": toks}, cfg,
                           AX)
    n_moe = cfg.n_layers - cfg.first_k_dense
    if not held:
        assert int(st["pairs_here"]) == n_moe * 32 * cfg.top_k
    assert st["loads"]["blocks"][0].shape == (n_moe, cfg.n_experts)


def test_rows_past_the_groups_never_reach_the_result(monkeypatch):
    """The TPU's ragged_dot kernel leaves the rows past the last group
    unwritten, in its value and in its input's cotangent.  With those rows
    NaN in both, the loss and every gradient are those of the plain
    kernel (which writes zeros there)."""
    from jax import lax
    from repro.models import loss_and_stats
    real = lax.ragged_dot

    def fill(a, sizes):
        past = jnp.arange(a.shape[0]) >= jnp.sum(sizes)
        return jnp.where(past[:, None], jnp.nan, a)

    @jax.custom_vjp
    def unwritten(x, w, sizes):
        return fill(real(x, w, sizes), sizes)

    def bwd(res, g):
        x, w, sizes = res
        dx, dw = jax.vjp(lambda x, w: real(x, w, sizes), x, w)[1](g)
        return fill(dx, sizes), dw, None
    unwritten.defvjp(lambda x, w, sizes: (unwritten(x, w, sizes),
                                          (x, w, sizes)), bwd)

    cfg = smoke()
    params, _ = init_params(jax.random.PRNGKey(9), cfg, AX)
    toks = jax.random.randint(jax.random.PRNGKey(10), (2, 16), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": toks}

    def grads():
        return jax.value_and_grad(
            lambda p: loss_and_stats(p, batch, cfg, AX)[0])(params)
    want = grads()
    monkeypatch.setattr(lax, "ragged_dot", unwritten)
    got = grads()
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
