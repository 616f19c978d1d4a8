"""Latent attention's causal splash kernel (``mla.causal_flash``) against
the blockwise softmax, its oracle, in Pallas interpret mode on the CPU at
MLA's head widths (q/k 192, v 128), and the choice between the two that
``mla.attention_train`` makes."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import get_smoke_config
from repro.models import mla
from repro.models.layers import MeshAxes
from repro.obs import spans

AX = MeshAxes(tp=1, dp=1, fsdp=False)
TOTAL, COMPUTED = "repro.mla.kv_tiles_total", "repro.mla.kv_tiles_computed"


def qkv(S, H, dtype, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(k[0], (1, S, H, 192)).astype(dtype),
            jax.random.normal(k[1], (1, S, H, 192)).astype(dtype),
            jax.random.normal(k[2], (1, S, H, 128)).astype(dtype),
            jax.random.normal(k[3], (1, S, H, 128), jnp.float32))


def value_and_grads(attend, q, k, v, g):
    def loss(q, k, v):
        o = attend(q, k, v)
        return jnp.sum(o.astype(jnp.float32) * g), o
    (_, o), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                       has_aux=True)(q, k, v)
    return [a.astype(jnp.float32) for a in (o, *grads)]


def tiles(fn, *args):
    """``fn(*args)`` and what it added to the two tile counters."""
    c0 = spans.counters()
    out = fn(*args)
    c1 = spans.counters()
    return out, tuple(c1.get(n, 0) - c0.get(n, 0) for n in (TOTAL, COMPUTED))


# float32: summation order alone (measured 2e-6 abs, 6e-7 relative);
# bfloat16: the inputs' and p's rounding (measured up to 1.6e-2 abs
# forward and 7.6e-3 relative gradients)
@pytest.mark.parametrize("dtype,fwd_atol,grad_rel", [
    (jnp.float32, 2e-5, 2e-5),
    (jnp.bfloat16, 3e-2, 2e-2),
])
def test_splash_equals_blockwise(dtype, fwd_atol, grad_rel):
    """Forward values and dq, dk, dv at S=512 in tiles of 128: 10 of the
    16 tiles computed, over two heads."""
    q, k, v, g = qkv(512, 2, dtype)
    got = value_and_grads(functools.partial(mla.causal_flash, block=128),
                          q, k, v, g)
    want = value_and_grads(
        functools.partial(mla.blockwise_attention, block=128), q, k, v, g)
    assert float(jnp.max(jnp.abs(got[0] - want[0]))) <= fwd_atol
    for name, a, b in zip(("dq", "dk", "dv"), got[1:], want[1:]):
        rel = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        assert rel <= grad_rel, (name, rel)


def test_splash_kernel_holds_no_tracer_between_traces():
    """A kernel first built inside a ``shard_map`` trace is reused by a
    later trace: its cached mask info holds arrays, not that trace's
    tracers."""
    q, k, v, _ = qkv(384, 3, jnp.float32, seed=1)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    spec = P("data")
    sm = jax.jit(jax.shard_map(
        functools.partial(mla.causal_flash, block=128), mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec, check_vma=False))
    first = sm(q, k, v)
    again = jax.jit(functools.partial(mla.causal_flash, block=128))(q, k, v)
    np.testing.assert_allclose(again, first, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("S,block,n", [
    (512, 512, 1), (2048, 512, 4), (8192, 512, 16), (8192, 1024, 8)])
def test_kernel_computes_the_causal_tiles(S, block, n):
    """The mask info's non-empty tiles of one head's square, without
    running the kernel: n(n+1)/2 of n^2 (136 of 256 at S=8192 in tiles of
    512, 36 of 64 in tiles of 1024)."""
    _, computed = mla._splash_kernel(S, 1, block, True)
    assert computed == n * (n + 1) // 2


def test_tpu_dispatch_counts_the_kernel_tiles(monkeypatch):
    """On the TPU S=8192 takes the kernel: the counters read the square's
    n^2 tiles and n(n+1)/2 computed.  The backend is steered here and the
    call only traced."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q, k, v, _ = qkv(8192, 1, jnp.bfloat16)
    out, counted = tiles(jax.eval_shape, mla.causal_attention, q, k, v)
    n = 8192 // mla.FLASH_BLOCK
    assert counted == (n * n, n * (n + 1) // 2)
    assert out.shape == (1, 8192, 1, 128)


@pytest.mark.parametrize("backend,S", [("cpu", 1024), ("tpu", 768)])
def test_attention_train_falls_back_to_blockwise(monkeypatch, backend, S):
    """On the CPU, and on the TPU for an S that the tile does not divide,
    training attention is the blockwise softmax: it counts the square's
    tiles (whole tiles, rounded up) and none computed by the kernel."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    cfg = get_smoke_config("moonlight-16b-a3b").with_overrides(
        dtype="float32")
    keys = jax.random.split(jax.random.PRNGKey(2), 5)
    p, _ = mla.init_params(
        keys, cfg, 1,
        lambda key, shape: jax.random.normal(key, shape) * 0.05)
    p = jax.tree.map(lambda a: a[0], p)
    x = jax.random.normal(keys[4], (1, S, cfg.d_model), jnp.float32)
    got, counted = tiles(mla.attention_train, p, x, cfg, AX)
    n = -(-S // mla.FLASH_BLOCK)
    assert counted == (n * n, 0)
    q, c, k_pe = mla._latent(p, x, cfg, AX, jnp.arange(S, dtype=jnp.int32))
    k, v = mla._keys_values(p, c, k_pe, cfg, AX)
    np.testing.assert_allclose(
        got, mla._out(p, mla.blockwise_attention(q, k, v), AX),
        rtol=1e-6, atol=1e-6)
