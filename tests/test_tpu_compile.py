"""Ahead-of-time compiles for a described TPU v5e 2x2 (no chip attached).

The TPU compiler is installed with jaxlib, so a chip that is described
rather than attached still refuses what Mosaic cannot lower.  These
compiles guard the in-graph decision kernel of every shipped tuner
policy, and the ``pallas32`` selector inside ``shard_map`` on four
devices, at no chip time.  Code that asks ``jax.default_backend()`` still
sees the CPU here, so the selector test steers its kernel to Mosaic by
patching :func:`repro.core.pallasc.on_tpu`.

The topology is described inside a module fixture, never while a module
is imported: only one process may load the TPU library, and every test
worker imports every test file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import pallasc
from repro.core.maps import device_shape
from repro.policies.loops import LOOP_POLICIES
from repro.policies.table1 import SAFE_POLICIES
from repro.policies.telemetry import bucket_profiler, bucket_tuner

POLICIES = SAFE_POLICIES + LOOP_POLICIES + [bucket_tuner, bucket_profiler]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("pol", POLICIES, ids=lambda p: p.program.name)
def test_pallas32_kernel_compiles_under_mosaic(pol, one_chip):
    prog = pol.program
    fn, names = pallasc.compile_pallas(prog, word_width=32, interpret=False)
    ctx = jax.ShapeDtypeStruct((prog.ctx_type.size // 8, 2), jnp.uint32,
                               sharding=one_chip)
    maps = {d.name: jax.ShapeDtypeStruct(
        (*device_shape(d.kind, d.value_size, d.max_entries), 2),
        jnp.uint32, sharding=one_chip) for d in prog.maps}
    text = jax.jit(fn).lower(ctx, maps).compile().as_text()
    assert "tpu_custom_call" in text


def test_pallas32_selector_all_reduce_compiles_in_shard_map(topo,
                                                            monkeypatch):
    from repro.collectives.ingraph import InGraphSelector

    monkeypatch.setattr(pallasc, "on_tpu", lambda: True)
    sel = InGraphSelector(bucket_tuner.program, tier="pallas32")
    mesh = Mesh(np.array(topo.devices[:4]), ("x",))
    rep = NamedSharding(mesh, P())
    state = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=rep)
             for k, v in sel.init_state().items()}
    x = jax.ShapeDtypeStruct((4 * 256, 256), jnp.float32,
                             sharding=NamedSharding(mesh, P("x")))

    def step(v, st):
        y, algo, st = sel.all_reduce(v, "x", st)
        return y, algo, st

    fn = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(P("x"), {k: P() for k in state}),
        out_specs=(P("x"), P(), {k: P() for k in state}), check_vma=False))
    text = fn.lower(x, state).compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text or "collective-permute" in text
