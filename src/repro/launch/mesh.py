"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state.  The training launcher spans the
devices present (:func:`make_device_mesh`); only dryrun.py (which sets
XLA_FLAGS first) asks for the 256/512-device production meshes.
"""

from __future__ import annotations

import jax

from ..models.layers import MeshAxes


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_device_mesh(tp: int = 1):
    """``("data", "model")`` mesh over every device this process sees,
    ``tp`` devices per tensor-parallel group on the model axis."""
    import numpy as np
    devs = jax.devices()
    if tp < 1 or len(devs) % tp:
        raise ValueError(f"tp={tp} does not divide the {len(devs)} "
                         f"device(s) present")
    return jax.sharding.Mesh(np.array(devs).reshape(len(devs) // tp, tp),
                             ("data", "model"))


def mesh_axes(mesh, *, fsdp: bool = True,
              gather_bf16: bool = False) -> MeshAxes:
    """Derive the MeshAxes descriptor from a mesh."""
    names = mesh.axis_names
    sizes = dict(zip(names, mesh.devices.shape))
    return MeshAxes(
        data="data", model="model",
        pod="pod" if "pod" in names else None,
        fsdp=fsdp, gather_bf16=gather_bf16,
        tp=sizes.get("model", 1),
        dp=sizes.get("data", 1),
        n_pods=sizes.get("pod", 1),
    )


def make_host_mesh(n: int = 1):
    """Small mesh over real host devices (tests/examples).

    Raises when fewer than ``n`` devices exist instead of silently
    shrinking — a shrunk mesh changes every collective's rank count and
    invalidates sizes/bandwidths downstream, which used to surface as a
    confusing shape error (or worse, silently different numbers) far
    from the cause.
    """
    import numpy as np
    avail = jax.devices()
    if len(avail) < n:
        raise ValueError(
            f"make_host_mesh(n={n}) needs {n} device(s) but this process "
            f"has {len(avail)} ({', '.join(str(d) for d in avail[:8])}"
            f"{'...' if len(avail) > 8 else ''}); for a host-CPU mesh "
            f"set XLA_FLAGS=--xla_force_host_platform_device_count={n} "
            "BEFORE importing jax")
    devs = avail[:n]
    return jax.sharding.Mesh(np.array(devs).reshape(1, len(devs)),
                             ("data", "model"))


def mesh_topology(mesh, axis_name: str = None) -> dict:
    """Topology facts the dispatcher feeds into policy contexts.

    Returns ``{"n_nodes", "ranks_per_node", "n_devices", "axis_sizes"}``.
    Node structure comes from ``Device.process_index`` — on a
    single-process host-CPU mesh every device reports process 0, so
    ``n_nodes == 1`` and ``ranks_per_node == n_devices``; a multi-process
    launch reports one node per process.  ``axis_name`` scopes the device
    set to one mesh axis (the axis a collective runs over); ``None``
    covers the whole mesh.
    """
    devs = list(mesh.devices.flat)
    names = list(mesh.axis_names)
    sizes = dict(zip(names, mesh.devices.shape))
    if axis_name is not None:
        if axis_name not in sizes:
            raise ValueError(f"mesh has no axis {axis_name!r}; "
                             f"axes: {names}")
    procs = {getattr(d, "process_index", 0) for d in devs}
    n_nodes = max(1, len(procs))
    n_devices = len(devs)
    return {
        "n_nodes": n_nodes,
        "ranks_per_node": max(1, n_devices // n_nodes),
        "n_devices": n_devices,
        "axis_sizes": sizes,
    }
