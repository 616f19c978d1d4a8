"""Record a small profiler trace on the chip and describe its layout.

  python3 bench/tools/record_trace.py [--chips N] [--out DIR]

Runs a few steps of a tiny jitted program (a matmul, an elementwise pass
and, on several chips, a ring of ``ppermute``s and an ``all_gather``)
under ``jax.profiler``, then prints each plane, line and the most
frequent event names with their stats.  The trace the benchmark's
reduction is tested on was recorded with this script.
"""

from __future__ import annotations

import argparse
import collections
import json
import shutil
from pathlib import Path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--out", default=".bench_out/trace_probe")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()[:args.chips]
    print(json.dumps({"platform": devs[0].platform,
                      "kind": devs[0].device_kind, "count": len(devs)}))
    mesh = Mesh(np.array(devs), ("x",))
    n = len(devs)

    def body(a, b):
        with jax.named_scope("probe_matmul"):
            c = a @ b
        with jax.named_scope("probe_elementwise"):
            c = jnp.tanh(c) * 1.5
        if n > 1:
            c = jax.named_call(
                lambda v: jax.lax.ppermute(
                    v, "x", [(i, (i + 1) % n) for i in range(n)]),
                name="probe_ring")(c)
            g = jax.lax.all_gather(c[:8], "x", tiled=True)
            c = c + jnp.sum(g)
        return c

    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("x"), P()),
                              out_specs=P("x"), check_vma=False))
    sh = NamedSharding(mesh, P("x"))
    a = jax.device_put(jnp.ones((1024 * n, 1024), jnp.bfloat16), sh)
    b = jax.device_put(jnp.ones((1024, 1024), jnp.bfloat16),
                       NamedSharding(mesh, P()))
    f(a, b).block_until_ready()
    out = Path(args.out)
    shutil.rmtree(out, ignore_errors=True)
    jax.profiler.start_trace(str(out))
    for _ in range(3):
        with jax.profiler.TraceAnnotation("probe_step"):
            f(a, b).block_until_ready()
    jax.profiler.stop_trace()

    path = sorted(out.rglob("*.xplane.pb"))[0]
    print("xplane", path, path.stat().st_size)
    pd = jax.profiler.ProfileData.from_file(str(path))
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r} lines={len(lines)}")
        for line in lines:
            evs = list(line.events)
            names = collections.Counter(e.name for e in evs)
            print(f"  LINE {line.name!r} events={len(evs)} "
                  f"top={names.most_common(12)}")
            for e in evs[:3]:
                print(f"    EV {e.name!r} start={e.start_ns} "
                      f"dur={e.duration_ns} stats={list(e.stats)[:12]}")


if __name__ == "__main__":
    main()
