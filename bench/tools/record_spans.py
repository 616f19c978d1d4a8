"""Record a small profile of the trainer with the program's spans.

  python3 bench/tools/record_spans.py [--out DIR] [--sleep S]

Builds a one-layer smoke-size ``qwen3-1.7b`` trainer on the first device,
compiles its step with one ``Trainer.run(steps=1)``, then profiles one
``Trainer.run(steps=2)``.  Here only, ``SyntheticLMDataset.batch`` sleeps
``--sleep`` seconds first, so that the device waits for the second batch
between the two steps.  Prints the trace's size, the ``repro.`` spans of
its host plane, and ``bench.trace_reduce.reduce`` with the program's
spans as labels, and writes ``<out>/spans.xplane.pb``: the part of the
trace that the reduction reads (:func:`strip`).
``bench/testdata/v5e_spans.xplane.pb`` was recorded with this script on
one TPU v5e.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
KEEP_LINES = ("XLA Ops", "XLA Modules")


def _xplane_pb2():
    """The XSpace protobuf module that TensorFlow ships, loaded from its
    file so that TensorFlow itself is not imported."""
    pkg = importlib.util.find_spec("tensorflow").submodule_search_locations[0]
    path = Path(pkg) / "tsl/profiler/protobuf/xplane_pb2.py"
    spec = importlib.util.spec_from_file_location("xplane_pb2", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def strip(src: Path, dst: Path) -> None:
    """Copy of the trace ``src`` with what ``bench/trace_reduce.py`` reads:
    per device its ``XLA Ops`` (each op's text cut to its name and
    opcode) and ``XLA Modules`` (with their ``run_id``), and on the host
    the ``CompleteCallbacks`` (with their ``run_id``) and the program's
    ``repro.`` spans."""
    from bench import trace_reduce
    pb = _xplane_pb2()
    space = pb.XSpace()
    space.ParseFromString(src.read_bytes())
    out = pb.XSpace()
    for plane in space.planes:
        device = plane.name.startswith("/device:") and \
            not plane.name.startswith("/device:CUSTOM")
        if not device and not plane.name.startswith("/host:CPU"):
            continue
        names = {k: m.name for k, m in plane.event_metadata.items()}
        stat_names = {k: m.name for k, m in plane.stat_metadata.items()}
        keep = out.planes.add(id=plane.id, name=plane.name)
        used, used_stats = set(), set()
        for line in plane.lines:
            if device and line.name not in KEEP_LINES:
                continue
            new = None
            for ev in line.events:
                name = names[ev.metadata_id]
                if not device and not (name == "CompleteCallbacks" or
                                       name.startswith("repro.")):
                    continue
                if new is None:
                    new = keep.lines.add(id=line.id, name=line.name,
                                         timestamp_ns=line.timestamp_ns)
                e = new.events.add(metadata_id=ev.metadata_id,
                                   offset_ps=ev.offset_ps,
                                   duration_ps=ev.duration_ps)
                used.add(ev.metadata_id)
                for st in ev.stats:
                    if stat_names.get(st.metadata_id) == "run_id":
                        e.stats.add().CopyFrom(st)
                        used_stats.add(st.metadata_id)
        for k in used:
            name = names[k]
            if device and " = " in name:
                name = (f"%{trace_reduce.instruction(name)} = "
                        f"{trace_reduce.opcode(name)}()")
            keep.event_metadata[k].id = k
            keep.event_metadata[k].name = name
        for k in used_stats:
            keep.stat_metadata[k].id = k
            keep.stat_metadata[k].name = stat_names[k]
    dst.write_bytes(out.SerializeToString())


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=".bench_out/spans_probe")
    ap.add_argument("--sleep", type=float, default=0.2)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

    import jax
    import numpy as np
    from jax.sharding import Mesh

    from bench import trace_reduce
    from repro.collectives.dispatch import reset_dispatcher
    from repro.configs import get_smoke_config
    from repro.core.runtime import PolicyRuntime
    from repro.data import DataConfig, pipeline
    from repro.launch.mesh import mesh_axes
    from repro.train import Trainer, TrainerConfig

    make = pipeline.SyntheticLMDataset.batch

    def slow_batch(self, step):
        time.sleep(args.sleep)
        return make(self, step)

    pipeline.SyntheticLMDataset.batch = slow_batch

    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform, "kind": dev.device_kind}))
    reset_dispatcher(runtime=PolicyRuntime())
    mesh = Mesh(np.array([dev]).reshape(1, 1), ("data", "model"))
    cfg = get_smoke_config("qwen3-1.7b").with_overrides(n_layers=1)
    tr = Trainer(cfg, mesh_axes(mesh, fsdp=True), mesh, TrainerConfig(
        steps=2, log_every=10 ** 9, ckpt_every=0,
        data=DataConfig(seq_len=64, global_batch=2)))
    tr.run(steps=1)

    out = Path(args.out)
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False      # keeps the file small
    jax.profiler.start_trace(str(out), profiler_options=opts)
    t0 = time.perf_counter()
    tr.run(steps=2)
    window_s = time.perf_counter() - t0
    jax.profiler.stop_trace()

    path = sorted(out.rglob("*.xplane.pb"))[-1]
    print("xplane", path, path.stat().st_size, "bytes")
    pd = jax.profiler.ProfileData.from_file(str(path))
    ops, mods, host, done = trace_reduce.load(pd)
    for name, s, e in sorted(host, key=lambda h: h[1]):
        if name.startswith("repro."):
            print(f"  {name} {s} {e - s}")
    print(json.dumps(trace_reduce.reduce(ops, mods, host, done, steps=2,
                                         window_s=window_s,
                                         labels=("repro.",))))
    kept = out / "spans.xplane.pb"
    strip(path, kept)
    print("kept", kept, kept.stat().st_size, "bytes")


if __name__ == "__main__":
    main()
