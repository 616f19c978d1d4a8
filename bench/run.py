"""The chip benchmark's one command.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell's entry in ``BENCHMARK.json``
names a configuration (``bench/configs/<config>.json``) and a traffic
mix (``bench/traffic/<traffic>.json``); the mix's ``kind`` names the
runner (``bench/kinds/<kind>.py``) and each metric is read by
``bench/metrics/<metric>.py``.  With ``--trace 0`` the result carries
the cell's end-to-end metrics, with ``--trace 1`` its per-layer ones.
The last line of standard output is the result; the numbers compared
for ``correct`` close standard error.

There is no CPU fallback: a host whose devices are not TPUs, or that
has fewer than the cell's chips, exits 2 with no result.
``--rehearse`` is the CPU rehearsal: the configuration's and the mix's
``smoke`` sizes on host-CPU devices, for the tests only.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = ROOT / ".bench_out"
CACHE = ROOT / ".jax_cache"


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the smoke sizes (tests only)")
    return ap.parse_args(argv)


def load_json(path: Path):
    return json.loads(path.read_text())


def smoke(entry: dict, rehearse: bool) -> dict:
    """The entry with its ``smoke`` overlay applied when rehearsing."""
    out = {k: v for k, v in entry.items() if k != "smoke"}
    if rehearse:
        for k, v in entry.get("smoke", {}).items():
            out[k] = {**out[k], **v} if isinstance(v, dict) and \
                isinstance(out.get(k), dict) else v
    return out


def load_cell(name: str, rehearse: bool):
    spec = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    config, traffic = load_files(ROOT / conf["file"], cell["traffic"],
                                 rehearse)
    return spec, cell, config, traffic


def load_files(config_file: Path, traffic: str, rehearse: bool):
    """A configuration file and a traffic mix, at the smoke sizes when
    rehearsing."""
    return (smoke(load_json(config_file), rehearse),
            smoke(load_json(BENCH / "traffic" / f"{traffic}.json"), rehearse))


def metrics_for(spec: dict, cell: str, trace: bool) -> list:
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if "workloads" not in m or cell in m["workloads"]]


def read_metric(name: str, rec: dict):
    mod = importlib.import_module(f"bench.metrics.{name}")
    return mod.read(rec)


def devices(chips: int, rehearse: bool):
    """The cell's devices: TPUs, at least ``chips`` of them."""
    import jax
    devs = jax.devices()
    want = "cpu" if rehearse else "tpu"
    if devs[0].platform != want or len(devs) < chips:
        print(f"bench: needs {chips} {want} device(s); found "
              f"{len(devs)} {devs[0].platform} ({devs[0].device_kind})",
              file=sys.stderr)
        return None
    return devs[:chips]


def start(chips: int, rehearse: bool):
    """Import the program and JAX, with the compile cache in the checkout
    (host-CPU devices instead when rehearsing); the cell's devices, or
    None when the program or the chips are missing."""
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   f" --xla_force_host_platform_device_count"
                                   f"={chips}").strip()
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
        # the TPU runtime's logs, which default to a fixed /tmp path
        os.environ.setdefault("TPU_LOG_DIR", str(OUT / "tpu_logs"))
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    try:
        import repro  # noqa: F401  the system under test
    except ImportError as e:
        print(f"bench: the program is not in this checkout ({e})",
              file=sys.stderr)
        return None
    import jax
    if not rehearse:
        jax.config.update("jax_compilation_cache_dir", str(CACHE))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return devices(chips, rehearse)


def main(argv=None) -> int:
    args = parse_args(argv)
    spec, cell, config, traffic = load_cell(args.workload, args.rehearse)
    devs = start(int(cell["chips"]), args.rehearse)
    if devs is None:
        return 2

    runner = importlib.import_module(f"bench.kinds.{traffic['kind']}")
    rec = runner.run(args=args, cell=cell, config=config, traffic=traffic,
                     devices=devs, t0=T0, out=OUT / cell["name"])

    metrics = {}
    for m in metrics_for(spec, cell["name"], bool(args.trace)):
        val = read_metric(m["name"], rec)
        if val is not None:
            metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    d = devs[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devs),
              "memory_peak_bytes": rec["memory_peak_bytes"]}
    result = {"correct": rec["correct"], "attempted": rec["attempted"],
              "failed": rec["failed"], "metrics": metrics, "device": device}
    if args.trace and rec.get("trace"):
        t = rec["trace"]
        device["busy_s"] = t["busy_s"]
        device["window_s"] = t["window_s"]
        result["breakdown"] = {"device_ops": t["device_ops"],
                               "idle_gaps": t["idle_gaps"]}
    result["compared"] = rec["compared"]
    for name, c in rec["compared"].items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(ROOT))
    sys.exit(main())
