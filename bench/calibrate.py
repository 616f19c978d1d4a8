"""Readings that the limits of ``correct`` are set from, on the chip.

  python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 [--faults]

For each seed, in one process: the program's checked steps against the
reference (a sound run: the lower reading), and with ``--faults`` the
control and the planted faults, each put in the program's place and
compared with the same reference:

  control     the reference with every matmul operand rounded to
              float8_e4m3fn (the precision below the configuration's
              bfloat16);
  half_batch  the reference's loss taken over half of the batch (the
              first half of the rows, or of the positions for one row);
  unchanged   a step that returns its state unchanged reads 1 on
              update_gap by construction, and is not run.

Prints one JSON line per seed and reading; the benchmark's own runs do
not run this.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench import check, run
    spec, cell, config, traffic = run.load_cell(args.workload, args.rehearse)
    devs = run.start(int(cell["chips"]), args.rehearse)
    if devs is None:
        return 2
    import jax.numpy as jnp
    from bench.kinds.train import Job
    limits = config["limits"]
    for seed in [int(s) for s in args.seeds.split(",")]:
        job = Job(seed=seed, config=config, traffic=traffic, devices=devs,
                  out=run.OUT / cell["name"])
        prog = job.checked_steps()
        job.free()
        want = job.reference()
        rows = {"program": prog}
        if args.faults:
            rows["control"] = job.reference(quant=jnp.float8_e4m3fn)
            rows["half_batch"] = job.reference(
                weights=job.half_batch_weights())
        for name, got in rows.items():
            c = check.compare(got, want, limits)
            print(json.dumps({"seed": seed, "reading": name,
                              **{k: v["value"] for k, v in c.items()},
                              "losses": got["losses"],
                              "ref_losses": want["losses"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
