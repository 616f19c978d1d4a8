"""Readings that the limits of ``correct`` are set from, on the chip, for
a cell of the ``train_mla_moe`` kind (``bench/calibrate.py`` drives the
``train`` kind's job, which binds the transformer reference).

  python3 bench/tools/calibrate_mla_moe.py --workload <cell> --seeds 1,2 \
      [--faults] [--rehearse]

For each seed, in one process: the program's checked steps against the
configuration's reference (a sound run: the lower reading), and with
``--faults`` the control (the reference with every matmul operand rounded
to float8_e4m3fn) and the half-batch fault, each compared with the same
reference.  Every reference routes by the program's choice of experts;
``choice_gap`` is the share of those chosen pairs that the reading's own
top-k lacks (the control's: its float8 scores').  Prints one JSON line
per seed and reading.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


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
    from bench.kinds.train_mla_moe import MoeJob
    limits = config["limits"]
    for seed in [int(s) for s in args.seeds.split(",")]:
        job = MoeJob(seed=seed, config=config, traffic=traffic, devices=devs,
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
                              # the program's: the reference's own top-k
                              "choice_gap": got.get("choice_gap",
                                                    want["choice_gap"]),
                              "losses": got["losses"],
                              "ref_losses": want["losses"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
