"""The training comparison of a configuration and mix outside the cells.

  python3 bench/tools/program_fault.py --config olmoe-1b-7b \
      --traffic train_dp2_tp2_b4_s4096 --chips 4 --seeds 1,2,3 \
      [--tp 1] [--grad-clip 1e9] [--policy none] [--dtype float32] [--rehearse]

Runs the checked steps of ``bench/kinds/train.py`` and the reference
for each seed and prints the compared numbers with, per leaf, the
program's first clipped gradient norm over the reference's.  It is how
the program faults that keep the OLMoE 2x2 cell out of the benchmark
were read (PERF.md, section 7); the options change the mix (mesh,
clipping, policy) or the program's compute type to find a path on which
the program and the reference agree.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--chips", type=int, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--tp", type=int)
    ap.add_argument("--grad-clip", type=float)
    ap.add_argument("--policy")
    ap.add_argument("--dtype")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, str(ROOT))
    from bench import check, run
    config, traffic = run.load_files(
        ROOT / "bench/configs" / f"{args.config}.json", args.traffic,
        args.rehearse)
    if args.tp:
        traffic["tp"] = args.tp
    if args.grad_clip:
        traffic["optimizer"]["grad_clip"] = args.grad_clip
    if args.policy:
        traffic["policy"] = args.policy
    if args.dtype:
        config["program"]["set"]["dtype"] = args.dtype
    devs = run.start(args.chips, args.rehearse)
    if devs is None:
        return 2
    from bench.kinds.train import Job
    for seed in [int(s) for s in args.seeds.split(",")]:
        job = Job(seed=seed, config=config, traffic=traffic, devices=devs,
                  out=run.OUT / "program_fault")
        prog = job.checked_steps()
        job.free()
        want = job.reference()
        c = check.compare(prog, want, config["limits"])
        ratio = {k: prog["grad_norms"][k] / want["grad_norms"][k]
                 for k in want["grad_norms"]}
        print(json.dumps({"seed": seed, "tp": traffic["tp"],
                          "grad_clip": traffic["optimizer"]["grad_clip"],
                          "policy": traffic["policy"],
                          **{k: v["value"] for k, v in c.items()},
                          "losses": prog["losses"],
                          "ref_losses": want["losses"],
                          "grad_ratio": ratio}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
