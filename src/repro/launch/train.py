"""Training driver.

  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
      --steps 200 --seq 256 --batch 8 [--smoke] [--policy size_aware]

The ``("data", "model")`` mesh spans every device the process sees, with
``--tp`` devices per tensor-parallel group on the model axis; parameters
are FSDP-sharded over the data axis.  ``--smoke`` swaps in the reduced
config of the same family (CPU-sized); ``--layers`` cuts the depth of
the published config so it fits fewer chips, keeping its widths.

The last line gives the final loss and where the steps' time went: the
mean milliseconds per step of waiting for data, upload, dispatch,
waiting for the device and the step's bookkeeping, and the prefetch
thread's batches started, used and dropped (:func:`span_summary`).
"""

from __future__ import annotations

import argparse
import collections
from typing import Dict, List, Optional, Sequence

from ..configs import get_config, get_smoke_config
from ..core.runtime import PolicyRuntime
from ..collectives.dispatch import reset_dispatcher
from ..data import DataConfig
from ..obs import spans
from ..train import AdamWConfig, Trainer, TrainerConfig, TrainStepConfig
from .cache import enable_compile_cache
from .mesh import make_device_mesh, mesh_axes


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--layers", type=int, default=0,
                    help="decoder depth (0 keeps the config's own)")
    ap.add_argument("--tp", type=int, default=1,
                    help="devices per tensor-parallel group")
    ap.add_argument("--policy", default="none")
    ap.add_argument("--bucketed", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    return ap.parse_args(argv)


def build_trainer(args: argparse.Namespace,
                  runtime: Optional[PolicyRuntime] = None) -> Trainer:
    """The Trainer ``main`` runs: ``runtime`` (a fresh host-tier one by
    default) backs the collective dispatcher, and ``--policy`` attaches
    a shipped verified policy to it."""
    rt = runtime if runtime is not None else PolicyRuntime()
    if args.policy != "none":
        import repro.policies as pol
        rt.attach(getattr(pol, args.policy).program)
        print(f"attached verified policy: {args.policy}")
    reset_dispatcher(runtime=rt)

    cfg = get_smoke_config(args.arch) if args.smoke \
        else get_config(args.arch).with_overrides(remat=True)
    if args.layers:
        cfg = cfg.with_overrides(n_layers=args.layers)
    mesh = make_device_mesh(tp=args.tp)
    ax = mesh_axes(mesh, fsdp=True)

    tcfg = TrainerConfig(
        steps=args.steps, log_every=args.log_every,
        ckpt_dir=args.ckpt_dir or f"/tmp/repro_ckpt_{args.arch}",
        ckpt_every=args.ckpt_every,
        data=DataConfig(seq_len=args.seq, global_batch=args.batch),
        step=TrainStepConfig(opt=AdamWConfig(lr=args.lr),
                             total_steps=args.steps, warmup_steps=max(
                                 args.steps // 20, 5),
                             bucketed_grad_sync=args.bucketed))
    return Trainer(cfg, ax, mesh, tcfg)


def span_summary(log: List[Dict[str, float]]) -> str:
    """Where the logged steps' time went, read from the span store: mean
    milliseconds per step of each part of ``repro.train.step``, over the
    steps after the first two (which compile), and the prefetch thread's
    batches started, used and dropped."""
    steps = {m["step"] for m in (log[2:] or log)}
    held = spans.snapshot()["spans"]
    ids = {s["id"] for s in held if s["name"] == "repro.train.step"
           and s["attrs"]["step"] in steps}
    ns: Dict[str, int] = collections.defaultdict(int)
    for s in held:
        if s["parent"] in ids and s["end_ns"] is not None:
            ns[s["name"]] += s["end_ns"] - s["start_ns"]
    parts = ", ".join(
        f"{p.removeprefix('train.')} {ns['repro.' + p] * 1e-6 / len(ids):.1f}"
        for p in ("data.wait", "train.upload", "train.dispatch",
                  "train.device_wait", "train.post"))
    c = spans.counters()
    batches = ", ".join(f"{k} {c.get('repro.data.batches_' + k, 0)}"
                        for k in ("started", "used", "dropped"))
    return (f"ms per step over {len(ids)} steps: {parts}; "
            f"batches {batches}")


def main(argv: Optional[Sequence[str]] = None):
    args = parse_args(argv)
    enable_compile_cache()
    tr = build_trainer(args)
    if args.ckpt_every and tr.maybe_restore():
        print(f"restored from step {tr.step_idx}")
    log = tr.run()
    print(f"final loss {log[-1]['loss']:.4f} over {len(log)} steps; "
          f"{span_summary(log)}")


if __name__ == "__main__":
    main()
