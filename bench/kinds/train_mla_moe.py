"""Runner of the training cells of a DeepSeek-V3-layer model (latent
attention, dense-then-MoE layers, dropless routing over a chip's share of
the experts): ``Trainer.run`` on the cell's mesh, as ``train.py`` builds
it, with what that module binds to ``reference/transformer.py`` taken
from the configuration's ``"reference"`` key instead.

The configuration file holds the published keys at its top level, as the
source's ``config.json`` names them, with the cuts of ``reduced`` applied;
``deployment.expert_parallel`` times the experts held here is the
router's width.  The window and the set-up are ``train.py``'s; besides,
the run records the step counters ``repro.moe.*`` over the window, and a
traced run sums the window's device time by name scope
(``bench/scope_reduce.py``) before the trace is deleted.  That no
routed pair is dropped shows in the comparison with the reference, whose
held experts each run over every token.

Before each checked step the run takes the experts the program chooses
for that step's batch (``repro.models.expert_choices``, at the step's
weights), and the reference routes by them: which experts a token picks
is a discrete decision that bfloat16 and float32 scores make differently
for the tokens whose scores lie closer than rounding, and each such token
would move the leaf gradients by a whole expert's share.  The choice is
checked on its own: ``choice_gap``, the share of the program's chosen
pairs that the reference's own top-k lacks, against the configuration's
limit of that name.
"""

from __future__ import annotations

import importlib
import math
import shutil
import sys
import time
from typing import Dict

from .. import check
from .train import N_CHECK, Job, Monitor, _join_prefetchers, _peak_bytes, \
    _leaf_norms

SCOPES = ("repro.mla", "repro.moe.route", "repro.moe.experts",
          "repro.moe.shared")
COUNTERS = ("repro.moe.pairs_here", "repro.moe.max_expert_pairs")


def published(config: dict) -> dict:
    """The published keys (the file's top level, the cuts applied) with
    the deployment and the assumed values that the reference reads."""
    pub = {k: v for k, v in config.items() if not isinstance(v, dict)}
    return {**pub, "deployment": config["deployment"],
            "assumed": config["assumed"]}


def program_view(config: dict) -> dict:
    """The configuration as ``train.Job`` reads it: ``published`` and the
    program's overrides, with the router over every expert of the layer,
    its balance and bias rates from ``assumed``, dropless sigmoid
    routing."""
    pub = published(config)
    prog = config["program"]
    width = pub["n_routed_experts"] * pub["deployment"]["expert_parallel"]
    return {**config, "published": pub, "program": {
        **prog, "set": {**prog["set"], "n_experts": width,
                        "seq_aux_coef": pub["assumed"]["seq_aux_alpha"],
                        "bias_rate": pub["assumed"]["bias_update_gamma"]}}}


def counters() -> Dict[str, int]:
    """The program's ``repro.moe.*`` counters (empty without them)."""
    try:
        from repro.obs import spans
    except ImportError:
        return {}
    c = spans.counters()
    return {k: c[k] for k in COUNTERS if k in c}


class MoeJob(Job):
    """``train.Job`` with the configuration's own reference."""

    def __init__(self, *, config, **kw):
        self.ref = importlib.import_module(
            f"bench.reference.{config['reference']}")
        self.choices, self._choose = None, None
        super().__init__(config=program_view(config), **kw)

    def program_choices(self, i: int):
        """The experts the program chooses, at its present weights, for
        the tokens of step ``i``: (MoE layers, tokens, top-k), on the
        host."""
        import jax
        import numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.models import expert_choices
        from repro.train.step import batch_specs
        tr = self.tr
        if self._choose is None:
            specs = batch_specs(tr.cfg, tr.ax)
            self._choose = jax.jit(jax.shard_map(
                lambda p, b: expert_choices(p, b, tr.cfg, tr.ax),
                mesh=tr.mesh, in_specs=(tr.param_specs, specs),
                out_specs=P(None, specs["tokens"][0], None),
                check_vma=False))
        b = self.stream.batch_at(i)
        return np.asarray(self._choose(tr.params, {
            "tokens": b["tokens"], "labels": b["labels"]}))

    def checked_steps(self) -> dict:
        """As ``train.Job.checked_steps``, with the parameters' change
        measured against this reference's weights of the seed, and the
        program's choice of experts for each checked step kept for the
        reference."""
        tr = self.tr
        self.choices = [self.program_choices(0)]
        tr.run(steps=1)
        grad_norms = _leaf_norms(tr.opt_state["m"],
                                 1.0 / (1.0 - self.opt["b1"]))
        for i in range(1, N_CHECK):
            self.choices.append(self.program_choices(i))
            t = time.perf_counter()
            tr.run(steps=1)
            self.last_step_wall_s = time.perf_counter() - t
        self._choose = None
        return {"losses": [m["loss"] for m in tr.metrics_log[:N_CHECK]],
                "grad_norms": grad_norms,
                "change_norms": self._change_norms(tr.params)}

    def _change_norms(self, params) -> Dict[str, float]:
        import jax
        import jax.numpy as jnp
        ref, s31, pub = self.ref, self.s31, self.pub
        have = ref.leaves(params)
        shard = {k: v.sharding for k, v in have.items()}

        def f(p, s):
            p0 = ref.leaves(ref.init(s, pub))
            return {k: jnp.sqrt(jnp.sum(jnp.square(
                v - jax.lax.with_sharding_constraint(p0[k], shard[k]))))
                for k, v in ref.leaves(p).items()
                if k in p0 and p0[k].shape == v.shape}
        got = {k: float(v) for k, v in jax.jit(f)(params, s31).items()}
        want = ref.leaves(jax.eval_shape(lambda: ref.init(s31, pub)))
        for k in set(have) | set(want):
            got.setdefault(k, float("inf"))
        return got

    def reference(self, *, quant=None, weights=None) -> dict:
        import jax.numpy as jnp
        batches = [{k: jnp.asarray(v) for k, v in
                    self.stream.batch_at(i).items()}
                   for i in range(N_CHECK)]
        with self.jax.default_device(self.devices[0]):
            with self.jax.default_matmul_precision("highest"):
                return self.ref.train(self.s31, self.pub, self.opt, batches,
                                      quant=quant, weights=weights,
                                      choices=self.choices)


def run(*, args, cell, config, traffic, devices, t0, out) -> dict:
    import jax
    mon = Monitor()
    job = MoeJob(seed=args.seed, config=config, traffic=traffic,
                 devices=devices, out=out)
    tr = job.tr
    prog = job.checked_steps()
    _join_prefetchers()
    setup_s = time.perf_counter() - t0

    step_wall = max(job.last_step_wall_s, 1e-3)
    trace_dir = out / "trace"
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    first = len(tr.metrics_log)
    c0 = counters()
    w0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.run"):
            tr.run(steps=max(2, round(args.seconds / step_wall)))
    w1 = time.perf_counter()
    if args.trace:
        jax.profiler.stop_trace()
    c1 = counters()
    steps = len(tr.metrics_log) - first
    window_losses = [m["loss"] for m in tr.metrics_log[first:]]
    compiles = len(mon.between(w0, w1, "/jax/compilation_cache/cache_misses"))
    peak = _peak_bytes(devices)

    rec = {"setup_s": setup_s, "window_s": w1 - w0, "steps": steps,
           "tokens": steps * job.B * job.S, "batch": job.B, "seq": job.S,
           "chips": job.chips, "swaps": [], "published": job.pub,
           "device_kind": devices[0].device_kind, "memory_peak_bytes": peak,
           "compiles_in_window": compiles, "rehearsal": args.rehearse,
           "moe_window": {k: c1[k] - c0.get(k, 0) for k in c1}}
    if args.trace:
        from .. import scope_reduce, trace_reduce
        rec["trace"] = trace_reduce.summarize(trace_dir, steps=steps,
                                              window_s=w1 - w0)
        rec["scopes"] = scope_reduce.summarize(trace_dir, SCOPES)
        shutil.rmtree(trace_dir, ignore_errors=True)

    del tr
    job.free()
    r0 = time.perf_counter()
    want = job.reference()
    compared = check.compare(prog, want, config["limits"])
    compared["choice_gap"] = {"value": want["choice_gap"],
                              "limit": config["limits"]["choice_gap"]}
    ref_s = time.perf_counter() - r0
    finite = all(math.isfinite(x) for x in window_losses)
    rec["correct"] = check.passes(compared) and finite
    rec["compared"] = compared
    rec["attempted"] = N_CHECK + steps
    rec["failed"] = sum(not math.isfinite(x) for x in window_losses)
    print(f"bench: {steps} window steps in {w1 - w0:.3f}s, setup "
          f"{setup_s:.3f}s, {compiles} compile-cache misses in window, "
          f"peak {peak} B, reference {ref_s:.1f}s, moe counters "
          f"{counters()}, device seconds by scope {rec.get('scopes')}",
          file=sys.stderr)
    return rec

