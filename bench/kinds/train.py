"""Runner of the training cells: ``Trainer.run`` as the program's launcher
builds it, on the cell's mesh, fed by the program's own data pipeline.

Set-up builds the trainer (parameters made on the device from the seed),
then runs the checked steps through ``Trainer.run``: these compile every
program the window uses, and their losses, first clipped gradient and
parameter change are what the reference is compared with.  A mix with a
``swap`` entry replaces the attached policy between the checked steps
too, so that both policies' step programs are traced and cached before
the window.  Each ``Trainer.run`` call starts the program's prefetch
thread anew; set-up ends once the checked steps' threads have ended.
The window then drives ``Trainer.run`` for about ``--seconds``, its
work fixed up front from the wall time of the last checked step:

* without ``swap``, one call of as many steps as fill the time;
* with ``swap``, as many rounds as fill the time of ``link.replace()``
  to the next policy, one step (the first on the new policy: retrace,
  cache load, the pipeline's first batch, step) and the rest of the
  round's steps.

After the window the program's state is freed and the reference runs
the checked steps again from the same seed, on batches that
``bench/stream.py`` makes as the program's pipeline does.
"""

from __future__ import annotations

import gc
import math
import shutil
import sys
import threading
import time
from typing import Dict, List

import numpy as np

from .. import check
from ..reference import transformer as ref
from ..stream import TokenStream

N_CHECK = 3         # the checked steps; the limits were read at three


class Monitor:
    """JAX's own compile events, with the host time they ended at."""
    DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration",
                 "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.events: List[tuple] = []
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, name, secs, **_):
        if name in self.DURATIONS:
            self.events.append((time.perf_counter(), name, secs))

    def _ev(self, name, **_):
        if name.startswith("/jax/compilation_cache/cache_"):
            self.events.append((time.perf_counter(), name, 0.0))

    def between(self, t0, t1, name=None):
        return [e for e in self.events if t0 <= e[0] <= t1 and
                (name is None or e[1] == name)]


def seed31(seed: int) -> int:
    """The 31-bit weight seed of a run seed of any size."""
    return int(np.random.default_rng([abs(int(seed)), 2]).integers(
        1, 2 ** 31 - 1))


def program_config(config: dict):
    """The program's ModelConfig: the registry's entry with the published
    sizes of the configuration file mapped onto it."""
    from repro.configs import get_config
    prog = config["program"]
    pub = config["published"]
    over = {k: pub[v] for k, v in prog["from_published"].items()}
    return get_config(prog["arch"]).with_overrides(**over, **prog.get("set", {}))


def semantics(config: dict, chips: int) -> dict:
    """The configuration's stated semantics, with the MoE dispatch groups
    of the mesh: each chip routes its own contiguous slice of tokens."""
    return {**config["semantics"], "dispatch_groups": chips}


def _leaf_norms(tree, scale=1.0) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32)))) * scale for k, v in ref.leaves(t).items()})
    return {k: float(v) for k, v in f(tree).items()}


def _change_norms(params, s31, c) -> Dict[str, float]:
    """Per-leaf norms of the parameters' change since the seed's weights,
    which are made again on the parameters' own devices.  A leaf that the
    program has and the seed's weights lack (or the other way) reads inf."""
    import jax
    import jax.numpy as jnp
    have = ref.leaves(params)
    shard = {k: v.sharding for k, v in have.items()}

    def f(p, s):                # the seed traced: one program for all seeds
        p0 = ref.leaves(ref.init(s, c))
        return {k: jnp.sqrt(jnp.sum(jnp.square(
            v - jax.lax.with_sharding_constraint(p0[k], shard[k]))))
            for k, v in ref.leaves(p).items()
            if k in p0 and p0[k].shape == v.shape}
    got = {k: float(v) for k, v in jax.jit(f)(params, s31).items()}
    want = ref.leaves(jax.eval_shape(lambda: ref.init(s31, c)))
    for k in set(have) | set(want):
        got.setdefault(k, float("inf"))
    return got


def _join_prefetchers():
    """Wait for the prefetch threads of finished ``Trainer.run`` calls,
    which end once the batch they are making is done."""
    for t in threading.enumerate():
        if t is not threading.current_thread() and t.name.endswith("(_work)"):
            t.join()


def _peak_bytes(devs) -> int:
    peaks = []
    for d in devs:
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0)))
    return max(peaks)


class Job:
    """The trainer of one run, built as ``repro.launch.train.build_trainer``
    builds it, on the cell's devices."""

    def __init__(self, *, seed, config, traffic, devices, out):
        import jax
        import repro.policies as pol
        from repro.collectives.dispatch import reset_dispatcher
        from repro.core.runtime import PolicyRuntime
        from repro.data import DataConfig
        from repro.launch.mesh import mesh_axes
        from repro.train import (AdamWConfig, Trainer, TrainerConfig,
                                 TrainStepConfig)

        self.jax, self.pol = jax, pol
        self.config, self.traffic, self.devices = config, traffic, devices
        self.pub = config["published"]
        self.B, self.S = int(traffic["batch"]), int(traffic["seq"])
        tp = int(traffic["tp"])
        self.chips = len(devices)
        self.opt = dict(traffic["optimizer"])
        self.sem = semantics(config, self.chips)
        self.s31 = seed31(seed)
        opt = self.opt

        rt = PolicyRuntime()
        self.link = rt.attach(getattr(pol, traffic["policy"]).program) \
            if traffic["policy"] != "none" else None
        reset_dispatcher(runtime=rt)
        cfg = program_config(config)
        mesh = jax.sharding.Mesh(
            np.array(devices).reshape(self.chips // tp, tp),
            ("data", "model"))
        tcfg = TrainerConfig(
            steps=10 ** 9, log_every=10 ** 9, ckpt_every=0,
            ckpt_dir=str(out / "ckpt"),
            data=DataConfig(seq_len=self.S, global_batch=self.B,
                            seed=self.s31,
                            zipf_a=float(traffic["stream"]["zipf_a"])),
            step=TrainStepConfig(
                opt=AdamWConfig(lr=opt["lr"], b1=opt["b1"], b2=opt["b2"],
                                eps=opt["eps"],
                                weight_decay=opt["weight_decay"],
                                grad_clip=opt["grad_clip"]),
                total_steps=opt["total_steps"],
                warmup_steps=opt["warmup_steps"]),
            seed=self.s31)
        self.stream = TokenStream(cfg.vocab, self.B, self.S, self.s31,
                                  traffic["stream"])
        self.tr = Trainer(cfg, mesh_axes(mesh, fsdp=True), mesh, tcfg)
        swap = traffic.get("swap")
        self.policies = [traffic["policy"]] + list(swap["policies"]) \
            if swap else []
        self.last_step_wall_s = 0.0

    def replace(self, i: int):
        with self.jax.profiler.TraceAnnotation("bench.replace"):
            self.link.replace(getattr(
                self.pol, self.policies[i % len(self.policies)]).program)

    def checked_steps(self) -> dict:
        """The first steps, through ``Trainer.run``: the program's losses,
        first clipped gradient (AdamW's first moment over 1 - b1) and
        parameter change, per leaf.  Records the wall time of the last
        call, first batch and step included, which plans the window."""
        tr = self.tr
        tr.run(steps=1)
        grad_norms = _leaf_norms(tr.opt_state["m"],
                                 1.0 / (1.0 - self.opt["b1"]))
        for i in range(1, N_CHECK):
            if self.policies:
                self.replace(i)
            t = time.perf_counter()
            tr.run(steps=1)
            self.last_step_wall_s = time.perf_counter() - t
        return {"losses": [m["loss"] for m in tr.metrics_log[:N_CHECK]],
                "grad_norms": grad_norms,
                "change_norms": _change_norms(tr.params, self.s31, self.pub)}

    def free(self):
        """Drop the program's state before the reference runs."""
        self.tr = None
        gc.collect()
        self.jax.clear_caches()

    def reference(self, *, quant=None, weights=None) -> dict:
        import jax.numpy as jnp
        batches = [{k: jnp.asarray(v) for k, v in
                    self.stream.batch_at(i).items()}
                   for i in range(N_CHECK)]
        with self.jax.default_device(self.devices[0]):
            return ref.train(self.s31, self.pub, self.sem, self.opt,
                             batches, quant=quant, weights=weights,
                             devices=self.devices)

    def half_batch_weights(self):
        """Loss weights of the half-batch fault: the first half of the rows
        (of the positions, for a batch of one row) kept."""
        import jax.numpy as jnp
        w = np.zeros((self.B, self.S), np.float32)
        if self.B > 1:
            w[: self.B // 2] = 1
        else:
            w[:, : self.S // 2] = 1
        return [jnp.asarray(w)] * N_CHECK


def run(*, args, cell, config, traffic, devices, t0, out) -> dict:
    import jax
    mon = Monitor()
    job = Job(seed=args.seed, config=config, traffic=traffic,
              devices=devices, out=out)
    tr, swap = job.tr, traffic.get("swap")
    prog = job.checked_steps()
    _join_prefetchers()
    setup_s = time.perf_counter() - t0

    # -- the window: its work fixed from the last checked step's wall time --
    step_wall = max(job.last_step_wall_s, 1e-3)
    trace_dir = out / "trace"
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    swaps = []
    first = len(tr.metrics_log)
    w0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        if swap:
            every = int(swap["every"])
            rounds = max(1, round(args.seconds / (every * step_wall)))
            for i in range(N_CHECK, N_CHECK + rounds):
                ts = time.perf_counter()
                job.replace(i)
                tr_ = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.step_after_replace"):
                    tr.run(steps=1)
                te = time.perf_counter()
                swaps.append({
                    "replace_s": tr_ - ts, "reload_to_step_s": te - ts,
                    "retrace_s": sum(e[2] for e in mon.between(tr_, te)
                                     if e[1] in Monitor.DURATIONS)})
                if every > 1:
                    tr.run(steps=every - 1)
        else:
            with jax.profiler.TraceAnnotation("bench.run"):
                tr.run(steps=max(2, round(args.seconds / step_wall)))
    w1 = time.perf_counter()
    if args.trace:
        jax.profiler.stop_trace()
    steps = len(tr.metrics_log) - first
    window_losses = [m["loss"] for m in tr.metrics_log[first:]]
    compiles = len(mon.between(w0, w1, "/jax/compilation_cache/cache_misses"))
    peak = _peak_bytes(devices)

    rec = {"setup_s": setup_s, "window_s": w1 - w0, "steps": steps,
           "tokens": steps * job.B * job.S, "batch": job.B, "seq": job.S,
           "chips": job.chips, "swaps": swaps, "published": job.pub,
           "device_kind": devices[0].device_kind, "memory_peak_bytes": peak,
           "compiles_in_window": compiles, "rehearsal": args.rehearse}
    if args.trace:
        from .. import trace_reduce
        rec["trace"] = trace_reduce.summarize(trace_dir, steps=steps,
                                              window_s=w1 - w0)
        shutil.rmtree(trace_dir, ignore_errors=True)

    # -- free the program, then the reference --------------------------------
    del tr
    job.free()
    r0 = time.perf_counter()
    compared = check.compare(prog, job.reference(), config["limits"])
    ref_s = time.perf_counter() - r0
    finite = all(math.isfinite(x) for x in window_losses)
    rec["correct"] = check.passes(compared) and finite
    rec["compared"] = compared
    rec["attempted"] = N_CHECK + steps
    rec["failed"] = sum(not math.isfinite(x) for x in window_losses)
    print(f"bench: {steps} window steps in {w1 - w0:.3f}s, setup "
          f"{setup_s:.3f}s, {compiles} compile-cache misses in window, "
          f"peak {peak} B, reference {ref_s:.1f}s", file=sys.stderr)
    return rec
