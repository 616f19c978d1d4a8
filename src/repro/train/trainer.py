"""Trainer loop: data -> step -> metrics -> checkpoint, with live policy
hot-reload (the paper's headline operational capability) and the
profiler-plugin closed loop.

Hot-reload semantics (§T3): the trainer watches the policy runtime's epoch;
when an operator reloads a policy mid-run, the next step retraces against
the new decisions (the retrace is the TPU analogue of NCCL's communicator
warmup) — the job itself never restarts.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np
from jax.sharding import Mesh

from ..collectives.dispatch import dispatcher
from ..core.context import CollType
from ..data import DataConfig, make_dataset
from ..models import init_params
from ..models.config import ModelConfig
from ..models.layers import MeshAxes
from ..obs.spans import count, span
from .checkpoint import latest_step, load_checkpoint, save_checkpoint
from .optimizer import adamw_init
from .step import (MOE_COUNTERS, TrainStepConfig, make_train_step,
                   named_shardings, opt_state_specs)


@dataclasses.dataclass
class TrainerConfig:
    steps: int = 100
    log_every: int = 10
    ckpt_every: int = 0                 # 0 = disabled
    ckpt_dir: str = "/tmp/repro_ckpt"
    data: DataConfig = dataclasses.field(default_factory=DataConfig)
    step: TrainStepConfig = dataclasses.field(default_factory=TrainStepConfig)
    seed: int = 0


class Trainer:
    def __init__(self, cfg: ModelConfig, ax: MeshAxes, mesh: Mesh,
                 tcfg: TrainerConfig):
        self.cfg = cfg
        self.ax = ax
        self.mesh = mesh
        self.tcfg = tcfg
        self.metrics_log: List[Dict[str, float]] = []

        self._init_state()
        self._build_step()
        self._policy_epoch = dispatcher().epoch
        self.step_idx = 0
        self._runs = 0

    def _init_state(self):
        """Parameters and AdamW state, created already sharded over the
        mesh.  Built eagerly, every array would land whole on the first
        device, which cannot hold a full-size model's state."""
        specs = {}

        def init(key):
            params, specs["params"] = init_params(key, self.cfg, self.ax)
            return params, adamw_init(params)

        key = jax.random.PRNGKey(self.tcfg.seed)
        jax.eval_shape(init, key)               # the specs, no arrays
        self.param_specs = specs["params"]
        out = named_shardings(self.mesh, (self.param_specs,
                                          opt_state_specs(self.param_specs)))
        self.params, self.opt_state = jax.jit(init, out_shardings=out)(key)

    def _build_step(self):
        self._step_fn, self.opt_specs = make_train_step(
            self.cfg, self.ax, self.mesh, self.param_specs, self.tcfg.step)

    # -- checkpoint -----------------------------------------------------------
    def maybe_restore(self) -> bool:
        st = latest_step(self.tcfg.ckpt_dir)
        if st is None:
            return False
        state, step, _ = load_checkpoint(
            self.tcfg.ckpt_dir, {"p": self.params, "o": self.opt_state})
        self.params, self.opt_state = state["p"], state["o"]
        self.step_idx = step
        return True

    def save(self):
        save_checkpoint(self.tcfg.ckpt_dir, self.step_idx,
                        {"p": self.params, "o": self.opt_state},
                        extra={"arch": self.cfg.name})

    # -- main loop --------------------------------------------------------------
    def run(self, *, steps: Optional[int] = None) -> List[Dict[str, float]]:
        """``steps`` steps (default ``tcfg.steps``) fed by a prefetch
        thread of its own.

        Spans (:mod:`repro.obs.spans`): ``repro.train.run`` per call, and
        per step a ``repro.train.step`` whose children are, in order:

        * ``repro.train.rebuild`` after a policy swap;
        * ``repro.data.wait``, the next batch from the prefetch thread;
        * ``repro.train.upload``;
        * ``repro.train.dispatch``: trace and compile (or cache load) when
          the step program is new, else the enqueue;
        * ``repro.train.device_wait``;
        * ``repro.train.post``: metrics, profiler feed, log, checkpoint;
          a dropless MoE stack's step counters go to the counters
          ``repro.moe.pairs_here`` and ``.max_expert_pairs`` (the step's
          largest held group, summed over steps).

        A step's ``step_time_s`` is its dispatch plus its device wait."""
        steps = steps or self.tcfg.steps
        self._runs += 1
        with span("repro.train.run", run=self._runs):
            data = make_dataset(self.cfg, self.tcfg.data,
                                start_step=self.step_idx)
            it = iter(data)
            disp = dispatcher()
            try:
                for _ in range(steps):
                    self._step(it, disp)
            finally:
                if hasattr(data, "stop"):
                    data.stop()
        return self.metrics_log

    def _step(self, it, disp) -> None:
        n = self.step_idx + 1
        with span("repro.train.step", step_trace=True, step=n):
            # live policy hot-reload: epoch bump -> rebuild (retrace)
            if disp.epoch != self._policy_epoch:
                with span("repro.train.rebuild"):
                    self._policy_epoch = disp.epoch
                    self._build_step()
            with span("repro.data.wait"):
                raw = next(it)
            with span("repro.train.upload"):
                batch = {k: jax.numpy.asarray(v) for k, v in raw.items()}
            with span("repro.train.dispatch") as call:
                self.params, self.opt_state, metrics = self._step_fn(
                    self.params, self.opt_state, batch)
            with span("repro.train.device_wait") as wait:
                jax.block_until_ready(metrics["loss"])
            dt = call.seconds + wait.seconds
            self.step_idx += 1

            with span("repro.train.post"):
                # profiler plugin feed: step latency -> shared eBPF maps
                disp.profiler_feed(
                    comm_id=0, latency_ns=int(dt * 1e9),
                    coll=CollType.ALL_REDUCE, channels=0,
                    ts_ns=time.monotonic_ns())

                m = {k: float(np.asarray(v)) for k, v in metrics.items()}
                for k in MOE_COUNTERS:
                    if k in m:
                        count("repro.moe." + k, int(m[k]))
                m["step"] = self.step_idx
                m["step_time_s"] = dt
                self.metrics_log.append(m)
                if self.step_idx % self.tcfg.log_every == 0:
                    print(f"step {self.step_idx:6d} loss {m['loss']:.4f} "
                          f"gnorm {m['grad_norm']:.3f} lr {m['lr']:.2e} "
                          f"{dt * 1e3:.0f} ms", flush=True)
                if self.tcfg.ckpt_every and \
                        self.step_idx % self.tcfg.ckpt_every == 0:
                    self.save()
