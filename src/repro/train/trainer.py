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
from .checkpoint import latest_step, load_checkpoint, save_checkpoint
from .optimizer import adamw_init
from .step import (TrainStepConfig, make_train_step, named_shardings,
                   opt_state_specs)


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
        steps = steps or self.tcfg.steps
        data = make_dataset(self.cfg, self.tcfg.data,
                            start_step=self.step_idx)
        it = iter(data)
        disp = dispatcher()
        t_last = time.perf_counter()
        try:
            for _ in range(steps):
                # live policy hot-reload: epoch bump -> rebuild (retrace)
                if disp.epoch != self._policy_epoch:
                    self._policy_epoch = disp.epoch
                    self._build_step()

                batch = {k: jax.numpy.asarray(v)
                         for k, v in next(it).items()}
                t0 = time.perf_counter()
                self.params, self.opt_state, metrics = self._step_fn(
                    self.params, self.opt_state, batch)
                jax.block_until_ready(metrics["loss"])
                dt = time.perf_counter() - t0
                self.step_idx += 1

                # profiler plugin feed: step latency -> shared eBPF maps
                disp.profiler_feed(
                    comm_id=0, latency_ns=int(dt * 1e9),
                    coll=CollType.ALL_REDUCE, channels=0,
                    ts_ns=time.monotonic_ns())

                m = {k: float(np.asarray(v)) for k, v in metrics.items()}
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
        finally:
            if hasattr(data, "stop"):
                data.stop()
        return self.metrics_log
