"""Deterministic, sharded, prefetching LM data pipeline.

Synthetic corpus: a mixture of Zipf-distributed unigrams with injected
n-gram structure (so the loss actually decreases — pure-uniform tokens
cannot be learned).  Deterministic per (seed, step): any host can
regenerate any batch, which is what makes the pipeline resumable and
multi-host-consistent without a data service.

For VLM/audio configs the pipeline also emits stub modality inputs
(patch/frame embeddings) per DESIGN.md's frontend-stub carve-out.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
from typing import Dict, Iterator, Optional

import numpy as np

from ..models.config import ModelConfig
from ..obs import spans


@dataclasses.dataclass(frozen=True)
class DataConfig:
    seq_len: int = 512
    global_batch: int = 8
    seed: int = 0
    zipf_a: float = 1.2
    ngram_order: int = 3
    prefetch: int = 2


def _cdf(p: np.ndarray) -> np.ndarray:
    cdf = p.cumsum()
    cdf /= cdf[-1]
    return cdf


class SyntheticLMDataset:
    """Markov-chain synthetic text: learnable structure, measurable loss."""

    def __init__(self, cfg: ModelConfig, dcfg: DataConfig):
        self.cfg = cfg
        self.dcfg = dcfg
        rng = np.random.RandomState(dcfg.seed)
        V = cfg.vocab
        # sparse per-state transition table: each state prefers 4 successors
        self.n_states = min(4096, V)
        self.succ = rng.randint(0, V, size=(self.n_states, 4))
        self.succ_p = np.array([0.5, 0.25, 0.15, 0.1])
        ranks = np.arange(1, V + 1, dtype=np.float64)
        zipf = 1.0 / ranks ** dcfg.zipf_a
        self.unigram = zipf / zipf.sum()
        # the tables RandomState.choice would rebuild on every call
        self.succ_cdf = _cdf(self.succ_p)
        self.unigram_cdf = _cdf(self.unigram)

    def batch(self, step: int) -> Dict[str, np.ndarray]:
        d, c = self.dcfg, self.cfg
        rng = np.random.RandomState((d.seed * 1_000_003 + step) % 2**31)
        B, S = d.global_batch, d.seq_len
        toks = np.empty((B, S + 1), np.int32)
        toks[:, 0] = rng.randint(0, c.vocab, B)
        # markov walk with 20% unigram resets.  Per position it reads, in
        # the order of rng.choice(4, B, p=succ_p), rng.rand(B) and
        # rng.choice(V, n_resets, p=unigram): B successor uniforms, B reset
        # coins, one uniform per reset.  All are drawn in one call (at most
        # 3 a row), then the generator is left where those calls leave it.
        state = rng.get_state()
        u = rng.random_sample(3 * B * S)
        at = 0
        for t in range(1, S + 1):
            choice = self.succ_cdf.searchsorted(u[at:at + B], side="right")
            nxt = self.succ[toks[:, t - 1] % self.n_states, choice]
            reset = u[at + B:at + 2 * B] < 0.2
            at += 2 * B
            k = int(reset.sum())
            if k:
                nxt[reset] = self.unigram_cdf.searchsorted(u[at:at + k],
                                                           side="right")
                at += k
            toks[:, t] = nxt
        rng.set_state(state)
        rng.random_sample(at)
        out = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
        if c.family == "audio":
            out["frames"] = rng.randn(B, c.n_audio_frames,
                                      c.d_model).astype(np.float32)
        if c.family == "vlm":
            out["patches"] = rng.randn(B, c.n_patch_tokens,
                                       c.d_model).astype(np.float32)
        return out

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        step = 0
        while True:
            yield self.batch(step)
            step += 1


class _Prefetcher:
    """Background-thread prefetch (host-side pipeline overlap).

    Each batch is a ``repro.data.batch`` span whose parent is the span
    open where the prefetcher was made (the trainer's run).  Counters:
    ``repro.data.batches_started``, ``batches_used`` (handed to the
    consumer) and ``batches_dropped`` (started and never handed out, as
    counted at :meth:`stop`: the batch in the making and any queued)."""

    def __init__(self, ds: SyntheticLMDataset, depth: int, start: int = 0):
        self.ds = ds
        self.q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._step = start
        self._parent = spans.current()
        self._lock = threading.Lock()       # orders a batch's start and stop
        self._started = self._used = 0
        self._t = threading.Thread(target=self._work, daemon=True)
        self._t.start()

    def _work(self):
        while True:
            with self._lock:
                if self._stop.is_set():
                    return
                self._started += 1
            spans.count("repro.data.batches_started")
            with spans.span("repro.data.batch", parent=self._parent,
                            step=self._step):
                b = self.ds.batch(self._step)
            self._step += 1
            while not self._stop.is_set():
                try:
                    self.q.put(b, timeout=0.1)
                    break
                except queue.Full:
                    continue

    def __iter__(self):
        while True:
            b = self.q.get()
            self._used += 1
            spans.count("repro.data.batches_used")
            yield b

    def stop(self):
        with self._lock:
            if self._stop.is_set():
                return
            self._stop.set()
            spans.count("repro.data.batches_dropped",
                        self._started - self._used)


def make_dataset(cfg: ModelConfig, dcfg: DataConfig, *,
                 prefetch: bool = True, start_step: int = 0):
    ds = SyntheticLMDataset(cfg, dcfg)
    if prefetch:
        return _Prefetcher(ds, dcfg.prefetch, start=start_step)
    return ds
