"""Token stream of the training cells: a seeded Zipf/Markov walk.

The benchmark's own copy of the process by which the program's
``repro.data.SyntheticLMDataset`` makes its batches, written again so
that the reference is given its batches by code that is not the
program's.  Each of ``n_states`` states prefers ``successors`` random
successor tokens with probabilities ``succ_p``; with probability
``reset_p`` a position is instead drawn from a Zipf(``zipf_a``) unigram
over the vocabulary.  Batch ``step`` of seed ``seed`` draws from
``RandomState((seed * 1_000_003 + step) % 2**31)`` in the program's
order (first tokens; then per position the successor choice, the reset
coin and one unigram draw per reset), so the same seed gives the same
tokens as the program's pipeline, bit for bit.  A mix whose parameters
the program does not follow (it fixes all but ``zipf_a``) gives other
tokens, and the loss comparison fails.

All uniforms of a batch are drawn in one call and read off in that
order, so a batch of 4,096 positions costs milliseconds here.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


class TokenStream:
    def __init__(self, vocab: int, batch: int, seq: int, seed: int,
                 params: dict):
        self.vocab, self.batch, self.seq = vocab, batch, seq
        self.seed = int(seed)
        p = params
        rng = np.random.RandomState(self.seed)
        self.n_states = min(int(p["n_states"]), vocab)
        self.n_succ = int(p["successors"])
        self.succ = rng.randint(0, vocab, size=(self.n_states, self.n_succ))
        self.succ_cdf = self._cdf(np.asarray(p["succ_p"], np.float64))
        zipf = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** \
            float(p["zipf_a"])
        self.uni_cdf = self._cdf(zipf / zipf.sum())
        self.reset_p = float(p["reset_p"])

    @staticmethod
    def _cdf(p):
        cdf = p.cumsum()
        cdf /= cdf[-1]
        return cdf

    def batch_at(self, step: int) -> Dict[str, np.ndarray]:
        B, S = self.batch, self.seq
        rng = np.random.RandomState((self.seed * 1_000_003 + int(step))
                                    % 2 ** 31)
        toks = np.empty((B, S + 1), np.int32)
        toks[:, 0] = rng.randint(0, self.vocab, B)
        u = rng.random_sample(3 * B * S)        # at most 3 draws a row
        at = 0
        for t in range(1, S + 1):
            choice = self.succ_cdf.searchsorted(u[at:at + B], side="right")
            nxt = self.succ[toks[:, t - 1] % self.n_states, choice]
            reset = u[at + B:at + 2 * B] < self.reset_p
            at += 2 * B
            k = int(reset.sum())
            if k:
                nxt[reset] = self.uni_cdf.searchsorted(u[at:at + k],
                                                       side="right")
                at += k
            toks[:, t] = nxt
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
