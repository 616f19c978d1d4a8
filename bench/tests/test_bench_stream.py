"""The reference's batches are the program's: ``bench/stream.py`` makes,
from the same seed and mix, the tokens that the program's data pipeline
feeds ``Trainer.run``."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench.stream import TokenStream  # noqa: E402
from repro.data import DataConfig, SyntheticLMDataset  # noqa: E402

MIX = json.loads((ROOT / "bench/traffic/train_b1_s4096.json").read_text())


class _Model:
    family = "dense"

    def __init__(self, vocab):
        self.vocab = vocab


@pytest.mark.parametrize("vocab,batch,seq,seed", [
    (512, 2, 64, 7), (512, 4, 32, 2 ** 31 - 5), (50304, 3, 128, 99)])
def test_stream_is_the_programs(vocab, batch, seq, seed):
    prog = SyntheticLMDataset(
        _Model(vocab), DataConfig(seq_len=seq, global_batch=batch, seed=seed,
                                  zipf_a=MIX["stream"]["zipf_a"]))
    mine = TokenStream(vocab, batch, seq, seed, MIX["stream"])
    for step in (0, 1, 17):
        want, got = prog.batch(step), mine.batch_at(step)
        for k in ("tokens", "labels"):
            assert got[k].dtype == want[k].dtype
            np.testing.assert_array_equal(got[k], want[k])


def test_other_mix_gives_other_tokens():
    """A mix the program does not follow (here more reset draws) changes
    the reference's tokens, so the loss comparison would catch it."""
    prog = SyntheticLMDataset(_Model(512), DataConfig(seq_len=64,
                                                      global_batch=2, seed=7))
    mine = TokenStream(512, 2, 64, 7, {**MIX["stream"], "reset_p": 0.5})
    assert not np.array_equal(prog.batch(0)["tokens"],
                              mine.batch_at(0)["tokens"])
