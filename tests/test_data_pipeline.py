"""The synthetic data pipeline: batches equal, bit for bit, those of the
per-position ``rng.choice`` walk it replaced, and the prefetcher hands
them out in step order when it runs ahead of its consumer."""

import time

import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.data import DataConfig, SyntheticLMDataset, make_dataset
from repro.obs import spans


def _walk_with_choice(ds, step):
    """The walk as first written, kept frozen as the oracle: one
    ``rng.choice`` per position, which rebuilds its CDF on each call."""
    d, c = ds.dcfg, ds.cfg
    rng = np.random.RandomState((d.seed * 1_000_003 + step) % 2**31)
    B, S = d.global_batch, d.seq_len
    toks = np.empty((B, S + 1), np.int32)
    toks[:, 0] = rng.randint(0, c.vocab, B)
    for t in range(1, S + 1):
        state = toks[:, t - 1] % ds.n_states
        choice = rng.choice(4, size=B, p=ds.succ_p)
        nxt = ds.succ[state, choice]
        reset = rng.rand(B) < 0.2
        nxt[reset] = rng.choice(c.vocab, size=reset.sum(), p=ds.unigram)
        toks[:, t] = nxt
    out = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    if c.family == "audio":
        out["frames"] = rng.randn(B, c.n_audio_frames,
                                  c.d_model).astype(np.float32)
    if c.family == "vlm":
        out["patches"] = rng.randn(B, c.n_patch_tokens,
                                   c.d_model).astype(np.float32)
    return out


@pytest.mark.parametrize("step", [0, 17])
@pytest.mark.parametrize("seed", [0, 2**31 - 5])
@pytest.mark.parametrize("arch,vocab,batch,seq", [
    ("tinyllama-1.1b", None, 1, 64),
    ("tinyllama-1.1b", None, 4, 64),
    ("qwen3-1.7b", 151_936, 1, 24),
    ("qwen3-1.7b", 151_936, 4, 24),
    ("whisper-large-v3", None, 1, 32),
    ("whisper-large-v3", None, 4, 32),
    ("llava-next-mistral-7b", None, 1, 32),
    ("llava-next-mistral-7b", None, 4, 32),
])
def test_batch_equals_the_per_position_choice_walk(arch, vocab, batch, seq,
                                                   seed, step):
    cfg = get_smoke_config(arch)
    if vocab:
        cfg = cfg.with_overrides(vocab=vocab)
    ds = SyntheticLMDataset(cfg, DataConfig(seq_len=seq, global_batch=batch,
                                            seed=seed))
    got, want = ds.batch(step), _walk_with_choice(ds, step)
    assert sorted(got) == sorted(want)
    assert {"audio": "frames", "vlm": "patches"}.get(cfg.family,
                                                     "tokens") in got
    for k in want:
        assert got[k].dtype == want[k].dtype and got[k].shape == \
            want[k].shape
        np.testing.assert_array_equal(got[k], want[k])


def _until(cond, timeout=30.0):
    end = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < end, "prefetch thread did not get there"
        time.sleep(0.005)


def test_a_prefetcher_ahead_of_its_consumer_keeps_step_order():
    """The producer fills its queue and blocks on ``put`` with one batch
    in hand; the consumer still gets the steps in order from the start
    step, and every started batch is used or dropped."""
    cfg = get_smoke_config("tinyllama-1.1b")
    dcfg = DataConfig(seq_len=32, global_batch=2, seed=3, prefetch=2)
    ds = SyntheticLMDataset(cfg, dcfg)
    before = spans.counters()
    pf = make_dataset(cfg, dcfg, start_step=5)
    it = iter(pf)
    got = []
    for used in range(4):
        # full queue, a batch in hand, the thread blocked on put
        _until(lambda: pf.q.full() and pf._started == used + dcfg.prefetch
               + 1)
        time.sleep(0.2)
        assert pf._started == used + dcfg.prefetch + 1
        got.append(next(it))
    pf.stop()
    pf._t.join(timeout=10)
    assert not pf._t.is_alive()

    for i, b in enumerate(got):
        want = ds.batch(5 + i)
        for k in ("tokens", "labels"):
            np.testing.assert_array_equal(b[k], want[k])
    after = spans.counters()
    d = {k: after.get(f"repro.data.batches_{k}", 0) -
         before.get(f"repro.data.batches_{k}", 0)
         for k in ("started", "used", "dropped")}
    assert d["used"] == 4
    assert d["started"] == d["used"] + d["dropped"]
    assert d["dropped"] >= dcfg.prefetch
