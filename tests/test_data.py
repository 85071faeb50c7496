"""Data pipeline: determinism, stream structure, prefetch."""
import numpy as np

from repro.data.pipeline import prefetch
from repro.data.synthetic import LMStream, classification


def test_lm_stream_deterministic():
    a = next(LMStream(vocab=64, seed=3).batches(2, 16))
    b = next(LMStream(vocab=64, seed=3).batches(2, 16))
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    np.testing.assert_array_equal(a["labels"], b["labels"])


def test_lm_stream_resume_midstream():
    it = LMStream(vocab=64, seed=3).batches(2, 16)
    next(it)
    second = next(it)
    resumed = next(LMStream(vocab=64, seed=3).batches(2, 16, start_step=1))
    np.testing.assert_array_equal(second["tokens"], resumed["tokens"])


def test_lm_stream_bigram_structure():
    s = LMStream(vocab=64, seed=0)
    b = next(s.batches(8, 128, p_bigram=0.9))
    follows = (s._succ[b["tokens"]] == b["labels"]).mean()
    assert follows > 0.8  # planted bigram is learnable signal


def test_labels_are_next_tokens():
    b = next(LMStream(vocab=64, seed=1).batches(2, 32))
    assert b["tokens"].shape == b["labels"].shape == (2, 32)


def test_classification_shared_means_across_splits():
    xtr, ytr = classification(512, 32, 4, seed=0)
    xte, yte = classification(512, 32, 4, seed=9)
    mu_tr = np.stack([xtr[ytr == c].mean(0) for c in range(4)])
    mu_te = np.stack([xte[yte == c].mean(0) for c in range(4)])
    # same class means up to sampling noise
    assert np.abs(mu_tr - mu_te).mean() < 0.2


def test_prefetch_preserves_order_and_count():
    items = [{"i": np.asarray([k])} for k in range(7)]
    out = list(prefetch(iter(items), size=3))
    assert [int(o["i"][0]) for o in out] == list(range(7))


def test_lm_stream_draws_match_rng_choice():
    """The precomputed-CDF draw is the same stream rng.choice(vocab, p=...)
    gives: identical tokens, batch after batch."""
    s = LMStream(vocab=300, seed=4)
    got = s.batches(3, 20)
    for step in range(2):
        rng = np.random.default_rng((4, step))
        toks = np.empty((3, 21), np.int32)
        toks[:, 0] = rng.choice(300, size=3, p=s._p)
        for t in range(20):
            follow = rng.random(3) < 0.8
            rand = rng.choice(300, size=3, p=s._p)
            toks[:, t + 1] = np.where(follow, s._succ[toks[:, t]], rand)
        np.testing.assert_array_equal(next(got)["tokens"], toks[:, :-1])
