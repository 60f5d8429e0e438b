"""The general generator of training traffic, driven by a traffic file.

Tokens follow the repository's ``synthetic-lm`` corpus: Zipf-distributed
tokens with a planted successor table, so that a share of transitions
is predictable and fine-tuning has signal.  The arithmetic is copied
from ``data/pipeline.py::_synthetic_corpus`` and vectorised over
samples; the program receives only the generated arrays.

Everything is a function of the seed: the corpus, the order of every
epoch, and so the rows of every step.  Every seed gets the same sizes.
"""
from __future__ import annotations

import numpy as np

MASK32 = 0xFFFFFFFF


def rng(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator for one use of the seed (any size of int)."""
    return np.random.default_rng([stream, seed & MASK32, seed >> 32])


def corpus(seed: int, *, samples: int, seq: int, vocab: int,
           zipf_s: float = 1.0, planted_share: float = 0.6) -> np.ndarray:
    """(samples, seq + 1) int32 tokens: each next token is the planted
    successor of the previous one with probability ``planted_share``,
    else a Zipf(``zipf_s``) draw over the vocabulary."""
    g = rng(seed, 1)
    succ = g.integers(0, vocab, size=vocab)
    p = 1.0 / np.arange(1, vocab + 1) ** zipf_s
    p /= p.sum()
    toks = np.empty((samples, seq + 1), np.int32)
    toks[:, 0] = g.integers(0, vocab, size=samples)
    planted = g.random((samples, seq)) < planted_share
    draws = g.choice(vocab, size=(samples, seq), p=p)
    for t in range(1, seq + 1):
        toks[:, t] = np.where(planted[:, t - 1], succ[toks[:, t - 1]],
                              draws[:, t - 1])
    return toks


class Epochs:
    """Batches of distinct samples; every epoch visits every sample
    once, in an order drawn from the seed."""

    def __init__(self, tokens: np.ndarray, batch: int, seed: int):
        self.tokens = tokens
        self.batch = batch
        self.n = tokens.shape[0]
        if self.n % batch:
            raise ValueError(f"{self.n} samples do not split into "
                             f"batches of {batch}")
        self._g = rng(seed, 2)
        self._order = None
        self.step = 0

    @property
    def steps_per_epoch(self) -> int:
        return self.n // self.batch

    def ids(self, step: int) -> np.ndarray:
        """Sample ids of ``step`` (steps are taken in order)."""
        i = step % self.steps_per_epoch
        if i == 0:
            self._order = self._g.permutation(self.n).astype(np.int32)
        return self._order[i * self.batch:(i + 1) * self.batch]

    def next(self) -> dict:
        ids = self.ids(self.step)
        self.step += 1
        chunk = self.tokens[ids]
        return {"tokens": chunk[:, :-1], "targets": chunk[:, 1:],
                "mask": np.ones(chunk[:, 1:].shape, np.float32),
                "sample_ids": ids}
