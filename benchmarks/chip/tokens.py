"""The benchmark's own token source.

``MarkovTokenStream`` is a copy of the generator in ``repro.data`` (a
Zipf-weighted order-1 Markov chain over the vocabulary), kept here so
that the benchmark's inputs do not change when the program's data
pipeline does.  ``PregeneratedFeed`` draws all the rows a run needs in
set-up, in one call per worker, and hands them to the program through
``next_batch`` as one ``jnp.asarray`` each, so the generator's numpy
loop is never billed to the measured window.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


class MarkovTokenStream:
    """Per-shard synthetic stream: shards draw from disjoint RNG streams
    over one shared transition structure."""

    def __init__(self, vocab_size: int, seq_len: int, shard: int = 0,
                 seed: int = 0, branch: int = 4, mix: float = 0.8):
        self.vocab = vocab_size
        self.seq_len = seq_len
        self.rng = np.random.default_rng(np.random.SeedSequence([seed, shard]))
        struct = np.random.default_rng(np.random.SeedSequence([seed, 12345]))
        ranks = np.arange(1, vocab_size + 1)
        self.unigram = (1.0 / ranks) / np.sum(1.0 / ranks)
        self.branch = branch
        self.succ = struct.integers(0, vocab_size, (vocab_size, branch))
        self.mix = mix

    def rows(self, n: int) -> np.ndarray:
        """(n, seq_len) int32 token rows."""
        B, S = n, self.seq_len
        out = np.empty((B, S), np.int64)
        out[:, 0] = self.rng.choice(self.vocab, size=B, p=self.unigram)
        follow = self.rng.random((B, S)) < self.mix
        which = self.rng.integers(0, self.branch, (B, S))
        resample = self.rng.choice(self.vocab, size=(B, S), p=self.unigram)
        for t in range(1, S):
            chained = self.succ[out[:, t - 1], which[:, t]]
            out[:, t] = np.where(follow[:, t], chained, resample[:, t])
        return out.astype(np.int32)


class PregeneratedFeed:
    """A worker's stream for the program: ``next_batch(b)`` returns the
    next ``b`` pre-generated rows as ``{"tokens": jnp.asarray(...)}``,
    cycling when the rows run out.  ``taken`` lists the (start, b) of
    every batch handed out, so the reference can be given the same rows."""

    def __init__(self, rows: np.ndarray):
        self.rows = rows
        self.pos = 0
        self.cycles = 0
        self.taken: list = []

    def next_batch(self, batch_size: int):
        with jax.profiler.TraceAnnotation("bench.data"):
            if self.pos + batch_size > self.rows.shape[0]:
                self.pos = 0
                self.cycles += 1
            start = self.pos
            self.pos += batch_size
            self.taken.append((start, batch_size))
            return {"tokens": jnp.asarray(self.rows[start:start + batch_size])}

    def batch_rows(self, i: int) -> np.ndarray:
        """The rows of the ``i``-th batch handed out."""
        start, b = self.taken[i]
        return self.rows[start:start + b]
