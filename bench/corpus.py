"""Synthetic training text: Zipf unigrams with repeated n-gram motifs.

A vectorised copy of ``repro.data.pipeline.SyntheticCorpus``: the stream
is a run of segments, each either one of ``n_motifs`` fixed motifs of
``motif_len`` tokens (with probability ``motif_prob``) or 4 to 31 tokens
drawn from a Zipf law of exponent ``zipf`` over the vocabulary.  Windows
of seq + 1 tokens are packed into rows: tokens and next-token labels.
Every batch is a function of (seed, step) alone, and every row differs.
"""

from __future__ import annotations

import numpy as np


class Corpus:
    def __init__(self, vocab: int, seed: int, *, zipf: float, motif_len: int,
                 n_motifs: int, motif_prob: float):
        self.vocab, self.seed = vocab, seed
        self.motif_len, self.motif_prob = motif_len, motif_prob
        rng = np.random.default_rng([seed, 0])
        self.motifs = rng.integers(0, vocab, size=(n_motifs, motif_len))
        p = 1.0 / np.arange(1, vocab + 1) ** zipf
        self.cdf = np.cumsum(p / p.sum())

    def stream(self, n: int, step: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, 1, step])
        out = np.minimum(np.searchsorted(self.cdf, rng.random(n)),
                         self.vocab - 1).astype(np.int32)
        # segments: enough of them to cover n (each is at least 4 long)
        k = n // 4 + 1
        is_motif = rng.random(k) < self.motif_prob
        lens = np.where(is_motif, self.motif_len, rng.integers(4, 32, k))
        starts = np.concatenate([[0], np.cumsum(lens)[:-1]])
        pick = rng.integers(len(self.motifs), size=k)
        sel = is_motif & (starts < n)
        pos = (starts[sel, None] + np.arange(self.motif_len)).ravel()
        toks = self.motifs[pick[sel]].ravel()
        keep = pos < n
        out[pos[keep]] = toks[keep]
        return out

    def batch(self, rows: int, seq: int, step: int) -> dict:
        w = self.stream(rows * (seq + 1), step).reshape(rows, seq + 1)
        return {"tokens": w[:, :-1].copy(), "labels": w[:, 1:].copy()}
