"""Synthetic token batches for training cells, drawn from ``--seed``.

The distribution is the program's own synthetic stream
(``repro.data.pipeline.SyntheticLMStream``), copied here so that the
benchmark's inputs cannot change with the program: Zipf-distributed
unigrams, with a repeated short motif on a random share of the rows.
Every batch is a pure function of ``(seed, step)``; every seed gives
the same shapes.

A traffic file (``bench/traffic/<name>.json``) sets the parameters:

    rows_per_chip   sequences each chip trains on per step
    seq             tokens per sequence
    zipf_a          exponent of the unigram distribution
    ngram_repeat    period of the motif
    motif_share     probability that a row is the motif
"""

from __future__ import annotations

import numpy as np


class ZipfBatches:
    """Batches of ``rows`` sequences of ``seq`` tokens over ``vocab``."""

    def __init__(self, traffic: dict, vocab: int, rows: int, seed: int):
        self.vocab, self.rows, self.seed = vocab, rows, seed
        self.seq = int(traffic["seq"])
        self.motif_share = float(traffic["motif_share"])
        ranks = np.arange(1, vocab + 1, dtype=np.float64)
        probs = ranks ** (-float(traffic["zipf_a"]))
        self._probs = probs / probs.sum()
        self._motif = np.random.default_rng(seed).integers(
            0, vocab, size=int(traffic["ngram_repeat"]))

    def batch_at(self, step: int) -> dict:
        """``{"tokens", "targets"}``, each ``(rows, seq)`` int32."""
        rng = np.random.default_rng((self.seed * 1_000_003 + step) * 65_537)
        S = self.seq
        base = rng.choice(self.vocab, size=(self.rows, S + 1), p=self._probs)
        motif_rows = rng.random(self.rows) < self.motif_share
        reps = -(-(S + 1) // len(self._motif))
        base[motif_rows] = np.tile(self._motif, reps)[: S + 1]
        return {"tokens": base[:, :-1].astype(np.int32),
                "targets": base[:, 1:].astype(np.int32)}
