"""The one generator of the benchmark's inputs, driven by a mix's file.

A mix's ``kind`` names the cell module that drives it
(``bench/<kind>_cell.py``).

* ``kind: train`` — a ring of token batches.  The rule is a copy of the
  program's synthetic pipeline (``repro.data.pipeline``): a noisy
  order-2 recurrence ``t_i = (31 t_{i-1} + 17 t_{i-2} + 7) mod V`` with a
  share ``noise`` of positions drawn uniformly, so a model can lower its
  loss.  Every batch of the ring differs.
"""

from __future__ import annotations

import json
import os

import numpy as np

from bench import seeds

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> dict:
    with open(os.path.join(HERE, f"{name}.json")) as f:
        return json.load(f)


def markov2(rng: np.random.Generator, batch: int, length: int, vocab: int,
            noise: float) -> np.ndarray:
    toks = np.empty((batch, length), np.int64)
    toks[:, :2] = rng.integers(0, vocab, (batch, 2))
    rand = rng.integers(0, vocab, (batch, length))
    noisy = rng.random((batch, length)) < noise
    for i in range(2, length):
        det = (toks[:, i - 1] * 31 + toks[:, i - 2] * 17 + 7) % vocab
        toks[:, i] = np.where(noisy[:, i], rand[:, i], det)
    return toks.astype(np.int32)


def train_batches(seed: int, mix: dict, batch: int, vocab: int):
    """``mix["ring"]`` (tokens, labels) pairs of [batch, seq_len] int32."""
    rng = seeds.host_rng(seed, "train_batches")
    out = []
    for _ in range(mix["ring"]):
        t = markov2(rng, batch, mix["seq_len"] + 1, vocab,
                    mix["data"]["noise"])
        out.append((t[:, :-1], t[:, 1:]))
    return out
