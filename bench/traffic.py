"""The one generator of the benchmark's inputs: token rows for training,
from a cell's traffic parameters and the run's seed.

Token ids follow a Zipf law over a fixed random order of the vocabulary
(drawn from the traffic file's own ``base_seed``), as text does. Every
seed gives rows of the same sizes; it draws only the ids and, where rows
pack documents, where they are cut.
"""
from __future__ import annotations

from typing import List

import numpy as np

from harness import np_rng


def zipf_ids(rng, vocab: int, n: int, exponent: float, order) -> np.ndarray:
    """``n`` token ids, rank r drawn with weight 1 / (r + 1) ** exponent."""
    w = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** exponent
    ranks = rng.choice(vocab, size=n, p=w / w.sum())
    return order[ranks].astype(np.int32)


def vocab_order(vocab: int, base_seed: int) -> np.ndarray:
    return np.random.default_rng(base_seed).permutation(vocab)


def train_rows(t: dict, vocab: int, seed: int, n_batches: int) -> List[dict]:
    """``n_batches`` batches of ``batch`` rows of ``seq`` tokens (and their
    next-token labels), every row different. With ``docs`` > 1 each row
    packs that many documents and carries ``segment_ids``."""
    rng = np_rng(seed, 1)
    order = vocab_order(vocab, t["base_seed"])
    B, T = t["batch"], t["seq"]
    out = []
    for _ in range(n_batches):
        x = zipf_ids(rng, vocab, B * (T + 1), t["zipf_exponent"],
                     order).reshape(B, T + 1)
        batch = {"tokens": x[:, :-1].copy(), "labels": x[:, 1:].copy()}
        if t.get("docs", 1) > 1:
            cuts = np.sort(rng.choice(np.arange(1, T), t["docs"] - 1,
                                      replace=False))
            seg = np.zeros((B, T), np.int32)
            for c in cuts:
                seg[:, c:] += 1
            last = np.concatenate([seg[:, 1:] != seg[:, :-1],
                                   np.ones((B, 1), bool)], axis=1)
            batch["labels"][last] = -100      # no target across documents
            batch["segment_ids"] = seg
        out.append(batch)
    return out

