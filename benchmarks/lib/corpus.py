"""The one traffic generator: a seeded token corpus for a train loop.

A traffic file gives the parameters (``batches``, ``batch``, ``seq`` and
``tokens``: the unigram distribution); the same seed gives the same corpus.
The program receives only the generated arrays.
"""
from __future__ import annotations

import numpy as np


def unigram(vocab_size: int, tokens: dict, rng: np.random.Generator):
    """Token probabilities, Zipf: p(rank r) ~ (r + 1) ** -exponent, the
    ranks dealt to token ids by a seeded permutation, so that a model can
    learn the unigram and the loss falls from ln(vocab) within a pass."""
    if tokens["distribution"] != "zipf":
        raise ValueError(f"unknown token distribution {tokens['distribution']!r}")
    p = (np.arange(vocab_size) + 1.0) ** -float(tokens["exponent"])
    return (p / p.sum())[rng.permutation(vocab_size)]


def make_corpus(seed: int, traffic: dict, vocab_size: int):
    """``(ids, targets)``, each int32 ``[batches, batch, seq]``; targets are
    the ids shifted by one inside each sequence."""
    rng = np.random.default_rng(seed)
    shape = (traffic["batches"], traffic["batch"], traffic["seq"])
    p = unigram(vocab_size, traffic["tokens"], rng)
    ids = rng.choice(vocab_size, size=shape, p=p).astype(np.int32)
    return ids, np.roll(ids, -1, axis=-1)
