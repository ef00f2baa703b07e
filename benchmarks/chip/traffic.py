"""The token feed of a training cell, made from the seed by one generator.

A traffic file (``traffic/<name>.json``) gives the shape of a step's batch and
how its tokens are drawn.  Rows are pseudo-text: Zipfian unigrams with a
repeated n-gram placed in each row, so the model has something to learn and
the softmax sees a skewed vocabulary.  Every row is a pure function of
``(seed, step, rank)``: each seed gets the same sizes, and every row of every
step differs.

The generator is a copy of ``repro.data.pipeline.synthetic_tokens``, kept
here so that the program cannot change what it is measured on.
"""

from __future__ import annotations

import numpy as np


def rows(mix: dict, vocab: int, seed: int, step: int, rank: int) -> np.ndarray:
    """``rows_per_chip`` rows of ``seq_len + 1`` token ids for one rank."""
    b, n = mix["rows_per_chip"], mix["seq_len"] + 1
    tok = mix["tokens"]
    rng = np.random.default_rng(np.random.SeedSequence([seed, step, rank]))
    out = (rng.zipf(tok["zipf_a"], size=(b, n)).astype(np.int64) - 1) % vocab
    g = tok["ngram_len"]
    for r in range(b):
        gram = rng.integers(0, vocab, g)
        for _ in range(max(1, n // tok["ngram_every"])):
            at = int(rng.integers(0, max(1, n - g)))
            out[r, at:at + g] = gram
    return out.astype(np.int32)


def batch(mix: dict, vocab: int, seed: int, step: int, ranks: int) -> dict:
    """The global batch of one step: rank ``r``'s rows are rows ``r*b:(r+1)*b``."""
    t = np.concatenate([rows(mix, vocab, seed, step, r) for r in range(ranks)])
    return {"tokens": t[:, :-1], "labels": t[:, 1:]}
