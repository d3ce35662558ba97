"""A synthetic token corpus, generated from a seed.

``synth_tokens`` is a copy of the program's corpus sampler
(``data/corpus.synth_tokens``): Zipf(1.3) unigrams folded into the
vocabulary, each position repeating the previous token with p = 0.3.
It is copied so that a change to the program cannot change the
benchmark's input.  The table has the program's corpus schema: one row
a sequence, ``tokens`` (seq_len,) int32, ``doc_id`` int32 and
``quality`` float32.
"""

from __future__ import annotations

import numpy as np


def synth_tokens(rng: np.random.Generator, n_seqs: int, seq_len: int,
                 vocab: int, zipf_a: float = 1.3,
                 repeat_p: float = 0.3) -> np.ndarray:
    z = rng.zipf(zipf_a, size=(n_seqs, seq_len)).astype(np.int64)
    toks = (z % vocab).astype(np.int32)
    rep = rng.random((n_seqs, seq_len)) < repeat_p
    rep[:, 0] = False
    out = toks.copy()
    for j in range(1, seq_len):
        out[:, j] = np.where(rep[:, j], out[:, j - 1], toks[:, j])
    return out


def generate(corpus: dict, seq_len: int, vocab: int,
             seed: int) -> dict[str, np.ndarray]:
    """The whole corpus table, in chunks of ``chunk_rows`` sequences as
    the program's corpus builder draws them."""
    n = int(corpus["n_seqs"])
    chunk = int(corpus.get("chunk_rows", 512))
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0xC0]))
    parts = {"tokens": [], "doc_id": [], "quality": []}
    for start in range(0, n, chunk):
        m = min(chunk, n - start)
        parts["tokens"].append(synth_tokens(
            rng, m, seq_len, vocab, corpus.get("zipf_a", 1.3),
            corpus.get("repeat_p", 0.3)))
        parts["doc_id"].append(rng.integers(0, max(n // 16, 1), m)
                               .astype(np.int32))
        parts["quality"].append(rng.beta(4, 2, m).astype(np.float32))
    return {k: np.concatenate(v) for k, v in parts.items()}


def rows_for_step(seed: int, step: int, n_rows: int,
                  batch: int) -> np.ndarray:
    """The sorted corpus rows of one step's batch.  The input path's
    contract: each epoch is a permutation of the rows drawn from
    ``SeedSequence([seed, epoch])``, and step ``s`` takes the next
    ``batch`` of it (the tail wraps to the permutation's head)."""
    per_epoch = max(n_rows // batch, 1)
    epoch, within = divmod(step, per_epoch)
    perm = np.random.default_rng(
        np.random.SeedSequence([int(seed), epoch])).permutation(n_rows)
    rows = perm[within * batch:(within + 1) * batch]
    if rows.size < batch:
        rows = np.concatenate([rows, perm[:batch - rows.size]])
    return np.sort(rows)
