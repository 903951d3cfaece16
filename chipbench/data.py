"""Seeded training batches: packed synthetic documents, vectorised.

A traffic file (``chipbench/traffic/<name>.json``) gives the batch, the
sequence length, how many distinct batches a run cycles, and the document
mix.  Every row of every batch is drawn from its own place in one numpy
stream keyed by ``(seed, batch index)``, so the same seed gives the same
batches and no two rows are alike.

The mix is that of ``repro.data.synthetic.SyntheticLM``: documents of
``doc_len`` tokens, each token either part of a repeated motif (with
probability ``motif_frac``) or drawn from a Zipf unigram over the
vocabulary, a pad token (0) after each document, next-token targets, and
a mask that leaves the pad targets out of the loss.  Here the draws are
made for whole arrays at once, so a batch of 131k tokens costs
milliseconds, and all batches are made before the measured window.
"""
from __future__ import annotations

import numpy as np

PAD = 0


def _zipf_cdf(vocab: int) -> np.ndarray:
    p = 1.0 / np.arange(1, vocab + 1, dtype=np.float64)
    return np.cumsum(p / p.sum())


def make_batch(traffic: dict, vocab: int, seed: int, index: int) -> dict:
    """Batch ``index`` of a run seeded with ``seed``: int32 tokens and
    targets of shape (batch, seq) and a float32 mask."""
    B, S = traffic["batch"], traffic["seq"]
    lo, hi = traffic["doc_len"]
    motif_len, n_motifs = traffic["motif_len"], traffic["n_motifs"]
    motifs = np.random.default_rng([seed, 2**32 - 1]).integers(
        1, vocab, size=(n_motifs, motif_len))
    rng = np.random.default_rng([seed, index])
    T = S + 1                                  # tokens plus the last target

    # a stream of units per row: a whole motif or one unigram token; T
    # units always hold at least T tokens
    is_motif = rng.random((B, T)) < traffic["motif_frac"]
    motif_id = rng.integers(0, n_motifs, size=(B, T))
    unigram = np.searchsorted(_zipf_cdf(vocab), rng.random((B, T)),
                              side="right").clip(max=vocab - 1)
    lengths = np.where(is_motif, motif_len, 1)
    starts = np.cumsum(lengths, axis=1) - lengths
    unit = np.stack([np.searchsorted(starts[b], np.arange(T), side="right")
                     - 1 for b in range(B)])
    offset = np.arange(T)[None, :] - np.take_along_axis(starts, unit, 1)
    stream = np.where(np.take_along_axis(is_motif, unit, 1),
                      motifs[np.take_along_axis(motif_id, unit, 1),
                             offset.clip(max=motif_len - 1)],
                      np.take_along_axis(unigram, unit, 1))

    # documents of doc_len tokens, each followed by a pad
    n_docs = T // lo + 1
    doc = rng.integers(lo, hi, size=(B, n_docs))
    pad_at = np.cumsum(doc + 1, axis=1) - 1
    is_pad = np.zeros((B, T + hi + 1), bool)
    np.put_along_axis(is_pad, pad_at.clip(max=T + hi), True, axis=1)
    is_pad = is_pad[:, :T]
    src = np.arange(T)[None, :] - np.cumsum(is_pad, axis=1)
    toks = np.where(is_pad, PAD, np.take_along_axis(stream, src, 1))
    tokens = toks[:, :-1].astype(np.int32)
    targets = toks[:, 1:].astype(np.int32)
    return {"tokens": tokens, "targets": targets,
            "mask": (targets != PAD).astype(np.float32)}


def make_batches(traffic: dict, vocab: int, seed: int) -> list:
    return [make_batch(traffic, vocab, seed, i)
            for i in range(traffic["n_batches"])]
