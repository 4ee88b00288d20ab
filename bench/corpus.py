"""The synthetic three-field corpus the benchmark serves.

A copy of the program's generator (``repro.data.corpus.make_corpus``), kept
here so that the data of a cell cannot change when the program does: a
Citeseer-like topic mixture per document, tf-idf weighted terms
feature-hashed into one dense block per field (title, authors, abstract),
every field block unit-normalised. Deterministic for a seed.
"""

from __future__ import annotations

import numpy as np


def _hash_terms(rng: np.random.Generator, vocab: int, dim: int):
    """Feature hashing: term id -> (coordinate, sign)."""
    coords = rng.integers(0, dim, size=vocab)
    signs = rng.choice(np.array([-1.0, 1.0], np.float32), size=vocab)
    return coords.astype(np.int64), signs


def _topic_field_matrix(
    rng: np.random.Generator,
    n_topics: int,
    vocab: int,
    dim: int,
    salient: int,
    idf: np.ndarray,
    coords: np.ndarray,
    signs: np.ndarray,
) -> np.ndarray:
    """(n_topics, dim) hashed tf-idf vectors of each topic's salient terms."""
    mats = np.zeros((n_topics, dim), np.float32)
    # Zipf term-frequency profile within a topic (rank 1 most frequent).
    tf = 1.0 / np.arange(1, salient + 1, dtype=np.float32)
    for t in range(n_topics):
        terms = rng.choice(vocab, size=salient, replace=False)
        w = tf * idf[terms]
        np.add.at(mats[t], coords[terms], signs[terms] * w)
    norms = np.linalg.norm(mats, axis=1, keepdims=True)
    return mats / np.maximum(norms, 1e-12)


def make_corpus(cfg: dict, seed: int) -> np.ndarray:
    """The ``(n, D)`` float32 corpus of the deployment ``cfg`` (the
    ``corpus`` group of a configuration file), every field unit-normalised,
    drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    n, s = cfg["n_docs"], len(cfg["field_dims"])

    # Latent topic mixture per document: 1-3 active topics.
    n_active = rng.integers(1, 4, size=n)
    doc_topics = np.zeros((n, cfg["n_topics"]), np.float32)
    active = rng.integers(0, cfg["n_topics"], size=(n, 3))
    mix = rng.dirichlet([cfg["topic_mix_alpha"]] * 3, size=n).astype(np.float32)
    for j in range(3):
        live = n_active > j
        np.add.at(doc_topics, (np.nonzero(live)[0], active[live, j]), mix[live, j])
    doc_topics /= np.maximum(doc_topics.sum(1, keepdims=True), 1e-12)

    fields = []
    for f in range(s):
        vocab, dim = cfg["vocab_sizes"][f], cfg["field_dims"][f]
        coords, signs = _hash_terms(rng, vocab, dim)
        # Zipf document frequency -> idf = log(n / df); rank-1 terms common.
        ranks = np.arange(1, vocab + 1, dtype=np.float32)
        df = np.maximum(n * (ranks ** -1.1) / np.sum(ranks ** -1.1) * 40, 1.0)
        idf = np.log(n / df).astype(np.float32)
        topic_mat = _topic_field_matrix(
            rng, cfg["n_topics"], vocab, dim, cfg["salient_per_topic"], idf, coords, signs
        )
        # Topical part: mixture of topic vectors, scaled by expected term count.
        x = doc_topics @ topic_mat * float(cfg["terms_per_field"][f])

        # Idiosyncratic rare terms (high idf — the tf-idf heavy tail).
        k_noise = cfg["noise_terms"][f]
        if k_noise > 0:
            noise_terms = rng.integers(vocab // 4, vocab, size=(n, k_noise))
            w = idf[noise_terms]                       # (n, k_noise)
            c = coords[noise_terms]
            sgn = signs[noise_terms]
            rows = np.repeat(np.arange(n), k_noise)
            np.add.at(x, (rows, c.reshape(-1)), (sgn * w).reshape(-1))

        norms = np.linalg.norm(x, axis=1, keepdims=True)
        fields.append(x / np.maximum(norms, 1e-12))

    return np.concatenate(fields, axis=1).astype(np.float32)
