"""Seeded data for the benchmark: WT10G-shaped collections and request mixes.

The collection generator follows ``repro.data.corpus.synthetic_zipf_collection``
(lognormal raw document lengths, Zipf-distributed tokens, duplicates removed)
and then renumbers terms by descending document frequency, as
``repro.data.preprocess.remap_df_descending`` does. It is kept here so that
the plain references never read data the program made. One change: the raw
document lengths are one fixed set, drawn from the configuration's
``length_seed``, and ``--seed`` only permutes them and draws the tokens. Every
seed therefore counts the same amount of work in another order.

A collection is two arrays, ``doc_ptr`` (int64, D + 1) and ``terms`` (int32,
per-document ascending unique ids), plus the vocabulary size.
"""

from __future__ import annotations

import numpy as np


def _rng(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per use of one seed (any non-negative int)."""
    return np.random.default_rng([int(seed), stream])


def zipf_probs(n: int, s: float) -> np.ndarray:
    """Probabilities of ranks 1..n under Zipf with exponent ``s``."""
    p = np.arange(1, n + 1, dtype=np.float64) ** (-s)
    return p / p.sum()


def _sort_within_docs(doc_of: np.ndarray, terms: np.ndarray, vocab: int,
                      num_docs: int, dedup: bool):
    """Sort term ids inside each document (dropping repeats if ``dedup``);
    returns (doc_ptr, terms)."""
    keys = doc_of.astype(np.int64) * vocab + terms.astype(np.int64)
    keys = np.unique(keys) if dedup else np.sort(keys)
    doc = keys // vocab
    ptr = np.zeros(num_docs + 1, dtype=np.int64)
    np.cumsum(np.bincount(doc, minlength=num_docs), out=ptr[1:])
    return ptr, (keys % vocab).astype(np.int32)


def raw_lengths(cfg: dict, rng: np.random.Generator) -> np.ndarray:
    """Raw token counts per document, before duplicates are dropped: the
    configuration's one fixed set (lognormal, from ``length_seed``) in the
    order ``rng`` draws."""
    sigma = float(cfg["length_sigma"])
    mu = np.log(float(cfg["mean_len"])) - 0.5 * sigma * sigma
    lens = np.maximum(
        _rng(cfg["length_seed"], 0).lognormal(
            mu, sigma, size=int(cfg["num_docs"])).astype(np.int64),
        int(cfg["min_len"]),
    )
    return rng.permutation(lens)


def make_collection(cfg: dict, seed: int):
    """The configuration's collection for ``seed``: (doc_ptr, terms, vocab),
    term ids renumbered by descending df (ties by the earlier id).

    Where the configuration names a ``documents_seed``, the documents are
    drawn from it, and ``seed`` only puts them in its own order: every seed
    then counts the same documents."""
    if "documents_seed" in cfg:
        ptr, terms, vocab = _draw_collection(cfg, int(cfg["documents_seed"]))
        order = _rng(seed, 5).permutation(len(ptr) - 1)
        lens = np.diff(ptr)[order]
        starts = ptr[:-1][order]
        idx = np.repeat(starts - np.concatenate([[0], np.cumsum(lens)[:-1]]), lens)
        idx += np.arange(int(lens.sum()), dtype=np.int64)
        out = np.zeros(len(ptr), dtype=np.int64)
        np.cumsum(lens, out=out[1:])
        return out, terms[idx], vocab
    return _draw_collection(cfg, seed)


def _draw_collection(cfg: dict, seed: int):
    docs, vocab = int(cfg["num_docs"]), int(cfg["vocab_size"])
    rng = _rng(seed, 1)
    lens = raw_lengths(cfg, rng)
    tokens = rng.choice(vocab, size=int(lens.sum()),
                        p=zipf_probs(vocab, float(cfg["zipf_s"])))
    doc_of = np.repeat(np.arange(docs, dtype=np.int64), lens)
    ptr, terms = _sort_within_docs(doc_of, tokens, vocab, docs, dedup=True)
    df = np.bincount(terms, minlength=vocab)
    order = np.argsort(-df, kind="stable")  # new id -> old id
    new_of_old = np.empty(vocab, dtype=np.int64)
    new_of_old[order] = np.arange(vocab)
    doc_of = np.repeat(np.arange(docs, dtype=np.int64), np.diff(ptr))
    ptr, terms = _sort_within_docs(doc_of, new_of_old[terms], vocab, docs,
                                   dedup=False)
    return ptr, terms, vocab


def ycsb_zipf_ranks(n_items: int, count: int, theta: float,
                    rng: np.random.Generator) -> np.ndarray:
    """``count`` draws of item ranks 0..n_items-1 from YCSB's zipfian
    generator (Gray et al., "Quickly generating billion-record synthetic
    databases"), with rank 0 the most popular; unscrambled."""
    zetan = np.sum(1.0 / np.arange(1, n_items + 1, dtype=np.float64) ** theta)
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / n_items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    u = rng.random(count)
    uz = u * zetan
    v = (n_items * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    out = np.where(uz < 1.0, 0, np.where(uz < zeta2, 1, v))
    return np.minimum(out, n_items - 1)
