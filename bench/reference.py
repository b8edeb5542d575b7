"""Plain references: co-occurrence rows and related-terms answers computed
straight from the seeded collection with numpy, importing nothing of the
program.

A term's row is the count, for every other term, of the documents holding
both: the documents that hold ``t`` are found through an inverted index, and
their terms are counted with one ``bincount``.
"""

from __future__ import annotations

import numpy as np


class Reference:
    """Rows, document frequencies and scores of one collection."""

    def __init__(self, doc_ptr: np.ndarray, terms: np.ndarray, vocab: int):
        self.doc_ptr, self.terms, self.vocab = doc_ptr, terms, vocab
        self.num_docs = len(doc_ptr) - 1
        lens = np.diff(doc_ptr)
        doc_of = np.repeat(np.arange(self.num_docs, dtype=np.int64), lens)
        order = np.argsort(terms, kind="stable")
        self._docs_of_term = doc_of[order]
        self.df = np.bincount(terms, minlength=vocab).astype(np.int64)
        self._start = np.concatenate([[0], np.cumsum(self.df)])

    def docs_of(self, t: int) -> np.ndarray:
        return self._docs_of_term[self._start[t]:self._start[t + 1]]

    def row(self, t: int) -> np.ndarray:
        """Dense (vocab,) int64 co-occurrence counts of ``t`` (0 at ``t``)."""
        docs = self.docs_of(t)
        lo, hi = self.doc_ptr[docs], self.doc_ptr[docs + 1]
        n = hi - lo
        idx = np.repeat(lo - np.concatenate([[0], np.cumsum(n)[:-1]]), n)
        idx += np.arange(int(n.sum()), dtype=np.int64)
        out = np.bincount(self.terms[idx], minlength=self.vocab).astype(np.int64)
        out[t] = 0
        return out

    def scores(self, t: int, row: np.ndarray, score: str) -> np.ndarray:
        """Float64 scores of every candidate of ``t`` (-inf where the count
        is 0): the count, PMI log(c·D / (df_t·df_n)) or Dice 2c / (df_t + df_n)."""
        c = row.astype(np.float64)
        df_n = np.maximum(self.df, 1).astype(np.float64)
        df_t = float(max(self.df[t], 1))
        with np.errstate(divide="ignore"):
            if score == "count":
                s = c
            elif score == "pmi":
                s = np.log(c * self.num_docs / (df_t * df_n))
            elif score == "dice":
                s = 2.0 * c / (df_t + df_n)
            else:
                raise ValueError(f"unknown score {score!r}")
        return np.where(row > 0, s, -np.inf)
