"""FREQ-SPLIT (beyond paper): dense-head / sparse-tail hybrid.

Requires df-descending term IDs (data/preprocess.remap_df_descending). Split
the vocabulary at rank H:

* head × head  (both IDs < H): dense tiled Gram matmul on the MXU — with
  Zipfian statistics the top-left of C is dense, so the matmul does almost no
  wasted work;
* everything else: tail-side LIST-SCAN — for each tail term t (df is small by
  construction), one histogram over the forward documents of postings(t)
  restricted to IDs < t yields the whole column C[:t, t]. Work is
  Σ_{t ≥ H} df_t · avg_len, i.e. proportional to actual postings; no empty
  intersections (LIST-PAIRS' waste) and no all-zero tiles (LIST-BLOCKS'
  waste at the tail).

Exactness is preserved: both paths compute exact integer counts and cover a
disjoint partition of the strict upper triangle.
"""

from __future__ import annotations

import numpy as np

from repro import obs
from repro.core.types import PairSink, emit_dense_rows
from repro.data.corpus import Collection
from repro.data.index import build_inverted_index, incidence_dense


def count_freq_split(
    c: Collection,
    sink: PairSink,
    *,
    head: int = 1024,
    doc_tile: int = 2048,
    use_kernel: bool = True,
) -> dict:
    """``sink`` must support emit_col (DenseSink / StatsSink do)."""
    from repro.kernels import ops as kops

    V, D = c.vocab_size, c.num_docs
    H = min(head, V)
    reg = obs.get_registry()

    # --- head × head: dense Gram over document tiles (MXU path) ---
    matmuls = 0
    with reg.span("ingest/count_head", head=H):
        acc = np.zeros((H, H), dtype=np.int64)
        for dlo in range(0, D, doc_tile):
            dhi = min(dlo + doc_tile, D)
            tile = incidence_dense(c, dlo, dhi, 0, H)
            acc += np.asarray(
                kops.cooc_gram(tile, tile, use_kernel=use_kernel)
            ).astype(np.int64)
            matmuls += 1
        emit_dense_rows(acc, sink, row_lo=0, col_lo=0)

    # --- tail columns: tail-side LIST-SCAN histograms ---
    tail_postings = 0
    with reg.span("ingest/count_tail", terms=V - H):
        inv = build_inverted_index(c)
        col = np.zeros(V, dtype=np.int64)
        for t in range(H, V):
            post = inv.postings(t)
            if len(post) == 0:
                continue
            col[:t] = 0
            for d in post:
                ts = c.doc(int(d))
                lower = ts[: np.searchsorted(ts, t)]  # strictly smaller IDs
                col[lower] += 1
                tail_postings += 1
            nz = np.nonzero(col[:t])[0]
            if len(nz):
                sink.emit_col(t, nz, col[nz])
    return {
        "head": H,
        "head_matmuls": matmuls,
        "tail_postings_scanned": tail_postings,
    }
