"""Batched query engine over a co-occurrence store.

Serving-side counterpart of the counting pipeline: pair-count point lookups,
and top-k neighbour queries scored by raw count, PMI, or Dice. Neighbour
rows are gathered from the mmap'd segments through a small LRU cache, padded
into a rectangular batch, and scored/top-k'd in one batched launch — the
same batched-gather discipline as the LM serving path (launch/serve.py),
applied to retrieval statistics.

Queries are typed request objects (store/requests.py): ``execute()`` takes a
batch of ``TopKRequest | PairCountsRequest | NeighboursRequest``, coalesces
compatible requests into single launches, and answers them through the same
``execute_groups`` path the multi-process serving workers use. The classic
``topk`` / ``pair_counts`` / ``neighbours`` methods remain as thin
byte-identical shims over that path.

Two interchangeable score-and-select backends (``kernel=``):

* ``"numpy"``  — the jitted reference: score the tile with jnp ops and rank
  with ``jax.lax.top_k`` (XLA, any backend);
* ``"pallas"`` — the Pallas top-k launch (kernels/topk_gather.py) that
  streams the XLA-scored tile through VMEM in column tiles; runs under the
  Pallas interpreter off-TPU, and is asserted **bit-identical** to the
  reference on every edge case (tests/test_topk_gather.py).

Scores (df = document frequency, D = total documents):
    count  c(t, n)                        — exact integer top-k
    pmi    log(c · D / (df_t · df_n))    — pointwise mutual information
    dice   2c / (df_t + df_n)            — Dice coefficient
"""

from __future__ import annotations

import functools
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.runtime import device
from repro.store.requests import (
    KERNELS,
    SCORES,
    NeighboursRequest,
    PairCountsRequest,
    TopKRequest,
    check_request_types,
    coalesce,
    execute_groups,
)
from repro.store.segments import Store


@functools.partial(jax.jit, static_argnames=("score", "k"))
def _score_topk(ids, cnts, df_t, df_n, num_docs, *, score: str, k: int):
    """Reference scorer: ids, cnts: (B, L) padded with id=-1 / cnt=0;
    df_t: (B,); df_n: (B, L).

    Returns (top_ids (B, k), top_scores (B, k)); padding slots score -inf
    (count: 0) and surface id -1."""
    valid = ids >= 0
    if score == "count":
        # integer path — exact, no float rounding in the ranking. int32 is
        # the widest integer top_k gets without x64; a pair count is bounded
        # by the store's document count, so this is exact below 2³¹ docs
        s = jnp.where(valid, cnts, 0).astype(jnp.int32)
    elif score == "pmi":
        s = jnp.log(
            cnts.astype(jnp.float32)
            * jnp.float32(num_docs)
            / (df_t[:, None].astype(jnp.float32) * df_n.astype(jnp.float32))
        )
        s = jnp.where(valid, s, -jnp.inf)
    elif score == "dice":
        s = (
            2.0
            * cnts.astype(jnp.float32)
            / (df_t[:, None] + df_n).astype(jnp.float32)
        )
        s = jnp.where(valid, s, -jnp.inf)
    else:
        raise ValueError(f"unknown score {score!r}; have {SCORES}")
    top_s, top_idx = jax.lax.top_k(s, k)
    top_ids = jnp.take_along_axis(ids, top_idx, axis=1)
    return top_ids, top_s


class QueryEngine:
    """Batched queries against a :class:`~repro.store.segments.Store` with an
    LRU row cache and a pluggable score-and-select kernel.

    The cache is the warm path: hot rows (Zipf head terms under real serving
    traffic) are answered from memory; cold rows fall through to the shared
    mmap'd segment files, touching only the pages a row needs. The cache
    auto-invalidates when the store's manifest version changes (append,
    ingest, compact).

    Args:
        store: an open :class:`Store`.
        cache_rows: LRU capacity (merged neighbour rows).
        kernel: ``"numpy"`` (jitted reference) or ``"pallas"`` (streaming
            top-k kernel, bit-identical results).
        interpret: Pallas interpreter mode; ``None`` lets the platform
            decide (compiled on a TPU, interpreted elsewhere, so the pallas
            path runs — and is tested — on CPU CI). The engine refuses to
            start on the CPU of a host whose TPU another process holds.
        registry: telemetry registry for ``query/*`` spans and
            cache/kernel-dispatch counters; ``None`` uses the process-global
            one (disabled by default — see repro/obs). Serving workers pass
            their own so metrics can cross the process boundary.

    Example::

        store, _ = count_to_store("auto", collection, "/tmp/store")
        eng = QueryEngine(store, kernel="pallas")
        ids, scores = eng.topk([3, 17], k=5, score="pmi")
        counts = eng.pair_counts(np.array([[3, 17]]))
    """

    def __init__(
        self,
        store: Store,
        *,
        cache_rows: int = 4096,
        kernel: str = "numpy",
        interpret: bool | None = None,
        registry: "obs.Registry | None" = None,
    ):
        if kernel not in KERNELS:
            raise ValueError(f"unknown kernel {kernel!r}; have {KERNELS}")
        self.store = store
        self.cache_rows = cache_rows
        self.kernel = kernel
        self.interpret = device.interpret() if interpret is None else interpret
        self._cache: OrderedDict[int, tuple[np.ndarray, np.ndarray]] = OrderedDict()
        self._df = store.df()
        self._num_docs = max(store.num_docs, 1)
        self._store_version = store.version
        self.stats = {"cache_hits": 0, "cache_misses": 0}
        self._registry = registry

    @property
    def registry(self) -> "obs.Registry":
        """The engine's telemetry registry (a fixed one if passed at
        construction, otherwise whatever is globally installed now)."""
        return self._registry if self._registry is not None else obs.get_registry()

    # ----------------------------------------------------------- cache
    def _maybe_invalidate(self) -> None:
        if self.store.version != self._store_version:
            self._cache.clear()
            self._df = self.store.df()
            self._num_docs = max(self.store.num_docs, 1)
            self._store_version = self.store.version

    def _row(self, t: int) -> tuple[np.ndarray, np.ndarray]:
        """Cached merged row of term ``t`` (no out-of-vocab validation —
        callers go through ``_check_terms`` first)."""
        self._maybe_invalidate()
        hit = self._cache.get(t)
        if hit is not None:
            self._cache.move_to_end(t)
            self.stats["cache_hits"] += 1
            return hit
        self.stats["cache_misses"] += 1
        ids, cnts = self.store.neighbours(t)
        row = (np.asarray(ids, dtype=np.int64), np.asarray(cnts, dtype=np.int64))
        self._cache[t] = row
        if len(self._cache) > self.cache_rows:
            self._cache.popitem(last=False)
        return row

    # --------------------------------------------------------- queries
    def _check_terms(self, terms: np.ndarray) -> None:
        V = self.store.vocab_size
        bad = terms[(terms < 0) | (terms >= V)]
        if bad.size:
            raise ValueError(
                f"out-of-vocab term id(s) {sorted(set(bad.tolist()))[:5]}; "
                f"store vocab_size is {V}"
            )

    def execute(self, requests) -> list:
        """Answer a batch of typed requests (store/requests.py) with as few
        kernel launches as possible — one ``topk`` launch per distinct
        ``(k, score)``, all pair lookups together. Returns one result per
        request, in order: ``(ids, scores)`` for top-k, a count vector for
        pairs, ``(ids, counts)`` for neighbours, and an **iterator of
        score-ordered chunks** for streamed top-k (``chunk=`` set).

        An invalid request (e.g. out-of-vocab term) raises the engine's
        canonical ``ValueError`` for the first offending request.

        Example::

            reqs = [TopKRequest([3, 17], k=5, score="pmi"),
                    PairCountsRequest(np.array([[3, 17]]))]
            (ids, scores), counts = eng.execute(reqs)
        """
        reqs = list(requests)
        check_request_types(reqs)
        results: dict[int, list] = {}
        errors: dict[int, str] = {}

        def emit(tag, ok, payload, *, seq=0, last=True, extra=None):
            if ok:
                results.setdefault(tag, []).append(payload)
            else:
                errors.setdefault(tag, payload[1])

        reg = self.registry
        qstats: dict | None = {} if reg.enabled else None
        hits0, misses0 = self.stats["cache_hits"], self.stats["cache_misses"]
        with reg.span("query/execute", requests=len(reqs), kernel=self.kernel):
            execute_groups(self, coalesce(list(enumerate(reqs))), emit, qstats)
        if qstats is not None:
            reg.counter("query.requests").inc(len(reqs))
            for key, n in qstats.items():
                # topk_launches / pair_launches are the kernel-dispatch
                # counters; the rest are per-query volumes
                reg.counter(f"query.{key}").inc(n)
            reg.counter("query.cache_hits").inc(
                self.stats["cache_hits"] - hits0
            )
            reg.counter("query.cache_misses").inc(
                self.stats["cache_misses"] - misses0
            )
        if errors:
            raise ValueError(errors[min(errors)])
        out = []
        for i, req in enumerate(reqs):
            if isinstance(req, TopKRequest) and req.chunk is not None:
                out.append(iter(results[i]))
            else:
                out.append(results[i][0])
        return out

    def neighbours(self, t: int) -> tuple[np.ndarray, np.ndarray]:
        """Merged ``(neighbour_ids, counts)`` of term ``t``, LRU-cached.
        Shim over :class:`NeighboursRequest` (out-of-vocab ids raise the
        same ``ValueError`` as every other query).

        Example::

            ids, cnts = eng.neighbours(3)   # every co-occurring term of 3
        """
        return self.execute([NeighboursRequest(t)])[0]

    def pair_counts(self, pairs: np.ndarray) -> np.ndarray:
        """Exact counts for a ``(B, 2)`` batch of unordered term pairs.
        Shim over :class:`PairCountsRequest`.

        Example::

            eng.pair_counts(np.array([[3, 17], [5, 5]]))  # diagonal -> 0
        """
        return self.execute([PairCountsRequest(pairs)])[0]

    def topk(
        self, terms, k: int = 10, *, score: str = "count"
    ) -> tuple[np.ndarray, np.ndarray]:
        """Top-k neighbours for a batch of terms. Shim over
        :class:`TopKRequest` — byte-identical to the request path.

        Returns ``(ids (B, k), scores (B, k))``; rows with fewer than k
        neighbours are padded with id -1 (score 0 for count, -inf else).
        Results are identical for both kernels, including tie order (ties
        rank the lower candidate-slot index first, like ``jax.lax.top_k``).

        Example::

            ids, scores = eng.topk([3, 17], k=5, score="count")
        """
        return self.execute([TopKRequest(terms, k=k, score=score)])[0]

    def topk_stream(
        self, terms, k: int, *, score: str = "count", chunk: int = 1024
    ):
        """Streaming top-k: an iterator of score-ordered ``(ids, scores)``
        column blocks of width ≤ ``chunk``. Concatenating the chunks along
        axis 1 equals ``topk(terms, k, score=score)`` exactly — chunking is
        a transport feature (serving moves large-k responses across the
        process boundary block by block), not an approximation.

        Example::

            chunks = list(eng.topk_stream([3], k=5000, chunk=512))
            ids = np.concatenate([c[0] for c in chunks], axis=1)  # (1, 5000)
        """
        return self.execute([TopKRequest(terms, k=k, score=score, chunk=chunk)])[0]

    def _topk_batch(
        self, terms: np.ndarray, k: int, score: str
    ) -> tuple[np.ndarray, np.ndarray]:
        """The batched gather + score + select launch (validated inputs),
        in three spans: ``query/gather`` (rows through the cache),
        ``query/pad`` (the padded host arrays) and ``query/device`` (the
        launch through the results back on the host)."""
        reg = self.registry
        B = len(terms)
        with reg.span("query/gather", terms=B):
            rows = [self._row(int(t)) for t in terms]
        L = max((len(r[0]) for r in rows), default=0)
        # jit cache friendliness: round the pad length up to a power of two
        L = max(8, 1 << (L - 1).bit_length()) if L else 8
        with reg.span("query/pad", width=L):
            ids = np.full((B, L), -1, dtype=np.int64)
            cnts = np.zeros((B, L), dtype=np.int64)
            for b, (rids, rcnts) in enumerate(rows):
                ids[b, : len(rids)] = rids
                cnts[b, : len(rids)] = rcnts
            # clamp BOTH df sides to >=1: stores built without df metadata
            # (write_segment df=None) would otherwise divide by zero and tie
            # every pmi candidate at +inf
            df_n = np.where(
                ids >= 0, np.maximum(self._df[np.maximum(ids, 0)], 1), 1
            )
            df_t = np.maximum(self._df[terms], 1)
        kk = min(k, L)
        with reg.span("query/device"):
            if self.kernel == "pallas":
                from repro.kernels.topk_gather import topk_gather

                top_ids, top_s = topk_gather(
                    ids, cnts, df_t, df_n,
                    num_docs=self._num_docs, score=score, k=kk,
                    interpret=self.interpret,
                )
            else:
                top_ids, top_s = _score_topk(
                    jnp.asarray(ids),
                    jnp.asarray(cnts),
                    jnp.asarray(df_t),
                    jnp.asarray(df_n),
                    self._num_docs,
                    score=score,
                    k=kk,
                )
            top_ids = np.asarray(top_ids)
            top_s = np.asarray(top_s)
        if k > top_ids.shape[1]:  # fewer candidates than k: pad out
            pad = k - top_ids.shape[1]
            top_ids = np.pad(top_ids, ((0, 0), (0, pad)), constant_values=-1)
            fill = 0 if score == "count" else -np.inf
            top_s = np.pad(top_s, ((0, 0), (0, pad)), constant_values=fill)
        return top_ids, top_s
