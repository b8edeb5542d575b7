"""Batched query engine over a co-occurrence store.

Serving-side counterpart of the counting pipeline: pair-count point lookups,
and top-k neighbour queries scored by raw count, PMI, or Dice. Neighbour
rows are read from the mmap'd segments through an LRU row cache whose rows
also live on the device, in int32 pages of a fixed pool; a top-k launch
ships only a page table, gathers the rows' pages into a rectangular tile on
the device, and scores and selects in the same launch.

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
import threading
from collections import OrderedDict
from dataclasses import dataclass

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


# the page pool: page 0 is the sentinel every page table is padded with (id
# -1, count 0, df 1); an upload's padding pages land on page 1
_SENTINEL, _SCRATCH, _RESERVED = 0, 1, 2
# a page holds P candidates' ids, pair counts and df (clamped to >= 1), one
# after the other in one pool row; these fill a padding slot
_PLANES = (-1, 0, 1)
# the pool takes at most this many bytes; None is a quarter of the device's
# memory (of 16 GiB, a v5e's, where the device does not report it)
POOL_CAP_BYTES: int | None = None
_DEVICE_BYTES_UNKNOWN = 16 << 30


def _pow2(n: int) -> int:
    """The least power of two at least ``n`` (1 for ``n`` <= 1)."""
    return 1 << max(n - 1, 0).bit_length()


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


@functools.partial(
    jax.jit, static_argnames=("num_docs", "score", "k", "kernel", "interpret")
)
def _topk_pages(pool, table, df_t, *, num_docs, score, k, kernel, interpret):
    """One top-k launch over rows held in the page pool.

    ``table`` (B, n) lists each row's pages, padded with the sentinel page;
    gathered, they make the (B, n·P) tile of ids, counts and candidate df a
    host would have padded (the row, then id -1 / count 0 / df 1), and the
    tile is scored and selected by ``kernel`` exactly as a host-built tile
    is: the same int32 inputs, the same answers, bit for bit."""
    B, n = table.shape
    tile = jnp.take(pool, table, axis=0, mode="clip").reshape(B, n, len(_PLANES), -1)
    ids, cnts, df_n = (tile[:, :, i].reshape(B, -1) for i in range(len(_PLANES)))
    if kernel == "pallas":
        from repro.kernels.topk_gather import _topk_gather

        return _topk_gather(
            ids, cnts, df_t[:, None], df_n, num_docs=num_docs, score=score,
            k=k, blk_b=8, interpret=interpret,
        )
    return _score_topk(ids, cnts, df_t, df_n, num_docs, score=score, k=k)


@functools.partial(jax.jit, donate_argnums=0)
def _upload_pages(pool, idx, pages):
    """Write ``pages`` (n, 3·P) at pool pages ``idx`` (n,), in place: the
    pool's buffer is donated, so the update copies no page it does not
    write."""
    return pool.at[idx].set(pages)


def pool_shape(vocab_size: int, cache_rows: int, cap_bytes: int) -> tuple[int, int]:
    """``(pages, P)`` of the page pool: P candidates a page (the kernel's
    column tile, or the least power of two from a lane that holds a row of
    a smaller vocabulary); room for ``min(cache_rows, vocab_size)`` rows of
    ``ceil(vocab_size / P)`` pages, at most ``cap_bytes`` of int32 ids,
    counts and df but never under one row, and the two reserved pages."""
    from repro.kernels.topk_gather import BLK_L, LANE

    V = max(vocab_size, 1)
    page = min(BLK_L, max(LANE, _pow2(V)))
    per_row = -(-V // page)
    cap_pages = cap_bytes // (len(_PLANES) * page * 4) - _RESERVED
    rows = min(cache_rows, V)
    return _RESERVED + max(per_row, min(rows * per_row, cap_pages)), page


@dataclass(eq=False)
class _Row:
    """A cached merged row: the host arrays ``neighbours`` answers with and
    the pool pages that hold it on the device (``None`` until a top-k
    launch needs them). ``pinned`` while a launch uses its pages."""

    term: int
    ids: np.ndarray
    cnts: np.ndarray
    pages: np.ndarray | None = None
    pinned: bool = False


class _PagePool:
    """Device-resident int32 pages of cached rows: ``data`` (pages, 3·P)
    holds each page's candidate ids, counts and df, all pages initially
    sentinels; a row of n candidates takes ceil(n / P) pages, its last one
    padded with id -1 / count 0 / df 1. Which page holds what is the
    engine's business; the pool keeps the free list and writes pages."""

    def __init__(self, n_pages: int, page: int):
        self.page = page
        fill = jnp.repeat(jnp.array(_PLANES, jnp.int32), page)
        self.data = jnp.broadcast_to(fill, (n_pages, len(fill)))
        self.usable = n_pages - _RESERVED
        self.free = list(range(n_pages - 1, _RESERVED - 1, -1))

    def pages(self, length: int) -> int:
        return -(-length // self.page)

    def upload(self, rows: list[_Row], df: np.ndarray) -> int:
        """Write the pages of ``rows`` in one donated scatter, padded to a
        power of two of pages (the padding lands on the scratch page), each
        candidate's df looked up in ``df``. Returns the pages written."""
        n = sum(len(r.pages) for r in rows)
        if not n:
            return 0
        n_pad = _pow2(n)
        idx = np.full(n_pad, _SCRATCH, dtype=np.int32)
        planes = np.empty((len(_PLANES), n_pad, self.page), dtype=np.int32)
        planes[:] = np.array(_PLANES, dtype=np.int32)[:, None, None]
        off = 0
        for r in rows:
            m, L = len(r.pages), len(r.ids)
            idx[off : off + m] = r.pages
            # clamp BOTH df sides to >=1: stores built without df metadata
            # (write_segment df=None) would otherwise divide by zero and tie
            # every pmi candidate at +inf
            for plane, v in zip(planes, (r.ids, r.cnts, np.maximum(df[r.ids], 1))):
                plane[off : off + m].reshape(-1)[:L] = v
            off += m
        # a page's pool row: its ids, then its counts, then its df
        pages = planes.transpose(1, 0, 2).reshape(n_pad, -1)
        self.data = _upload_pages(self.data, idx, pages)
        return n


class QueryEngine:
    """Batched queries against a :class:`~repro.store.segments.Store` with an
    LRU row cache and a pluggable score-and-select kernel.

    The cache is the warm path: hot rows (Zipf head terms under real serving
    traffic) are answered from memory; cold rows fall through to the shared
    mmap'd segment files, touching only the pages a row needs. The cache
    auto-invalidates when the store's manifest version changes (append,
    ingest, compact).

    A row a top-k launch has used also lives on the device, in int32 pages
    of P candidates' ids, counts and df (P = the kernel's column tile,
    2,048, or the least power of two from 128 that holds a whole row of a
    smaller vocabulary). The
    pool is made at the first top-k launch with room for ``cache_rows``
    rows of the longest possible length, ``min(cache_rows, vocab) ×
    ceil(vocab / P)`` pages, capped at a quarter of the device's memory;
    where the cap binds, the LRU evicts rows until their pages make room.
    A launch uploads only the pages of rows that have none yet, in one
    scatter, and ships a page table and the queried terms' df.

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
        self._cache: OrderedDict[int, _Row] = OrderedDict()
        self._df = store.df()
        self._pool: _PagePool | None = None
        self._num_docs = max(store.num_docs, 1)
        self._store_version = store.version
        self.stats = {"cache_hits": 0, "cache_misses": 0, "pages_uploaded": 0}
        self._registry = registry
        # one request batch at a time: launches share the pool and its pins
        self._lock = threading.Lock()

    @property
    def registry(self) -> "obs.Registry":
        """The engine's telemetry registry (a fixed one if passed at
        construction, otherwise whatever is globally installed now)."""
        return self._registry if self._registry is not None else obs.get_registry()

    # ----------------------------------------------------------- cache
    def _maybe_invalidate(self) -> None:
        if self.store.version != self._store_version:
            for row in self._cache.values():
                self._drop(row)  # frees its pages; the device is not touched
            self._cache.clear()
            self._df = self.store.df()
            self._num_docs = max(self.store.num_docs, 1)
            self._store_version = self.store.version

    def _entry(self, t: int) -> _Row:
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
        row = _Row(t, np.asarray(ids, dtype=np.int64),
                   np.asarray(cnts, dtype=np.int64))
        self._cache[t] = row
        if len(self._cache) > self.cache_rows:
            self._drop(self._cache.popitem(last=False)[1])
        return row

    def _row(self, t: int) -> tuple[np.ndarray, np.ndarray]:
        """``(ids, counts)`` of term ``t``'s cached merged row."""
        row = self._entry(t)
        return row.ids, row.cnts

    def _drop(self, row: _Row) -> None:
        """Return the pages of a row leaving the cache to the free list; a
        pinned row keeps them until its launch is over (``_release``)."""
        if row.pages is not None and not row.pinned:
            self._pool.free.extend(row.pages.tolist())
            row.pages = None

    # ------------------------------------------------------- page pool
    def _page_pool(self) -> _PagePool:
        """The pool, made at the first top-k launch (see the class)."""
        if self._pool is None:
            cap = POOL_CAP_BYTES
            if cap is None:
                cap = (device.memory_bytes() or _DEVICE_BYTES_UNKNOWN) // 4
            n_pages, page = pool_shape(self.store.vocab_size, self.cache_rows, cap)
            self._pool = _PagePool(n_pages, page)
        return self._pool

    def _fit(self, rows: list[_Row], lo: int) -> int:
        """The end of the longest run of ``rows`` from ``lo`` whose pages
        the pool holds at once (at least one row: the pool holds any)."""
        pool, need, seen, hi = self._pool, 0, set(), lo
        while hi < len(rows):
            r = rows[hi]
            if id(r) not in seen:
                need += pool.pages(len(r.ids))
                if need > pool.usable and hi > lo:
                    break
                seen.add(id(r))
            hi += 1
        return hi

    def _claim(self, rows: list[_Row]) -> list[_Row]:
        """Pin ``rows`` and give pages to those without, evicting the least
        recently used unpinned rows while the free list is short. Returns
        the rows whose pages must be uploaded."""
        pool = self._pool
        for r in rows:
            r.pinned = True
        fresh = list({id(r): r for r in rows if r.pages is None}.values())
        need = sum(pool.pages(len(r.ids)) for r in fresh)
        if need > len(pool.free):
            victims, got = [], len(pool.free)
            for t, r in self._cache.items():
                if got >= need:
                    break
                if not r.pinned and r.pages is not None:
                    victims.append(t)
                    got += len(r.pages)
            for t in victims:
                self._drop(self._cache.pop(t))
        for r in fresh:
            n = pool.pages(len(r.ids))
            r.pages = np.array([pool.free.pop() for _ in range(n)], dtype=np.int32)
        return fresh

    def _release(self, rows: list[_Row]) -> None:
        """Unpin ``rows`` after their launch; a row that left the cache
        meanwhile gives its pages back now."""
        for r in rows:
            r.pinned = False
            if self._cache.get(r.term) is not r:
                self._drop(r)

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
        with self._lock:
            before = dict(self.stats)
            with reg.span("query/execute", requests=len(reqs), kernel=self.kernel):
                execute_groups(self, coalesce(list(enumerate(reqs))), emit, qstats)
            moved = {key: self.stats[key] - n for key, n in before.items()}
        if qstats is not None:
            reg.counter("query.requests").inc(len(reqs))
            for key, n in qstats.items():
                # topk_launches / pair_launches are the kernel-dispatch
                # counters; the rest are per-query volumes
                reg.counter(f"query.{key}").inc(n)
            # cache_hits / cache_misses: the LRU row cache; pages_uploaded:
            # pages written to the device pool
            for key, n in moved.items():
                reg.counter(f"query.{key}").inc(n)
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
        in three spans: ``query/gather`` (rows and their pages through the
        cache), ``query/pad`` (the page table and ``df_t``) and
        ``query/device`` (``query/upload``, the scatter of the missing
        pages; the launch; the results back on the host). A batch whose
        rows outgrow the pool runs as several launches, each in its own
        three spans."""
        reg = self.registry
        terms = np.asarray(terms, dtype=np.int64)
        B = len(terms)
        parts, hi = [], 0
        while hi < B or not parts:
            with reg.span("query/gather", terms=B - hi):
                if not parts:
                    rows = [self._entry(int(t)) for t in terms]
                    pool = self._page_pool()
                lo, hi = hi, self._fit(rows, hi)
                chunk = rows[lo:hi]
                fresh = self._claim(chunk)
            try:
                parts.append(self._launch(pool, terms[lo:hi], chunk, fresh, k, score))
            except BaseException:
                for r in fresh:  # their pages may never have been written
                    r.pinned = False
                    self._drop(r)
                raise
            finally:
                self._release(chunk)
        if len(parts) == 1:
            return parts[0]
        return tuple(np.concatenate(p) for p in zip(*parts))

    def _launch(self, pool, terms, rows, fresh, k, score):
        """One launch over ``rows``, pinned in the pool (``fresh`` still to
        upload); answers (B, k), padded past the rows' candidates."""
        reg = self.registry
        B = len(rows)
        L = max((len(r.ids) for r in rows), default=0)
        # the reference's selection width: a power of two, at least 8, of
        # the longest row; every column past it is padding
        L = max(8, _pow2(L))
        kk = min(k, L)
        n = _pow2(max((len(r.pages) for r in rows), default=1))
        with reg.span("query/pad", width=n * pool.page):
            # jit cache friendliness: powers of two of rows and of pages
            table = np.full((_pow2(B), n), _SENTINEL, dtype=np.int32)
            for b, r in enumerate(rows):
                table[b, : len(r.pages)] = r.pages
            df_t = np.ones(len(table), dtype=np.int32)
            df_t[:B] = np.maximum(self._df[terms], 1)
        with reg.span("query/device"):
            with reg.span("query/upload", rows=len(fresh)):
                self.stats["pages_uploaded"] += pool.upload(fresh, self._df)
            top_ids, top_s = jax.device_get(_topk_pages(
                pool.data, table, df_t,
                num_docs=self._num_docs, score=score, k=kk,
                kernel=self.kernel, interpret=self.interpret,
            ))
        top_ids, top_s = top_ids[:B], top_s[:B]
        if k > kk:  # fewer candidates than k: pad out
            pad = k - kk
            top_ids = np.pad(top_ids, ((0, 0), (0, pad)), constant_values=-1)
            fill = 0 if score == "count" else -np.inf
            top_s = np.pad(top_s, ((0, 0), (0, pad)), constant_values=fill)
        return top_ids, top_s
