"""The query engine's device-resident paged row cache: every top-k answer is
bit-identical to the host-built padded tile scored by the reference, across
rows of one page, several pages and exact page multiples, empty and short
rows, hits and misses, a pool small enough to evict by pages, and a store
version change; the launch ships int32 page tables, and page uploads donate
the pool."""

import os
import queue

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.store import NeighboursRequest, QueryEngine, Store, TopKRequest
from repro.store import query
from repro.store.query import _score_topk

SCORES = ["count", "pmi", "dice"]
KERNELS = ["numpy", "pallas"]
V = 8000  # pages of 2,048 candidates, four to the longest possible row
PAGE = 2048
NUM_DOCS = 1000

# upper rows (primary, secondaries): term 0 holds 4,096 candidates (two
# whole pages), term 1 2,049 (two, the last nearly empty), term 2 2,048
# (one whole page), term 3 11, terms 5 and 7505 one, 5001 two; 4099 and
# 7999 co-occur with nothing
UPPER = [(0, np.arange(1, 4097)), (1, np.arange(4100, 4100 + 2048)),
         (2, np.arange(5000, 5000 + 2047)), (3, np.arange(7500, 7510))]


def _rows(upper, seed):
    rng = np.random.default_rng(seed)
    for p, secs in upper:
        # a narrow count range: many ties
        yield p, secs.astype(np.int64), rng.integers(1, 10, size=len(secs))


def _store(path, seed=0) -> Store:
    store = Store.create(str(path), V)
    rng = np.random.default_rng(seed + 100)
    df = rng.integers(0, 50, size=V)  # some df 0: the clamp to 1
    store.add_segment_from_rows(_rows(UPPER, seed), df=df, num_docs=NUM_DOCS)
    return store


@pytest.fixture
def store(tmp_path):
    return _store(tmp_path / "s")


def host_reference(store, terms, k, score):
    """The host-built tile: rows padded with id -1 / count 0 to a power of
    two of at least 8, df looked up on the host, scored and selected by the
    reference scorer, padded out to k."""
    terms = np.asarray(terms, dtype=np.int64)
    rows = [store.neighbours(int(t)) for t in terms]
    df = store.df()
    L = max((len(r[0]) for r in rows), default=0)
    L = max(8, 1 << (L - 1).bit_length()) if L else 8
    ids = np.full((len(terms), L), -1, dtype=np.int64)
    cnts = np.zeros((len(terms), L), dtype=np.int64)
    for b, (rids, rcnts) in enumerate(rows):
        ids[b, : len(rids)] = rids
        cnts[b, : len(rids)] = rcnts
    df_n = np.where(ids >= 0, np.maximum(df[np.maximum(ids, 0)], 1), 1)
    df_t = np.maximum(df[terms], 1)
    kk = min(k, L)
    top_ids, top_s = _score_topk(
        jnp.asarray(ids), jnp.asarray(cnts), jnp.asarray(df_t),
        jnp.asarray(df_n), max(store.num_docs, 1), score=score, k=kk,
    )
    top_ids, top_s = np.asarray(top_ids), np.asarray(top_s)
    if k > kk:
        top_ids = np.pad(top_ids, ((0, 0), (0, k - kk)), constant_values=-1)
        fill = 0 if score == "count" else -np.inf
        top_s = np.pad(top_s, ((0, 0), (0, k - kk)), constant_values=fill)
    return top_ids, top_s


def assert_answers(eng, terms, k, score):
    got_ids, got_s = eng.topk(terms, k=k, score=score)
    want_ids, want_s = host_reference(eng.store, terms, k, score)
    np.testing.assert_array_equal(got_ids, want_ids, err_msg=f"ids {score}")
    np.testing.assert_array_equal(got_s, want_s, err_msg=f"scores {score}")
    assert got_s.dtype == want_s.dtype
    check_pool(eng)


def check_pool(eng):
    """Every usable page is either free or held by exactly one cached row;
    no row is pinned between launches."""
    pool = eng._pool
    held = [p for r in eng._cache.values() if r.pages is not None
            for p in r.pages.tolist()]
    assert not any(r.pinned for r in eng._cache.values())
    pages = held + pool.free
    assert len(pages) == len(set(pages)) == pool.usable
    assert set(pages) == set(range(query._RESERVED, query._RESERVED + pool.usable))


def small_pool(monkeypatch, usable):
    """Cap the pool at ``usable`` pages beside the two reserved ones."""
    monkeypatch.setattr(
        query, "POOL_CAP_BYTES", (query._RESERVED + usable) * 3 * PAGE * 4
    )


@pytest.mark.parametrize("kernel", KERNELS)
@pytest.mark.parametrize("score", SCORES)
def test_paged_topk_matches_host_tile(store, kernel, score):
    """Rows of one page, of several, of exact page multiples, short, empty;
    first all misses, then hits mixed with misses and a repeated term."""
    eng = QueryEngine(store, kernel=kernel)
    assert eng.store.neighbours(0)[0].size == 2 * PAGE
    assert_answers(eng, [0, 1, 2, 3, 5, 4099, 7999], 10, score)
    assert eng.stats["cache_misses"] == 7 and eng.stats["cache_hits"] == 0
    assert eng._pool.page == PAGE
    assert eng.stats["pages_uploaded"] == 2 + 2 + 1 + 1 + 1
    assert_answers(eng, [3, 0, 7505, 5001, 0], 10, score)
    assert eng.stats["cache_hits"] == 3 and eng.stats["cache_misses"] == 9
    assert eng.stats["pages_uploaded"] == 7 + 2
    # k past the short rows' candidates, and past the tile's own width
    assert_answers(eng, [3, 5, 4099], 40, score)
    assert_answers(eng, [2], 3 * PAGE, score)


@pytest.mark.parametrize("kernel", KERNELS)
def test_small_pool_evicts_by_pages(store, monkeypatch, kernel):
    """A pool of four pages: rows leave the cache, least recently used
    first, until their pages make room; hits and misses follow."""
    small_pool(monkeypatch, 4)
    eng = QueryEngine(store, kernel=kernel)
    for t in [0, 1]:  # two pages each: the pool is full
        assert_answers(eng, [t], 10, "pmi")
    assert eng._pool.usable == 4 and not eng._pool.free
    assert_answers(eng, [2], 10, "pmi")  # evicts 0, the oldest
    assert sorted(eng._cache) == [1, 2]
    assert_answers(eng, [0], 10, "pmi")  # a miss again; evicts 1
    assert sorted(eng._cache) == [0, 2]
    assert_answers(eng, [2], 10, "pmi")  # still there: a hit
    assert eng.stats == {"cache_hits": 1, "cache_misses": 4,
                         "pages_uploaded": 2 + 2 + 1 + 2}


@pytest.mark.parametrize("kernel", KERNELS)
@pytest.mark.parametrize("score", SCORES)
def test_batch_larger_than_the_pool_runs_in_parts(store, monkeypatch, kernel,
                                                  score):
    """Six pages of rows through a four-page pool: several launches, one
    answer, each row read from the store once."""
    small_pool(monkeypatch, 4)
    eng = QueryEngine(store, kernel=kernel)
    assert_answers(eng, [0, 1, 2, 3, 0, 4099], 10, score)
    assert eng.stats["cache_misses"] == 5 and eng.stats["cache_hits"] == 1


@pytest.mark.parametrize("kernel", KERNELS)
def test_eviction_spares_the_rows_of_the_launch(store, monkeypatch, kernel):
    """A five-page pool with rows 3, 5 and 2 on the device; the batch
    [1, 3, 0, 2] runs as [1, 3, 0] then [2]. Making room for 1 and 0
    evicts 5 and then 2, and never 3, which this launch reads, though 3 is
    older than 2. The second launch reads row 2 from outside the cache
    (evicting 1 for its page) and frees that page after it."""
    small_pool(monkeypatch, 5)
    eng = QueryEngine(store, kernel=kernel)
    for t in [3, 5, 2]:
        assert_answers(eng, [t], 10, "pmi")
    assert_answers(eng, [1, 3, 0, 2], 10, "pmi")
    assert eng.stats == {"cache_hits": 2, "cache_misses": 5,
                         "pages_uploaded": 3 + 2 + 2 + 1}
    assert sorted(eng._cache) == [0, 3]


@pytest.mark.parametrize("kernel", KERNELS)
def test_batch_larger_than_the_row_cache(store, kernel):
    """More terms than ``cache_rows``: rows leave the cache while their
    launch still needs their pages, which come back after it."""
    eng = QueryEngine(store, kernel=kernel, cache_rows=2)
    assert_answers(eng, [0, 1, 2, 3, 0], 10, "count")
    assert len(eng._cache) == 2
    assert_answers(eng, [3, 5, 1], 10, "dice")


def test_threads_share_one_engine(store, monkeypatch):
    """Eight threads on one engine with a pool that evicts by pages: every
    answer is the reference's and no page is lost or shared."""
    import sys
    import threading

    small_pool(monkeypatch, 5)
    eng = QueryEngine(store)
    batches = [[0, 3], [1, 5, 2], [2, 0], [7505, 1, 3], [4099, 5001]]
    want = {i: host_reference(store, b, 6, "dice") for i, b in enumerate(batches)}
    errors = []

    def caller(n):
        try:
            for j in range(10):
                i = (n + j) % len(batches)
                ids, scores = eng.topk(batches[i], k=6, score="dice")
                np.testing.assert_array_equal(ids, want[i][0])
                np.testing.assert_array_equal(scores, want[i][1])
        except Exception as e:  # surfaced below
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=caller, args=(n,)) for n in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    assert eng.stats["cache_hits"] + eng.stats["cache_misses"] == 8 * 10 * 12 // 5
    check_pool(eng)


@pytest.mark.parametrize("kernel", KERNELS)
def test_version_change_frees_pages_and_leaks_no_counts(store, monkeypatch,
                                                        kernel):
    """After an append every page is free again without a device write, and
    pages reused by shorter rows show none of the counts they held."""
    small_pool(monkeypatch, 4)
    eng = QueryEngine(store, kernel=kernel)
    assert_answers(eng, [0, 1], 20, "count")
    assert not eng._pool.free
    before = eng._pool.data
    store.add_segment_from_rows(
        _rows([(3, np.arange(7600, 7620)), (7000, np.arange(7999, 8000))], 1),
        df=np.ones(V, dtype=np.int64), num_docs=NUM_DOCS,
    )
    eng._maybe_invalidate()
    assert not eng._cache and len(eng._pool.free) == eng._pool.usable
    assert eng._pool.data is before  # the device untouched
    for score in SCORES:
        assert_answers(eng, [3, 5, 7999, 4099], 40, score)
    assert_answers(eng, [0, 1, 3], 20, "pmi")


@pytest.mark.parametrize("kernel", KERNELS)
def test_neighbours_and_topk_in_one_execute(store, kernel):
    eng = QueryEngine(store, kernel=kernel)
    (n5_ids, n5_cnts), (ids, scores), (n0_ids, _) = eng.execute([
        NeighboursRequest(5),
        TopKRequest([5, 0, 1], k=7, score="pmi"),
        NeighboursRequest(0),
    ])
    want_ids, want_s = host_reference(store, [5, 0, 1], 7, "pmi")
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_array_equal(scores, want_s)
    np.testing.assert_array_equal(n5_ids, store.neighbours(5)[0])
    np.testing.assert_array_equal(n5_cnts, store.neighbours(5)[1])
    np.testing.assert_array_equal(n0_ids, store.neighbours(0)[0])
    # the top-k hits the row the first neighbours cached and uploads its
    # page; the second neighbours hits a row the top-k cached
    assert eng.stats["cache_hits"] == 2 and eng.stats["cache_misses"] == 3
    assert eng.stats["pages_uploaded"] == 1 + 2 + 2
    check_pool(eng)


def test_upload_donates_the_pool(store):
    """The scatter of new pages consumes the pool's buffers: the update is
    in place, not a copy of the whole pool."""
    eng = QueryEngine(store)
    eng.topk([0], k=5)
    old = eng._pool.data
    eng.topk([1], k=5)
    assert old.is_deleted()
    assert eng._pool.data.shape == old.shape
    eng.topk([1], k=5)  # a hit: nothing uploaded, nothing replaced
    assert not eng._pool.data.is_deleted()


def test_launch_ships_int32_page_tables(store, monkeypatch):
    """The launch gets an int32 page table, rows and pages each padded to a
    power of two, and the queried terms' df; nothing int64, and the host
    array ``topk_gather`` is not used."""
    import importlib

    kmod = importlib.import_module("repro.kernels.topk_gather")
    seen = []
    launch = query._topk_pages

    def spy(*args, **kw):
        seen.append((args, kw))
        return launch(*args, **kw)

    def refuse(*a, **kw):
        raise AssertionError("the host-array topk_gather ran")

    monkeypatch.setattr(query, "_topk_pages", spy)
    monkeypatch.setattr(kmod, "topk_gather", refuse)
    eng = QueryEngine(store, kernel="pallas")
    eng.topk([0, 1, 3], k=5, score="pmi")
    ((args, kw),) = seen
    pool, table, df_t = args
    assert table.dtype == np.int32 and table.shape == (4, 2)
    assert (table[3] == query._SENTINEL).all()
    assert df_t.dtype == np.int32 and df_t.tolist()[3] == 1
    assert pool.dtype == jnp.int32 and pool.shape[1] == 3 * PAGE
    assert all(getattr(a, "dtype", np.int32) != np.int64 for a in args)
    assert kw["kernel"] == "pallas" and kw["k"] == 5


def test_pool_size_follows_vocab_rows_and_cap(store, monkeypatch):
    eng = QueryEngine(store, cache_rows=3)
    eng.topk([3], k=2)
    assert eng._pool.usable == 3 * 4  # three rows of four pages
    small_pool(monkeypatch, 2)  # under one row's pages: one row still fits
    eng = QueryEngine(store)
    eng.topk([0], k=2)
    assert eng._pool.usable == 4


def test_pages_uploaded_reach_the_registry_and_the_worker_stats(
        store, tmp_path):
    """``pages_uploaded`` counts in ``engine.stats``, in the registry as
    ``query.pages_uploaded``, and in the serving worker's published stats."""
    from repro.store import serving
    from repro.store.requests import make_envelope

    reg = obs.Registry(enabled=True)
    eng = QueryEngine(store, registry=reg)
    eng.execute([TopKRequest([0, 3], k=3, score="pmi")])
    eng.execute([TopKRequest([0, 3], k=3, score="pmi")])
    assert reg.snapshot()["counters"]["query.pages_uploaded"] == 3

    request_q, response_q, stats_q = queue.Queue(), queue.Queue(), queue.Queue()
    for i, t in enumerate([1, 1, 2]):
        request_q.put(make_envelope(0, i, 0, 1, TopKRequest([t], k=3)))
    request_q.put(serving._STOP)
    cfg = serving.ServingConfig(workers=1, batch_window_ms=0.0)
    serving._worker_main(0, store.path, cfg, request_q, response_q, stats_q)
    final = None
    while not stats_q.empty():
        msg = stats_q.get()
        if msg[0] == "final":
            final = msg[2]
    assert final["stats"]["pages_uploaded"] == 2 + 1
    assert final["stats"]["cache_hits"] == 1


@pytest.mark.parametrize("vocab, rows, cap, want", [
    # the served WT10G-shaped store on a v5e: 4,096 rows of 32 pages, 3 GiB
    (65_536, 4096, (16 << 30) // 4, (2 + 4096 * 32, 2048)),
    # the full 5.75M vocabulary: the quarter of the chip binds
    (5_750_000, 4096, (16 << 30) // 4, ((4 << 30) // (3 * 2048 * 4), 2048)),
    # a small vocabulary: pages of a lane or a power of two, one a row
    (96, 4096, 1 << 30, (2 + 96, 128)),
    (200, 8, 1 << 30, (2 + 8, 256)),
    # a cap under one row still holds one row
    (8000, 4096, 0, (2 + 4, 2048)),
])
def test_pool_shape(vocab, rows, cap, want):
    assert query.pool_shape(vocab, rows, cap) == want
    pages, page = want
    # within the cap, unless the cap is under the one row a pool must hold
    assert pages * 3 * page * 4 <= cap or pages - 2 == -(-vocab // page)
