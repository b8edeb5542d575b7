"""Serving-kernel equivalence: the Pallas top-k gather (interpret mode on
CPU) must be **bit-identical** to the jitted-numpy reference scorer on every
query-engine edge case — empty rows, out-of-vocab terms, k larger than the
row nnz, and ties in count/PMI/Dice. Identity is asserted both at the raw
kernel level and end-to-end through two QueryEngines over the same store."""

import numpy as np
import pytest

from repro.core.cooc import count_to_store
from repro.data.corpus import synthetic_zipf_collection
from repro.data.preprocess import preprocess_documents
from repro.kernels.topk_gather import BLK_L, topk_gather
from repro.store import QueryEngine
from repro.store.query import _score_topk

SCORES = ["count", "pmi", "dice"]


def _reference(ids, cnts, df_t, df_n, num_docs, score, k):
    import jax.numpy as jnp

    ri, rs = _score_topk(
        jnp.asarray(ids), jnp.asarray(cnts), jnp.asarray(df_t),
        jnp.asarray(df_n), num_docs, score=score, k=k,
    )
    return np.asarray(ri), np.asarray(rs)


def _assert_identical(ids, cnts, df_t, df_n, num_docs, score, k):
    ri, rs = _reference(ids, cnts, df_t, df_n, num_docs, score, k)
    pi, ps = topk_gather(
        ids, cnts, df_t, df_n, num_docs=num_docs, score=score, k=k,
        interpret=True,
    )
    np.testing.assert_array_equal(ri, np.asarray(pi), err_msg=f"ids {score}")
    np.testing.assert_array_equal(rs, np.asarray(ps), err_msg=f"scores {score}")
    return ri, rs


# ------------------------------------------------------------- raw kernel
@pytest.mark.parametrize("score", SCORES)
def test_kernel_random_tiles_identical(score):
    rng = np.random.default_rng(3)
    for B, L, k in [(1, 8, 1), (4, 16, 5), (9, 130, 17)]:
        lens = rng.integers(0, L + 1, size=B)
        ids = np.full((B, L), -1, dtype=np.int64)
        cnts = np.zeros((B, L), dtype=np.int64)
        for b in range(B):
            n = int(lens[b])
            ids[b, :n] = np.sort(rng.choice(4 * L, size=n, replace=False))
            cnts[b, :n] = rng.integers(1, 6, size=n)  # narrow range: many ties
        df_t = rng.integers(1, 40, size=B)
        df_n = np.where(ids >= 0, rng.integers(1, 40, size=(B, L)), 1)
        _assert_identical(ids, cnts, df_t, df_n, 500, score, k)


@pytest.mark.parametrize("score", SCORES)
def test_kernel_all_empty_rows(score):
    """A tile of entirely empty rows: every slot padded, ids all -1."""
    B, L, k = 3, 8, 4
    ids = np.full((B, L), -1, dtype=np.int64)
    cnts = np.zeros((B, L), dtype=np.int64)
    df_t = np.ones(B, dtype=np.int64)
    df_n = np.ones((B, L), dtype=np.int64)
    ri, rs = _assert_identical(ids, cnts, df_t, df_n, 10, score, k)
    assert (ri == -1).all()
    if score == "count":
        assert (rs == 0).all()
    else:
        assert np.isneginf(rs).all()


@pytest.mark.parametrize("score", SCORES)
def test_kernel_ties_exact_order(score):
    """All-equal counts and dfs: every candidate ties; both kernels must
    agree on the full selection order (lowest slot index first)."""
    B, L, k = 2, 16, 16
    ids = np.tile(np.arange(10, 10 + L, dtype=np.int64), (B, 1))
    cnts = np.full((B, L), 7, dtype=np.int64)
    df_t = np.full(B, 3, dtype=np.int64)
    df_n = np.full((B, L), 5, dtype=np.int64)
    ri, _ = _assert_identical(ids, cnts, df_t, df_n, 100, score, k)
    np.testing.assert_array_equal(ri[0], np.arange(10, 10 + L))


@pytest.mark.parametrize("score", SCORES)
def test_kernel_merges_topk_across_column_tiles(score):
    """Rows several column tiles long: the running top-k carried from tile
    to tile must give lax.top_k's exact order, ties across tile borders
    included, and k may exceed one tile's width."""
    assert BLK_L == 2048
    rng = np.random.default_rng(11)
    for B, L, k in [(3, 5000, 10), (9, 4100, 200), (1, 2200, 2100)]:
        lens = rng.integers(L // 2, L + 1, size=B)
        lens[0] = 5  # one row ending inside the first tile
        ids = np.full((B, L), -1, dtype=np.int64)
        cnts = np.zeros((B, L), dtype=np.int64)
        for b in range(B):
            n = int(lens[b])
            ids[b, :n] = np.sort(rng.choice(8 * L, size=n, replace=False))
            cnts[b, :n] = rng.integers(1, 4, size=n)  # ties in every tile
        df_t = rng.integers(1, 30, size=B)
        df_n = np.where(ids >= 0, rng.integers(1, 30, size=(B, L)), 1)
        _assert_identical(ids, cnts, df_t, df_n, 400, score, k)


def test_kernel_k_bounds():
    ids = np.array([[1, 2, -1, -1]])
    cnts = np.array([[1, 1, 0, 0]])
    with pytest.raises(ValueError, match="k=9"):
        topk_gather(ids, cnts, np.array([1]), np.ones_like(ids),
                    num_docs=10, k=9, interpret=True)
    with pytest.raises(ValueError, match="unknown score"):
        topk_gather(ids, cnts, np.array([1]), np.ones_like(ids),
                    num_docs=10, k=1, score="tfidf", interpret=True)


# ------------------------------------------------ end-to-end QueryEngine
@pytest.fixture(scope="module")
def engines(tmp_path_factory):
    docs = [[0, 1, 2], [0, 1], [3], [4, 5, 4], []]  # term 6 never occurs
    c = preprocess_documents(docs, vocab_size=8)
    store, _ = count_to_store(
        "list-scan", c, str(tmp_path_factory.mktemp("s") / "store")
    )
    return (
        QueryEngine(store, kernel="numpy"),
        QueryEngine(store, kernel="pallas", interpret=True),
    )


@pytest.mark.parametrize("score", SCORES)
def test_engine_empty_row_identical(engines, score):
    ref, pal = engines
    for eng in (ref, pal):
        ids, scores = eng.topk([6], k=3, score=score)  # term with no pairs
        assert (ids == -1).all()
    np.testing.assert_array_equal(*(e.topk([6], k=3, score=score)[0] for e in engines))


@pytest.mark.parametrize("score", SCORES)
def test_engine_k_exceeds_nnz_identical(engines, score):
    ref, pal = engines
    ri, rs = ref.topk([0, 3, 6], k=50, score=score)
    pi, ps = pal.topk([0, 3, 6], k=50, score=score)
    np.testing.assert_array_equal(ri, pi)
    np.testing.assert_array_equal(rs, ps)
    assert ri.shape == (3, 50) and (ri[2] == -1).all()


@pytest.mark.parametrize("kernel", ["numpy", "pallas"])
def test_engine_out_of_vocab_raises(engines, kernel):
    eng = engines[0] if kernel == "numpy" else engines[1]
    with pytest.raises(ValueError, match="out-of-vocab"):
        eng.topk([0, 8], k=2)
    with pytest.raises(ValueError, match="out-of-vocab"):
        eng.topk([-1], k=2)
    with pytest.raises(ValueError, match="out-of-vocab"):
        eng.pair_counts(np.array([[0, 99]]))


@pytest.mark.parametrize("score", SCORES)
def test_engine_zipf_store_identical(score, tmp_path):
    """Both kernels, whole-store sweep: identical ids AND scores."""
    c = synthetic_zipf_collection(150, vocab=96, mean_len=12, seed=4)
    store, _ = count_to_store("list-scan", c, str(tmp_path / "store"))
    ref = QueryEngine(store, kernel="numpy")
    pal = QueryEngine(store, kernel="pallas", interpret=True)
    terms = np.arange(96)
    ri, rs = ref.topk(terms, k=9, score=score)
    pi, ps = pal.topk(terms, k=9, score=score)
    np.testing.assert_array_equal(ri, pi)
    np.testing.assert_array_equal(rs, ps)
