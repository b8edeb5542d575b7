"""The seeded data and the plain reference."""

import numpy as np

import data
from reference import Reference

CFG = {"num_docs": 300, "vocab_size": 700, "mean_len": 30, "length_sigma": 0.9,
       "min_len": 2, "zipf_s": 1.07, "length_seed": 0}


def test_same_seed_same_collection_large_seed():
    a = data.make_collection(CFG, 2**31 + 17)
    b = data.make_collection(CFG, 2**31 + 17)
    assert all(np.array_equal(x, y) for x, y in zip(a[:2], b[:2]))


def test_collection_is_sorted_unique_and_df_descending():
    ptr, terms, vocab = data.make_collection(CFG, 5)
    for d in range(len(ptr) - 1):
        assert np.all(np.diff(terms[ptr[d]:ptr[d + 1]]) > 0)
    df = np.bincount(terms, minlength=vocab)
    assert np.all(np.diff(df) <= 0)


def test_every_seed_gets_the_same_raw_lengths_in_its_own_order():
    """One set of raw lengths, permuted by the seed: the work is alike."""
    a = data.raw_lengths(CFG, np.random.default_rng(1))
    b = data.raw_lengths(CFG, np.random.default_rng(2))
    assert np.array_equal(np.sort(a), np.sort(b))
    assert not np.array_equal(a, b)


def test_reference_rows_equal_a_dense_gram():
    ptr, terms, vocab = data.make_collection(CFG, 9)
    ref = Reference(ptr, terms, vocab)
    b = np.zeros((len(ptr) - 1, vocab), dtype=np.int64)
    for d in range(len(ptr) - 1):
        b[d, terms[ptr[d]:ptr[d + 1]]] = 1
    gram = b.T @ b
    np.fill_diagonal(gram, 0)
    for t in range(vocab):
        assert np.array_equal(ref.row(t), gram[t])


def test_ycsb_zipf_ranks_favour_low_ranks():
    r = data.ycsb_zipf_ranks(1000, 20000, 0.99, np.random.default_rng(0))
    counts = np.bincount(r, minlength=1000)
    assert r.min() >= 0 and r.max() < 1000
    assert counts[0] > counts[1] > counts[10] > counts[500]


def test_documents_seed_gives_every_seed_the_same_documents():
    cfg = {**CFG, "documents_seed": 0}
    a, b = data.make_collection(cfg, 1), data.make_collection(cfg, 2)

    def docs(c):
        ptr, terms, _ = c
        return sorted(tuple(terms[ptr[i]:ptr[i + 1]]) for i in range(len(ptr) - 1))

    assert docs(a) == docs(b)
    assert not np.array_equal(a[1], b[1])
    assert np.array_equal(a[0], data.make_collection(cfg, 1)[0])



def pair_occurrences_at_published_scale(cfg: dict, mean_len: int) -> float:
    """Pairs the documents hold, summed over the published collection: a
    sample of 4,000 documents of ``mean_len`` at the published vocabulary."""
    pub = cfg["published"]
    rng = np.random.default_rng(7)
    lens = data.raw_lengths({**cfg, "num_docs": 4000, "mean_len": mean_len}, rng)
    tokens = rng.choice(pub["vocab_size"], size=int(lens.sum()),
                        p=data.zipf_probs(pub["vocab_size"], cfg["zipf_s"]))
    doc = np.repeat(np.arange(len(lens), dtype=np.int64), lens)
    keys = np.unique(doc * pub["vocab_size"] + tokens)
    u = np.bincount(keys // pub["vocab_size"], minlength=len(lens)).astype(float)
    return float(np.mean(u * (u - 1) / 2) * pub["num_docs"])


def test_document_length_is_the_least_that_holds_table_1_distinct_pairs():
    """A collection's distinct pairs are never more than the pairs its
    documents hold: at the published scale the configured length gives at
    least Table 1's distinct pairs, and 10 tokens less does not."""
    import common

    cfg = common.load_json(common.BENCH, "configs", "wt10g-build.json")
    want = cfg["published"]["distinct_pairs"]
    assert pair_occurrences_at_published_scale(cfg, cfg["mean_len"]) >= want
    assert pair_occurrences_at_published_scale(cfg, cfg["mean_len"] - 10) < want
