"""The counting kernel's operations, bytes and roofline share."""

import pytest

import roofline

PEAK = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}


def test_ops_and_bytes_of_one_call():
    assert roofline.cooc_gram_ops(2048, 1024, 1024) == 2 * 2048 * 1024 * 1024
    assert roofline.cooc_gram_bytes(2048, 1024, 512) == (
        2048 * 1024 + 2048 * 512 + 4 * 1024 * 512)


def test_compute_bound_share():
    calls = [(2048, 1024, 1024)] * 4 + [(1808, 1024, 1024)]
    least = sum(2 * d * m * n for d, m, n in calls) / PEAK["int8_ops_per_s"]
    pct, bound = roofline.share(calls, 2 * least, PEAK)
    assert bound == "compute"
    assert pct == pytest.approx(50.0)


def test_memory_bound_share():
    calls = [(8, 4096, 4096)]
    least = roofline.cooc_gram_bytes(8, 4096, 4096) / PEAK["hbm_bytes_per_s"]
    pct, bound = roofline.share(calls, least, PEAK)
    assert bound == "memory"
    assert pct == pytest.approx(100.0)


def test_peak_table_knows_v5e_and_refuses_the_rest():
    assert roofline.peaks("TPU v5 lite")["int8_ops_per_s"] == 393e12
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
