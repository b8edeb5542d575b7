"""The trace reduction, checked on a small trace recorded on a TPU v5e by
``record_trace.py``: four ``cooc_gram`` calls inside one harness
annotation, with 50 ms of idle device between the third and the fourth."""

import json
import os

import pytest

import common
import devtrace
import roofline

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TRACE = os.path.join(DATA, "small.xplane.pb")


@pytest.fixture(scope="module")
def red():
    return devtrace.reduce(TRACE, annotation="bench/build")


@pytest.fixture(scope="module")
def meta():
    with open(os.path.join(DATA, "small.json")) as f:
        return json.load(f)


def test_one_chip_ran_the_kernel_once_per_call(red, meta):
    assert red.chips == 1
    launches = {a for _, module, a, _ in red.ops() if "cooc_gram_kernel" in module}
    assert len(launches) == meta["calls"]


def test_busy_is_the_union_of_device_ops_inside_the_window(red):
    (lo, hi), = red.window
    lo_d, hi_d = lo - devtrace.SLACK_S, hi + devtrace.SLACK_S
    spans = sorted((max(a, lo_d), min(b, hi_d)) for *_, a, b in red.ops())
    busy, end = 0.0, lo_d
    for a, b in spans:  # a plain sweep, apart from common.union
        if b > end:
            busy += b - max(a, end)
            end = b
    assert red.busy_s == pytest.approx(busy, rel=1e-9)
    assert 0 < red.busy_s < red.window_s == pytest.approx(hi - lo)


def test_the_idle_sleep_is_the_longest_gap(red, meta):
    gaps = red.breakdown()["idle_gaps"]
    assert gaps[0][1] >= 0.9 * meta["idle_sleep_s"]
    assert all(g[1] >= h[1] for g, h in zip(gaps, gaps[1:]))
    assert red.window_s - red.busy_s >= gaps[0][1]


def test_breakdown_lists_the_kernel_among_the_top_device_ops(red):
    ops = red.breakdown()["device_ops"]
    assert 1 <= len(ops) <= 10
    assert all(a[1] >= b[1] for a, b in zip(ops, ops[1:]))
    assert any("cooc_gram_kernel" in name for name, _ in ops)


def test_roofline_share_of_the_recorded_calls_is_a_share(red, meta):
    kernel_s = sum(b - a for _, module, a, b in red.ops()
                   if "cooc_gram_kernel" in module)
    d, m, n = meta["shape"]
    pct, bound = roofline.share([(d, m, n)] * meta["calls"], kernel_s,
                                roofline.peaks(meta["device_kind"]))
    assert 0 < pct <= 100
    assert bound in ("compute", "memory")


def test_interval_helpers():
    assert common.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert common.subtract([(0, 10)], [(2, 3), (5, 6)]) == [(0, 2), (3, 5), (6, 10)]
    assert common.measure(common.clip([(0, 5), (8, 12)], 1, 10)) == 6
