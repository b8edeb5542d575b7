"""The readers of the program's finer spans, on inputs built by hand: the
build's obs spans on one clock, and a serving profile reduced to host
annotations and device operations."""

import os

import pytest

import common
import devtrace

METRICS = os.path.join(common.BENCH, "metrics")


def reader(name: str):
    return common.load_module(os.path.join(METRICS, name + ".py"),
                              "metric_" + name.replace(".", "_"))


# two builds: (name, t0, t1, depth) as drivers/build.py hands them over
BUILD_SPANS = [
    ("bench/build", 0.0, 10.0, 0), ("bench/build", 10.0, 20.0, 0),
    ("ingest/count", 0.5, 3.0, 2), ("ingest/count_head", 1.0, 2.0, 3),
    ("ingest/count_tail", 2.0, 3.0, 3), ("ingest/spill", 2.5, 2.6, 4),
    ("ingest/segment_write", 3.0, 8.0, 3), ("ingest/segment_rows", 3.0, 6.0, 4),
    ("ingest/bucket_merge", 3.5, 4.0, 5), ("ingest/bucket_merge", 5.0, 5.5, 5),
    ("ingest/segment_symmetric", 6.0, 8.0, 4),
    ("ingest/count", 10.5, 13.0, 2), ("ingest/count_head", 11.0, 12.5, 3),
    ("ingest/segment_write", 13.0, 15.0, 3),
    ("ingest/segment_rows", 13.0, 14.0, 4),
    ("ingest/segment_symmetric", 14.0, 15.0, 4),
]


@pytest.mark.parametrize("metric, value", [
    ("count_head_s.build", (1.0 + 1.5) / 2),
    ("segment_rows_s.build", (3.0 - 0.5 - 0.5 + 1.0) / 2),
    ("segment_symmetric_s.build", (2.0 + 1.0) / 2),
])
def test_build_span_readers(metric, value):
    layer = {"spans": BUILD_SPANS, "builds": 2}
    assert reader(metric).read(layer) == pytest.approx(value)


@pytest.mark.parametrize("metric", ["count_head_s.build", "segment_rows_s.build",
                                    "segment_symmetric_s.build"])
def test_build_span_readers_without_their_spans(metric):
    older = [s for s in BUILD_SPANS if s[0] in
             ("bench/build", "ingest/count", "ingest/segment_write")]
    assert reader(metric).read({"spans": older, "builds": 2}) is None
    assert reader(metric).read({"spans": [], "builds": 1}) is None


def test_build_span_readers_agree_with_the_stage_readers():
    layer = {"spans": BUILD_SPANS, "builds": 2}
    assert (reader("count_head_s.build").read(layer)
            <= reader("count_s.build").read(layer))
    assert (reader("segment_rows_s.build").read(layer)
            + reader("segment_symmetric_s.build").read(layer)
            <= reader("store_write_s.build").read(layer))


# a 10 s serving window; one query/gather runs past its end
HOST = [
    ("serving/batch", 1.0, 4.2), ("query/gather", 1.0, 2.0),
    ("query/pad", 2.0, 2.5), ("query/device", 2.5, 4.0),
    ("np.asarray(jax.Array)", 3.8, 4.0),
    ("serving/batch", 5.0, 6.0), ("query/gather", 5.0, 5.5),
    ("query/gather", 9.5, 11.0),
]
OPS = {"/device:TPU:0": [("_topk_gather.1", "jit__topk_gather", 3.0, 3.8),
                         ("_topk_gather.1", "jit__topk_gather", 5.2, 5.4)]}


def serving_trace(host=HOST):
    return devtrace.Reduction(OPS, host, [(0.0, 10.0)])


@pytest.mark.parametrize("metric, value", [
    ("row_gather_share.serve", 100 * (1.0 + 0.5 + 0.5) / 10),
    ("pad_share.serve", 100 * 0.5 / 10),
    ("device_call_share.serve", 100 * 1.5 / 10),
    # in a batch with the device idle: 1-3, 3.8-4.2, 5-5.2, 5.4-6
    ("idle_in_batch.serve", 100 * (2.0 + 0.4 + 0.2 + 0.6) / 10),
])
def test_serve_span_readers(metric, value):
    assert reader(metric).read({"trace": serving_trace()}) == pytest.approx(value)


@pytest.mark.parametrize("metric", ["row_gather_share.serve", "pad_share.serve",
                                    "device_call_share.serve",
                                    "idle_in_batch.serve"])
def test_serve_span_readers_without_their_spans(metric):
    assert reader(metric).read({"trace": None}) is None
    assert reader(metric).read({}) is None
    bare = serving_trace([("np.asarray(jax.Array)", 3.8, 4.0)])
    assert reader(metric).read({"trace": bare}) is None


def test_serve_span_readers_agree():
    layer = {"trace": serving_trace()}
    stages = sum(reader(m).read(layer) for m in (
        "row_gather_share.serve", "pad_share.serve", "device_call_share.serve"))
    batch = 100 * (3.2 + 1.0) / 10
    assert stages <= 100
    assert reader("idle_in_batch.serve").read(layer) <= batch
