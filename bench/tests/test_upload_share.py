"""The reader of the serving worker's ``query/upload`` span, on a serving
profile built by hand; a program without the span reads nothing."""

import os

import pytest

import common
import devtrace

READER = common.load_module(
    os.path.join(common.BENCH, "metrics", "upload_share.serve.py"),
    "metric_upload_share_serve")

# a 10 s serving window: uploads nest in query/device; one runs past its end
HOST = [
    ("serving/batch", 1.0, 4.2), ("query/gather", 1.0, 2.0),
    ("query/pad", 2.0, 2.5), ("query/device", 2.5, 4.0),
    ("query/upload", 2.5, 2.75),
    ("serving/batch", 5.0, 6.0), ("query/device", 5.2, 6.0),
    ("query/upload", 5.2, 5.3), ("query/upload", 5.25, 5.45),
    ("query/device", 9.8, 10.5), ("query/upload", 9.8, 10.5),
]
OPS = {"/device:TPU:0": [("_topk_pages.1", "jit__topk_pages", 3.0, 3.8)]}


def test_upload_share_reads_its_spans():
    red = devtrace.Reduction(OPS, HOST, [(0.0, 10.0)])
    want = 100 * (0.25 + 0.25 + 0.2) / 10
    assert READER.read({"trace": red}) == pytest.approx(want)
    device = [(a, b) for n, a, b in HOST if n == "query/device"]
    assert READER.read({"trace": red}) <= 100 * common.measure(
        common.clip(device, 0.0, 10.0)) / 10


@pytest.mark.parametrize("host", [
    [("query/device", 2.5, 4.0)],  # the parent: no upload span
    [],
])
def test_upload_share_without_its_span(host):
    assert READER.read({"trace": devtrace.Reduction(OPS, host, [(0.0, 10.0)])}) is None
    assert READER.read({"trace": None}) is None
    assert READER.read({}) is None
