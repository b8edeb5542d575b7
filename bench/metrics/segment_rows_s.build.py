"""Seconds per build inside the program's ``ingest/segment_rows`` spans
(the segment writer streaming the upper CSR rows out of the merge, and its
final flush), less the ``ingest/bucket_merge`` spans nested in them: the
writing, not the merging."""

import common


def read(layer: dict):
    spans = layer.get("spans") or ()
    rows = [(a, b) for n, a, b, _ in spans if n == "ingest/segment_rows"]
    merge = [(a, b) for n, a, b, _ in spans if n == "ingest/bucket_merge"]
    if not rows:
        return None
    return common.measure(common.subtract(rows, merge)) / layer["builds"]
