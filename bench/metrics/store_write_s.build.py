"""Seconds per build inside the program's store-writing spans:
``ingest/spill``, ``ingest/bucket_merge``, ``ingest/segment_write`` and
``ingest/refresh`` (their union, as merges run inside the segment write)."""

import common

STAGES = ("ingest/spill", "ingest/bucket_merge", "ingest/segment_write",
          "ingest/refresh")


def read(layer: dict):
    spans = [(a, b) for n, a, b, _ in layer.get("spans") or () if n in STAGES]
    if not spans:
        return None
    return common.measure(spans) / layer["builds"]
