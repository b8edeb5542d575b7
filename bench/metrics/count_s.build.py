"""Seconds per build inside the program's ``ingest/count`` spans, less the
``ingest/spill`` spans nested in them (counting, not writing runs)."""

import common


def read(layer: dict):
    spans = layer.get("spans")
    if not spans:
        return None
    count = [(a, b) for n, a, b, _ in spans if n == "ingest/count"]
    spill = [(a, b) for n, a, b, _ in spans if n == "ingest/spill"]
    if not count:
        return None
    return common.measure(common.subtract(count, spill)) / layer["builds"]
