"""Seconds per build inside the program's ``ingest/segment_symmetric``
spans: the segment writer's two-pass build of the symmetric adjacency from
the upper CSR on disk (their union)."""

import common


def read(layer: dict):
    sym = [(a, b) for n, a, b, _ in layer.get("spans") or ()
           if n == "ingest/segment_symmetric"]
    if not sym:
        return None
    return common.measure(sym) / layer["builds"]
