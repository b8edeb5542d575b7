"""Seconds per build inside the program's ``ingest/count_head`` spans:
``freq-split``'s head Gram over the document tiles and the emission of its
dense rows (their union)."""

import common


def read(layer: dict):
    head = [(a, b) for n, a, b, _ in layer.get("spans") or ()
            if n == "ingest/count_head"]
    if not head:
        return None
    return common.measure(head) / layer["builds"]
