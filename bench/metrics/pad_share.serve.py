"""Percent of the serving window that the worker spent in its ``query/pad``
spans (the padded int64 candidate arrays of a micro-batch and its df
lookups): the union of those annotations in the worker's profile, clipped to
the trace window, over ``window_s``."""

import common

SPAN = "query/pad"


def read(layer: dict):
    red = layer.get("trace")
    if red is None or red.window_s <= 0:
        return None
    spans = [(a, b) for n, a, b in red.host if n == SPAN]
    if not spans:
        return None
    inside = [p for lo, hi in red.window for p in common.clip(spans, lo, hi)]
    return 100.0 * common.measure(inside) / red.window_s
