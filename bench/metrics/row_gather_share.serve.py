"""Percent of the serving window that the worker spent in its ``query/gather``
spans (the rows of a micro-batch gathered through the query engine's LRU row
cache and the segments): the union of those annotations in the worker's
profile, clipped to the trace window, over ``window_s``."""

import common

SPAN = "query/gather"


def read(layer: dict):
    red = layer.get("trace")
    if red is None or red.window_s <= 0:
        return None
    spans = [(a, b) for n, a, b in red.host if n == SPAN]
    if not spans:
        return None
    inside = [p for lo, hi in red.window for p in common.clip(spans, lo, hi)]
    return 100.0 * common.measure(inside) / red.window_s
