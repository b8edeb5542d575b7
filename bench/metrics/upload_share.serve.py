"""Percent of the serving window that the worker spent in its ``query/upload``
spans (inside ``query/device``: the pages of the rows a micro-batch found
without device pages, staged and scattered into the device's page pool):
the union of those annotations in the worker's profile, clipped to the trace
window, over ``window_s``. A program without the span reads nothing."""

import common

SPAN = "query/upload"


def read(layer: dict):
    red = layer.get("trace")
    if red is None or red.window_s <= 0:
        return None
    spans = [(a, b) for n, a, b in red.host if n == SPAN]
    if not spans:
        return None
    inside = [p for lo, hi in red.window for p in common.clip(spans, lo, hi)]
    return 100.0 * common.measure(inside) / red.window_s
