"""Percent of the serving window in which the worker was inside a
micro-batch (its ``serving/batch`` annotations, from the claim to the last
response) and no operation ran on the device: the union of those
annotations clipped to the trace window, less the device's busy intervals,
over ``window_s``. What the host holds the chip back by while it has work."""

import common

SPAN = "serving/batch"


def read(layer: dict):
    red = layer.get("trace")
    if red is None or red.window_s <= 0:
        return None
    spans = [(a, b) for n, a, b in red.host if n == SPAN]
    if not spans:
        return None
    inside = [p for lo, hi in red.window for p in common.clip(spans, lo, hi)]
    idle = common.subtract(inside, red.busy_intervals())
    return 100.0 * common.measure(idle) / red.window_s
