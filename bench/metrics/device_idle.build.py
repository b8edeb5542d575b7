"""Percent of the traced build time in which no operation ran on the
device: one less the union of the device operations' intervals over the
time inside the harness's build annotations."""


def read(layer: dict):
    red = layer.get("trace")
    if red is None or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
