"""Share of the roofline reached by the ``cooc_gram`` kernel in the traced
builds: the least time of its calls at their unpadded shapes (bench/
roofline.py) over the device time of its events. Its events are the
operations of the jitted ``cooc_gram_kernel`` module; the reader returns
nothing when the trace holds no such events or not one per call."""

import roofline

MODULE = "cooc_gram_kernel"


def read(layer: dict):
    red, calls = layer.get("trace"), [c for b in layer["gram_calls"] for c in b]
    if red is None or not calls:
        return None
    events = [(a, b) for _, module, a, b in red.ops() if MODULE in module]
    launches = {round(a, 9) for a, _ in events}
    if not events or len(launches) < len(calls):
        return None
    kernel_s = sum(b - a for a, b in events)
    pct, bound = roofline.share(calls, kernel_s, layer["peak"])
    layer.setdefault("notes", {})["cooc_gram_bound"] = bound
    return pct
