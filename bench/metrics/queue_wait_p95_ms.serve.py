"""95th percentile of the serving worker's ``serving/queue_wait_s`` over
the window (its histogram at the end less its state at the start), in ms:
``server_timing.queue_wait_ms.p95`` of ``CoocServer.stats()`` for the
window alone."""


def read(layer: dict):
    h = (layer.get("window_hist") or {}).get("serving/queue_wait_s")
    if h is None or h.count <= 0:
        return None
    return h.percentile(95) * 1e3
