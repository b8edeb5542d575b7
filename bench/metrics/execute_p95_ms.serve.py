"""95th percentile of the serving worker's ``serving/execute_s`` (one
micro-batch through the query engine and ``topk_gather``) over the window,
in ms: ``server_timing.execute_ms.p95`` for the window alone."""


def read(layer: dict):
    h = (layer.get("window_hist") or {}).get("serving/execute_s")
    if h is None or h.count <= 0:
        return None
    return h.percentile(95) * 1e3
