"""Percent of row lookups in the window that the query engine's LRU row
cache answered (``cache_hit_rate`` of ``CoocServer.stats()``, counted from
the window's start)."""


def read(layer: dict):
    hits, misses = layer.get("cache") or (0, 0)
    if hits + misses <= 0:
        return None
    return 100.0 * hits / (hits + misses)
