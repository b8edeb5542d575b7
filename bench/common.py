"""What every driver of the benchmark shares: the run's context, the device
rule, compile accounting, percentiles and the interval arithmetic that the
trace and span readers use."""

from __future__ import annotations

import dataclasses
import json
import math
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class NoChip(SystemExit):
    """The run found no TPU, or fewer chips than the cell asks for: it exits
    with code 3 and prints no result."""

    def __init__(self, why: str):
        print(f"no chip: {why}", file=sys.stderr, flush=True)
        super().__init__(3)


@dataclasses.dataclass
class Context:
    """One run of one cell."""

    workload: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    t_process: float                      # time.monotonic() at process start
    work_dir: str                         # fixed, inside the checkout

    def note(self, what: str, **fields) -> None:
        """An earlier line of the run's standard output (never the last)."""
        print(f"{what}: " + json.dumps(fields, sort_keys=True, default=float),
              flush=True)


@dataclasses.dataclass
class Outcome:
    """What a driver hands back to ``run.py``."""

    attempted: int
    failed: int
    checks: dict                          # name -> (value, limit)
    end_to_end: dict                      # metric name -> value
    layer: dict                           # what per-layer readers read
    device: dict
    breakdown: dict | None = None

    @property
    def correct(self) -> bool:
        return all(v <= lim for v, lim in self.checks.values())


def require_tpu(platform: str, kind: str, count: int, chips: int) -> None:
    """The cell runs only on TPU chips, as many as it asks for, of a kind
    that ``peaks.json`` knows (another kind raises ``KeyError``)."""
    import roofline

    if platform != "tpu":
        raise NoChip(f"JAX computes on {platform!r}, not on a TPU")
    if count < chips:
        raise NoChip(f"JAX sees {count} chip(s), the cell asks for {chips}")
    roofline.peaks(kind)


def host_tpu_chips() -> int:
    """TPU chips attached to this host, read without starting JAX."""
    from repro.runtime.device import tpu_chips

    return tpu_chips()


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a benchmark file by path (file names may hold dots), once per
    process under ``name``."""
    import importlib.util

    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class CompileMeter:
    """JAX's compile and persistent-cache events in this process, counted
    through ``jax.monitoring`` (as ``chip_smoke.py`` does), so set-up and the
    window can each report what compiled in them."""

    _EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.traces = 0
        self.cache_hits = 0
        self.cache_writes = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event in self._EVENTS:
            self.compile_s += duration
            self.traces += event == self._EVENTS[0]

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_writes += 1

    def totals(self) -> dict:
        return {"compile_s": self.compile_s, "traces": self.traces,
                "cache_hits": self.cache_hits,
                "cache_writes": self.cache_writes}

    @staticmethod
    def since(before: dict, after: dict) -> dict:
        return {k: after[k] - before[k] for k in after}


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (``inf`` entries count as the slowest)."""
    xs = sorted(values)
    if not xs:
        return math.nan
    return xs[max(1, math.ceil(q / 100.0 * len(xs))) - 1]


def union(intervals) -> list[tuple[float, float]]:
    """Sorted disjoint union of (start, end) intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def measure(intervals) -> float:
    return sum(b - a for a, b in union(intervals))


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def subtract(intervals, minus) -> list[tuple[float, float]]:
    """The parts of ``intervals`` not covered by ``minus`` (both unions)."""
    out = []
    cut = union(minus)
    for a, b in union(intervals):
        cur = a
        for c, d in cut:
            if d <= cur or c >= b:
                continue
            if c > cur:
                out.append((cur, c))
            cur = max(cur, d)
        if cur < b:
            out.append((cur, b))
    return out


def since_process_start(t_process: float) -> float:
    return time.monotonic() - t_process
