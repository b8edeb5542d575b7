"""Reduction of a JAX profiler trace to the benchmark's device numbers.

``reduce(trace_dir, ...)`` reads the ``.xplane.pb`` that
``jax.profiler.start_trace`` wrote and returns a :class:`Reduction`:

* device operations: the events of the ``XLA Ops`` line of every TPU plane,
  each named by its HLO op (``cooc_gram_kernel.1`` of the event's HLO text)
  and by the ``XLA Modules`` event it runs in (``jit_cooc_gram_kernel``);
* host events: every event of the host planes, among them the harness's
  own ``jax.profiler.TraceAnnotation`` spans;
* busy time: the union of the device operations' intervals inside the
  window, averaged over the chips that ran anything; the idle share is one
  less busy over window;
* the breakdown: the device operations that took most time, and the longest
  idle gaps, each named by what the host was doing then.

All times are seconds on the trace's own clock. The device planes' clock
runs up to a few milliseconds apart from the host's in these traces (a
kernel can show before the annotation that launched it), so device
operations are taken from the window widened by ``SLACK_S`` on each side.
"""

from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict

import common

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SLACK_S = 0.005


class Reduction:
    def __init__(self, device_ops: dict, host: list, window: list):
        self.device_ops = device_ops      # plane -> [(name, module, t0, t1)]
        self.host = host                  # [(name, t0, t1)]
        self.window = common.union(window)
        self.window_s = common.measure(self.window)
        used = [ops for ops in device_ops.values() if ops]
        self.chips = len(used)
        self.busy_s = (
            sum(common.measure(self._clip([(a, b) for _, _, a, b in ops]))
                for ops in used) / self.chips if used else 0.0
        )

    def _clip(self, intervals):
        out = []
        for lo, hi in self.window:
            out += common.clip(intervals, lo - SLACK_S, hi + SLACK_S)
        return out

    def ops(self):
        """Every device operation inside the window, over all chips."""
        out = []
        for ops in self.device_ops.values():
            for name, module, a, b in ops:
                if any(a < hi + SLACK_S and b > lo - SLACK_S
                       for lo, hi in self.window):
                    out.append((name, module, a, b))
        return out

    def annotations(self, prefix: str):
        return sorted((n, a, b) for n, a, b in self.host if n.startswith(prefix))

    def busy_intervals(self):
        return common.union(self._clip(
            [(a, b) for ops in self.device_ops.values() for _, _, a, b in ops]))

    def idle_gaps(self):
        """The window's intervals in which no chip ran an operation."""
        return common.subtract(self.window, self.busy_intervals())

    def host_label(self, a: float, b: float) -> str:
        """The longest host event overlapping (a, b), other than the
        harness's window annotations."""
        best, best_len = "no host event", 0.0
        for name, x, y in self.host:
            if name.startswith("bench/"):
                continue
            over = min(b, y) - max(a, x)
            if over > best_len:
                best, best_len = name, over
        return best

    def breakdown(self, pieces=None, top: int = 10) -> dict:
        """``device_ops``: [name, seconds] of the operations that took most
        device time (name = ``module:op``); ``idle_gaps``: [label, seconds]
        of the longest idle stretches. ``pieces(a, b)`` cuts a gap into
        labelled parts [(label, a, b), ...] (by what the host was doing);
        without it a gap is one part, named after the host event that
        overlaps it most."""
        per = defaultdict(float)
        for name, module, a, b in self.ops():
            per[f"{module}:{name}" if module else name] += b - a
        ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
        if pieces is None:  # label only the longest gaps: a scan each
            gaps = sorted(self.idle_gaps(), key=lambda g: g[0] - g[1])[:top]
            parts = [(self.host_label(a, b), a, b) for a, b in gaps]
        else:
            parts = [p for a, b in self.idle_gaps() for p in pieces(a, b)]
            parts.sort(key=lambda p: p[1] - p[2])
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, b - a] for n, a, b in parts[:top]]}


def xplane_file(trace_dir: str) -> str:
    """The newest profile under a ``start_trace`` directory (or the file)."""
    if trace_dir.endswith(".xplane.pb"):
        return trace_dir
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no profile under {trace_dir}")
    return files[-1]


def op_name(hlo: str) -> str:
    """``cooc_gram_kernel.1`` of ``%cooc_gram_kernel.1 = f32[...] ...``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def read(path: str):
    """(device ops per TPU plane, host events) of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = sorted((ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9,
                           ev.name.split("(", 1)[0])
                          for ev in lines.get(MODULES_LINE, []))
            starts = [m[0] for m in mods]
            ops = []
            for ev in lines.get(OPS_LINE, []):
                t0 = ev.start_ns * 1e-9
                i = bisect.bisect_right(starts, t0) - 1
                module = mods[i][2] if i >= 0 and t0 <= mods[i][1] else ""
                ops.append((op_name(ev.name), module, t0,
                            t0 + ev.duration_ns * 1e-9))
            device[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    t0 = ev.start_ns * 1e-9
                    host.append((ev.name, t0, t0 + ev.duration_ns * 1e-9))
    return device, host


def reduce(trace_dir: str, *, annotation: str | None = None,
           window_s: float | None = None) -> Reduction:
    """Reduce the newest profile under ``trace_dir`` (or that file). The
    window is the union of the host annotations named ``annotation…`` when
    given, else the span from the first to the last event of the trace;
    ``window_s``, when given, replaces the window's length (a window timed
    by the host that started and stopped the profiler)."""
    device, host = read(xplane_file(trace_dir))
    if annotation is not None:
        window = [(a, b) for n, a, b in host if n.startswith(annotation)]
    else:
        times = [t for _, a, b in host for t in (a, b)]
        times += [t for ops in device.values() for *_, a, b in ops for t in (a, b)]
        window = [(min(times), max(times))] if times else []
    red = Reduction(device, host, window)
    if window_s is not None:
        red.window_s = window_s
    return red
