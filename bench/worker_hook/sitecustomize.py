"""Benchmark hook inside the serving worker process.

The serving driver puts this directory first on ``PYTHONPATH`` and names a
directory in ``COOC_BENCH_HOOK_DIR`` before it starts ``CoocServer``. Each
process that ``multiprocessing`` spawns then makes a named pipe
``<dir>/cmd-<pid>`` and a thread that reads commands from it, so that the
worker, which holds the chip, answers what only it can tell. Each command
writes its reply to ``<dir>/<command>-<pid>.json``:

* ``count``: JAX's compile events in this process since the first
  ``count`` (traces, compile seconds, persistent-cache hits and writes);
* ``window-start``: the same, then start sampling the worker's main thread
  and, when ``COOC_BENCH_TRACE`` is 1, JAX's profiler;
* ``window-stop``: stop both, and report the device as JAX sees it, its
  peak memory, the compile events and the sampled stretches.

The sampler looks at the main thread's stack every ``SAMPLE_S`` seconds and
reports the longest stretches it spent in one place: where a stall lies
when the worker is the side that stalls. The thread blocks on the pipe
between commands, so the hook costs the worker nothing outside the window.

``COOC_BENCH_FAULT`` (tests only) breaks the worker's timed path on purpose:
``alter-topk`` swaps the first and the last id of every top-k answer row
where it is produced, leaving the scores as they were.

Without ``COOC_BENCH_HOOK_DIR`` the module does nothing, and with it a
process that ``multiprocessing`` did not spawn does nothing either.
"""

import os

_DIR = os.environ.get("COOC_BENCH_HOOK_DIR")
SAMPLE_S = 0.05


def _install() -> None:
    import json
    import sys
    import threading
    import time
    import traceback

    counts = {"compile_s": 0.0, "traces": 0, "cache_hits": 0, "cache_writes": 0}
    state = {"listening": False}
    trace_dir = os.path.join(_DIR, "trace")
    main_id = threading.main_thread().ident

    def on_duration(event, duration, **_):
        if event in ("/jax/core/compile/jaxpr_trace_duration",
                     "/jax/core/compile/jaxpr_to_mlir_module_duration",
                     "/jax/core/compile/backend_compile_duration"):
            counts["compile_s"] += duration
            counts["traces"] += event == "/jax/core/compile/jaxpr_trace_duration"

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            counts["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            counts["cache_writes"] += 1

    def compiles() -> dict:
        if not state["listening"]:
            import jax

            jax.monitoring.register_event_duration_secs_listener(on_duration)
            jax.monitoring.register_event_listener(on_event)
            state["listening"] = True
        return dict(counts)

    def sample(stop: threading.Event, out: list) -> None:
        while not stop.wait(SAMPLE_S):
            frame = sys._current_frames().get(main_id)
            where = traceback.extract_stack(frame, limit=4) if frame else []
            out.append((time.monotonic(), " < ".join(
                f"{os.path.basename(f.filename)}:{f.name}:{f.lineno}"
                for f in reversed(where))))

    def stretches(samples: list, n: int = 3) -> list:
        """The ``n`` longest runs of samples in one place: [seconds, where]."""
        runs = []
        for t, where in samples:
            if runs and runs[-1][2] == where:
                runs[-1][1] = t
            else:
                runs.append([t, t, where])
        runs.sort(key=lambda r: r[0] - r[1])
        return [[b - a + SAMPLE_S, w] for a, b, w in runs[:n]]

    def on_window_start() -> dict:
        out = {"compiles": compiles()}
        state["samples"], state["stop"] = [], threading.Event()
        state["sampler"] = threading.Thread(
            target=sample, args=(state["stop"], state["samples"]),
            name="bench-hook-sampler", daemon=True)
        state["sampler"].start()
        if os.environ.get("COOC_BENCH_TRACE") == "1":
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            state["tracing"] = True
        out["t_start"] = time.time()
        return out

    def on_window_stop() -> dict:
        import jax

        out = {"t_stop": time.time()}
        if state.pop("tracing", False):
            jax.profiler.stop_trace()
            out["trace_dir"] = trace_dir
        if "stop" in state:
            state["stop"].set()
            state["sampler"].join()
        devs = jax.devices()
        out.update(
            platform=devs[0].platform, kind=devs[0].device_kind,
            count=len(devs),
            memory_peak_bytes=max(
                (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in devs),
            compiles=compiles(),
            stretches=stretches(state.get("samples", [])),
            samples=len(state.get("samples", [])),
        )
        return out

    handlers = {"count": lambda: {"compiles": compiles()},
                "window-start": on_window_start,
                "window-stop": on_window_stop}

    def write(name: str, payload: dict) -> None:
        tmp = os.path.join(_DIR, f".{name}.tmp")
        with open(tmp, "w") as f:
            json.dump(payload, f, default=float)
        os.replace(tmp, os.path.join(_DIR, name))

    fifo = os.path.join(_DIR, f"cmd-{os.getpid()}")

    def commands():
        while True:
            with open(fifo) as f:  # blocks until the harness writes
                cmd = f.read().strip()
            if cmd not in handlers:
                continue
            try:
                reply = handlers[cmd]()
            except Exception:
                reply = {"error": traceback.format_exc()}
            write(f"{cmd}-{os.getpid()}.json", reply)

    os.mkfifo(fifo)
    threading.Thread(target=commands, name="bench-hook", daemon=True).start()
    if os.environ.get("COOC_BENCH_FAULT") == "alter-topk":
        _break_topk()
    write(f"loaded-{os.getpid()}", {"argv": sys.argv})


def _break_topk() -> None:
    """Wrap ``QueryEngine._topk_batch`` once ``repro.store.query`` is
    imported, so that the first and the last id of every answer row trade
    places."""
    import importlib.abc
    import sys

    def patch(mod):
        orig = mod.QueryEngine._topk_batch

        def altered(self, terms, k, score):
            ids, scores = orig(self, terms, k, score)
            ids = ids.copy()
            ids[:, [0, -1]] = ids[:, [-1, 0]]
            return ids, scores

        mod.QueryEngine._topk_batch = altered

    class Finder(importlib.abc.MetaPathFinder):
        def find_spec(self, name, path, target=None):
            if name != "repro.store.query":
                return None
            sys.meta_path.remove(self)
            import importlib.util

            spec = importlib.util.find_spec(name)
            loader = spec.loader
            exec_module = loader.exec_module

            def exec_and_patch(module):
                exec_module(module)
                patch(module)

            loader.exec_module = exec_and_patch
            return spec

    sys.meta_path.insert(0, Finder())


if _DIR and "--multiprocessing-fork" in __import__("sys").argv:
    _install()
