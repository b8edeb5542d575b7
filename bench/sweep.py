#!/usr/bin/env python3
"""Find a serving cell's knee: its open-loop traffic at a few fixed rates,
one after another against one server, each for ``--seconds``.

    python3 bench/sweep.py --workload serve-wt10g-zipf --seed 7 \
        --rates 100,200,400,800 --seconds 20

Set-up is the cell's own (store, ``CoocServer``), and so is the warm-up
before each rate (``bench/drivers/serve.py``, for that rate's requests). Each rate prints one JSON
line: the offered and completed rates, latency percentiles from each
request's due time, whether latency grew from the first to the last quarter
of the window (a growing backlog), the 99th percentile of its micro-batch
sizes and its compile events. The knee is the highest rate whose completed rate keeps up with the offered one
without a growing backlog. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import common  # noqa: E402
import data  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)

    from repro.core.plan import CountJob, Planner, PlanExecutor
    from repro.data.corpus import Collection
    from repro.runtime.device import configure_compile_cache

    configure_compile_cache()
    bench = common.load_json(common.ROOT, "BENCHMARK.json")
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    cfg = common.load_json(BENCH, "configs", cell["config"] + ".json")
    traffic = common.load_json(BENCH, "traffic", cell["traffic"] + ".json")
    drv = common.load_module(os.path.join(BENCH, "drivers", "serve.py"),
                             "driver_serve")
    work = os.path.join(common.ROOT, ".bench_work", "sweep")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    try:
        ptr, terms, vocab = data.make_collection(cfg, args.seed)
        path = os.path.join(work, "store")
        PlanExecutor().execute(Planner().plan(CountJob(
            collection=Collection(ptr, terms, vocab), output="store",
            out_path=path, method=cfg["store_method"], df_descending=True)))
        n_live = int(np.count_nonzero(np.bincount(terms, minlength=vocab)))
        rates = [float(r) for r in args.rates.split(",")]
        hook = os.path.join(work, "hook")
        server = drv.start_server(path, hook, trace=False)
        try:
            threads, pid, answered = int(traffic["client_threads"]), None, 0
            for rate in rates:
                tr = {**traffic, "rate_per_s": rate}
                sched = drv.schedule(tr, n_live, args.seconds, args.seed)
                pid, warmed, warm = drv.warm_up(
                    server, hook, sched, drv.schedule(
                        tr, n_live, float(tr["warm_max_s"]), args.seed, stream=6),
                    path, tr, pid)
                answered += warmed
                start = drv.settled_stats(server, answered)
                before = drv.ask(hook, pid, "count")["compiles"]
                t_open, res = drv.send_open_loop(
                    server, sched, threads, float(traffic["request_timeout_s"]))
                after = drv.ask(hook, pid, "count")["compiles"]
                answered += sum(r[3] for r in res)
                end = drv.settled_stats(server, answered)
                due = t_open + sched["due"]
                lat = np.array([(r[2] - d) if r and r[3] else math.inf
                                for r, d in zip(res, due)])
                done = np.array([r[2] for r in res if r and r[3]])
                n = len(lat)
                q = n // 4
                bsz = drv.hist_diff(
                    end["metrics"]["histograms"].get("serving/batch_requests"),
                    start["metrics"]["histograms"].get("serving/batch_requests"))
                print(json.dumps({
                    "rate": rate, "requests": n,
                    "warm": warm,
                    "completed_in_window_per_s":
                        float(np.sum(done <= t_open + args.seconds) / args.seconds),
                    "failed": int(np.sum(~np.isfinite(lat))),
                    "p50_ms": common.percentile(lat, 50) * 1e3,
                    "p95_ms": common.percentile(lat, 95) * 1e3,
                    "p99_ms": common.percentile(lat, 99) * 1e3,
                    "first_quarter_p50_ms": float(np.median(lat[:q]) * 1e3),
                    "last_quarter_p50_ms": float(np.median(lat[-q:]) * 1e3),
                    "batch_requests_p99": bsz.percentile(99) if bsz else None,
                    "window_traces": after["traces"] - before["traces"],
                }, default=float), flush=True)
                if float(np.sum(done <= t_open + args.seconds)) < 0.9 * rate * args.seconds:
                    break  # past the knee: higher rates only pile up a backlog
        finally:
            server.stop()
    finally:
        shutil.rmtree(work, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
