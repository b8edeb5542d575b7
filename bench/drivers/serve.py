"""Related-terms service: an open-loop stream of top-k lookups against a
``CoocServer`` over a store of the seeded collection.

Set-up builds the store with host ``list-scan`` in this process, which never
brings up a JAX backend (the one serving worker holds the chip), and starts
``CoocServer(store, workers=1, kernel="pallas", stats_interval_s=...)``. The
warm-up has two parts. First, one launch for every shape that the window's
own requests can form under the program's padding (a model of
``QueryEngine._topk_batch``: the terms of a micro-batch, and their longest
row padded to a power of two). Then, knowing nothing of shapes, it replays
the window's draws in another order at the window's rate, a few seconds at
a time, until a stretch brings no new compilation in the worker: where the
program's padding has moved away from the model, this still warms what the
traffic forms most often.
The window then sends each request at its due time from a pool of client
threads, whether or not earlier ones have been answered, and times it from
that due time to its complete answer. Afterwards every answer is compared
with the plain reference.

The worker's histograms and counters come from ``CoocServer.stats()``: at
the window's start (once the worker has published its state after the
warm-up) and after ``stop()``. The worker is reached through
``bench/worker_hook/sitecustomize.py`` only for what needs the worker
itself: its compile events, the device, its peak memory, the profiler, and
where its main thread spent the window.
"""

from __future__ import annotations

import gc
import json
import math
import multiprocessing
import os
import queue
import shutil
import threading
import time

import numpy as np

import common
import data
from reference import Reference

STATS_INTERVAL_S = 1.0


# --------------------------------------------------------------- traffic
def schedule(traffic: dict, n_live: int, seconds: float, seed: int,
             stream: int = 3) -> dict:
    """The run's requests in due order: per request its term, and its due
    time in seconds from the window's start.

    Every seed gets the same set of draws (from ``mix_seed``) in its own
    order: the same count of requests, the same popularity ranks and the
    same inter-arrival gaps; ``stream`` picks another order for one seed.
    Ranks index the live terms by descending df, most popular first and
    unscrambled; live terms are the ids 0 .. n_live - 1 of a df-descending
    collection, so a rank is a term id."""
    n = int(round(float(traffic["rate_per_s"]) * seconds))
    fixed = np.random.default_rng([int(traffic["mix_seed"]), 2])
    if traffic["distribution"] == "zipf":
        ranks = data.ycsb_zipf_ranks(n_live, n, float(traffic["theta"]), fixed)
    elif traffic["distribution"] == "uniform":
        ranks = fixed.integers(0, n_live, size=n)
    else:
        raise ValueError(f"unknown distribution {traffic['distribution']!r}")
    gaps = fixed.exponential(size=n)
    gaps *= seconds / (gaps.sum() + fixed.exponential())
    order = np.random.default_rng([int(seed), stream])
    return {"term": ranks[order.permutation(n)],
            "due": np.cumsum(order.permutation(gaps)),
            "k": int(traffic["k"]), "score": str(traffic["score"])}


def make_request(sched: dict, i: int):
    from repro.store import TopKRequest

    return TopKRequest([int(sched["term"][i])], k=sched["k"], score=sched["score"])


def part(sched: dict, lo: float, hi: float) -> dict:
    """The requests due in [lo, hi), due times from ``lo``."""
    sel = (sched["due"] >= lo) & (sched["due"] < hi)
    return {**sched, "term": sched["term"][sel], "due": sched["due"][sel] - lo}


def pad_bucket(row_len: int) -> int:
    """The candidate width a top-k launch pads a row of this length to
    (``QueryEngine._topk_batch``: a power of two, at least 8)."""
    return max(8, 1 << (row_len - 1).bit_length()) if row_len else 8


def shape_groups(sched: dict, row_len: dict, window: int) -> list:
    """One group of terms per launch shape the window can form: any run of
    up to ``window`` consecutive requests may share a micro-batch, and its
    terms make one launch of shape (terms, padded width of the longest)."""
    seen: dict = {}
    terms = [int(t) for t in sched["term"]]
    for a in range(len(terms)):
        width = 0
        for n, t in enumerate(terms[a:a + window], 1):
            width = max(width, pad_bucket(row_len[t]))
            seen.setdefault((n, width), terms[a:a + n])
    return [seen[key] for key in sorted(seen)]


# ------------------------------------------------------------ open loop
def send_open_loop(server, sched: dict, threads: int, timeout: float):
    """Send every request at its due time from a pool of client threads.

    Returns (start, per-request [offered, picked, done, ok, answer]):
    ``offered`` is when the generator handed the request to the pool,
    ``picked`` when a client thread took it up."""
    n = len(sched["due"])
    results: list = [None] * n
    offered = np.zeros(n)
    todo: queue.SimpleQueue = queue.SimpleQueue()

    def client_loop():
        client = server.client()
        while True:
            i = todo.get()
            if i is None:
                return
            picked = time.perf_counter()
            try:
                ans, ok = client.execute([make_request(sched, i)],
                                         timeout=timeout)[0], True
            except Exception as e:  # refused, failed or timed out
                ans, ok = f"{type(e).__name__}: {e}", False
            results[i] = (offered[i], picked, time.perf_counter(), ok, ans)

    pool = [threading.Thread(target=client_loop, daemon=True)
            for _ in range(threads)]
    for t in pool:
        t.start()
    t0 = time.perf_counter() + 0.05
    for i, d in enumerate(sched["due"]):
        wait = t0 + d - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        offered[i] = time.perf_counter()
        todo.put(i)
    for _ in pool:
        todo.put(None)
    for t in pool:
        t.join()
    return t0, results


class HarnessWatch:
    """How long this process could not run: the longest garbage collection,
    and the most a 10 ms sleep overslept in a thread of its own."""

    def __init__(self):
        self.gc_max_s, self.gc_full, self.tick_late_max_s = 0.0, 0, 0.0
        self._gc_t0 = 0.0
        self._stop = threading.Event()
        self._ticker = threading.Thread(target=self._tick, daemon=True)

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        else:
            self.gc_max_s = max(self.gc_max_s, time.perf_counter() - self._gc_t0)
            self.gc_full += info["generation"] == 2

    def _tick(self):
        while True:
            t = time.perf_counter()
            if self._stop.wait(0.01):
                return
            self.tick_late_max_s = max(self.tick_late_max_s,
                                       time.perf_counter() - t - 0.01)

    def __enter__(self):
        gc.callbacks.append(self._on_gc)
        self._ticker.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._ticker.join()
        gc.callbacks.remove(self._on_gc)

    def report(self) -> dict:
        return {"gc_max_ms": self.gc_max_s * 1e3, "gc_full": self.gc_full,
                "tick_late_max_ms": self.tick_late_max_s * 1e3}


# ----------------------------------------------------------------- check
def check_answers(ref: Reference, sched: dict, results: list,
                  score_limit: float) -> dict:
    """Compare every answer with the reference. Returns the numbers compared
    and, per request, whether its answer is correct.

    ``answer_errors`` counts exact disagreements: a count that differs, a
    returned id that does not co-occur with the term, a repeated id, a
    padding slot where the reference has a candidate or the reverse.
    ``score_gap`` is the widest gap of a PMI or Dice answer: the larger of
    ``value_gap``, how far a served score lies from the float64 reference
    score of its id, and ``rank_gap``, how far that id's reference score
    lies below the reference's score at the same rank; each as a share of
    the reference score (for PMI, of at least 1)."""
    n = len(results)
    good = np.zeros(n, dtype=bool)
    errors, unanswered = 0, 0
    gaps = {"value_gap": 0.0, "rank_gap": 0.0}
    by_term: dict = {}
    for i, r in enumerate(results):
        if r is None or not r[3]:
            unanswered += 1
        else:
            by_term.setdefault(int(sched["term"][i]), []).append(i)
    for t, idx in by_term.items():
        row = ref.row(t)
        ref_s = ref.scores(t, row, sched["score"])
        for i in idx:
            ids, vals = (np.asarray(x).reshape(-1) for x in results[i][4])
            bad, v, rk = compare_topk(row, ref_s, sched["score"], ids, vals,
                                      sched["k"])
            errors += bad
            gaps["value_gap"] = max(gaps["value_gap"], v)
            gaps["rank_gap"] = max(gaps["rank_gap"], rk)
            good[i] = bad == 0 and max(v, rk) <= score_limit
    return {"answer_errors": errors, "score_gap": max(gaps.values()),
            "unanswered": unanswered, "good": good, **gaps}


def compare_topk(row: np.ndarray, ref_s: np.ndarray, score: str,
                 ids: np.ndarray, vals: np.ndarray, k: int):
    """(exact disagreements, value gap, rank gap) of one served top-k row
    against the term's reference row and float64 scores."""
    live = int(np.count_nonzero(row))
    top = np.sort(ref_s)[::-1][:k]
    bad, value_gap, rank_gap = 0, 0.0, 0.0
    if len(ids) != k or len(set(ids[ids >= 0].tolist())) != int(np.sum(ids >= 0)):
        return k, 0.0, 0.0
    for j in range(k):
        cid = int(ids[j])
        if j >= live:
            bad += cid != -1
            continue
        if not 0 <= cid < len(row) or row[cid] == 0:
            bad += 1
            continue
        if score == "count":
            bad += int(vals[j]) != row[cid] or row[cid] != top[j]
            continue
        want = ref_s[cid]
        scale = max(abs(want), 1.0) if score == "pmi" else abs(want)
        value_gap = max(value_gap, abs(float(vals[j]) - want) / scale)
        rank_gap = max(rank_gap, (top[j] - want) / scale)
    return bad, value_gap, rank_gap


# ------------------------------------------------------------ worker hook
HOOK = os.path.join(common.BENCH, "worker_hook")


def start_server(store_path: str, hook_dir: str, trace: bool):
    """``CoocServer(store, workers=1, kernel="pallas")`` with the hook in
    its worker, publishing its stats every ``STATS_INTERVAL_S``."""
    from repro.store import CoocServer

    os.makedirs(hook_dir, exist_ok=True)
    os.environ["PYTHONPATH"] = os.pathsep.join(
        [HOOK] + [p for p in os.environ.get("PYTHONPATH", "").split(os.pathsep) if p])
    os.environ["COOC_BENCH_HOOK_DIR"] = hook_dir
    os.environ["COOC_BENCH_TRACE"] = "1" if trace else "0"
    return CoocServer(store_path, workers=1, kernel="pallas",
                      stats_interval_s=STATS_INTERVAL_S).start()


def hooked_worker(hook_dir: str) -> int:
    """The pid of this process's one live child that loaded the hook."""
    alive = {p.pid for p in multiprocessing.active_children()}
    pids = sorted(int(f.rsplit("-", 1)[1]) for f in os.listdir(hook_dir)
                  if f.startswith("loaded-") and int(f.rsplit("-", 1)[1]) in alive)
    if len(pids) != 1:
        raise RuntimeError(f"expected one hooked serving worker, found {pids}")
    return pids[0]


def ask(hook_dir: str, pid: int, cmd: str, seconds: float = 120.0) -> dict:
    """Send the worker's hook one command and wait for its reply."""
    reply = os.path.join(hook_dir, f"{cmd}-{pid}.json")
    if os.path.exists(reply):
        os.remove(reply)
    with open(os.path.join(hook_dir, f"cmd-{pid}"), "w") as f:
        f.write(cmd)
    deadline = time.monotonic() + seconds
    while not os.path.exists(reply):
        if time.monotonic() > deadline:
            raise RuntimeError(f"the serving worker did not answer {cmd!r}")
        time.sleep(0.02)
    with open(reply) as f:
        out = json.load(f)
    if "error" in out:
        raise RuntimeError(out["error"])
    return out


def warm_shapes(client, groups: list, sched: dict) -> None:
    """One launch per group of terms."""
    from repro.store import TopKRequest

    for g in groups:
        client.execute([TopKRequest(g, k=sched["k"], score=sched["score"])],
                       timeout=600.0)


def replay_until_quiet(server, hook_dir: str, pid: int, sched: dict,
                       traffic: dict) -> dict:
    """Replay ``sched`` a chunk of ``warm_chunk_s`` seconds at a time until
    ``warm_quiet_chunks`` chunks in a row compile nothing in the worker, or
    the schedule runs out. Counts the requests answered."""
    chunk = float(traffic["warm_chunk_s"])
    quiet_needed = int(traffic["warm_quiet_chunks"])
    last = ask(hook_dir, pid, "count")["compiles"]["traces"]
    answered, quiet, chunks, t = 0, 0, 0, 0.0
    while quiet < quiet_needed and t < sched["due"][-1]:
        p = part(sched, t, t + chunk)
        _, res = send_open_loop(server, p, int(traffic["client_threads"]), 600.0)
        answered += sum(r[3] for r in res)
        chunks += 1
        t += chunk
        now = ask(hook_dir, pid, "count")["compiles"]["traces"]
        quiet = quiet + 1 if now == last else 0
        last = now
    return {"chunks": chunks, "answered": answered, "quiet": quiet >= quiet_needed}


def warm_up(server, hook_dir: str, sched: dict, warm_sched: dict,
            store_path: str, traffic: dict, pid: int | None = None):
    """Both parts of the warm-up for the window ``sched``; returns (worker
    pid, requests answered, what to report)."""
    from repro.store import Store

    t0 = time.perf_counter()
    store = Store.open(store_path)
    row_len = {int(t): len(store.neighbours(int(t))[0])
               for t in np.unique(sched["term"])}
    groups = shape_groups(sched, row_len, int(traffic["warm_batch_requests"]))
    client, first = server.client(), pid is None
    if first:
        client.execute([make_request(sched, 0)], timeout=600.0)
        pid = hooked_worker(hook_dir)
    warm_shapes(client, groups, sched)
    t1 = time.perf_counter()
    replay = replay_until_quiet(server, hook_dir, pid, warm_sched, traffic)
    return pid, first + len(groups) + replay["answered"], {
        "shapes": len(groups), "shapes_s": t1 - t0,
        "replay_s": time.perf_counter() - t1, "replay": replay}


def settled_stats(server, requests: int, seconds: float = 30.0) -> dict:
    """``server.stats()`` once the worker has published a state that counts
    ``requests`` requests (it does when idle for ``STATS_INTERVAL_S``)."""
    deadline = time.monotonic() + seconds
    while True:
        s = server.stats()
        if s.get("requests", 0) >= requests:
            return s
        if time.monotonic() > deadline:
            raise RuntimeError(f"the worker published {s.get('requests')} of "
                               f"{requests} requests")
        time.sleep(0.1)


def hist_diff(end: dict | None, start: dict | None):
    """The histogram of what was recorded between two states of it."""
    from repro.obs import Histogram

    if not end:
        return None
    h = Histogram.from_state(end)
    if start:
        s = Histogram.from_state(start)
        for b, c in s.buckets.items():
            h.buckets[b] -= c
        h.count -= s.count
        h.total -= s.total
        h.vmin, h.vmax = 0.0, math.inf
    return h


# ------------------------------------------------------------------- run
def run(ctx: common.Context) -> common.Outcome:
    from repro.core.plan import CountJob, Planner, PlanExecutor
    from repro.data.corpus import Collection
    from repro.runtime import device

    chips = ctx.workload["chips"]
    if common.host_tpu_chips() < chips:
        raise common.NoChip(f"this host has {common.host_tpu_chips()} TPU "
                            f"chip(s), the cell asks for {chips}")
    cfg, tr = ctx.config, ctx.traffic
    ptr, terms, vocab = data.make_collection(cfg, ctx.seed)
    ref = Reference(ptr, terms, vocab)
    store_path = os.path.join(ctx.work_dir, "store")
    t0 = time.perf_counter()
    PlanExecutor().execute(Planner().plan(CountJob(
        collection=Collection(ptr, terms, vocab), output="store",
        out_path=store_path, method=cfg["store_method"], df_descending=True)))
    t1 = time.perf_counter()
    os.sync()  # the store's pages reach the disk before the window, not in it
    ctx.note("store_build", seconds=t1 - t0, sync_s=time.perf_counter() - t1,
             backend_started=device.backend_started())
    n_live = int(np.count_nonzero(ref.df))
    sched = schedule(tr, n_live, ctx.seconds, ctx.seed)
    warm_sched = schedule(tr, n_live, float(tr["warm_max_s"]), ctx.seed, stream=6)
    gc.collect()
    gc.freeze()

    hook_dir = os.path.join(ctx.work_dir, "hook")
    server = start_server(store_path, hook_dir, ctx.trace)
    try:
        pid, answered, warm = warm_up(server, hook_dir, sched, warm_sched,
                                      store_path, tr)
        start = settled_stats(server, answered)
        ctx.note("warm_up", **warm)
        hook0 = ask(hook_dir, pid, "window-start")
        setup_s = common.since_process_start(ctx.t_process)
        with HarnessWatch() as watch:
            t_open, results = send_open_loop(
                server, sched, int(tr["client_threads"]),
                float(tr["request_timeout_s"]))
        hook = ask(hook_dir, pid, "window-stop")
    finally:
        stats = server.stop()
    common.require_tpu(hook["platform"], hook["kind"], hook["count"], chips)
    ctx.note("window_compiles", **{k: hook["compiles"][k] - hook0["compiles"][k]
                                   for k in hook["compiles"]})
    start_h = start["metrics"]["histograms"]
    end_h = stats["metrics"]["histograms"]
    bsz = hist_diff(end_h.get("serving/batch_requests"),
                    start_h.get("serving/batch_requests"))
    ctx.note("server", batches=stats["batches"] - start["batches"],
             batch_requests_p99=bsz.percentile(99) if bsz else None,
             shed=stats["resilience"]["shed"], workers_lost=stats["workers_lost"])
    ctx.note("worker_stretches", samples=hook["samples"],
             longest=hook["stretches"])

    due = t_open + sched["due"]
    lat = np.array([(r[2] - d) if (r and r[3]) else math.inf
                    for r, d in zip(results, due)])
    ctx.note("generator", requests=len(results),
             offer_late_max_ms=float(max(r[0] - d for r, d in zip(results, due)) * 1e3),
             pick_wait_max_ms=float(max(r[1] - r[0] for r in results) * 1e3),
             **watch.report())

    limits = cfg["limits"]
    t0 = time.perf_counter()
    verdict = check_answers(ref, sched, results, limits["score_gap"])
    ctx.note("check", seconds=time.perf_counter() - t0,
             value_gap=verdict["value_gap"], rank_gap=verdict["rank_gap"])
    good = int(verdict["good"].sum())
    layer = {"window_hist": {
        name: hist_diff(end_h.get(name), start_h.get(name))
        for name in ("serving/queue_wait_s", "serving/execute_s")},
        "cache": (stats["cache_hits"] - start["cache_hits"],
                  stats["cache_misses"] - start["cache_misses"])}
    device_info = {"platform": hook["platform"], "kind": hook["kind"],
                   "count": hook["count"],
                   "memory_peak_bytes": hook["memory_peak_bytes"]}
    breakdown = None
    if ctx.trace:
        import devtrace

        red = devtrace.reduce(hook["trace_dir"],
                              window_s=hook["t_stop"] - hook0["t_start"])
        device_info.update(busy_s=red.busy_s, window_s=red.window_s)
        breakdown = red.breakdown()
        layer["trace"] = red
    shutil.rmtree(store_path, ignore_errors=True)
    return common.Outcome(
        attempted=len(results),
        failed=len(results) - good,
        checks={name: (verdict[name], limits[name])
                for name in ("answer_errors", "score_gap", "unanswered")},
        end_to_end={
            "setup_s": setup_s,
            "lookup_p50_ms": common.percentile(lat, 50) * 1e3,
            "lookup_p95_ms": common.percentile(lat, 95) * 1e3,
            "lookups_per_s": good / ctx.seconds,
        },
        layer=layer,
        device=device_info,
        breakdown=breakdown,
    )
