#!/usr/bin/env python3
"""Smoke run of the count → store → query path on one TPU chip.

    python3 chip_smoke.py [--docs 10000] [--vocab 65536] [--seed 0]

Drives the system's main path once, through the entry points a user calls,
on a WT10G-shaped collection (the paper's Table 1 shape: Zipf document
frequencies, about 230 raw terms per document before de-duplication;
vocabulary cut to the 65,536-term head that ``configs/cooc_wt10g.py`` uses):

1. device      — JAX must report a TPU; anything else exits non-zero
                 before any other phase runs;
2. collection  — ``synthetic_zipf_collection`` from ``--seed``, renumbered
                 df-descending;
3. reference   — ``CountJob(method="list-scan", output="store")`` through
                 ``Planner`` → ``PlanExecutor``: the host reference build;
4. device_count — the same collection through ``method="freq-split"``,
                 whose head Gram runs the compiled ``cooc_gram`` kernel
                 (the planner takes ``use_kernel`` from the platform); every
                 segment array must be byte-identical to the reference's;
5. queries     — ``QueryEngine(kernel="pallas")``, compiled, answers top-k
                 under count, PMI and Dice for a batch holding the 8
                 highest-df terms, plus a pair-count batch; results must be
                 bit-identical to ``kernel="numpy"``, count top-k must equal
                 a host numpy ranking of the store rows, and pair counts
                 the counts read from those rows;
6. server      — ``CoocServer(workers=1, kernel="pallas")`` answers the same
                 requests identically; its worker must report a TPU.

A chip belongs to one process at a time, so phases 1-5 run in one spawned
child that exits before the server's worker takes the chip; this process
never starts a JAX backend itself. Each phase prints one line with its wall
time, the time spent compiling and the compilation-cache hits apart. The
last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import multiprocessing as mp
import os
import queue
import shutil
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

from repro.core.plan import CountJob, Planner, PlanExecutor  # noqa: E402
from repro.data.corpus import synthetic_zipf_collection  # noqa: E402
from repro.data.preprocess import remap_df_descending  # noqa: E402
from repro.runtime.device import configure_compile_cache  # noqa: E402
from repro.store import (  # noqa: E402
    CoocServer,
    PairCountsRequest,
    QueryEngine,
    TopKRequest,
)

SCORES = ("count", "pmi", "dice")
K = 10
HEAD_TERMS = 8      # the highest-df terms: the longest neighbour rows
OTHER_TERMS = 24    # drawn from the rest of the live vocabulary
PAIRS = 256


class CompileMeter:
    """Totals of JAX's compile-time and persistent-cache events in this
    process, so each phase can report compilation apart from running."""

    _EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_writes = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event in self._EVENTS:
            self.compile_s += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_writes += 1

    def totals(self) -> tuple[float, int, int]:
        return self.compile_s, self.cache_hits, self.cache_writes


@contextlib.contextmanager
def phase(name: str, meter: CompileMeter | None = None):
    """Print ``phase <name>: wall_s=… compile_s=… run_s=…`` for the block;
    the dict it yields (the block may fill it) is appended to the line."""
    c0, h0, w0 = meter.totals() if meter else (0.0, 0, 0)
    t0 = time.perf_counter()
    info: dict = {}
    yield info
    wall = time.perf_counter() - t0
    c1, h1, w1 = meter.totals() if meter else (0.0, 0, 0)
    fields = {"wall_s": wall}
    if meter:
        fields.update(
            compile_s=c1 - c0, run_s=wall - (c1 - c0),
            cache_hits=h1 - h0, cache_writes=w1 - w0,
        )
    fields.update(info)
    print(f"phase {name}: " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


# ----------------------------------------------------------------- phases
def check_device() -> dict:
    """The device as JAX reports it; exits non-zero unless it is a TPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"phase device: FAILED, JAX found {devs[0].platform!r}, not a "
              "TPU", file=sys.stderr, flush=True)
        sys.exit(2)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def make_collection(docs: int, vocab: int, seed: int):
    """A WT10G-shaped collection, term ids renumbered by descending df."""
    c = synthetic_zipf_collection(docs, vocab=vocab, mean_len=230, seed=seed)
    return remap_df_descending(c)[0]


def build_store(c, method: str, path: str, **job_kwargs):
    """Count ``c`` into a new store at ``path`` through the planner."""
    job = CountJob(collection=c, output="store", method=method, out_path=path,
                   df_descending=True, **job_kwargs)
    plan = Planner().plan(job)
    res = PlanExecutor().execute(plan)
    return plan, res.store


def assert_same_segments(a, b) -> int:
    """Every array file of the two stores' single segments is identical;
    returns the number of distinct pairs."""
    (sa,), (sb,) = a.segments, b.segments
    names = sorted(f for f in os.listdir(sa.path) if f.endswith(".bin"))
    if names != sorted(f for f in os.listdir(sb.path) if f.endswith(".bin")):
        raise AssertionError("segments hold different files")
    for name in names:
        x = np.fromfile(os.path.join(sa.path, name), dtype=np.uint8)
        y = np.fromfile(os.path.join(sb.path, name), dtype=np.uint8)
        if not np.array_equal(x, y):
            raise AssertionError(f"{name} differs between the two builds")
    return int(sa.nnz)


def make_requests(store, seed: int) -> list:
    """Top-k under every score for the head terms plus a seeded draw of
    other live terms, and a pair batch that mixes co-occurring pairs with
    random ones."""
    rng = np.random.default_rng(seed)
    df = store.df()
    live = np.nonzero(df > 0)[0]
    others = rng.choice(live[live >= HEAD_TERMS], size=OTHER_TERMS, replace=False)
    terms = np.concatenate([np.arange(HEAD_TERMS), np.sort(others)])
    a = rng.choice(live, size=PAIRS)
    b = rng.choice(live, size=PAIRS)
    for i in range(0, PAIRS, 2):  # every other pair: a real neighbour of a
        ids, _ = store.neighbours(int(a[i]))
        if len(ids):
            b[i] = ids[rng.integers(len(ids))]
    reqs = [TopKRequest(terms, k=K, score=s) for s in SCORES]
    return reqs + [PairCountsRequest(np.stack([a, b], axis=1))]


def host_topk_count(store, terms) -> tuple[np.ndarray, np.ndarray]:
    """Count top-k by plain numpy over the store rows: highest count first,
    ties to the earlier slot of the row (the engine's tie rule)."""
    ids_out = np.full((len(terms), K), -1, dtype=np.int64)
    cnt_out = np.zeros((len(terms), K), dtype=np.int64)
    for r, t in enumerate(terms):
        ids, cnts = store.neighbours(int(t))
        order = np.argsort(-np.asarray(cnts), kind="stable")[:K]
        ids_out[r, :len(order)] = np.asarray(ids)[order]
        cnt_out[r, :len(order)] = np.asarray(cnts)[order]
    return ids_out, cnt_out


def host_pair_counts(store, pairs) -> np.ndarray:
    """Each pair's count read from the first term's store row (0 where the
    second term is not in it)."""
    out = np.zeros(len(pairs), dtype=np.int64)
    for i, (a, b) in enumerate(pairs):
        ids, cnts = store.neighbours(int(a))
        hit = np.nonzero(np.asarray(ids) == b)[0]
        if len(hit):
            out[i] = np.asarray(cnts)[hit[0]]
    return out


def check_answers(reqs, got, want, what: str) -> None:
    """Bit-identical answers to the same request list."""
    for req, g, w in zip(reqs, got, want):
        if isinstance(req, PairCountsRequest):
            g, w = (g,), (w,)
        for x, y in zip(g, w):
            if not np.array_equal(np.asarray(x), np.asarray(y)):
                raise AssertionError(f"{what}: {type(req).__name__} differs")


def run_queries(store, reqs) -> tuple[list, bool, int]:
    """Answer ``reqs`` with the Pallas and the numpy engines, check them
    against each other and the host references; returns the answers, the
    Pallas engine's interpret flag and the longest row in the batch."""
    pallas = QueryEngine(store, kernel="pallas")
    numpy_ = QueryEngine(store, kernel="numpy")
    got = pallas.execute(reqs)
    check_answers(reqs, got, numpy_.execute(reqs), "pallas vs numpy")
    terms = reqs[0].terms
    ids, cnts = host_topk_count(store, terms)
    count_ans = got[SCORES.index("count")]
    if not (np.array_equal(count_ans[0], ids)
            and np.array_equal(count_ans[1], cnts)):
        raise AssertionError("count top-k differs from the host ranking")
    if not np.array_equal(got[-1], host_pair_counts(store, reqs[-1].pairs)):
        raise AssertionError("pair counts differ from the store rows")
    longest = max(len(store.neighbours(int(t))[0]) for t in terms)
    return got, pallas.interpret, longest


def serve_requests(store_path: str, reqs, want) -> dict:
    """Answer ``reqs`` through a one-worker ``CoocServer``; returns the
    device the worker reported."""
    with phase("server") as info:
        t0 = time.perf_counter()
        server = CoocServer(store_path, workers=1, kernel="pallas").start()
        try:
            client = server.client()
            check_answers(reqs, client.execute(reqs, timeout=600.0), want,
                          "server")
            info["first_call_s"] = time.perf_counter() - t0
            t1 = time.perf_counter()
            check_answers(reqs, client.execute(reqs, timeout=600.0), want,
                          "server")
            info["run_s"] = time.perf_counter() - t1
            devices = server.stats()["devices"]
            deadline = time.monotonic() + 30.0
            while not devices and time.monotonic() < deadline:
                time.sleep(0.05)
                devices = server.stats()["devices"]
        finally:
            server.stop()
        info["worker"] = json.dumps(devices.get(0), sort_keys=True)
    return devices.get(0) or {}


def device_phases(args, out_dir: str, result_q) -> None:
    """Phases 1-5, in the one process that holds the chip while they run;
    sends the device, the requests and their answers back to the parent."""
    configure_compile_cache()
    meter = CompileMeter()
    with phase("device", meter) as info:
        dev = check_device()
        info.update(platform=dev["platform"], kind=repr(dev["kind"]),
                    count=dev["count"])
    with phase("collection", meter) as info:
        c = make_collection(args.docs, args.vocab, args.seed)
        info.update(docs=c.num_docs, vocab=c.vocab_size,
                    postings=c.num_postings)
    with phase("reference", meter) as info:
        _, ref = build_store(c, "list-scan", os.path.join(out_dir, "reference"))
        info["pairs"] = int(ref.segments[0].nnz)
    with phase("device_count", meter) as info:
        path = os.path.join(out_dir, "freq_split")
        plan, dev_store = build_store(c, "freq-split", path)
        if plan.method_kwargs["use_kernel"] != (dev["platform"] == "tpu"):
            raise AssertionError("the platform did not decide the kernel")
        info["identical_pairs"] = assert_same_segments(ref, dev_store)
        del dev_store
        shutil.rmtree(path)
    reqs = make_requests(ref, args.seed)
    with phase("queries_first", meter) as info:
        _, interp, longest = run_queries(ref, reqs)
        if interp != (dev["platform"] != "tpu"):
            raise AssertionError("the platform did not decide interpretation")
        info.update(terms=len(reqs[0].terms), longest_row=longest)
    with phase("queries", meter):
        answers, _, _ = run_queries(ref, reqs)
    result_q.put({"device": dev, "requests": reqs, "answers": answers})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--docs", type=int, default=10_000)
    ap.add_argument("--vocab", type=int, default=65_536)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    configure_compile_cache()  # exported: the children use the same cache

    out_dir = tempfile.mkdtemp(prefix="chip_smoke_")
    ctx = mp.get_context("spawn")
    result_q = ctx.Queue()
    child = ctx.Process(target=device_phases, args=(args, out_dir, result_q))
    try:
        child.start()
        result = None
        while result is None:
            try:
                result = result_q.get(timeout=1.0)
            except queue.Empty:
                if child.exitcode is not None and result_q.empty():
                    break
        child.join(timeout=60)
        if result is None or child.exitcode != 0:
            print(f"device phases failed (exit code {child.exitcode})",
                  file=sys.stderr, flush=True)
            return 1
        worker = serve_requests(
            os.path.join(out_dir, "reference"),
            result["requests"], result["answers"],
        )
        dev = result["device"]
        if (worker.get("platform"), worker.get("device_kind")) != (
            dev["platform"], dev["kind"]
        ):
            raise AssertionError(f"server worker reported {worker}")
    finally:
        if child.is_alive():
            child.terminate()
            child.join(timeout=10)
        shutil.rmtree(out_dir, ignore_errors=True)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
