"""Batch build: the seeded collection counted into a new store, back to
back, through the path that ``cooc_run --method auto`` takes.

Each build runs ``CountJob(collection, output="store", out_path,
method="auto", df_descending=True)`` → ``Planner().plan`` →
``PlanExecutor().execute`` and ends with the store open and queryable. The
window starts builds until ``--seconds`` have passed; the build under way
then completes, so at least one always does. Every build writes a store of
its own, kept for the check.

Set-up warms the device shapes the plan will use: for ``freq-split``, the
head Gram's document tiles through the same ``cooc_gram`` entry point;
for any other plan, one whole build. Afterwards a sample of every store's
rows, the highest-df terms among them, and its document frequencies are
compared with the plain reference.
"""

from __future__ import annotations

import os
import shutil
import time

import numpy as np

import common
import data
from reference import Reference


def gram_calls(plan, num_docs: int) -> list[tuple[int, int, int]]:
    """The unpadded (d, m, n) of every ``cooc_gram`` call the plan makes:
    ``freq-split`` runs one per document tile of its head block."""
    if plan.method != "freq-split":
        return []
    kw = plan.method_kwargs
    h, tile = min(kw["head"], plan.job.collection.vocab_size), kw["doc_tile"]
    return [(min(tile, num_docs - lo), h, h) for lo in range(0, num_docs, tile)]


def warm_up(plan, coll, build) -> None:
    """Compile (or load from the cache) what the window's builds run."""
    from repro.data.index import incidence_dense
    from repro.kernels import ops

    calls = gram_calls(plan, coll.num_docs)
    if not calls:
        build("warm")
        return
    kw = plan.method_kwargs
    lo, done = 0, set()
    for d, m, _ in calls:
        if d not in done:
            tile = incidence_dense(coll, lo, lo + d, 0, m)
            np.asarray(ops.cooc_gram(tile, tile, use_kernel=kw["use_kernel"]))
            done.add(d)
        lo += d


def sample_terms(ref: Reference, cfg: dict, seed: int) -> np.ndarray:
    """The highest-df terms (the longest rows) and a seeded draw of the
    other live terms."""
    live = np.flatnonzero(ref.df)
    head = live[np.argsort(-ref.df[live], kind="stable")][:cfg["check_top_rows"]]
    rest = np.setdiff1d(live, head)
    rng = np.random.default_rng([int(seed), 4])
    drawn = rng.choice(rest, size=min(cfg["check_sampled_rows"], len(rest)),
                       replace=False)
    return np.concatenate([head, np.sort(drawn)])


def compare_store(store, ref: Reference, terms: np.ndarray) -> dict:
    """Rows and document frequencies of a store that differ from the
    reference."""
    rows = 0
    for t in terms:
        ids, cnts = store.neighbours(int(t))
        want = ref.row(int(t))
        got = np.zeros(ref.vocab, dtype=np.int64)
        ids = np.asarray(ids, dtype=np.int64)
        ok = len(np.unique(ids)) == len(ids) and bool(np.all(np.asarray(cnts) > 0))
        got[ids] = np.asarray(cnts)
        rows += not (ok and np.array_equal(got, want))
    df = np.asarray(store.df(), dtype=np.int64)
    df_bad = int(np.sum(df != ref.df)) if len(df) == ref.vocab else ref.vocab
    df_bad += int(store.num_docs != ref.num_docs)
    return {"rows_differing": rows, "df_differing": df_bad}


def run(ctx: common.Context) -> common.Outcome:
    import jax

    from repro import obs
    from repro.core.plan import CountJob, Planner, PlanExecutor
    from repro.data.corpus import Collection
    # what PlanExecutor imports on first use: loaded in set-up
    from repro import checkpoint, store  # noqa: F401
    from repro.runtime import fault  # noqa: F401

    meter = common.CompileMeter()
    devs = jax.devices()
    common.require_tpu(devs[0].platform, devs[0].device_kind, len(devs),
                       ctx.workload["chips"])
    ptr, terms, vocab = data.make_collection(ctx.config, ctx.seed)
    coll = Collection(ptr, terms, vocab)
    reg = obs.configure(enabled=True) if ctx.trace else obs.get_registry()

    def build(name: str):
        path = os.path.join(ctx.work_dir, name)
        job = CountJob(collection=coll, output="store", out_path=path,
                       method="auto", df_descending=True)
        plan = Planner().plan(job)
        return plan, PlanExecutor().execute(plan).store

    plan = Planner().plan(CountJob(
        collection=coll, output="store", out_path=ctx.work_dir,
        method="auto", df_descending=True))
    warm_up(plan, coll, build)
    shutil.rmtree(os.path.join(ctx.work_dir, "warm"), ignore_errors=True)
    ctx.note("plan", method=plan.method, warm=meter.totals())

    if ctx.trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(os.path.join(ctx.work_dir, "trace"),
                                 profiler_options=opts)
    setup_s = common.since_process_start(ctx.t_process)
    before = meter.totals()
    builds, stores, anchors, plans = [], [], [], []
    t_win = time.perf_counter()
    while True:
        name = f"store{len(builds)}"
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench/build {name}"), \
                reg.span("bench/build"):
            p, st = build(name)
        t1 = time.perf_counter()
        builds.append(t1 - t0)
        anchors.append(t0)
        plans.append(p)
        stores.append(st)
        if t1 - t_win >= ctx.seconds:
            break
    if ctx.trace:
        jax.profiler.stop_trace()
    ctx.note("window_compiles", **common.CompileMeter.since(before, meter.totals()))
    ctx.note("builds", seconds=builds, methods=[p.method for p in plans])
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs)
    device_info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                   "count": len(devs), "memory_peak_bytes": peak}

    layer = {"builds": len(builds),
             "gram_calls": [gram_calls(p, coll.num_docs) for p in plans],
             "peak": None, "spans": [], "trace": None}
    breakdown = None
    if ctx.trace:
        import devtrace
        import roofline

        layer["peak"] = roofline.peaks(devs[0].device_kind)
        layer["spans"] = span_intervals(reg.span_events(), anchors)
        red = devtrace.reduce(os.path.join(ctx.work_dir, "trace"),
                              annotation="bench/build")
        layer["trace"] = red
        device_info.update(busy_s=red.busy_s, window_s=red.window_s)
        breakdown = red.breakdown(span_pieces(layer["spans"], red))

    t0 = time.perf_counter()
    ref = Reference(ptr, terms, vocab)
    sample = sample_terms(ref, ctx.config, ctx.seed)
    found = [compare_store(st, ref, sample) for st in stores]
    ctx.note("check", seconds=time.perf_counter() - t0, rows=len(sample),
             stores=found)
    limits = ctx.config["limits"]
    return common.Outcome(
        attempted=len(builds),
        failed=sum(any(v > limits[k] for k, v in f.items()) for f in found),
        checks={k: (max(f[k] for f in found), limits[k]) for k in limits},
        end_to_end={"setup_s": setup_s,
                    "build_docs_per_s": coll.num_docs * len(builds) / sum(builds)},
        layer=layer,
        device=device_info,
        breakdown=breakdown,
    )


def span_intervals(events: list, anchors: list) -> list:
    """The program's telemetry spans as (name, t0, t1, depth) on the
    perf_counter clock, placed by the harness's ``bench/build`` spans,
    which open at the known times ``anchors``."""
    marks = sorted(e["ts_us"] for e in events if e["name"] == "bench/build")
    offset = anchors[0] - marks[0] * 1e-6
    return [(e["name"], offset + e["ts_us"] * 1e-6,
             offset + (e["ts_us"] + e["dur_us"]) * 1e-6, e["depth"])
            for e in events]


def span_pieces(spans: list, red):
    """Cut a trace interval into parts, each labelled by the deepest program
    span covering it; perf_counter times map onto the trace's clock through
    the ``bench/build`` annotations."""
    ann = red.annotations("bench/build")
    marks = sorted(a for n, a, _, _ in spans if n == "bench/build")
    if not ann or len(ann) != len(marks):
        return None
    shift = ann[0][1] - marks[0]
    inner = [(n, a + shift, b + shift, d) for n, a, b, d in spans
             if n != "bench/build"]

    def label(t: float) -> str:
        best = ("outside the program's spans", -1)
        for name, x, y, depth in inner:
            if x <= t <= y and depth > best[1]:
                best = (name, depth)
        return best[0]

    def pieces(a: float, b: float) -> list:
        cuts = sorted({a, b} | {t for _, x, y, _ in inner for t in (x, y)
                                if a < t < b})
        out = []
        for x, y in zip(cuts, cuts[1:]):
            name = label((x + y) / 2)
            if out and out[-1][0] == name:
                out[-1] = (name, out[-1][1], y)
            else:
                out.append((name, x, y))
        return out

    return pieces
