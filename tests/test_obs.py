"""The telemetry subsystem (src/repro/obs/): metric primitives, span
nesting, exporters, and the instrumented pipelines.

The load-bearing assertions:

* histogram **merge correctness** — percentiles of N merged per-worker
  histograms agree with percentiles of the pooled samples to within one log
  bucket's relative width (the property that makes fleet-level p99 honest);
* span **nesting and attribute propagation** across a PlanExecutor
  crash-and-resume (the resumed run re-counts only the un-checkpointed
  shards, and its spans say so);
* the executor's stage spans **tile** the root ``ingest/execute`` span
  (count + segment_write + refresh cover >= 90% of the root's wall time on
  a store-output run — the ISSUE 6 acceptance criterion);
* the disabled path records nothing and hands out shared null objects.
"""

import dataclasses
import json
import math

import numpy as np
import pytest

from repro import obs
from repro.obs.metrics import (
    SUBDIV,
    _MIN_IDX,
    Histogram,
    bucket_index,
    bucket_mid,
    merge_snapshots,
)

# one log bucket's relative width (the merge-percentile error bound), with
# a little headroom for numpy's interpolating percentile definition
BUCKET_FACTOR = 2.0 ** (1.5 / SUBDIV)


# ---------------------------------------------------------------------------
# metric primitives
# ---------------------------------------------------------------------------


def test_bucket_index_clamps_and_orders():
    assert bucket_index(0.0) == _MIN_IDX
    assert bucket_index(-1.0) == _MIN_IDX
    assert bucket_index(1e-300) == _MIN_IDX
    assert bucket_index(1e300) == bucket_index(1e299)  # clamped at the top
    # monotone in value, and the midpoint lands inside the bucket
    for v in (1e-6, 0.001, 0.5, 1.0, 7.0, 1234.5):
        i = bucket_index(v)
        assert bucket_index(v * 4) > i
        assert 2 ** (i / SUBDIV) <= bucket_mid(i) <= 2 ** ((i + 1) / SUBDIV)


def test_counter_and_gauge_state():
    reg = obs.Registry(enabled=True)
    reg.counter("a.b").inc()
    reg.counter("a.b").inc(41)
    reg.gauge("g").set(0.25)
    snap = reg.snapshot()
    assert snap["counters"]["a.b"] == 42
    assert snap["gauges"]["g"] == 0.25


def test_histogram_merge_matches_pooled_percentiles():
    """Percentiles of merged per-worker histograms == percentiles of the
    pooled samples, to within one bucket's relative width — the property
    the serving parent relies on when it turns worker snapshots into fleet
    p50/p95/p99."""
    rng = np.random.default_rng(7)
    # three "workers" with deliberately different latency regimes
    worker_samples = [
        rng.lognormal(mean=-6.0, sigma=0.5, size=1500),   # fast worker
        rng.lognormal(mean=-4.5, sigma=0.8, size=1000),   # slow worker
        rng.lognormal(mean=-5.5, sigma=1.2, size=500),    # noisy worker
    ]
    hists = []
    for samples in worker_samples:
        h = Histogram()
        for v in samples:
            h.record(float(v))
        hists.append(h)

    merged = Histogram()
    for h in hists:
        merged.merge(h)
    pooled = np.concatenate(worker_samples)
    assert merged.count == len(pooled)
    assert merged.total == pytest.approx(pooled.sum())
    assert merged.vmin == pooled.min() and merged.vmax == pooled.max()
    for q in (10, 50, 90, 95, 99):
        got = merged.percentile(q)
        want = float(np.percentile(pooled, q))
        assert want / BUCKET_FACTOR <= got <= want * BUCKET_FACTOR, (
            f"p{q}: merged {got} vs pooled {want}"
        )
    # merging must be equivalent to recording everything in one histogram
    one = Histogram()
    for v in pooled:
        one.record(float(v))
    assert one.buckets == merged.buckets
    assert one.percentile(99) == merged.percentile(99)


def test_histogram_percentile_clamps_to_observed_range():
    h = Histogram()
    h.record(0.003)
    # a single sample: every quantile is that sample, not a bucket midpoint
    assert h.percentile(50) == 0.003
    assert h.percentile(99) == 0.003
    assert Histogram().percentile(99) == 0.0  # empty -> 0, not NaN


def test_histogram_state_survives_json_roundtrip():
    h = Histogram()
    for v in (0.001, 0.004, 0.002, 1.5):
        h.record(v)
    back = Histogram.from_state(json.loads(json.dumps(h.state())))
    assert back.count == h.count
    assert back.buckets == h.buckets  # keys re-int'ed after stringification
    assert back.percentile(95) == h.percentile(95)
    assert back.mean == h.mean


def test_merge_snapshots_counters_add_histograms_merge():
    a, b = obs.Registry(enabled=True), obs.Registry(enabled=True)
    a.counter("n").inc(3)
    b.counter("n").inc(4)
    b.counter("only_b").inc(1)
    a.gauge("g").set(1.0)
    b.gauge("g").set(2.0)
    for v in (0.001, 0.002):
        a.histogram("lat").record(v)
    b.histogram("lat").record(0.004)
    merged = merge_snapshots([a.snapshot(), None, b.snapshot()])
    assert merged["counters"] == {"n": 7, "only_b": 1}
    assert merged["gauges"]["g"] == 2.0  # last write wins
    h = Histogram.from_state(merged["histograms"]["lat"])
    assert h.count == 3
    assert h.vmax == 0.004


# ---------------------------------------------------------------------------
# registry + spans
# ---------------------------------------------------------------------------


def test_disabled_registry_is_nullobject_noop():
    reg = obs.Registry(enabled=False)
    assert reg.span("x") is obs.NULL_SPAN
    assert reg.counter("c") is obs.NULL_METRIC
    assert reg.gauge("g") is obs.NULL_METRIC
    assert reg.histogram("h") is obs.NULL_METRIC
    with reg.span("x", a=1) as sp:
        sp.set(b=2)
        reg.counter("c").inc(5)
        reg.histogram("h").record(0.1)
    assert reg.span_events() == []
    assert reg.snapshot() == {
        "counters": {}, "gauges": {}, "histograms": {}, "dropped_events": 0,
    }


def test_module_default_registry_starts_disabled():
    # the process-global default must be off (BENCH overhead contract);
    # tests that enable it go through obs.scoped() which restores the old one
    assert obs.get_registry().enabled is False


def test_span_nesting_depth_and_attrs():
    reg = obs.Registry(enabled=True)
    with reg.span("a", k=1):
        with reg.span("a/b") as sp:
            sp.set(rows=7)
        with reg.span("a/c"):
            pass
    events = reg.span_events()  # completion order: a/b, a/c, a
    assert [e["name"] for e in events] == ["a/b", "a/c", "a"]
    assert [e["depth"] for e in events] == [1, 1, 0]
    assert events[0]["args"] == {"rows": 7}
    assert events[2]["args"] == {"k": 1}
    root = events[2]
    for child in events[:2]:  # children nest inside the root's interval
        assert child["ts_us"] >= root["ts_us"]
        assert child["ts_us"] + child["dur_us"] <= (
            root["ts_us"] + root["dur_us"] + 1.0
        )


def test_span_event_cap_counts_drops():
    reg = obs.Registry(enabled=True, max_events=2)
    for _ in range(5):
        with reg.span("s"):
            pass
    assert len(reg.span_events()) == 2
    assert reg.dropped_events == 3
    assert reg.snapshot()["dropped_events"] == 3


def test_registry_without_log_keeps_no_events():
    """``log=False`` keeps no span log and counts no drops, however many
    spans run; metrics and absorbed snapshots' metrics still land."""
    reg = obs.Registry(enabled=True, log=False)
    for _ in range(5):
        with reg.span("serving/batch", seq=0) as sp:
            sp.set(requests=1)
            reg.counter("c").inc()
    reg.absorb({"counters": {"c": 2},
                "events": [{"name": "x", "ts_us": 0.0, "dur_us": 1.0}]})
    assert reg.span_events() == []
    assert reg.dropped_events == 0
    assert reg.stage_totals() == {}
    assert reg.snapshot()["dropped_events"] == 0
    assert reg.snapshot()["counters"] == {"c": 7}


def test_scoped_installs_and_restores():
    before = obs.get_registry()
    with obs.scoped() as reg:
        assert obs.get_registry() is reg
        assert reg.enabled
    assert obs.get_registry() is before


def test_stage_totals_sums_by_name():
    reg = obs.Registry(enabled=True)
    for _ in range(3):
        with reg.span("ingest/count"):
            pass
    with reg.span("query/execute"):
        pass
    totals = reg.stage_totals("ingest/")
    assert set(totals) == {"ingest/count"}
    assert totals["ingest/count"] > 0


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------


def test_chrome_trace_roundtrip(tmp_path):
    reg = obs.Registry(enabled=True)
    with reg.span("ingest/count", shard=0):
        reg.counter("ingest.docs_counted").inc(10)
    path = str(tmp_path / "trace.json")
    assert reg.write_trace(path) == path
    trace = obs.load_trace(path)
    assert {e["ph"] for e in trace["traceEvents"]} <= {"X", "M"}
    assert obs.span_names(trace) == {"ingest/count"}
    x = [e for e in trace["traceEvents"] if e["ph"] == "X"][0]
    assert x["cat"] == "ingest"
    assert x["args"]["shard"] == 0 and x["args"]["depth"] == 0
    meta = [e for e in trace["traceEvents"] if e["ph"] == "M"][0]
    assert meta["args"]["counters"] == {"ingest.docs_counted": 10}


def test_load_trace_rejects_non_trace(tmp_path):
    p = tmp_path / "not_a_trace.json"
    p.write_text('{"hello": 1}')
    with pytest.raises(ValueError, match="traceEvents"):
        obs.load_trace(str(p))


def test_prometheus_text_format():
    reg = obs.Registry(enabled=True)
    reg.counter("ingest.spills").inc(3)
    reg.gauge("serving/batch_window_occupancy").set(0.5)
    for v in (0.001, 0.002, 0.004):
        reg.histogram("serving/queue_wait_s").record(v)
    text = reg.prometheus_text()
    assert "# TYPE repro_ingest_spills counter" in text
    assert "repro_ingest_spills 3" in text
    assert "repro_serving_batch_window_occupancy 0.5" in text
    assert 'repro_serving_queue_wait_s{quantile="0.99"}' in text
    assert "repro_serving_queue_wait_s_count 3" in text
    # names must be exposition-safe: no dots or slashes survive
    for line in text.splitlines():
        if line and not line.startswith("#"):
            assert "/" not in line.split(" ")[0]
            assert "." not in line.split("{")[0].split(" ")[0]


# ---------------------------------------------------------------------------
# instrumented pipelines
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def coll():
    from repro.data.corpus import synthetic_zipf_collection

    return synthetic_zipf_collection(120, vocab=200, mean_len=14, seed=11)


def test_executor_stage_spans_tile_root(tmp_path, coll):
    """A store-output spill run emits all five stage spans, and the
    top-level stages (count + segment_write + refresh) account for >= 90%
    of the root ``ingest/execute`` wall time (the acceptance criterion —
    `cooc_run --trace-out` checks the same property end-to-end)."""
    from repro.core.plan import CountJob, Planner

    job = CountJob(
        collection=coll, output="store", method="list-scan",
        out_path=str(tmp_path / "store"), dense_vocab_cap=1,
        num_shards=3, memory_budget_pairs=256,
    )
    with obs.scoped() as reg:
        res = Planner().plan(job).execute(out_dir=str(tmp_path / "run"))
    assert res.summary["exact"] is True
    totals = reg.stage_totals("ingest/")
    assert {
        "ingest/execute", "ingest/count", "ingest/spill",
        "ingest/bucket_merge", "ingest/segment_write", "ingest/refresh",
    } <= set(totals)
    covered = (
        totals["ingest/count"]
        + totals["ingest/segment_write"]
        + totals["ingest/refresh"]
    )
    assert covered >= 0.9 * totals["ingest/execute"], totals
    # counters rode along with the spans
    snap = reg.snapshot()
    assert snap["counters"]["ingest.shards_done"] == 3
    assert snap["counters"]["ingest.docs_counted"] == coll.num_docs
    assert snap["counters"]["ingest.rows_written"] > 0
    assert snap["counters"]["ingest.spills"] >= 3  # budget forced spills


def test_executor_span_attrs_across_resume(tmp_path, coll, monkeypatch):
    """Crash after the first checkpoint, resume, and read the story off the
    span log: the first run counted only some shards, the resumed run's
    root span says resume=True and its count spans cover exactly the shards
    the checkpoint didn't."""
    from repro.core.plan import CountJob, Planner
    from repro.core.specs import REGISTRY

    job = CountJob(
        collection=coll, output="stats", method="list-scan",
        dense_vocab_cap=1, num_shards=6, memory_budget_pairs=128,
    )
    plan = Planner().plan(job)
    out = str(tmp_path / "run")

    real = REGISTRY["list-scan"]
    calls = {"n": 0}

    def failing(c, sink, **kw):
        calls["n"] += 1
        if calls["n"] > 3:
            raise RuntimeError("injected crash")
        return real.fn(c, sink, **kw)

    monkeypatch.setitem(REGISTRY, "list-scan", dataclasses.replace(real, fn=failing))
    with obs.scoped() as reg1:
        with pytest.raises(RuntimeError, match="injected crash"):
            plan.execute(out_dir=out, ckpt_every=2)
    counted1 = {
        e["args"]["shard"]
        for e in reg1.span_events()
        if e["name"] == "ingest/count" and "shard" in e["args"]
    }
    monkeypatch.setitem(REGISTRY, "list-scan", real)

    with obs.scoped() as reg2:
        res = plan.execute(out_dir=out, ckpt_every=2, resume=True)
    from repro.core.oracle import brute_force_counts

    oracle = brute_force_counts(coll)
    assert res.summary["total_count"] == int(oracle.sum())

    events2 = reg2.span_events()
    root = [e for e in events2 if e["name"] == "ingest/execute"]
    assert len(root) == 1
    assert root[0]["args"]["resume"] is True
    assert root[0]["args"]["shards"] == 6
    counted2 = {
        e["args"]["shard"]
        for e in events2
        if e["name"] == "ingest/count" and "shard" in e["args"]
    }
    # the checkpoint held 2 completed shards; the resumed run counts the
    # other 4 (including the shard the injected crash interrupted)
    assert len(counted2) == 4
    assert counted2 | counted1 == set(range(6))
    assert reg2.snapshot()["counters"]["ingest.shards_done"] == 4
    # every count span carries its method + doc attribution
    for e in events2:
        if e["name"] == "ingest/count":
            assert e["args"]["method"] == "list-scan"
            assert e["args"]["docs"] > 0


def test_query_engine_spans_and_cache_counters(tmp_path, coll):
    from repro.core.cooc import count_to_store
    from repro.store import QueryEngine, TopKRequest

    store, _ = count_to_store(
        "list-scan", coll, str(tmp_path / "store"), memory_budget_pairs=512
    )
    with obs.scoped() as reg:
        engine = QueryEngine(store)
        terms = np.arange(8)
        engine.execute([TopKRequest(terms, k=5, score="count")])
        engine.execute([TopKRequest(terms, k=5, score="count")])  # cache hits
    events = [e for e in reg.span_events() if e["name"] == "query/execute"]
    assert len(events) == 2
    assert all(e["args"]["requests"] == 1 for e in events)
    snap = reg.snapshot()
    assert snap["counters"]["query.requests"] == 2
    assert snap["counters"]["query.topk_queries"] == 16
    assert snap["counters"]["query.cache_misses"] >= 8
    assert snap["counters"]["query.cache_hits"] >= 8  # second pass was warm


@pytest.mark.parametrize("kernel", ["numpy", "pallas"])
def test_query_engine_topk_stage_spans_in_order(tmp_path, coll, kernel):
    """One top-k launch emits query/gather, query/pad and query/device, one
    after the other, inside its query/execute span, and query/upload inside
    query/device (the pallas kernel runs interpreted on the CPU)."""
    from repro.core.cooc import count_to_store
    from repro.store import QueryEngine, TopKRequest

    store, _ = count_to_store("list-scan", coll, str(tmp_path / "store"))
    reg = obs.Registry(enabled=True)
    engine = QueryEngine(store, kernel=kernel, registry=reg)
    engine.execute([TopKRequest(np.arange(4), k=3, score="pmi")])
    events = sorted(reg.span_events(), key=lambda e: e["ts_us"])
    assert [e["name"] for e in events] == [
        "query/execute", "query/gather", "query/pad", "query/device",
        "query/upload",
    ]
    root, stages, upload = events[0], events[1:4], events[4]
    assert all(e["depth"] == root["depth"] + 1 for e in stages)
    device = stages[-1]
    assert upload["depth"] == device["depth"] + 1
    assert upload["args"] == {"rows": 4}
    assert device["ts_us"] <= upload["ts_us"]
    assert upload["ts_us"] + upload["dur_us"] <= (
        device["ts_us"] + device["dur_us"] + 1.0
    )
    for a, b in zip(stages, stages[1:]):  # in order, never overlapping
        assert a["ts_us"] + a["dur_us"] <= b["ts_us"] + 1.0
    assert stages[-1]["ts_us"] + stages[-1]["dur_us"] <= (
        root["ts_us"] + root["dur_us"] + 1.0
    )
    assert stages[0]["args"] == {"terms": 4}
    assert stages[1]["args"]["width"] >= 8


def _inside(child: dict, parent: dict) -> bool:
    return (parent["ts_us"] <= child["ts_us"] + 1.0
            and child["ts_us"] + child["dur_us"]
            <= parent["ts_us"] + parent["dur_us"] + 1.0
            and child["depth"] > parent["depth"])


def test_freq_split_store_build_stage_spans_nest(tmp_path, coll):
    """A freq-split store build splits ingest/count into its head and tail
    stages, and ingest/segment_write into the row stream (with the lazy
    bucket merges inside it) and the symmetric adjacency."""
    from repro.core.plan import CountJob, Planner
    from repro.data.preprocess import remap_df_descending

    cd, _ = remap_df_descending(coll)
    job = CountJob(
        collection=cd, output="store", method="freq-split",
        out_path=str(tmp_path / "store"), dense_vocab_cap=1,
        memory_budget_pairs=256, df_descending=True,
        method_kwargs={"head": 32, "use_kernel": False},
    )
    with obs.scoped() as reg:
        res = Planner().plan(job).execute(out_dir=str(tmp_path / "run"))
    assert res.summary["exact"] is True
    events = reg.span_events()
    by = {}
    for e in events:
        by.setdefault(e["name"], []).append(e)

    def under(child: str, parent: str) -> None:
        assert by.get(child), (child, sorted(by))
        for c in by[child]:
            assert any(_inside(c, p) for p in by[parent]), (child, parent)

    under("ingest/count_head", "ingest/count")
    under("ingest/count_tail", "ingest/count")
    under("ingest/segment_rows", "ingest/segment_write")
    under("ingest/segment_symmetric", "ingest/segment_write")
    under("ingest/bucket_merge", "ingest/segment_rows")
    # head before tail, rows before the symmetric build
    assert by["ingest/count_head"][0]["ts_us"] < by["ingest/count_tail"][0]["ts_us"]
    assert (by["ingest/segment_rows"][0]["ts_us"]
            < by["ingest/segment_symmetric"][0]["ts_us"])
    assert by["ingest/count_head"][0]["args"] == {"head": 32}


def test_query_engine_private_registry_overrides_global():
    # serving workers hand the engine their own registry; the global one
    # (disabled here) must not see anything
    private = obs.Registry(enabled=True)

    class _Fake:
        pass

    from repro.store.query import QueryEngine

    engine = QueryEngine.__new__(QueryEngine)
    engine._registry = private
    assert engine.registry is private
    engine._registry = None
    assert engine.registry is obs.get_registry()


# ---------------------------------------------------------------------------
# spans on the profiler's clock: every jax.profiler trace of the suite is
# recorded here, one per test
# ---------------------------------------------------------------------------


def _profile(trace_dir, fn) -> list:
    """Run ``fn`` under one ``jax.profiler`` trace; returns the trace's host
    events as (name, start_ns, end_ns, stats) read back by ProfileData."""
    import glob

    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(
        str(trace_dir / "plugins" / "profile" / "*" / "*.xplane.pb")
    )
    return [
        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for ev in line.events
    ]


def test_annotated_spans_reach_the_profiler(tmp_path):
    """With ``annotate`` on, spans show in a JAX profile under their exact
    names, nested inside their parents, with entry attributes as stats;
    with it off, or the registry disabled, nothing shows."""
    on = obs.Registry(enabled=True, annotate=True)
    off = obs.Registry(enabled=True)
    disabled = obs.Registry(enabled=False, annotate=True)

    def work():
        with on.span("ann/outer", seq=3, requests=2):
            with on.span("ann/inner"):
                pass
            with off.span("ann/off"):
                pass
            with disabled.span("ann/disabled"):
                pass

    events = [e for e in _profile(tmp_path / "trace", work)
              if e[0].startswith("ann/")]
    assert sorted(e[0] for e in events) == ["ann/inner", "ann/outer"]
    outer = next(e for e in events if e[0] == "ann/outer")
    inner = next(e for e in events if e[0] == "ann/inner")
    assert outer[1] <= inner[1] and inner[2] <= outer[2]
    assert outer[3] == {"seq": 3, "requests": 2}
    # annotating leaves the registry's own log as it was
    assert [e["name"] for e in on.span_events()] == ["ann/inner", "ann/outer"]
    assert [e["name"] for e in off.span_events()] == ["ann/off"]


def test_serving_worker_spans_reach_the_profiler(tmp_path, coll, monkeypatch):
    """The serving worker's micro-batches show in a profile of its process
    as serving/batch annotations, each holding query/gather, query/pad and
    query/device (with query/upload inside) in that order; its registry
    keeps no span log."""
    import queue

    from repro.core.cooc import count_to_store
    from repro.store import TopKRequest
    from repro.store import serving
    from repro.store.requests import make_envelope

    store_path = str(tmp_path / "store")
    count_to_store("list-scan", coll, store_path)
    made = []

    class Spy(obs.Registry):
        def __init__(self, **kw):
            super().__init__(**kw)
            made.append(self)

    monkeypatch.setattr(obs, "Registry", Spy)
    request_q, response_q, stats_q = queue.Queue(), queue.Queue(), queue.Queue()
    for i in range(3):
        request_q.put(make_envelope(0, i, 0, 1, TopKRequest([i], k=3, score="pmi")))
    request_q.put(serving._STOP)
    cfg = serving.ServingConfig(workers=1, batch_window_ms=0.0)

    events = _profile(tmp_path / "trace", lambda: serving._worker_main(
        0, store_path, cfg, request_q, response_q, stats_q))

    answers = []
    while not response_q.empty():
        msg = response_q.get()
        if msg[0] != "claim":
            answers.append(msg)
    assert len(answers) == 3 and all(m[6] for m in answers)
    by_start = sorted(events, key=lambda e: e[1])
    batches = [e for e in by_start if e[0] == "serving/batch"]
    assert [b[3] for b in batches] == [
        {"seq": i, "requests": 1} for i in range(3)
    ]
    stages = [e for e in by_start if e[0].startswith("query/")]
    assert [e[0] for e in stages] == [
        "query/gather", "query/pad", "query/device", "query/upload",
    ] * 3
    for j, b in enumerate(batches):
        for e in stages[4 * j:4 * j + 4]:
            assert b[1] <= e[1] and e[2] <= b[2]
        device, upload = stages[4 * j + 2:4 * j + 4]
        assert device[1] <= upload[1] and upload[2] <= device[2]
    (reg,) = made
    assert reg.log is False
    assert reg.span_events() == [] and reg.dropped_events == 0
