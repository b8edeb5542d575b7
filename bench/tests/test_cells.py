"""Each cell's run at a tiny size on the CPU: the result line, and the
timed path broken underneath so that ``correct`` comes out false."""

import os

import numpy as np
import pytest

SERVE = ("serve-wt10g-zipf",)
KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


def bench_metrics(section, cell):
    import common

    bench = common.load_json(common.ROOT, "BENCHMARK.json")
    return {m["name"] for m in bench[section]
            if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("cell", ["build-wt10g-10k", *SERVE])
def test_cell_reports_its_end_to_end_metrics(run_cell, cell):
    line = run_cell(cell, seed=2**31 + 5, trace=0)
    assert KEYS <= set(line) and list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == bench_metrics("end_to_end", cell)
    assert all(v["value"] > 0 for v in line["metrics"].values())


@pytest.mark.parametrize("cell", ["build-wt10g-10k", *SERVE])
def test_traced_cell_reports_per_layer_metrics(run_cell, cell):
    line = run_cell(cell, seed=77, trace=1)
    assert line["correct"] is True
    assert {"busy_s", "window_s"} <= set(line["device"])
    got = set(line["metrics"])
    # the CPU has no TPU plane: the device-trace readers find nothing there
    # except the idle share
    want = {m for m in bench_metrics("per_layer", cell)
            if m != "cooc_gram_roofline.build"}
    assert got == want
    assert len(line["breakdown"]["idle_gaps"]) <= 10


# ------------------------------------------------------------- build faults
def _gram_unchanged(real):
    def gram(b_i, b_j, **kw):
        return np.zeros((b_i.shape[1], b_j.shape[1]), np.float32)
    return gram


def _gram_altered(real):
    def gram(b_i, b_j, **kw):
        out = np.array(real(b_i, b_j, **kw))
        out[0, 1] += 1
        return out
    return gram


@pytest.mark.parametrize("fault", ["state_unchanged", "half_left_out",
                                   "answer_altered"])
def test_build_fault_is_not_correct(run_cell, monkeypatch, fault):
    from repro.core import hybrid
    from repro.kernels import ops

    if fault == "state_unchanged":
        monkeypatch.setattr(ops, "cooc_gram", _gram_unchanged(ops.cooc_gram))
    elif fault == "answer_altered":
        monkeypatch.setattr(ops, "cooc_gram", _gram_altered(ops.cooc_gram))
    else:
        real = hybrid.incidence_dense

        def half(c, lo, hi, *a, **k):
            tile = real(c, lo, hi, *a, **k)
            tile[len(tile) // 2:] = 0
            return tile

        monkeypatch.setattr(hybrid, "incidence_dense", half)
    line = run_cell("build-wt10g-10k", seed=31)
    assert line["correct"] is False
    assert line["checks"]["rows_differing"]["value"] > 0


# ------------------------------------------------------------- serve faults
@pytest.mark.parametrize("cell", SERVE)
def test_serve_answer_altered_is_not_correct(run_cell, monkeypatch, cell):
    monkeypatch.setenv("COOC_BENCH_FAULT", "alter-topk")
    line = run_cell(cell, seed=41)
    assert line["correct"] is False
    # a padding slot moved first is an exact error; a long row's tenth
    # candidate moved first lies below the reference's best
    c = line["checks"]
    assert (c["answer_errors"]["value"] > 0
            or c["score_gap"]["value"] > c["score_gap"]["limit"])
    assert line["metrics"]["lookups_per_s"]["value"] < 20.0


@pytest.mark.parametrize("cell", SERVE)
def test_serve_half_the_requests_left_out_is_not_correct(run_cell, monkeypatch, cell):
    """Every other request of the window is never sent, as if lost."""
    import common
    from repro.store import serving

    drv = common.load_module(
        os.path.join(common.BENCH, "drivers", "serve.py"), "driver_serve")
    make, execute = drv.make_request, serving.CoocClient.execute
    left_out = set()

    def tagged(sched, i):
        req = make(sched, i)
        if i % 2:
            left_out.add(id(req))
        return req

    def dropping(self, requests, **kw):
        if any(id(r) in left_out for r in requests):
            raise TimeoutError("left out")
        return execute(self, requests, **kw)

    monkeypatch.setattr(drv, "make_request", tagged)
    monkeypatch.setattr(serving.CoocClient, "execute", dropping)
    line = run_cell(cell, seed=43)
    assert line["correct"] is False
    assert line["checks"]["unanswered"]["value"] > 0
    assert line["failed"] > 0


@pytest.mark.parametrize("cell", ["build-wt10g-10k", *SERVE])
def test_the_cpu_is_refused_without_a_result(cell, capsys, monkeypatch):
    import common
    import run

    real = common.load_json
    monkeypatch.setattr(common, "load_json", lambda *p: {
        **real(*p), **({"num_docs": 50, "vocab_size": 256} if "configs" in p else {})})
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", cell, "--seed", "1", "--seconds", "1"])
    assert e.value.code == 3
    assert "{" not in capsys.readouterr().out


@pytest.mark.parametrize("cell", SERVE)
def test_every_seed_sends_the_same_requests_in_its_own_order(cell):
    import common

    bench = common.load_json(common.ROOT, "BENCHMARK.json")
    traffic = {w["name"]: w for w in bench["workloads"]}[cell]["traffic"]
    tr = common.load_json(common.BENCH, "traffic", traffic + ".json")
    drv = common.load_module(
        os.path.join(common.BENCH, "drivers", "serve.py"), "driver_serve")
    a, b = (drv.schedule(tr, 5000, 4.0, s) for s in (2**31 + 1, 2**31 + 2))
    warm = drv.schedule(tr, 5000, 4.0, 2**31 + 1, stream=6)
    for other in (b, warm):
        assert np.array_equal(np.sort(a["term"]), np.sort(other["term"]))
        gaps = [np.sort(np.diff(x["due"], prepend=0.0)) for x in (a, other)]
        assert np.allclose(*gaps)
        assert not np.array_equal(a["term"], other["term"])
    assert np.all(np.diff(a["due"]) > 0) and a["due"][-1] < 4.0
