"""The chip smoke script's phases, run tiny on the CPU with the Pallas
kernels interpreted (the device check itself is what the script adds on a
TPU), and the script's refusal to run anywhere but on a TPU."""

import importlib.util
import os
import subprocess
import sys

import numpy as np

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_phases_tiny_on_cpu(tmp_path):
    smoke = _smoke()
    c = smoke.make_collection(40, 2048, seed=0)
    _, ref = smoke.build_store(c, "list-scan", str(tmp_path / "ref"))
    # the kernel path in interpret mode: what the TPU runs compiled
    plan, dev = smoke.build_store(
        c, "freq-split", str(tmp_path / "fs"), use_kernel=True
    )
    assert plan.method_kwargs["use_kernel"] is True
    assert smoke.assert_same_segments(ref, dev) == ref.segments[0].nnz > 0
    reqs = smoke.make_requests(ref, seed=0)
    answers, interpreted, longest = smoke.run_queries(ref, reqs)
    assert interpreted  # the CPU runs the Pallas kernel interpreted
    assert longest > 0
    np.testing.assert_array_equal(reqs[0].terms[: smoke.HEAD_TERMS],
                                  np.arange(smoke.HEAD_TERMS))
    worker = smoke.serve_requests(str(tmp_path / "ref"), reqs, answers)
    assert worker["platform"] == "cpu"


def test_smoke_phases_catch_a_wrong_answer(tmp_path):
    smoke = _smoke()
    c = smoke.make_collection(20, 512, seed=1)
    _, ref = smoke.build_store(c, "list-scan", str(tmp_path / "ref"))
    reqs = smoke.make_requests(ref, seed=1)
    answers, _, _ = smoke.run_queries(ref, reqs)
    answers[-1] = answers[-1] + 1
    try:
        smoke.check_answers(reqs, smoke.QueryEngine(ref).execute(reqs),
                            answers, "test")
    except AssertionError as e:
        assert "PairCountsRequest" in str(e)
    else:
        raise AssertionError("a changed pair count went unnoticed")


def test_smoke_script_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), "--docs", "20"],
        capture_output=True, text=True, timeout=300, env=env,
    )
    assert proc.returncode != 0
    assert "phase device: FAILED" in proc.stderr
    # nothing ran after the device check, and no result line was printed
    assert "phase collection" not in proc.stdout
    assert '"ok"' not in proc.stdout
