"""Runs the benchmark's cells at a tiny size on the CPU.

    pytest bench/tests

Not collected by the repository's own test run (``testpaths = ["tests"]``).
The device rule is steered from inside the tests: ``require_tpu`` and the
host's chip count are replaced, and the CPU reads the v5e peak table.
"""

import json
import os
import sys
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
# compiled CPU programs stay out of the checkout's cache
os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(prefix="bench_tests_jax_")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import pytest  # noqa: E402

import common  # noqa: E402
import roofline  # noqa: E402

TINY_CONFIG = {"num_docs": 400, "vocab_size": 2048, "mean_len": 40,
               "check_top_rows": 64, "check_sampled_rows": 128}
TINY_TRAFFIC = {"rate_per_s": 20.0, "request_timeout_s": 5, "warm_chunk_s": 1,
                "warm_max_s": 6, "warm_batch_requests": 4}


@pytest.fixture
def tiny(monkeypatch):
    """Shrink every configuration and serving rate, and accept the CPU."""
    real_load, real_peaks = common.load_json, roofline.peaks

    def load(*parts):
        d = real_load(*parts)
        if "configs" in parts:
            d.update(TINY_CONFIG)
        if "traffic" in parts and d.get("driver") == "serve":
            d.update(TINY_TRAFFIC)
        return d

    monkeypatch.setattr(common, "load_json", load)
    monkeypatch.setattr(common, "require_tpu", lambda *a, **k: None)
    monkeypatch.setattr(common, "host_tpu_chips", lambda: 1)
    monkeypatch.setattr(roofline, "peaks", lambda kind: real_peaks("TPU v5 lite"))
    return load


@pytest.fixture
def run_cell(tiny, capsys):
    """Run ``bench/run.py`` in this process; returns its result line."""
    import run

    def go(workload, seed=12345, seconds=2.0, trace=0):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)])
        assert rc == 0
        return json.loads(capsys.readouterr().out.strip().splitlines()[-1])

    return go
