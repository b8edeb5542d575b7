#!/usr/bin/env python3
"""Record the small device trace that ``test_devtrace.py`` reduces.

    python3 bench/tests/record_trace.py    # on a TPU host

Inside one harness annotation (``bench/build one``): three ``cooc_gram``
calls on (512, 256) 0/1 tiles, then 50 ms in which the device idles, then a
fourth call. The profile is copied to ``bench/tests/data/small.xplane.pb``
with the call times the host saw beside it (``small.json``).
"""

import glob
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels import ops  # noqa: E402


def main() -> None:
    tile = (np.random.default_rng(0).random((512, 256)) < 0.3).astype(np.float32)
    np.asarray(ops.cooc_gram(tile, tile))  # compile outside the trace
    out = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(out, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench/build one"):
        for _ in range(3):
            np.asarray(ops.cooc_gram(tile, tile))
        time.sleep(0.05)
        np.asarray(ops.cooc_gram(tile, tile))
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(out, "plugins", "profile", "*", "*.xplane.pb"))[0]
    dst = os.path.join(HERE, "data")
    os.makedirs(dst, exist_ok=True)
    shutil.copy(src, os.path.join(dst, "small.xplane.pb"))
    with open(os.path.join(dst, "small.json"), "w") as f:
        json.dump({"device_kind": jax.devices()[0].device_kind, "calls": 4,
                   "shape": [512, 256, 256], "idle_sleep_s": 0.05}, f)
    print(os.path.getsize(os.path.join(dst, "small.xplane.pb")), "bytes")


if __name__ == "__main__":
    main()
