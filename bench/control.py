#!/usr/bin/env python3
"""Controls of the benchmark's comparisons: the plain reference put in the
program's place and computed in bfloat16, one step below what the
configurations state (exact integer counts; float32 scores). Each control
has to come out as not correct.

    python3 bench/control.py --workload <name> --seeds 11,12,13 [--seconds 20]

* ``build-*``: the sampled store rows as a bfloat16 Gram on the device,
  (terms, docs) @ (docs, vocab) with a bfloat16 result, which is exact only
  up to 256; compared as ``bench/drivers/build.py`` compares a store.
* ``serve-*``: the answers to the run's own top-k requests, with counts,
  document frequencies and scores in bfloat16 on the device, ranked by those
  scores; compared as ``bench/drivers/serve.py`` compares served answers.

Each seed prints one JSON line with the numbers compared and their limits.
The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import common  # noqa: E402
import data  # noqa: E402
from reference import Reference  # noqa: E402


def incidence(ref: Reference, dtype):
    """The (docs, vocab) 0/1 incidence matrix on the device."""
    import jax.numpy as jnp

    lens = np.diff(ref.doc_ptr)
    docs = np.repeat(np.arange(ref.num_docs), lens)
    b = jnp.zeros((ref.num_docs, ref.vocab), dtype)
    return b.at[docs, ref.terms].set(1)


def build_rows_bf16(ref: Reference, terms: np.ndarray, block: int = 512):
    """Rows of ``terms`` from a bfloat16 Gram: yields (term, row)."""
    import jax
    import jax.numpy as jnp

    b = incidence(ref, jnp.bfloat16)
    gram = jax.jit(lambda a, b: jnp.dot(a.T, b, preferred_element_type=jnp.bfloat16))
    for lo in range(0, len(terms), block):
        sel = terms[lo:lo + block]
        rows = np.asarray(gram(b[:, sel], b).astype(jnp.float32)).astype(np.int64)
        for t, r in zip(sel, rows):
            r[t] = 0
            yield int(t), r


def topk_bf16(ref: Reference, t: int, row: np.ndarray, score: str, k: int):
    """A top-k answer scored in bfloat16 on the device: (ids, scores)."""
    import jax.numpy as jnp

    bf = jnp.bfloat16
    c = jnp.asarray(row).astype(bf)
    df_n = jnp.asarray(np.maximum(ref.df, 1)).astype(bf)
    df_t = jnp.asarray(max(ref.df[t], 1)).astype(bf)
    if score == "count":
        s = c
    elif score == "pmi":
        s = jnp.log(c * jnp.asarray(ref.num_docs).astype(bf) / (df_t * df_n))
    else:
        s = jnp.asarray(2.0).astype(bf) * c / (df_t + df_n)
    s = jnp.where(jnp.asarray(row) > 0, s, -jnp.inf).astype(jnp.float32)
    s = np.asarray(s)
    order = np.argsort(-s, kind="stable")[:k]
    ids = np.where(np.isfinite(s[order]), order, -1)
    vals = np.where(ids >= 0, s[order], 0 if score == "count" else -np.inf)
    return ids, vals


def run_control(workload: str, seed: int, seconds: float) -> dict:
    bench = common.load_json(common.ROOT, "BENCHMARK.json")
    cell = {w["name"]: w for w in bench["workloads"]}[workload]
    cfg = common.load_json(common.BENCH, "configs", cell["config"] + ".json")
    traffic = common.load_json(common.BENCH, "traffic", cell["traffic"] + ".json")
    drv = common.load_module(
        os.path.join(BENCH, "drivers", traffic["driver"] + ".py"),
        "driver_" + traffic["driver"])
    ptr, terms, vocab = data.make_collection(cfg, seed)
    ref = Reference(ptr, terms, vocab)
    limits = cfg["limits"]
    if traffic["driver"] == "build":
        sample = drv.sample_terms(ref, cfg, seed)
        rows = sum(not np.array_equal(r, ref.row(t))
                   for t, r in build_rows_bf16(ref, sample))
        found = {"rows_differing": int(rows), "df_differing": 0}
        extra = {}
    else:
        sched = drv.schedule(traffic, int(np.count_nonzero(ref.df)), seconds, seed)
        results = [None] * len(sched["term"])
        for t in np.unique(sched["term"]):
            row = ref.row(int(t))
            ans = topk_bf16(ref, int(t), row, sched["score"], sched["k"])
            for i in np.flatnonzero(sched["term"] == t):
                results[i] = (0.0, 0.0, 0.0, True, ans)
        v = drv.check_answers(ref, sched, results, limits["score_gap"])
        found = {k: v[k] for k in ("answer_errors", "score_gap", "unanswered")}
        extra = {k: v[k] for k in ("value_gap", "rank_gap")}
    return {"workload": workload, "seed": seed, **extra,
            "checks": {k: {"value": found[k], "limit": limits[k]} for k in found},
            "fails": any(found[k] > limits[k] for k in found)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    import jax

    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    for s in args.seeds.split(","):
        print(json.dumps(run_control(args.workload, int(s), args.seconds),
                         default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
