#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration, ``bench/configs/<config>.json``, and a
traffic mix, ``bench/traffic/<traffic>.json``; the mix names its driver,
``bench/drivers/<driver>.py``, which sets up, measures for ``--seconds`` and
checks the answers against the plain reference. With ``--trace 0`` the line
holds the cell's end-to-end metrics; with ``--trace 1`` its per-layer
metrics, each read by ``bench/metrics/<metric>.py`` (a reader that finds
nothing leaves its metric out). The numbers compared, each beside its
limit, close the standard error and the line's ``checks`` key.

The run exits 3 and prints no result where JAX finds no TPU or fewer chips
than the cell asks for. JAX's compilation cache is kept in
``JAX_COMPILATION_CACHE_DIR`` where set, else in ``<checkout>/.jax_cache``;
scratch files go to ``<checkout>/.bench_work/<cell>``, removed at the end.
"""

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

import common  # noqa: E402


def cell_metrics(bench: dict, section: str, cell: str) -> list:
    """The metrics of a section that this cell reports."""
    return [m for m in bench[section] if cell in m.get("workloads", [cell])]


def result_line(bench: dict, cell: str, out: common.Outcome, trace: bool) -> dict:
    metrics = {}
    if trace:
        for m in cell_metrics(bench, "per_layer", cell):
            reader = common.load_module(
                os.path.join(BENCH, "metrics", m["name"] + ".py"),
                "metric_" + m["name"].replace(".", "_"))
            value = reader.read(out.layer)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell_metrics(bench, "end_to_end", cell):
            metrics[m["name"]] = {"value": out.end_to_end[m["name"]],
                                  "unit": m["unit"]}
    line = {"correct": out.correct, "attempted": out.attempted,
            "failed": out.failed, "metrics": metrics, "device": out.device}
    if trace and out.breakdown is not None:
        line["breakdown"] = out.breakdown
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in out.checks.items()}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = common.load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        ap.error(f"no workload {args.workload!r}; have {sorted(cells)}")
    cell = cells[args.workload]
    traffic = common.load_json(BENCH, "traffic", cell["traffic"] + ".json")
    driver = common.load_module(
        os.path.join(BENCH, "drivers", traffic["driver"] + ".py"),
        "driver_" + traffic["driver"])

    from repro.runtime.device import configure_compile_cache

    configure_compile_cache()
    work = os.path.join(ROOT, ".bench_work", args.workload)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    ctx = common.Context(
        workload=cell,
        config=common.load_json(BENCH, "configs", cell["config"] + ".json"),
        traffic=traffic, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), t_process=T_PROCESS, work_dir=work)
    try:
        out = driver.run(ctx)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    line = result_line(bench, args.workload, out, ctx.trace)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(line, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
