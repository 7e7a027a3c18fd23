"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload sage-products.train --seed 7 \
        --seconds 40 --trace 0

The cell's configuration, model reference, traffic mix, limits and
metrics are found by name from ``BENCHMARK.json`` (see
``bench/README.md``).  The run loads the graph (built once per checkout
under ``bench/.cache``), warms every shape it will use, measures for
``--seconds`` and checks what the timed path produced against the plain
reference (``bench/reference.py`` with ``bench/models/<model>.py``).
The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the end-to-end metrics with
``--trace 0``, the per-layer ones with ``--trace 1``), ``device``,
``breakdown`` when traced, and ``checks`` last; the checks are also the
last lines of standard error.
Without a TPU, or with fewer chips than the cell asks for, it exits 2
and prints no result.

``--calibrate N`` (seeds ``--seed`` .. ``--seed+N-1``) and ``--sweep``
(serving: rates to offer) read the numbers the limits and the serving
rate were set from; they print no result line.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    # import the benchmark as the ``bench`` package (its ``trace`` module
    # must not shadow the standard library's), and the program from src/
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--calibrate", type=int, default=0)
    ap.add_argument("--sweep", default="")
    ap.add_argument("--fault", default="",
                    help="plant a fault (the harness's own tests)")
    return ap.parse_args(argv)


def main(argv=None, root: Path = ROOT, require_chip: bool = True,
         t_start: float = T_START) -> int:
    args = parse(argv)
    from bench import checks, common, graphgen, peaks
    from bench import trace as tracing
    cell = common.load_cell(args.workload, root)
    import jax
    cache_dir = common.enable_compile_cache(root)
    dev = common.device_info()
    if require_chip and (dev["platform"] != "tpu"
                         or dev["count"] < cell["chips"]):
        common.stderr(f"no accelerator for {args.workload}: {dev['count']} "
                      f"{dev['platform']} device(s), {cell['chips']} "
                      f"TPU chip(s) needed")
        return 2
    import os
    common.log(f"[env] jax {jax.__version__}; {dev['count']} x "
               f"{dev['platform']} ({dev['kind']}); {os.cpu_count()} host "
               f"CPUs; compile cache {cache_dir}")
    # float32 as the configuration states it: XLA's default on a TPU
    # multiplies float32 matrices in one bfloat16 pass
    jax.config.update("jax_default_matmul_precision",
                      cell["config"]["matmul_precision"])
    compiles = common.Compiles()
    arrays = graphgen.load_or_build(cell["config"], root / "bench" / ".cache",
                                    log=common.log)
    graph = common.make_graph(cell["config"], arrays)
    common.log(f"[graph] {graph.num_nodes} nodes, {graph.num_edges} "
               f"entries, F={graph.feat_dim}; "
               f"{time.perf_counter() - t_start:.1f} s since start")
    loop = importlib.import_module(f"bench.{cell['traffic']['kind']}")
    if args.calibrate:
        loop.calibrate(cell, graph, args, compiles, common.log)
        return 0
    if args.sweep:
        loop.sweep(cell, graph, args, compiles, common.log)
        return 0
    traced = bool(args.trace)
    trace_dir = root / "bench" / ".traces" / args.workload
    shutil.rmtree(trace_dir, ignore_errors=True)
    res = loop.run(cell, graph, args, compiles, common.log, traced,
                   trace_dir)
    device = dict(dev, memory_peak_bytes=res["peak"])
    out = {}
    if traced:
        summary = tracing.summarize(
            tracing.read(tracing.latest_xplane(trace_dir)),
            kernels=common.kernels_read(cell["per_layer"], root))
        if summary is not None:
            device.update(busy_s=summary["busy_s"],
                          window_s=summary["window_s"])
            out["breakdown"] = summary["breakdown"]
            common.log(f"[trace] {json.dumps(summary)}")
        ctx = dict(res["ctx"], trace=summary,
                   peaks=peaks.peaks(dev["kind"]))
        metrics = common.read_per_layer(cell["per_layer"], ctx, root)
    else:
        metrics = {m["name"]: {"value": res["end_to_end"][m["name"]],
                               "unit": m["unit"]}
                   for m in cell["end_to_end"] if m["name"] != "setup_s"}
        metrics["setup_s"] = {"value": res["t_window"] - t_start,
                              "unit": "s"}
    judged = checks.judge(res["readings"], cell["limits"])
    correct = checks.passed(judged)
    for k, v in judged.items():
        common.stderr(f"{k} {v['value']!r} limit {v['limit']!r}")
    line = {"correct": correct, "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": device,
            **out, "checks": judged}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
