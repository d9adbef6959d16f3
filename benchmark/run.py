#!/usr/bin/env python3
"""One process, one cell, once: load, warm, measure ``--seconds``, print
the contract's one-line object, exit.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

No TPU, or fewer chips than the cell asks for: non-zero exit and no line.
``JAX_PLATFORMS=cpu`` is the tests' tiny-size rehearsal only; its line
names the device ``cpu``.  The compile cache stays where the program puts
it (``<checkout>/.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR``).
"""

import time

T_START = time.perf_counter()

import argparse   # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import shutil     # noqa: E402
import sys        # noqa: E402
import tempfile   # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None, root: str = ROOT, t_start: float = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="1: the lower-precision control stands in the "
                    "program's place; correct has to read false (by hand)")
    args = ap.parse_args(argv)
    t_start = T_START if t_start is None else t_start

    from benchmark import contract, harness
    bench = harness.load_benchmark(root)
    cell = harness.load_cell(bench, args.workload, root)

    device = harness.device_fields()
    on_cpu_by_request = (device["platform"] == "cpu" and os.environ.get(
        "JAX_PLATFORMS", "").strip().lower() == "cpu")
    if device["platform"] != "tpu" and not on_cpu_by_request:
        print(f"benchmark: JAX found {device['platform']}, not a TPU; "
              "nothing was run", file=sys.stderr)
        return 2
    if device["count"] < cell["chips"]:
        print(f"benchmark: {args.workload} needs {cell['chips']} chip(s), "
              f"JAX found {device['count']}", file=sys.stderr)
        return 2
    from anomod.utils.platform import enable_compile_cache
    enable_compile_cache()
    meter = harness.CompileMeter()
    traced = bool(args.trace)
    trace_dir = tempfile.mkdtemp(prefix="anomod_bench_trace_")
    try:
        driver = harness.module_for("drivers", cell["traffic"]["driver"],
                                    root)
        res = driver.run(cell, args.seed, args.seconds, traced, t_start,
                         meter, trace_dir, control=bool(args.control))
        if traced:
            from benchmark import trace_reduce, work
            xplane = trace_reduce.find_xplane(trace_dir)
            trace = trace_reduce.reduce_xplane(xplane)
            ctx = dict(res, trace=trace, cell=cell,
                       peaks=work.load_peaks(device["kind"]))
            metrics = harness.layer_metrics(bench, args.workload, ctx, root)
            device["busy_s"] = trace.busy_s
            device["window_s"] = trace.window_s
        else:
            due = contract.metrics_due(bench, args.workload, False)
            values = dict(res["end_to_end"], setup_s=res["setup_s"])
            metrics = {n: {"value": float(values[n]), "unit": u}
                       for n, u in due.items()}
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    device["memory_peak_bytes"] = res["memory_peak_bytes"]
    checks = res["checks"]
    line = {"correct": all(c.ok for c in checks),
            "attempted": int(res["attempted"]), "failed": int(res["failed"]),
            "metrics": metrics, "device": device}
    if traced:
        line["breakdown"] = trace.breakdown()
    line["notes"] = dict(res["notes"], compile_s=meter.seconds,
                         cache_hits=meter.hits, cache_misses=meter.misses)
    line["checks"] = [c.row() for c in checks]
    reasons = contract.check_last_line(line, bench, args.workload, traced)
    if reasons:
        for r in reasons:
            print(f"benchmark: malformed line: {r}", file=sys.stderr)
        return 3
    sys.stdout.flush()
    print(json.dumps(line["notes"]), flush=True)
    for c in checks:
        print(f"check {c.name}: {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
