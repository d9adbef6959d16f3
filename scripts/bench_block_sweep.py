#!/usr/bin/env python
"""Pallas replay-kernel block-size sweep on the TPU.

Evidences the docs claim that throughput is flat (within a few %) across
block sizes 1024-8192 with a committed bench_runs/ record per sweep —
docs/BENCHMARKS.md cites the record instead of prose.  Also captures the
XLA scan path on the same staged corpus for the kernel-vs-XLA ratio.

Run through the chip tool: ``python scripts/bench_block_sweep.py``.
Exits non-zero off-TPU.
"""

import json
import os
import sys
import time


def main() -> int:
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax
    if jax.devices()[0].platform != "tpu":
        print(json.dumps({"error": "no TPU backend (JAX found "
                          f"{jax.devices()[0].platform})"}))
        return 2
    import numpy as np

    from anomod import labels, synth
    from anomod.ops.pallas_replay import make_pallas_replay_fn
    from anomod.provenance import capture_record, write_capture
    from anomod.replay import (ReplayConfig, measure_throughput,
                               stage_columns, stage_pallas_planes)
    from anomod.schemas import concat_span_batches

    batch = concat_span_batches([
        synth.generate_spans(l, n_traces=2_000)
        for l in labels.labels_for_testbed("TT")])
    cfg = ReplayConfig(n_services=batch.n_services)
    chunks, n = stage_columns(batch, cfg)
    sid_np, planes_np = stage_pallas_planes(chunks)
    replicate = 64
    sid = jax.device_put(np.asarray(sid_np))
    planes = jax.device_put(np.asarray(planes_np))

    def time_fn(run):
        """Shared measurement policy for every sweep point: warm/compile,
        then 3 timed runs with the out[:1] host read-back barrier, median
        wall.  Returns (wall, raw_walls)."""
        jax.block_until_ready(run())
        walls = []
        for _ in range(3):
            t0 = time.perf_counter()
            np.asarray(run()[:1])           # host read-back barrier
            walls.append(time.perf_counter() - t0)
        return sorted(walls)[1], walls

    points = []
    for block in (1024, 2048, 4096, 8192):
        fn = make_pallas_replay_fn(cfg.sw, cfg.n_hist_buckets, block=block,
                                   inner_repeats=replicate)
        wall, walls = time_fn(lambda: fn(sid, planes))
        points.append({"block": block,
                       "spans_per_sec": round(n * replicate / wall, 1),
                       "wall_s": round(wall, 4),
                       "raw_wall_s": [round(w, 4) for w in walls]})
        print(json.dumps(points[-1]))

    # sorted-window variant: sweep (block, k) over the same corpus — its
    # one-hot is k lanes wide, so block can grow without VMEM pressure.
    # At replicate 64 the fixed per-dispatch overhead can mask block
    # preferences, so the sweep also runs each point at replicate 512
    # (kernel-dominated) — that column is the one that ranks configs.
    from anomod.ops.pallas_replay import (make_pallas_replay_sorted_fn,
                                          stage_sorted_planes)
    sorted_points = []
    for block in (1024, 2048, 4096, 8192, 16384):
        for k in (128, 256):
            sid_l, planes_s, wids = stage_sorted_planes(
                sid_np, planes_np, cfg.sw, k=k, block=block)
            sid_d = jax.device_put(sid_l)
            planes_d = jax.device_put(planes_s)
            wids_d = jax.device_put(wids)
            point = {"block": block, "k": k,
                     "staged_rows": int(sid_l.shape[0])}
            for rep in (replicate, 512):
                fn = make_pallas_replay_sorted_fn(cfg.sw,
                                                  cfg.n_hist_buckets,
                                                  k=k, block=block,
                                                  inner_repeats=rep)
                wall, walls = time_fn(
                    lambda: fn(sid_d, planes_d, wids_d))
                tag = "" if rep == replicate else f"_r{rep}"
                point[f"spans_per_sec{tag}"] = round(n * rep / wall, 1)
                point[f"wall_s{tag}"] = round(wall, 4)
                point[f"raw_wall_s{tag}"] = [round(w, 4) for w in walls]
            sorted_points.append(point)
            print(json.dumps(point))

    # replicate scaling at the default sorted config: if spans/sec keeps
    # rising with on-device replication, the fixed dispatch/read-back
    # overhead still dominates the wall and the kernel's true rate is
    # higher than the headline
    replicate_points = []
    sid_l, planes_s, wids = stage_sorted_planes(sid_np, planes_np, cfg.sw)
    sid_d, planes_d, wids_d = (jax.device_put(sid_l),
                               jax.device_put(planes_s),
                               jax.device_put(wids))
    for rep in (64, 256, 1024):
        fn = make_pallas_replay_sorted_fn(cfg.sw, cfg.n_hist_buckets,
                                          inner_repeats=rep)
        wall, walls = time_fn(lambda: fn(sid_d, planes_d, wids_d))
        replicate_points.append({
            "replicate": rep, "spans_per_sec": round(n * rep / wall, 1),
            "wall_s": round(wall, 4),
            "raw_wall_s": [round(w, 4) for w in walls]})
        print(json.dumps(replicate_points[-1]))

    xla = measure_throughput(batch, cfg, repeats=3, replicate=replicate,
                             kernel="xla")
    best = max(p["spans_per_sec"] for p in points)
    worst = min(p["spans_per_sec"] for p in points)
    rec = capture_record(
        "pallas_block_sweep", best, "spans/sec/chip",
        device=str(jax.devices()[0]), n_spans=n * replicate,
        points=points, flatness=round(worst / best, 4),
        sorted_points=sorted_points,
        sorted_best=max(p["spans_per_sec"] for p in sorted_points),
        sorted_best_r512=max(p["spans_per_sec_r512"]
                             for p in sorted_points),
        replicate_points=replicate_points,
        xla_spans_per_sec=round(xla.spans_per_sec, 1),
        xla_raw_wall_s=[round(w, 4) for w in xla.raw_wall_s])
    path = write_capture(rec)
    print(json.dumps({"capture_file": path, "best": best,
                      "flatness": rec["flatness"],
                      "vs_xla": round(best / xla.spans_per_sec, 3)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
