#!/usr/bin/env python
"""Roofline ablation for the sorted replay kernel on the TPU.

The headline kernel sits at ~1.6e9 spans/s — ~38 GB/s of 24-byte rows on a
part with ~800 GB/s HBM, so HBM is NOT the wall.  This probe measures what
is, by running ablations of the kernel's stages at kernel-dominated
replication (same corpus, same staging, same grid):

  - ``onehot_only``    — the [B, k] iota-compare one-hot plus a 1-row
                         matmul (counts): the irreducible scatter
                         densification.  One 128-lane compare per span is
                         the hardware's minimum for ANY one-hot
                         formulation (VPU lanes are 128 wide; a narrower
                         one-hot still burns a full lane register).
  - ``no_hist``        — full moment pipeline, histogram plane ablated
                         (ROWS 25 -> 9).
  - ``full``           — the shipping kernel.

``full / onehot_only`` bounds how far the full kernel sits from the
formulation's hardware ceiling; the VERDICT's roofline criterion is met
when that ratio is within ~2x.  Writes one bench_runs/ record with every
ablation's rate.  Exits non-zero off-TPU.
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
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from anomod import labels, synth
    from anomod.ops.pallas_replay import (N_PLANES, _build_rhs_t,
                                          make_pallas_replay_sorted_fn,
                                          stage_sorted_planes)
    from anomod.provenance import capture_record, write_capture
    from anomod.replay import (ReplayConfig, stage_columns,
                               stage_pallas_planes)
    from anomod.schemas import concat_span_batches

    k, block, replicate, n_hist = 128, 4096, 4096, 16
    batch = concat_span_batches([
        synth.generate_spans(l, n_traces=2_000)
        for l in labels.labels_for_testbed("TT")])
    cfg = ReplayConfig(n_services=batch.n_services)
    chunks, n = stage_columns(batch, cfg)
    sid_np, planes_np = stage_pallas_planes(chunks)
    sid_l, planes_s, wids = stage_sorted_planes(sid_np, planes_np, cfg.sw,
                                                k=k, block=block)
    sid_d = jax.device_put(sid_l)
    planes_d = jax.device_put(planes_s)
    wids_d = jax.device_put(wids)
    t = sid_l.shape[0]
    nw = (cfg.sw + 1 + k - 1) // k

    def make_ablation(rows_mode: str):
        """Ablated sorted kernels sharing grid/staging with the real one.
        rows_mode: "counts" (1-row rhs) or "no_hist" (9-row rhs)."""
        ROWS = 1 if rows_mode == "counts" else 9
        NWK = nw * k

        def kernel(wids_ref, sid_ref, planes_ref, out_ref):
            @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
            def _init():
                out_ref[:] = jnp.zeros_like(out_ref)
            sid = sid_ref[:]
            planes = planes_ref[:]
            if rows_mode == "counts":
                rhs_t = planes[0:1].astype(jnp.bfloat16)
            else:
                moments = planes[3:6]
                hi = moments.astype(jnp.bfloat16)
                lo = (moments - hi.astype(jnp.float32)).astype(jnp.bfloat16)
                rhs_t = jnp.concatenate(
                    [planes[0:3].astype(jnp.bfloat16), hi, lo], axis=0)
            seg_iota = jax.lax.broadcasted_iota(jnp.int32, (block, k), 1)
            onehot = (seg_iota == sid[:, None]).astype(jnp.bfloat16)
            partial = jax.lax.dot_general(
                rhs_t, onehot, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            col = wids_ref[pl.program_id(1)] * k
            out_ref[:, pl.ds(col, k)] += partial

        @jax.jit
        def run(sid_local, planes, wids):
            return pl.pallas_call(
                kernel,
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=1,
                    grid=(replicate, t // block),
                    in_specs=[
                        pl.BlockSpec((block,), lambda r, i, w: (i,)),
                        pl.BlockSpec((N_PLANES, block),
                                     lambda r, i, w: (0, i)),
                    ],
                    out_specs=pl.BlockSpec((ROWS, NWK),
                                           lambda r, i, w: (0, 0)),
                ),
                out_shape=jax.ShapeDtypeStruct((ROWS, NWK), jnp.float32),
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("arbitrary", "arbitrary")),
            )(wids, sid_local, planes)

        return run

    def timed(run, *args):
        out = np.asarray(run(*args))       # compile + warm
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            out = np.asarray(run(*args))
            times.append(time.perf_counter() - t0)
        wall = sorted(times)[1]
        return n * replicate / wall, wall, float(out[..., 0].sum())

    results = {}
    full = make_pallas_replay_sorted_fn(cfg.sw, n_hist, k=k, block=block,
                                        inner_repeats=replicate)
    results["full"], w, _ = timed(full, sid_d, planes_d, wids_d)
    for mode, name in (("counts", "onehot_only"), ("no_hist", "no_hist")):
        results[name], _, _ = timed(make_ablation(mode), sid_d, planes_d,
                                    wids_d)

    ceiling = results["onehot_only"]
    best = results["full"]
    verdict = {
        "metric": "replay_kernel_roofline",
        "value": round(best, 1),
        "unit": "spans/sec/chip",
        "rates": {m: round(v, 1) for m, v in results.items()},
        "onehot_ceiling_ratio": round(ceiling / max(best, 1.0), 3),
        "within_2x_of_formulation_ceiling": bool(ceiling / best <= 2.0),
        "params": dict(k=k, block=block, replicate=replicate,
                       n_spans=n, device=str(jax.devices()[0])),
    }
    # device must be TOP-LEVEL: write_capture names the file by the
    # record's "device" field (…_tpu.json)
    rec = capture_record("replay_kernel_roofline", verdict["value"],
                         "spans/sec/chip",
                         device=str(jax.devices()[0]),
                         **{kk: vv for kk, vv in verdict.items()
                            if kk not in ("metric", "value", "unit")})
    path = write_capture(rec)
    verdict["capture_file"] = str(path)
    print(json.dumps(verdict))
    return 0


if __name__ == "__main__":
    sys.exit(main())
