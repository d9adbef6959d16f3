"""Driver ``replay-closed``: one job at a time over a device-resident
archive — fold all of it, read the state back, again, for the window.

From the program: ``replay.ReplayConfig``, the staging calls
``stage_columns`` -> ``stage_pallas_planes`` -> ``stage_sorted_planes``
and the kernel factory ``make_pallas_replay_sorted_fn`` (what ``bench.py``
selects on a TPU).  The archive is made on the device in one jitted call:
``K`` copies of the staged base, each perturbed by
``benchmark.traffic.perturb``, segment ids unchanged, so the host sort is
paid once.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import harness, traffic, work
from benchmark.reference import replay_fold


def _span_batch(base: dict, n_services: int):
    from anomod.schemas import SpanBatch
    n = len(base["service"])
    err = base["is_error"]
    return SpanBatch(
        trace=np.zeros(n, np.int32), parent=np.full(n, -1, np.int32),
        service=base["service"], endpoint=np.zeros(n, np.int32),
        start_us=base["start_us"], duration_us=base["duration_us"],
        is_error=err, status=np.where(err, 500, 200).astype(np.int16),
        kind=np.zeros(n, np.int8),
        services=tuple(f"svc{i:02d}" for i in range(n_services)),
        endpoints=("ep",), trace_ids=("t",))


def build_archive(sid_l, planes, wids, keys, f_tab, l_tab, flip_per_1024):
    """[K*T] sid, [6, K*T] planes, [K*T/block] wids from the staged base
    (planes rows: valid, err, 5xx, dur_raw, dur, dur^2)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def build(sid_l, planes, wids, keys, f_tab, l_tab):
        k = keys.shape[0]
        valid, err, s5, raw, dur = (planes[i][None, :] for i in range(5))
        bits = jax.lax.bitcast_convert_type(raw, jnp.uint32)
        cols = traffic.perturb(jnp, bits, keys[:, None], raw, dur, err, s5,
                               valid, f_tab, l_tab, flip_per_1024)
        shape = (k, sid_l.shape[0])
        out = jnp.stack([jnp.broadcast_to(valid, shape)]
                        + [jnp.broadcast_to(c, shape) for c in cols])
        return (jnp.tile(sid_l, k), out.reshape(6, -1), jnp.tile(wids, k))

    return build(sid_l, planes, wids, keys, f_tab, l_tab)


def fold_passes(pfn, archive, sub_passes: int):
    """One job: the whole archive folded from a zero state, in
    ``sub_passes`` equal dispatches whose states add (1 unless one pass
    would push a segment's float32 count past 2^24)."""
    sid, planes, wids = archive
    if sub_passes == 1:
        return np.asarray(pfn(sid, planes, wids))
    n, nb = sid.shape[0] // sub_passes, wids.shape[0] // sub_passes
    parts = [pfn(sid[i * n:(i + 1) * n], planes[:, i * n:(i + 1) * n],
                 wids[i * nb:(i + 1) * nb]) for i in range(sub_passes)]
    return np.sum([np.asarray(p, np.float64) for p in parts], axis=0)


def run(cell: dict, seed: int, seconds: float, traced: bool, t_start: float,
        meter: harness.CompileMeter, trace_dir: str,
        control: bool = False) -> dict:
    """``control``: the reference's bfloat16 twin stands in the program's
    place in the comparison, which then has to come out not correct."""
    import jax

    from anomod.ops.pallas_replay import (make_pallas_replay_sorted_fn,
                                          stage_sorted_planes)
    from anomod.replay import (ReplayConfig, pallas_block, stage_columns,
                               stage_pallas_planes)

    cfg, p = cell["config"], cell["traffic"]["params"]
    limits = cell["traffic"]["limits"]
    rcfg = ReplayConfig(n_services=cfg["n_services"],
                        n_windows=cfg["n_windows"],
                        n_hist_buckets=cfg["n_hist_buckets"],
                        chunk_size=cfg["chunk_size"],
                        window_us=cfg["window_us"])
    params = dict(p, n_services=cfg["n_services"],
                  window_us=cfg["window_us"], copies=cfg["copies"])
    k = int(cfg["copies"])
    base = traffic.archive_base(params, seed)
    chunks, n_base = stage_columns(_span_batch(base, cfg["n_services"]), rcfg)
    block = pallas_block(rcfg.chunk_size)
    sid_l, planes_s, wids = stage_sorted_planes(
        *stage_pallas_planes(chunks), rcfg.sw, block=block)
    # f32 counts are exact to 2^24 a segment (bench.py's replicate clamp)
    hottest = int(np.bincount(replay_fold.segment_ids(
        base["service"], base["start_us"], rcfg.n_windows,
        rcfg.window_us)).max())
    sub_passes = -(-k * hottest // (1 << 24))
    if k % sub_passes:
        raise ValueError(f"{k} copies do not split into {sub_passes} passes")
    f_tab, l_tab = traffic.jitter_tables(params)
    archive = build_archive(
        jax.device_put(sid_l), jax.device_put(planes_s), jax.device_put(wids),
        jax.device_put(traffic.copy_keys(seed, k)), jax.device_put(f_tab),
        jax.device_put(l_tab), int(params["error_flip_per_1024"]))
    pfn = make_pallas_replay_sorted_fn(
        rcfg.sw, rcfg.n_hist_buckets, block=block, inner_repeats=1,
        interpret=jax.devices()[0].platform != "tpu")
    fold_passes(pfn, archive, sub_passes)          # compile + warm
    n_spans = k * n_base
    if traced:
        seconds = min(seconds, float(cell["traffic"]["trace_seconds"]))

    compiles0 = meter.compiles
    passes, state = 0, None
    setup_s = time.perf_counter() - t_start
    with harness.traced_window(traced, trace_dir):
        t0 = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("bench.pass"):
                state = fold_passes(pfn, archive, sub_passes)
            passes += 1
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
    peak = harness.memory_peak_bytes()
    resident = sum(int(a.nbytes) for a in archive)
    del archive

    want, ctl = replay_fold.fold_archive(base, cfg, params, seed,
                                         control=True)
    ctl_got = replay_fold.compare(ctl, want)
    if control:
        state = ctl
    got = replay_fold.compare(state, want)
    checks = [
        harness.Check("compiles_in_window", meter.compiles - compiles0, 0),
        harness.Check("spans_folded_minus_staged",
                      abs(float(np.asarray(state, np.float64)[:, 0].sum())
                          - n_spans), 0),
        harness.Check("exact_cells_differing",
                      got["exact_cells_differing"], 0),
        harness.Check("moment_gap", got["moment_gap"],
                      limits["moment_gap"]),
    ]
    return {
        "attempted": passes, "failed": 0, "setup_s": setup_s,
        "memory_peak_bytes": peak, "checks": checks,
        "end_to_end": {"replay_spans_per_s": passes * n_spans / elapsed},
        "notes": {"passes": passes, "window_s": elapsed, "n_spans": n_spans,
                  "staged_rows": int(sid_l.shape[0]) * k,
                  "resident_bytes": resident, "sub_passes": sub_passes,
                  "hottest_segment": k * hottest,
                  "control_moment_gap": ctl_got["moment_gap"]},
        "work": dict(work.fold_work(n_spans, rcfg.sw, rcfg.n_hist_buckets),
                     calls=passes),
    }
