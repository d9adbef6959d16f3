#!/usr/bin/env python3
"""The quickest proof that the program still starts on the chip.

Run as ``python chip_smoke.py`` from the checkout root, in ONE process
(a chip belongs to one process at a time).  It prints the device JAX
found, exits non-zero at once unless that device is a TPU, and then
drives the three main paths once through the entry points the CLI
calls, at the sizes those default to:

- **replay** — the TT bench corpus through ``measure_throughput`` with
  each device kernel, the span-count assert on, the aggregate compared
  with the numpy oracle; plus the XLA t-digest plane once.
- **serve** — ``run_power_law`` at :func:`serve_run_kw`'s configuration
  with RCA on, default engines; the device branches (matmul lanes, jax
  pool) must have run and the pool's count plane must sum to the served
  spans.  Whether fused ≡ sequential (that run's own served log,
  re-scored one tenant at a time) and device ≡ host state hold bit for
  bit ON THE CHIP is reported, not asserted.
- **train** — ``train_rca`` of the attention model for a few dozen steps.
- **four chips** — sharded replay and one dp x tp train step on a
  4-device mesh when the host has four; otherwise ``not run: <n>
  device(s)``.

There is no ``except`` around a phase: any failure is a traceback and a
non-zero exit, never a degraded result.  The last line of stdout is
``{"ok": true, "device": {...}}``.  ``tests/test_chip_smoke.py`` imports
the phases and rehearses them at a tiny size on the CPU so the script
cannot rot between chip runs.
"""

import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

#: the full-size run (the serve size is serve_run_kw()); the CPU
#: rehearsal in tests/test_chip_smoke.py passes tiny twins
N_TRACES, REPLICATE = 2000, 2
TRAIN = dict(epochs=36, train_seeds=8, n_traces=80)


def serve_run_kw(capacity: float = 25_000, duration: float = 60,
                 tenants: int = 200) -> dict:
    """The serve phase's ``run_power_law`` configuration."""
    return dict(
        n_tenants=int(tenants), n_services=12,
        capacity_spans_per_s=float(capacity), overload=2.0,
        duration_s=float(duration), tick_s=0.5, seed=7,
        window_s=5.0, baseline_windows=4, fault_tenants=2,
        # the fixed shed budget: 8 seconds of capacity worth of
        # backlog — scale-invariant, so a down-sized contract run
        # sheds in the same regime as the full-size one
        max_backlog=int(8 * float(capacity)))


def engines_identical(eng_a, eng_b):
    """(alerts_same, states_same) over the union of the two engines'
    tenants — the one definition every parity bit of the on-chip report
    reads."""
    import numpy as np
    tids = sorted(set(eng_a._tenant_det) | set(eng_b._tenant_det))
    alerts = all(eng_a.alerts_for(t) == eng_b.alerts_for(t) for t in tids)
    states = all(
        t in eng_a._tenant_replay and t in eng_b._tenant_replay
        and np.array_equal(np.asarray(eng_a._tenant_replay[t].state.agg),
                           np.asarray(eng_b._tenant_replay[t].state.agg))
        and np.array_equal(np.asarray(eng_a._tenant_replay[t].state.hist),
                           np.asarray(eng_b._tenant_replay[t].state.hist))
        for t in tids)
    return alerts, states


class CompileMeter:
    """Backend-compile seconds and persistent-cache traffic, from JAX's
    own monitoring events (a cache hit costs its retrieval time)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.seconds, self.hits, self.misses


def assert_replay_parity(state, want):
    """The 0/1 planes and the histogram exact, the latency moments within
    the bf16 hi/lo split's error (~6e-6 on the chip).  The moment
    tolerance is ten times tighter than tpu_tests/test_mosaic_parity.py's
    2e-3: that one let a zeroed lo term — bf16-only moments, 3e-3 off —
    through (PR 21).  Both are [SW, 6+H] aggregate ‖ histogram."""
    import numpy as np
    np.testing.assert_array_equal(state[:, :3], want[:, :3])
    np.testing.assert_array_equal(state[:, 6:], want[:, 6:])
    np.testing.assert_allclose(state[:, 3:6], want[:, 3:6],
                               rtol=2e-4, atol=3e-2)


def phase_native():
    """Build the native staging library from source (git does not commit
    the .so, and a copied tree's mtimes hide a stale one); a failed build
    fails the smoke instead of quietly serving the Python fill."""
    subprocess.run(["make", "-C", os.path.join(HERE, "native"), "clean",
                    "all"], check=True, capture_output=True, timeout=300)
    from anomod.io import native
    status = native.status()
    assert status["available"], f"native runtime unusable: {status}"
    return {"native_staging": native.staging_enabled(None),
            "mode": status["mode"]}


def phase_replay(n_traces, replicate, kernels=("xla", "pallas",
                                               "pallas-sorted")):
    import numpy as np

    from anomod.io.dataset import load_bench_corpus
    from anomod.replay import (ReplayConfig, measure_throughput,
                               replay_digests, replay_numpy, stage_columns)

    batch, _ = load_bench_corpus("TT", n_traces)
    cfg = ReplayConfig(n_services=batch.n_services)
    chunks, n = stage_columns(batch, cfg)
    ref = replay_numpy(chunks, cfg)
    want = replicate * np.concatenate([ref.agg, ref.hist], axis=1)
    info = {"n_spans": n, "replicate": replicate}
    for kernel in kernels:
        r = measure_throughput(batch, cfg, repeats=2, replicate=replicate,
                               kernel=kernel)     # span-count assert inside
        assert r.state.shape == (cfg.sw, 6 + cfg.n_hist_buckets)
        assert np.isfinite(r.state).all()
        assert_replay_parity(r.state, want)
        info[kernel] = {"spans_per_sec": round(r.spans_per_sec, 1),
                        "wall_s": round(r.wall_s, 4),
                        "compile_s": round(r.compile_s, 2)}
    # the engine="auto" digest plane: the jitted XLA build on a TPU
    digests = replay_digests(batch, cfg)
    assert digests.mean.shape == (cfg.sw, 64)
    assert np.isfinite(digests.mean).all()
    np.testing.assert_allclose(digests.weight.sum(axis=1),
                               ref.agg[:, 0], rtol=1e-5)
    return info


def fused_vs_sequential(eng, served_log):
    """Is the fused engine's output bit-equal to per-tenant SEQUENTIAL
    replay on this backend, at the size the engine just ran?  The tier-1
    pin's construction (tests/test_serve.py, fused-vs-sequential with
    coalescing) over the run's own served log: every tenant's served
    batches, coalesced per tick, re-fed to a solo StreamReplay +
    OnlineDetector built like the engine's."""
    import dataclasses

    import numpy as np

    from anomod.schemas import concat_span_batches
    from anomod.stream import OnlineDetector, StreamReplay

    per_tenant = {}
    for served in served_log:
        mine = {}
        for qb in served:
            mine.setdefault(qb.tenant_id, []).append(qb.spans)
        for tid, spans in mine.items():
            per_tenant.setdefault(tid, []).append(
                spans[0] if len(spans) == 1 else concat_span_batches(spans))
    alerts = states = True
    for tid in sorted(per_tenant):
        solo = OnlineDetector(eng.services, eng.cfg, eng.t0_us,
                              replay=StreamReplay(eng.cfg, eng.t0_us),
                              **eng._det_kw)
        for spans in per_tenant[tid]:
            solo.push(spans)
        solo.finish()
        alerts &= ([dataclasses.asdict(a) for a in eng.alerts_for(tid)]
                   == [dataclasses.asdict(a) for a in solo.alerts])
        rep = eng._tenant_replay[tid]
        states &= bool(
            np.array_equal(np.asarray(rep.state.agg),
                           np.asarray(solo.replay.state.agg))
            and np.array_equal(np.asarray(rep.state.hist),
                               np.asarray(solo.replay.state.hist)))
    return {"alerts": alerts, "states": states,
            "tenants": len(per_tenant),
            "pushes": sum(map(len, per_tenant.values()))}


def phase_serve(serve_kw, expect_engines=("matmul", "jax"),
                expect_alerted=(0,)):
    import numpy as np

    from anomod.replay import F_COUNT
    from anomod.serve.engine import run_power_law

    served_log = []
    eng, rep = run_power_law(shards=1, rca=True, served_log=served_log,
                             **serve_kw)
    pool = eng.runner.pool
    got = (eng.runner.engine, pool.engine)
    assert got == tuple(expect_engines), \
        f"serve ran (step, pool) engines {got}, expected {expect_engines}"
    assert eng.runner.lane_engine == eng.runner.engine
    assert rep.served_spans > 0
    assert rep.n_alerts > 0
    # WHICH scripted fault tenants alert is fixed by seed and admission,
    # not by the device: at the full-size configuration tenant 0 (priority 0)
    # does, and tenant 1 (priority 1) cannot — under the 2x overload it
    # is served ~38 s behind arrival, so by the end of the 60 virtual
    # seconds the detector has seen its spans up to t = 22 s and the
    # fault starts at 30 s (the same 1 of 2 on the CPU since PR 2)
    fd = rep.fault_detection
    alerted = tuple(t for t in range(serve_kw["fault_tenants"])
                    if eng.alerts_for(t))
    assert alerted == tuple(expect_alerted) \
        and fd["n_detected"] == len(alerted), \
        f"fault tenants {alerted} alerted, expected {expect_alerted}: {fd}"
    assert rep.n_rca_runs > 0
    # one invariant a wrong device result would break: every served span
    # was folded into exactly one live slot of the pool (slot 0 is the
    # dead slot; the 32-window ring never rolls inside the run)
    folded = float(pool.gather_rows(np.arange(1, pool.capacity + 1))
                   [:, :, F_COUNT].astype(np.float64).sum())
    assert folded == rep.served_spans, \
        f"pool count plane sums to {folded}, served {rep.served_spans}"
    # bit parity on THIS backend at THIS size — reported, not asserted
    # (CPU tier-1 pins both on the scatter/numpy branch only)
    t0 = time.perf_counter()
    fused = fused_vs_sequential(eng, served_log)
    fused["wall_s"] = round(time.perf_counter() - t0, 1)
    del served_log
    eng_host, rep_host = run_power_law(shards=1, rca=True, state="host",
                                       **serve_kw)
    alerts, states = engines_identical(eng, eng_host)
    parity = {"device_eq_host_state": {"alerts": alerts, "states": states},
              "fused_eq_sequential": fused}
    return {
        "engines": {"step": got[0], "pool": got[1]},
        "lanes_by_bucket": {str(b): n for b, n
                            in sorted(rep.lanes_by_bucket.items())},
        "served_spans": rep.served_spans,
        "sustained_spans_per_sec": rep.sustained_spans_per_sec,
        "serve_wall_s": rep.serve_wall_s,
        "compile_s": round(rep.compile_s + rep.lane_compile_s, 2),
        "n_alerts": rep.n_alerts, "fault_detection": fd,
        "n_rca_runs": rep.n_rca_runs,
        "native_staging": rep.native_staging,
        "serve_wall_s_host_state": rep_host.serve_wall_s,
        "bit_parity": parity,
    }


def phase_train(epochs, train_seeds, n_traces, platform="tpu"):
    import jax
    import numpy as np

    from anomod.rca import train_rca

    r = train_rca("TT", "transformer", train_seeds=range(train_seeds),
                  eval_seeds=range(100, 102), epochs=epochs,
                  n_traces=n_traces)
    losses = np.asarray(r.losses)
    assert len(losses) == epochs and np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], \
        f"loss did not fall: {losses[0]} -> {losses[-1]}"
    leaves = jax.tree_util.tree_leaves(r.params)
    on = {d.platform for leaf in leaves for d in leaf.devices()}
    assert on == {platform}, f"parameters live on {on}"
    return {"epochs": epochs, "loss_first": float(losses[0]),
            "loss_last": float(losses[-1]), "top1": r.top1,
            "n_params": int(sum(leaf.size for leaf in leaves))}


def phase_four_chips(n_traces, n_devices=4):
    import math

    import jax
    import numpy as np

    if len(jax.devices()) < n_devices:
        return f"not run: {len(jax.devices())} device(s)"

    from anomod.io.dataset import load_bench_corpus
    from anomod.parallel import make_mesh, stage_sharded
    from anomod.parallel.replay import sharded_throughput
    from anomod.parallel.train import (make_distributed_train_step,
                                       make_mesh2d)
    from anomod.rca import _stack, build_dataset
    from anomod.replay import ReplayConfig, measure_throughput

    batch, _ = load_bench_corpus("TT", n_traces)
    cfg = ReplayConfig(n_services=batch.n_services)
    mesh = make_mesh(n_devices)
    one = measure_throughput(batch, cfg, repeats=1, kernel="xla").state
    info = {}
    for kernel in ("xla", "pallas"):
        r = sharded_throughput(batch, mesh, cfg, repeats=2, kernel=kernel)
        # psum over four partial f32 sums reorders the adds: the exact
        # planes stay exact, the moments move in the last bits
        assert_replay_parity(r.state, one)
        info[kernel] = {"spans_per_sec": round(r.spans_per_sec, 1),
                        "compile_s": round(r.compile_s, 2)}
    dev_chunks, _ = stage_sharded(batch, mesh, cfg)
    placed = {s.device for s in dev_chunks["sid"].addressable_shards}
    assert len(placed) == n_devices, f"staged chunks sit on {placed}"

    mesh2d = make_mesh2d(n_devices)
    data = mesh2d.shape["data"]
    samples, _ = build_dataset("TT", seeds=[0], n_traces=10, n_windows=4)
    n_batch = math.ceil(len(samples) / data) * data
    stacked = _stack((samples * data)[:n_batch])
    params, opt_state, step, put_batch = make_distributed_train_step(
        "gcn", stacked, mesh2d)
    sharded = [leaf for leaf in jax.tree_util.tree_leaves(params)
               if not leaf.sharding.is_fully_replicated]
    assert sharded, "no tp-sharded kernel on the (data, model) mesh"
    placed = {s.device for s in sharded[0].addressable_shards}
    assert len(placed) == n_devices, f"tp-sharded kernel sits on {placed}"
    _, _, loss = step(params, opt_state, put_batch(stacked))
    assert np.isfinite(float(loss)), f"non-finite loss {loss}"
    info["train_step"] = {"mesh": dict(mesh2d.shape), "loss": float(loss)}
    return info


def main() -> int:
    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"device: platform={device['platform']} kind={device['kind']} "
          f"count={device['count']} jax={jax.__version__}", flush=True)
    if device["platform"] != "tpu":
        print("chip_smoke: JAX found no TPU; nothing was run",
              file=sys.stderr)
        return 2

    from anomod.utils.platform import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    meter = CompileMeter()
    phases = (
        ("native", phase_native),
        ("replay", lambda: phase_replay(N_TRACES, REPLICATE)),
        ("serve", lambda: phase_serve(serve_run_kw())),
        ("train", lambda: phase_train(**TRAIN)),
        ("four_chips", lambda: phase_four_chips(N_TRACES)),
    )
    t_start = time.perf_counter()
    for name, run in phases:
        c0, h0, m0 = meter.snapshot()
        t0 = time.perf_counter()
        info = run()
        c1, h1, m1 = meter.snapshot()
        print(f"[{name}] wall_s={time.perf_counter() - t0:.1f} "
              f"compile_s={c1 - c0:.1f} cache_hits={h1 - h0} "
              f"cache_misses={m1 - m0} {json.dumps(info)}", flush=True)
    c, h, m = meter.snapshot()
    print(f"[total] wall_s={time.perf_counter() - t_start:.1f} "
          f"compile_s={c:.1f} cache_hits={h} cache_misses={m}", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
