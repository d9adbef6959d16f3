"""Driver ``fleet-open``: a tenant fleet served in real time, open loop at
a rate fixed in the cell.

From the program: ``ServeEngine`` (its constructor and ``tick``), the
``TenantSpec`` / ``SpanBatch`` records it is fed, the spans it opens on
the tracer it is handed, and its runner's wall and dispatch counters.
Everything it is fed comes from ``benchmark.traffic.fleet_schedule``,
pre-generated in set-up from the seed.

Set-up feeds a pre-window (``pre_window_s`` virtual seconds, as fast as
the engine takes it) so that detectors are past their baseline; it holds
a roll call, one span of every tenant of the fleet, so that every row of
the pool is state the traffic has written, and a tenant's
once-in-a-lifetime pool slot and calibration are paid before the window.
Tick ``k`` of the window carries
the arrivals of virtual interval ``k`` whatever the wall does and may
not start before ``t0 + (k+1) * tick_s``: inputs, and so outputs, do not
depend on speed.  A batch's lag is the wall time the tick that served it
returned (device work drained) minus the end of the interval it arrived
in.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import harness, traffic
from benchmark.reference import fleet_score

COUNTERS = ("stage_wall_s", "dispatch_wall_s", "fold_wall_s", "score_wall_s",
            "fused_dispatches")
#: declared capacity over the cell's offered rate: admission must never cap
#: below the wall, and a tick's Poisson excursion on top of the roll call's
#: share must not queue (at 1.1 the pre-window left a backlog that was shed
#: inside the window)
DECLARED_OVER_OFFERED = 2.0


def build_engine(cfg: dict, p: dict, tracer):
    from anomod.replay import ReplayConfig
    from anomod.serve.engine import ServeEngine
    from anomod.serve.queues import TenantSpec

    rates = traffic.fleet_rates(p, int(cfg["n_tenants"]))
    specs = [TenantSpec(tenant_id=t, name=f"tenant{t:05d}",
                        priority=t % int(cfg["n_priorities"]),
                        rate_spans_per_s=float(r))
             for t, r in enumerate(rates)]
    services = tuple(f"svc{i:02d}" for i in range(int(cfg["n_services"])))
    rcfg = ReplayConfig(n_services=cfg["n_services"],
                        n_windows=cfg["n_windows"],
                        n_hist_buckets=cfg["n_hist_buckets"],
                        chunk_size=cfg["chunk_size"],
                        window_us=cfg["window_us"])
    declared = float(p["offered_spans_per_s"]) * DECLARED_OVER_OFFERED
    engine = ServeEngine(
        specs, services, rcfg, capacity_spans_per_s=declared,
        tick_s=float(cfg["tick_s"]),
        max_backlog=int(float(cfg["max_backlog_s"]) * declared),
        baseline_windows=int(cfg["baseline_windows"]),
        z_threshold=float(cfg["z_threshold"]),
        min_count=float(cfg["min_count"]), tracer=tracer,
        shards=int(cfg["shards"]), state=cfg["state"],
        ckpt_every=int(cfg["ckpt_every"]),
        flight_digest_every=int(cfg["flight_digest_every"]))
    return engine, services


def warm(engine) -> None:
    """What ``ServeEngine.run`` does before its first tick."""
    engine.runner.warm()
    if engine.fuse:
        engine.runner.warm_lanes()


class Arrivals:
    """The schedule as the ``(tenant_id, SpanBatch)`` lists ``tick`` takes:
    slices of flat columns, built once in set-up."""

    def __init__(self, sched: dict, services: tuple):
        from anomod.schemas import SpanBatch
        n = len(sched["service"])
        err = sched["is_error"]
        cols = (sched["trace"], np.full(n, -1, np.int32), sched["service"],
                np.zeros(n, np.int32), sched["start_us"],
                sched["duration_us"], err,
                np.where(err, 500, 200).astype(np.int16),
                np.zeros(n, np.int8))
        tail = (services, ("ep",), tuple(f"t{i:02d}" for i in range(64)))
        ticks = sched["batch_tick"]
        self.n_ticks = int(sched["n_ticks"])
        edges = np.searchsorted(ticks, np.arange(self.n_ticks + 1))
        tenants = sched["batch_tenant"].tolist()
        los, his = sched["batch_lo"].tolist(), sched["batch_hi"].tolist()
        self.ticks = []
        for k in range(self.n_ticks):
            self.ticks.append([
                (tenants[b], SpanBatch(*[c[los[b]:his[b]] for c in cols],
                                       *tail))
                for b in range(edges[k], edges[k + 1])])


def _drain_device(engine) -> None:
    """Wait for the tick's last pool folds: ``tick`` returns once its
    results are read, and a scatter it issued may still be running."""
    pool = engine.runner.pool
    for plane in (() if pool is None else (pool.agg, pool.hist)):
        if hasattr(plane, "block_until_ready"):
            plane.block_until_ready()


def _counters(engine) -> dict:
    return {c: float(getattr(engine.runner, c)) for c in COUNTERS}


def feed(engine, arrivals: Arrivals, k: int, served_log: list):
    import jax
    with jax.profiler.TraceAnnotation("bench.tick"):
        served = engine.tick(arrivals.ticks[k])
        _drain_device(engine)
    served_log.append(served)
    return served


def sample_tenants(seed: int, served_log: list, n: int, busiest: int) -> list:
    """The tenants compared, of those the run served: the ``busiest`` first
    ranks and a draw from the seed over the rest."""
    served = sorted({qb.tenant_id for tick in served_log for qb in tick})
    head, rest = served[:busiest], served[busiest:]
    drawn = traffic.rng_for(seed, 4).choice(
        len(rest), size=min(max(n - len(head), 0), len(rest)), replace=False)
    return head + sorted(rest[i] for i in drawn)


def read_program(engine, tenant: int, spans: dict, cfg: dict):
    """A tenant's state and alerts as the program holds them."""
    state = engine._tenant_replay[tenant].get_state()
    return (np.asarray(state.agg), np.asarray(state.hist),
            [(a.window, a.service, a.z_latency, a.z_error, a.z_drop,
              a.z_drop_cum) for a in engine.alerts_for(tenant)])


def read_control(tenant: int, spans: dict, cfg: dict):
    """The control in the program's place: the reference with its latency
    moments summed from bfloat16, and the alerts that state bears out."""
    agg, hist = fleet_score.fold(spans, cfg, "bfloat16")
    return agg, hist, fleet_score.alerts_of(
        agg, fleet_score.last_window(spans, cfg), cfg)


COLUMNS = ("service", "start_us", "duration_us", "is_error", "status")


def _columns(batches: list) -> dict:
    return {k: np.concatenate([getattr(b, k) for b in batches])
            for k in COLUMNS}


def served_spans_of(served_log: list, tenants: list) -> dict:
    """tenant -> the columns of its served spans, in served order."""
    logs = {t: [] for t in tenants}
    for served in served_log:
        for qb in served:
            if qb.tenant_id in logs:
                logs[qb.tenant_id].append(qb.spans)
    return {t: _columns(batches) for t, batches in logs.items() if batches}


def sent_spans_of(arrivals: Arrivals, tenants: list) -> dict:
    """tenant -> the columns of every span the benchmark made for it, in
    the order it hands them over."""
    logs = {t: [] for t in tenants}
    for tick in arrivals.ticks:
        for tenant, batch in tick:
            if tenant in logs:
                logs[tenant].append(batch)
    return {t: _columns(batches) for t, batches in logs.items() if batches}


def served_not_as_sent(served: dict, sent: dict) -> int:
    """Sampled tenants whose served spans are not exactly the first rows
    of what was sent to them: the program's own log is what the reference
    folds, so a batch lost from log and state alike shows here only."""
    bad = 0
    for t, got in served.items():
        n = len(got["service"])
        want = sent.get(t)
        bad += want is None or n > len(want["service"]) or any(
            not np.array_equal(got[k], want[k][:n]) for k in COLUMNS)
    return int(bad)


def windows_unscored(engine, spans: dict, cfg: dict) -> int:
    """Closed windows of the sampled tenants, past the baseline, that the
    program's detector has not scored: a window is closed once a served
    span starts in a later one."""
    B, missing = int(cfg["baseline_windows"]), 0
    for t, cols in spans.items():
        last = fleet_score.last_window(cols, cfg)
        if last > B:
            det = engine._tenant_det.get(t)
            done = -1 if det is None else int(det._scored_through)
            missing += max(last - 1 - done, 0)
    return missing


def tenant_numbers(outputs: dict, spans: dict, cfg: dict) -> dict:
    """The comparison over the sampled tenants (``outputs``: tenant ->
    ``(agg, hist, alerts)``): the worst of each of
    ``fleet_score.compare_tenant``'s numbers, counts summed."""
    out = {"exact_cells_differing": 0, "moment_gap": 0.0, "z_gap": 0.0,
           "alerts_unborne": 0, "alerts_compared": 0, "windows_scored": 0,
           "tenants_compared": 0, "spans_compared": 0}
    for t, (agg, hist, alerts) in outputs.items():
        got = fleet_score.compare_tenant(agg, hist, alerts, spans[t], cfg)
        for k in ("exact_cells_differing", "alerts_unborne",
                  "alerts_compared", "windows_scored"):
            out[k] += got[k]
        for k in ("moment_gap", "z_gap"):
            out[k] = max(out[k], got[k])
        out["tenants_compared"] += 1
        out["spans_compared"] += len(spans[t]["service"])
    return out


def run(cell: dict, seed: int, seconds: float, traced: bool, t_start: float,
        meter: harness.CompileMeter, trace_dir: str,
        control: bool = False) -> dict:
    """``control``: the reference's bfloat16 twin stands in the program's
    place in the comparison, which then has to come out not correct."""
    cfg, wl = cell["config"], cell["traffic"]
    p, limits = wl["params"], wl["limits"]
    tick_s = float(cfg["tick_s"])
    if traced:
        seconds = min(seconds, float(wl["trace_seconds"]))
    n_pre = int(round(float(p["pre_window_s"]) / tick_s))
    n_win = int(np.ceil(seconds / tick_s))
    t_phase = time.perf_counter()
    phases = {}

    def phase(name):
        nonlocal t_phase
        now = time.perf_counter()
        phases[name] = now - t_phase
        t_phase = now
        harness.progress(f"{name} {phases[name]:.1f}", t_start)

    sched = traffic.fleet_schedule(p, cfg, seed, n_pre + n_win)
    phase("schedule_s")
    tracer = harness.SpanTracer()
    engine, services = build_engine(cfg, p, tracer)
    phase("engine_s")
    warm(engine)
    phase("warm_s")
    arrivals = Arrivals(sched, services)
    del sched
    phase("arrivals_s")
    served_log = []
    for k in range(n_pre):
        feed(engine, arrivals, k, served_log)
        if k % 10 == 9:
            harness.progress(f"pre-window tick {k + 1} of {n_pre}", t_start)
    phase("pre_window_s")
    pre_backlog = int(engine.admission.backlog_spans)

    compiles0, counters0 = meter.compiles, _counters(engine)
    shed0 = engine.admission.totals().shed_spans
    shed_batches0 = engine.admission.totals().shed_batches
    rows = []                      # (tick, due, start, returned)
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    with harness.traced_window(traced, trace_dir):
        for j in range(n_win):
            due = t0 + (j + 1) * tick_s
            if due >= t0 + seconds:
                break
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            start = time.perf_counter()
            if start >= t0 + seconds:
                break              # the window closed on a backlog
            feed(engine, arrivals, n_pre + j, served_log)
            rows.append((j, due, start, time.perf_counter()))
    if not rows:
        raise ValueError(f"--seconds {seconds} holds no {tick_s} s tick")
    elapsed = max(seconds, rows[-1][3] - t0)
    counters1 = _counters(engine)
    totals = engine.admission.totals()
    peak = harness.memory_peak_bytes()

    # what was due in the window, what its ticks handed to the engine,
    # what was served, and how late.  ``attempted`` is what was handed over
    # and ``failed`` what admission shed of it: a batch whose tick had not
    # come up when the window closed waits (above capacity a third of those
    # due do, more in a run the host held up), and is late, not failed
    n_due = sum(1 for j in range(n_win) if (j + 1) * tick_s < seconds)
    due_batches = sum(len(arrivals.ticks[n_pre + j]) for j in range(n_due))
    attempted = sum(len(arrivals.ticks[n_pre + j]) for j, *_ in rows)
    lags, served_spans, served_batches = [], 0, 0
    for (j, due, start, ret), served in zip(rows, served_log[n_pre:]):
        for qb in served:
            arrived = int(round(qb.enqueued_s / tick_s)) - 1 - n_pre
            lags.append(ret - (t0 + (max(arrived, 0) + 1) * tick_s))
        served_spans += sum(qb.n_spans for qb in served)
        served_batches += len(served)
    lags = np.asarray(lags) * 1e3
    late = np.asarray([start - due for _, due, start, _ in rows]) * 1e3
    walls = np.asarray([ret - start for _, _, start, ret in rows])

    # the program's answers are read, its state is freed, and only then
    # does the reference run
    tenants = sample_tenants(seed, served_log, int(wl["sample_tenants"]),
                             int(wl["sample_busiest"]))
    ccfg = dict(cfg, alert_margin=limits["alert_margin"])
    spans = served_spans_of(served_log, tenants)
    not_as_sent = served_not_as_sent(spans, sent_spans_of(arrivals, tenants))
    outputs = {t: read_program(engine, t, spans[t], ccfg) for t in spans}
    unscored = windows_unscored(engine, spans, ccfg)
    n_alerts = sum(len(d.alerts) for d in engine._tenant_det.values())
    live = len(engine._tenant_replay)
    engine.close()
    del engine, arrivals, served_log
    ctl = tenant_numbers({t: read_control(t, spans[t], ccfg) for t in spans},
                         spans, ccfg)
    got = ctl if control else tenant_numbers(outputs, spans, ccfg)

    checks = [
        harness.Check("compiles_in_window", meter.compiles - compiles0, 0),
        harness.Check("shed_spans", totals.shed_spans - shed0, 0),
        harness.Check("served_not_as_sent", not_as_sent, 0),
        harness.Check("windows_unscored", unscored, 0),
        harness.Check("exact_cells_differing",
                      got["exact_cells_differing"], 0),
        harness.Check("moment_gap", got["moment_gap"], limits["moment_gap"]),
        harness.Check("z_gap", got["z_gap"],
                      limits["z_gap"]),
        harness.Check("tenants_not_compared", max(
            min(int(wl["sample_tenants"]), live) - got["tenants_compared"],
            0), 0),
    ]
    ticks = len(rows)
    return {
        "attempted": attempted,
        "failed": int(totals.shed_batches - shed_batches0),
        "setup_s": setup_s, "memory_peak_bytes": peak, "checks": checks,
        "end_to_end": {
            "served_spans_per_s": served_spans / elapsed,
            "scored_lag_p50_ms": float(np.percentile(lags, 50)),
            "scored_lag_p95_ms": float(np.percentile(lags, 95))},
        "notes": dict(
            phases, ticks=ticks, window_s=elapsed, served_spans=served_spans,
            served_batches=served_batches, due_batches=due_batches,
            waiting_batches=max(due_batches - served_batches, 0),
            tick_wall_p50_ms=float(np.median(walls)) * 1e3,
            tick_wall_max_ms=float(walls.max()) * 1e3,
            tick_late_p50_ms=float(np.median(late)),
            tick_late_p95_ms=float(np.percentile(late, 95)),
            tick_late_max_ms=float(late.max()),
            live_tenants=live, alerts=n_alerts,
            pre_window_backlog_spans=pre_backlog,
            alerts_compared=got["alerts_compared"],
            alerts_unborne=got["alerts_unborne"],
            windows_scored=got["windows_scored"],
            spans_compared=got["spans_compared"],
            control_moment_gap=ctl["moment_gap"],
            control_z_gap=ctl["z_gap"],
            control_alerts_unborne=ctl["alerts_unborne"],
            backlog_spans=int(totals.offered_spans - totals.served_spans
                              - totals.shed_spans)),
        "ticks": ticks, "tick_wall_s": float(walls.sum()),
        "counters": {c: counters1[c] - counters0[c] for c in COUNTERS},
        "tracer": tracer, "window_t0": t0,
    }
