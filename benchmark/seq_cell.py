"""What a cell of the ``fleet-seq-open`` family does whatever its model:
set-up to a full table, the timed window, the read-out of the program's
answers, the reference's runs and the counts and notes that follow from
them.  A driver of the family adds its own policy replay, the checks of
its pre-window and of its model's forms, and its notes
(``fleet_seq_swa_open`` is written so; ``fleet_seq_hybrid_open`` and
``fleet_seq_open`` hold the same lines inline and are the accepted
benchmark's, not this file's to edit).

``fleet-open``'s and ``fleet-seq-open``'s helpers are used as they are,
through :func:`harness.module_for`.
"""

from __future__ import annotations

import json
import os
import time
import types

import numpy as np

from benchmark import harness, traffic

GAP_NOTES = ("surprisal_gap_mean", "surprisal_gap_group_max",
             "surprisal_gap_p50", "surprisal_gap_p99", "logit_gap",
             "logit_gap_max")


def serve(cell: dict, seed: int, seconds: float, traced: bool,
          t_start: float, meter: harness.CompileMeter, trace_dir: str,
          gauges: tuple, held) -> types.SimpleNamespace:
    """Set-up (schedule, engine, warm-up, the pre-window at ``pre_merge``
    intervals a tick) and the timed window.  ``gauges``: the plane's
    counters that hold a level; ``held(table)``: the progress line's
    words about the table.  Returns the run so far: the engine still
    holds its state."""
    from anomod.serve import seqplane
    import jax
    base = harness.module_for("drivers", "fleet-open")
    seq = harness.module_for("drivers", "fleet-seq-open")
    cfg, wl = cell["config"], cell["traffic"]
    fleet, p = cfg["fleet"], wl["params"]
    tick_s = float(fleet["tick_s"])
    if traced:
        seconds = min(seconds, float(wl["trace_seconds"]))
    merge = int(p["pre_merge"])
    n_pre = int(round(float(p["pre_window_s"]) / tick_s / merge)) * merge
    n_win = int(np.ceil(seconds / tick_s))
    w = types.SimpleNamespace(base=base, seq=seq, cfg=cfg, wl=wl, seed=seed,
                              meter=meter, t_start=t_start, phases={},
                              t_phase=time.perf_counter(),
                              trace_dir=trace_dir)
    sched = traffic.fleet_schedule(p, fleet, seed, n_pre + n_win)
    services = tuple(f"svc{i:02d}" for i in range(int(fleet["n_services"])))
    arrivals = base.Arrivals(sched, services)
    del sched
    phase(w, "schedule_s")
    w.tenants = seq.sample_tenants(seed, arrivals, n_pre,
                                   int(wl["sample_tenants"]),
                                   int(wl["sample_busiest"]))
    w.tracer = tracer = harness.SpanTracer()
    engine, _ = seq.build_engine(cfg, p, tracer, dict(
        cfg, weights_seed=seed, audit_tenants=w.tenants))
    plane = engine._seq
    phase(w, "engine_s")
    base.warm(engine)
    plane.warm()
    phase(w, "warm_s")
    served_log = []
    for k in range(0, n_pre, merge):
        with jax.profiler.TraceAnnotation("bench.tick"):
            served_log.append(engine.tick(
                [a for j in range(k, k + merge) for a in arrivals.ticks[j]]))
            base._drain_device(engine)
        if (k // merge) % 10 == 9:
            harness.progress(f"pre-window tick {k // merge + 1} of "
                             f"{n_pre // merge}, {held(plane.table)}",
                             t_start)
    w.n_pre_ticks = len(served_log)
    phase(w, "pre_window_s")
    w.pre_backlog = int(engine.admission.backlog_spans)

    names = base.COUNTERS + tuple(seqplane.COUNTERS)

    def counters():
        return dict({c: float(getattr(engine.runner, c))
                     for c in base.COUNTERS},
                    **{c: float(v) for c, v in plane.counters.items()})

    compiles0, w.counters0 = meter.compiles, counters()
    shed0 = engine.admission.totals().shed_spans
    shed_batches0 = engine.admission.totals().shed_batches
    rows = []                      # (tick, due, start, returned)
    w.t0 = t0 = time.perf_counter()
    w.setup_s = t0 - t_start
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
            base.feed(engine, arrivals, n_pre + j, served_log)
            rows.append((j, due, start, time.perf_counter()))
    if not rows:
        raise ValueError(f"--seconds {seconds} holds no {tick_s} s tick")
    w.elapsed = max(seconds, rows[-1][3] - t0)
    w.compiles_in_window = meter.compiles - compiles0  # the reference's follow
    counters1 = counters()
    w.totals = totals = engine.admission.totals()
    w.peak = harness.memory_peak_bytes()
    w.delta = {c: counters1[c] - w.counters0[c] for c in names}
    for gauge in gauges:
        w.delta[gauge] = counters1[gauge]
    w.t_phase = time.perf_counter()

    n_due = sum(1 for j in range(n_win) if (j + 1) * tick_s < seconds)
    w.due_batches = sum(len(arrivals.ticks[n_pre + j]) for j in range(n_due))
    w.attempted = sum(len(arrivals.ticks[n_pre + j]) for j, *_ in rows)
    window_log = served_log[w.n_pre_ticks:]
    w.served_spans = sum(qb.n_spans for served in window_log
                         for qb in served)
    w.served_batches = sum(len(served) for served in window_log)
    w.tenants_a_tick = [len({qb.tenant_id for qb in served if qb.n_spans})
                        for served in window_log]
    w.late = np.asarray([start - due for _, due, start, _ in rows]) * 1e3
    w.walls = np.asarray([ret - start for _, _, start, ret in rows])
    w.ticks = len(rows)
    w.shed_spans = totals.shed_spans - shed0
    w.failed = int(totals.shed_batches - shed_batches0)
    w.engine, w.plane, w.arrivals, w.served_log = (engine, plane, arrivals,
                                                   served_log)
    return w


def phase(w, name: str) -> None:
    now = time.perf_counter()
    w.phases[name] = now - w.t_phase
    w.t_phase = now
    harness.progress(f"{name} {w.phases[name]:.1f}", w.t_start)


def compare(w, ref, replay_policy, control: bool, extra=None) -> None:
    """The program's answers are read and its state is freed, the pools
    first (of the weights it served with only the digests stay); the
    session policy is replayed by ``replay_policy(served_log, cfg,
    n_pre_ticks)`` -> ``(per tick segments, policy, at the window's
    start)``; every session of the sampled tenants that the window
    touched is run whole through ``ref``, the control over a draw of
    them.  ``extra(w, runner, keys)`` may add readings to ``w.notes``
    over the control's sessions.  Sets on ``w`` what the checks and
    notes read."""
    base, seq, cfg, wl, meter = w.base, w.seq, w.cfg, w.wl, w.meter
    plane, tenants, seed = w.plane, w.tenants, w.seed
    got_sessions, got_rows, got_segments = seq.program_sessions(plane)
    n_hist = int(cfg["fleet"]["n_hist_buckets"])
    spans = base.served_spans_of(w.served_log, tenants)
    w.not_as_sent = base.served_not_as_sent(
        spans, base.sent_spans_of(w.arrivals, tenants))
    plane.state = {}
    served_with = ref.digests(plane.params)
    w.engine.close()
    del w.engine, w.arrivals

    segments, w.policy, w.at_start = replay_policy(w.served_log, cfg,
                                                   w.n_pre_ticks)
    want_segments = [s for tick in segments for s in tick
                     if s[0] in set(tenants)]
    w.bounds_differing = len(set(want_segments) ^ set(got_segments))
    starts_of = {}
    for t, number, start, _ in want_segments:
        starts_of.setdefault((t, number), []).append(start)
    sessions = seq.sessions_of(w.served_log, segments, tenants,
                               w.n_pre_ticks, n_hist)
    del w.served_log
    w.touched = touched = {k: tok for k, (tok, hit) in sessions.items()
                           if hit}
    w.tokens_differing = sum(
        k not in got_sessions
        or not np.array_equal(got_sessions[k][0], tok)
        for k, tok in touched.items())
    phase(w, "replay_s")

    lengths = tuple(int(n) for n in wl["reference_lengths"])
    compile_s0 = meter.seconds
    flat = dict(cfg, **{k: v for k, v in cfg["assumed"].items()
                        if not isinstance(v, (dict, list, str))})
    params = ref.draw_params(flat, seed)
    own = ref.digests(params)
    w.weights_differing = sum(served_with.get(k) != own.get(k)
                              for k in set(served_with) | set(own))
    phase(w, "reference_weights_s")
    runner = ref.SessionRunner(flat, params, lengths)
    w.reference, w.program = reference, program = {}, {}
    for key, tok in sorted(touched.items()):
        rows_at = sorted(got_rows.get(key, {}))[-runner.max_rows:]
        s, logits = runner.run(tok, rows_at)
        reference[key] = (s, dict(zip(rows_at, logits)))
        if key in got_sessions:
            program[key] = (got_sessions[key][1], got_rows.get(key, {}))
    phase(w, "reference_s")
    w.ctl, w.least = ctl, least = {}, int(wl["own_mean_least_spans"])
    for key in seq.control_sessions(touched, seed, int(wl["sample_busiest"]),
                                    int(wl["control_tokens"]), least):
        rows_at = sorted(reference[key][1])
        s, logits = runner.run(touched[key], rows_at, control=True,
                               bounds=starts_of.get(key, ()))
        ctl[key] = (s, dict(zip(rows_at, logits)))
    phase(w, "control_s")
    w.gaps = lambda got: ref.compare(
        got, {k: reference[k] for k in got}, least)
    w.ctl_numbers = w.gaps(ctl)
    w.same_numbers = w.gaps({k: program[k] for k in ctl if k in program})
    w.missing = [k for k in reference if k not in program]
    w.numbers = w.ctl_numbers if control else w.gaps(program)
    w.notes = {}
    if extra is not None:
        extra(w, runner, sorted(ctl))
    w.reference_compile_s = meter.seconds - compile_s0


def common_checks(w) -> list:
    """The exact counts every cell of the family holds to 0."""
    return [
        harness.Check("compiles_in_window", w.compiles_in_window, 0),
        harness.Check("shed_spans", w.shed_spans, 0),
        harness.Check("served_not_as_sent", w.not_as_sent, 0),
        harness.Check("spans_scored_minus_served",
                      abs(w.delta["seq_tokens"] - w.served_spans), 0),
        harness.Check("session_bounds_differing", w.bounds_differing, 0),
        harness.Check("tokens_differing", w.tokens_differing, 0),
        harness.Check("sessions_not_compared", len(w.missing), 0),
        harness.Check("weights_differing", w.weights_differing, 0)]


def gap_checks(w, names) -> list:
    limits = w.wl["limits"]
    return [harness.Check(n, w.numbers[n], limits[n]) for n in names]


def result(w, checks: list, tick_unnamed: str, notes: dict) -> dict:
    """The driver's return value: the family's notes with ``notes`` of
    the cell's own.  ``tick_unnamed``: the metric whose file lists the
    leaf spans of ``serve.tick`` in this cell; an untraced run prices the
    same spans in ``notes``."""
    ticks, tracer, t0, walls = w.ticks, w.tracer, w.t0, w.walls
    spans_ctx = {"ticks": ticks, "tracer": tracer, "window_t0": t0}
    with open(os.path.join(os.path.dirname(__file__), "metrics",
                           tick_unnamed + ".json")) as f:
        unnamed = json.load(f)
    unnamed_ms = harness.module_for("readers", unnamed["reader"]).read(
        spans_ctx, **unnamed["args"]) or 0.0
    span_ms = {name[6:] + "_ms": 1e3 * tracer.seconds((name,), t0) / ticks
               for name in unnamed["args"]["less"]}
    numbers, ctl_numbers, totals = w.numbers, w.ctl_numbers, w.totals
    return {
        "attempted": w.attempted, "failed": w.failed,
        "setup_s": w.setup_s, "memory_peak_bytes": w.peak, "checks": checks,
        "end_to_end": {"served_spans_per_s": w.served_spans / w.elapsed},
        "notes": dict(
            w.phases, ticks=ticks, window_s=w.elapsed,
            served_spans=w.served_spans, served_batches=w.served_batches,
            due_batches=w.due_batches,
            waiting_batches=max(w.due_batches - w.served_batches, 0),
            tick_wall_p50_ms=float(np.median(walls)) * 1e3,
            tick_wall_max_ms=float(walls.max()) * 1e3,
            tick_late_p50_ms=float(np.median(w.late)),
            tick_late_max_ms=float(w.late.max()),
            tick_unnamed_ms=unnamed_ms,
            tick_unnamed_pct=100.0 * unnamed_ms * ticks / 1e3
            / float(walls.sum()),
            span_ms_per_tick=span_ms,
            pre_window_backlog_spans=w.pre_backlog,
            pre_window_ticks=w.n_pre_ticks,
            tenants_a_tick_mean=float(np.mean(w.tenants_a_tick)),
            tenants_a_tick_max=int(max(w.tenants_a_tick)),
            seq_tokens=w.delta["seq_tokens"],
            seq_pad_tokens=w.delta["seq_pad_tokens"],
            seq_steps=w.delta["seq_steps"],
            tenants_compared=len(w.tenants),
            sessions_compared=len(w.program),
            spans_compared=numbers["spans_compared"],
            rows_compared=numbers["rows_compared"],
            surprisal_gap_p50=numbers["surprisal_gap_p50"],
            surprisal_gap_p99=numbers["surprisal_gap_p99"],
            surprisal_gap_max=numbers["surprisal_gap_max"],
            reference_compile_s=w.reference_compile_s,
            logit_gap_max=numbers["logit_gap_max"],
            groups_with_a_mean=numbers["groups_with_a_mean"],
            control_sessions=len(w.ctl),
            control_spans=ctl_numbers["spans_compared"],
            control_rows=ctl_numbers["rows_compared"],
            control_longest_session=max(
                map(len, (w.touched[k] for k in w.ctl)), default=0),
            **{f"{who}_{name}": n[name]
               for who, n in (("control", ctl_numbers),
                              ("same_sessions", w.same_numbers))
               for name in GAP_NOTES},
            backlog_spans=int(totals.offered_spans - totals.served_spans
                              - totals.shed_spans),
            **w.notes, **notes),
        "ticks": ticks, "tick_wall_s": float(walls.sum()),
        "counters": w.delta, "tracer": tracer, "window_t0": t0,
        "trace_dir": w.trace_dir,
    }
