"""Driver ``fleet-seq-hybrid-open``: ``fleet-seq-open``'s cell for a model
whose sessions hold a state slot beside their blocks (the hybrid
state-space, attention and latent-expert decoder): a tenant fleet served
in real time, open loop at a rate fixed in the cell, every served span
scored by the sequence model in the serve tick
(``ServeEngine(seq_model=)``).

From the program: what ``fleet-seq-open`` takes (whose engine builder,
tenant sample, control draw, served-log and audit helpers are used as
they are, as it uses ``fleet-open``'s), and the plane's slot counters.

Set-up feeds a pre-window of the same per-tenant rates, ``pre_merge``
virtual intervals to an engine tick; two checks hold it to what it is
for: at the window's start at least 90% of the STATE SLOTS are held by
sessions and each of the ten busiest tenants has ended a session.

``correct``, after the window: the program's state is freed (the pools
first), the session-and-slot policy is replayed from the served log by
the reference's own code, and for the sampled tenants every session that
the window touched is run WHOLE through the float32 reference (the
recurrence token by token), layer by layer on the device; compared are
every span's surprisal (the mean over all of them, and the worst
session's or tenant's own mean), the kept logits rows, the session
boundaries and the token ids; both scan forms have to have run.  The
control (the same reference run in the served log's segments with the
carried state, convolution tail, keys and values rounded to float8 where
a cache would hold them) runs over sessions of the window's own lengths
and stands in the program's place under ``--control 1``.
"""

from __future__ import annotations

# a program without the model fails here, at once
from anomod.models import hybrid_ssm_moe  # noqa: F401

import json
import os
import time

import numpy as np

from benchmark import harness, traffic
from benchmark.reference import hybrid_ssm_moe_decoder as ref

SLOTS_HELD_PCT = 90.0
BUSIEST_ROLLED = 10
#: the metric whose file lists the leaf spans of ``serve.tick`` in this
#: cell; an untraced run prices the same spans in ``notes``
TICK_UNNAMED = "tick_unnamed_ms.n3s"
#: counters that hold the table's present count, not a sum over steps
GAUGES = ("sessions_rolled", "sessions_evicted", "pool_blocks_held",
          "state_slots_held")


def replay_policy(served_log: list, cfg: dict, n_pre_ticks: int):
    """The session-and-slot policy replayed from the served log.  Returns
    ``(per tick segments, policy, (slots held, blocks held, sessions begun
    per tenant) at the window's start)``."""
    a = cfg["assumed"]
    policy = ref.SessionPolicy(
        int(a["pool_tokens"]) // int(a["block_tokens"]) - 1,
        int(a["context_tokens"]), int(a["block_tokens"]),
        int(a["state_slots"]) - 1)
    ticks, at_start = [], None
    for k, served in enumerate(served_log):
        if k == n_pre_ticks:
            at_start = (policy.slots_held, policy.blocks_held,
                        dict(policy.begun))
        counts = {}
        for qb in served:
            counts[qb.tenant_id] = counts.get(qb.tenant_id, 0) + qb.n_spans
        chunks = [(t, n) for t, n in counts.items() if n]
        ticks.append(policy.tick(chunks) if chunks else [])
    return ticks, policy, at_start


def run(cell: dict, seed: int, seconds: float, traced: bool, t_start: float,
        meter: harness.CompileMeter, trace_dir: str,
        control: bool = False) -> dict:
    from anomod.serve import seqplane
    base = harness.module_for("drivers", "fleet-open")
    seq = harness.module_for("drivers", "fleet-seq-open")
    cfg, wl = cell["config"], cell["traffic"]
    fleet, p, limits = cfg["fleet"], wl["params"], wl["limits"]
    tick_s = float(fleet["tick_s"])
    if traced:
        seconds = min(seconds, float(wl["trace_seconds"]))
    merge = int(p["pre_merge"])
    n_pre = int(round(float(p["pre_window_s"]) / tick_s / merge)) * merge
    n_win = int(np.ceil(seconds / tick_s))
    t_phase = time.perf_counter()
    phases = {}

    def phase(name):
        nonlocal t_phase
        now = time.perf_counter()
        phases[name] = now - t_phase
        t_phase = now
        harness.progress(f"{name} {phases[name]:.1f}", t_start)

    sched = traffic.fleet_schedule(p, fleet, seed, n_pre + n_win)
    services = tuple(f"svc{i:02d}" for i in range(int(fleet["n_services"])))
    arrivals = base.Arrivals(sched, services)
    del sched
    phase("schedule_s")
    tenants = seq.sample_tenants(seed, arrivals, n_pre,
                                 int(wl["sample_tenants"]),
                                 int(wl["sample_busiest"]))
    tracer = harness.SpanTracer()
    engine, _ = seq.build_engine(cfg, p, tracer, dict(
        cfg, weights_seed=seed, audit_tenants=tenants))
    plane = engine._seq
    phase("engine_s")
    base.warm(engine)
    plane.warm()
    phase("warm_s")
    served_log = []
    import jax
    for k in range(0, n_pre, merge):
        with jax.profiler.TraceAnnotation("bench.tick"):
            served_log.append(engine.tick(
                [a for j in range(k, k + merge) for a in arrivals.ticks[j]]))
            base._drain_device(engine)
        if (k // merge) % 10 == 9:
            harness.progress(f"pre-window tick {k // merge + 1} of "
                             f"{n_pre // merge}, slots held "
                             f"{plane.table.slots_held}, blocks held "
                             f"{plane.table.blocks_held}", t_start)
    n_pre_ticks = len(served_log)
    phase("pre_window_s")
    pre_backlog = int(engine.admission.backlog_spans)

    names = base.COUNTERS + tuple(seqplane.COUNTERS)

    def counters():
        return dict({c: float(getattr(engine.runner, c))
                     for c in base.COUNTERS},
                    **{c: float(v) for c, v in plane.counters.items()})

    compiles0, counters0 = meter.compiles, counters()
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
            base.feed(engine, arrivals, n_pre + j, served_log)
            rows.append((j, due, start, time.perf_counter()))
    if not rows:
        raise ValueError(f"--seconds {seconds} holds no {tick_s} s tick")
    elapsed = max(seconds, rows[-1][3] - t0)
    compiles_in_window = meter.compiles - compiles0   # the reference's follow
    counters1 = counters()
    totals = engine.admission.totals()
    peak = harness.memory_peak_bytes()
    delta = {c: counters1[c] - counters0[c] for c in names}
    for gauge in GAUGES:
        delta[gauge] = counters1[gauge]
    t_phase = time.perf_counter()

    n_due = sum(1 for j in range(n_win) if (j + 1) * tick_s < seconds)
    due_batches = sum(len(arrivals.ticks[n_pre + j]) for j in range(n_due))
    attempted = sum(len(arrivals.ticks[n_pre + j]) for j, *_ in rows)
    window_log = served_log[n_pre_ticks:]
    served_spans = sum(qb.n_spans for served in window_log for qb in served)
    served_batches = sum(len(served) for served in window_log)
    tenants_a_tick = [len({qb.tenant_id for qb in served if qb.n_spans})
                      for served in window_log]
    late = np.asarray([start - due for _, due, start, _ in rows]) * 1e3
    walls = np.asarray([ret - start for _, _, start, ret in rows])

    # the program's answers are read and its state is freed, the pools
    # first; of the weights it served with only the digests stay
    got_sessions, got_rows, got_segments = seq.program_sessions(plane)
    n_hist = int(fleet["n_hist_buckets"])
    slots_total, blocks_total = plane.table.usable_slots, plane.table.usable
    spans = base.served_spans_of(served_log, tenants)
    not_as_sent = base.served_not_as_sent(
        spans, base.sent_spans_of(arrivals, tenants))
    plane.state = {}
    served_with = ref.digests(plane.params)
    engine.close()
    del engine, arrivals

    segments, policy, at_start = replay_policy(served_log, cfg, n_pre_ticks)
    slots0, blocks0, begun0 = at_start
    want_segments = [s for tick in segments for s in tick
                     if s[0] in set(tenants)]
    bounds_differing = len(set(want_segments) ^ set(got_segments))
    starts_of = {}
    for t, number, start, _ in want_segments:
        starts_of.setdefault((t, number), []).append(start)
    sessions = seq.sessions_of(served_log, segments, tenants, n_pre_ticks,
                               n_hist)
    del served_log
    touched = {k: tok for k, (tok, hit) in sessions.items() if hit}
    tokens_differing = sum(
        k not in got_sessions
        or not np.array_equal(got_sessions[k][0], tok)
        for k, tok in touched.items())
    phase("replay_s")

    lengths = tuple(int(n) for n in wl["reference_lengths"])
    compile_s0 = meter.seconds
    flat = dict(cfg, **{k: v for k, v in cfg["assumed"].items()
                        if not isinstance(v, (dict, list, str))})
    params = ref.draw_params(flat, seed)
    own = ref.digests(params)
    weights_differing = sum(served_with.get(k) != own.get(k)
                            for k in set(served_with) | set(own))
    phase("reference_weights_s")
    runner = ref.SessionRunner(flat, params, lengths)
    reference, program = {}, {}
    for key, tok in sorted(touched.items()):
        rows_at = sorted(got_rows.get(key, {}))[-runner.max_rows:]
        s, logits = runner.run(tok, rows_at)
        reference[key] = (s, dict(zip(rows_at, logits)))
        if key in got_sessions:
            program[key] = (got_sessions[key][1], got_rows.get(key, {}))
    phase("reference_s")
    ctl, least = {}, int(wl["own_mean_least_spans"])
    for key in seq.control_sessions(touched, seed, int(wl["sample_busiest"]),
                                    int(wl["control_tokens"]), least):
        rows_at = sorted(reference[key][1])
        s, logits = runner.run(touched[key], rows_at, control=True,
                               bounds=starts_of.get(key, ()))
        ctl[key] = (s, dict(zip(rows_at, logits)))
    phase("control_s")
    compare = lambda got: ref.compare(
        got, {k: reference[k] for k in got}, least)
    ctl_numbers = compare(ctl)
    same_numbers = compare({k: program[k] for k in ctl if k in program})
    missing = [k for k in reference if k not in program]
    numbers = ctl_numbers if control else compare(program)
    recurrent_pct = 100.0 * delta["ssm_recurrent_tokens"] \
        / max(delta["seq_tokens"], 1)

    checks = [
        harness.Check("compiles_in_window", compiles_in_window, 0),
        harness.Check("shed_spans", totals.shed_spans - shed0, 0),
        harness.Check("served_not_as_sent", not_as_sent, 0),
        harness.Check("spans_scored_minus_served",
                      abs(delta["seq_tokens"] - served_spans), 0),
        harness.Check("session_bounds_differing", bounds_differing, 0),
        harness.Check("tokens_differing", tokens_differing, 0),
        harness.Check("sessions_not_compared", len(missing), 0),
        harness.Check("weights_differing", weights_differing, 0),
        harness.Check("slots_unheld_pct",
                      100.0 - 100.0 * slots0 / slots_total,
                      100.0 - SLOTS_HELD_PCT),
        harness.Check("busiest_unrolled", sum(
            begun0.get(t, 0) < 2 for t in range(BUSIEST_ROLLED)), 0),
        harness.Check("forms_unreached", int(
            not (delta["ssm_recurrent_tokens"] > 0
                 and delta["ssm_scan_blocks"] > 0)), 0),
        harness.Check("surprisal_gap_mean", numbers["surprisal_gap_mean"],
                      limits["surprisal_gap_mean"]),
        harness.Check("surprisal_gap_group_max",
                      numbers["surprisal_gap_group_max"],
                      limits["surprisal_gap_group_max"]),
        harness.Check("logit_gap", numbers["logit_gap"],
                      limits["logit_gap"]),
    ]
    ticks = len(rows)
    spans_ctx = {"ticks": ticks, "tracer": tracer, "window_t0": t0}
    with open(os.path.join(os.path.dirname(os.path.dirname(__file__)),
                           "metrics", TICK_UNNAMED + ".json")) as f:
        unnamed = json.load(f)
    unnamed_ms = harness.module_for("readers", unnamed["reader"]).read(
        spans_ctx, **unnamed["args"]) or 0.0
    span_ms = {name[6:] + "_ms": 1e3 * tracer.seconds((name,), t0) / ticks
               for name in unnamed["args"]["less"]}
    return {
        "attempted": attempted,
        "failed": int(totals.shed_batches - shed_batches0),
        "setup_s": setup_s, "memory_peak_bytes": peak, "checks": checks,
        "end_to_end": {"served_spans_per_s": served_spans / elapsed},
        "notes": dict(
            phases, ticks=ticks, window_s=elapsed, served_spans=served_spans,
            served_batches=served_batches, due_batches=due_batches,
            waiting_batches=max(due_batches - served_batches, 0),
            tick_wall_p50_ms=float(np.median(walls)) * 1e3,
            tick_wall_max_ms=float(walls.max()) * 1e3,
            tick_late_p50_ms=float(np.median(late)),
            tick_late_max_ms=float(late.max()),
            tick_unnamed_ms=unnamed_ms,
            tick_unnamed_pct=100.0 * unnamed_ms * ticks / 1e3
            / float(walls.sum()),
            span_ms_per_tick=span_ms,
            pre_window_backlog_spans=pre_backlog,
            pre_window_ticks=n_pre_ticks,
            state_slots=slots_total, state_slots_held_at_start=slots0,
            state_slots_held_at_end=policy.slots_held,
            pool_blocks=blocks_total, pool_blocks_held_at_start=blocks0,
            pool_blocks_held_at_end=policy.blocks_held,
            sessions_rolled=policy.rolled, sessions_evicted=policy.evicted,
            sessions_evicted_by_slots=policy.evicted_by_slots,
            steps_split_by_slots=policy.steps_split,
            window_evictions_by_slots=delta["sessions_evicted_by_slots"],
            window_steps_split_by_slots=delta["steps_split_by_slots"],
            tenants_a_tick_mean=float(np.mean(tenants_a_tick)),
            tenants_a_tick_max=int(max(tenants_a_tick)),
            ssm_recurrent_token_share=recurrent_pct,
            ssm_scan_blocks=delta["ssm_scan_blocks"],
            ssm_state_rows=delta["ssm_state_rows"],
            seq_tokens=delta["seq_tokens"], gqa_pairs=delta["gqa_pairs"],
            seq_pad_tokens=delta["seq_pad_tokens"],
            seq_steps=delta["seq_steps"],
            tenants_compared=len(tenants),
            sessions_compared=len(program),
            spans_compared=numbers["spans_compared"],
            rows_compared=numbers["rows_compared"],
            surprisal_gap_p50=numbers["surprisal_gap_p50"],
            surprisal_gap_p99=numbers["surprisal_gap_p99"],
            surprisal_gap_max=numbers["surprisal_gap_max"],
            reference_compile_s=meter.seconds - compile_s0,
            logit_gap_max=numbers["logit_gap_max"],
            groups_with_a_mean=numbers["groups_with_a_mean"],
            control_sessions=len(ctl),
            control_spans=ctl_numbers["spans_compared"],
            control_rows=ctl_numbers["rows_compared"],
            control_longest_session=max(map(len, (touched[k] for k in ctl)),
                                        default=0),
            **{f"{who}_{name}": n[name]
               for who, n in (("control", ctl_numbers),
                              ("same_sessions", same_numbers))
               for name in ("surprisal_gap_mean", "surprisal_gap_group_max",
                            "surprisal_gap_p50", "surprisal_gap_p99",
                            "logit_gap", "logit_gap_max")},
            backlog_spans=int(totals.offered_spans - totals.served_spans
                              - totals.shed_spans)),
        "ticks": ticks, "tick_wall_s": float(walls.sum()),
        "counters": delta, "tracer": tracer, "window_t0": t0,
        "trace_dir": trace_dir,
    }
