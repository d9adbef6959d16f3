"""Driver ``fleet-seq-open``: a tenant fleet served in real time, open loop
at a rate fixed in the cell, every served span scored by the sequence
model in the serve tick (``ServeEngine(seq_model=)``).

From the program: what ``fleet-open`` takes (whose arrival tables, feed
and served-log helpers are used as they are), the sequence-model plane's
counters, and its per-span surprisals and kept logits rows of the audit
tenants.  Its weights are read for a digest only: the reference draws its
own from ``--seed`` and ``weights_differing`` counts the leaves whose
bits are not the same.

Set-up feeds a pre-window of the same per-tenant rates, ``pre_merge``
virtual intervals to an engine tick, as fast as the engine takes it; two
checks hold it to what it is for: at the window's start at least 80% of
the pool's blocks are held by sessions and each of the ten busiest
tenants has ended a session.  The window is ``fleet-open``'s: tick ``k``
carries virtual interval ``k`` and starts no earlier than its slot.

``correct``, after the window: the program's state is freed (the pool
first), the session policy is replayed from the served log by the
reference's own code, and for the sampled tenants every session that the
window touched is run WHOLE through the float32 reference, layer by layer
on the device; compared are every span's surprisal (the mean over all of
them, and the worst session's or tenant's own mean), the kept logits
rows, the session boundaries and the token ids.  The control (the same
reference with its latents rounded to float8 where a cache would hold
them) runs over sessions of the window's own lengths
(:func:`control_sessions`) and stands in the program's place under
``--control 1``; the program's numbers over those same sessions go to
``notes`` beside the control's.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

from benchmark import harness, traffic
from benchmark.reference import latent_moe_decoder as ref

#: declared capacity over the offered rate: a pre-window tick carries
#: ``pre_merge`` intervals and nothing may queue behind admission's budget
DECLARED_OVER_OFFERED = 6.0
BLOCKS_HELD_PCT = 80.0
BUSIEST_ROLLED = 10
#: the metric whose file lists the leaf spans of ``serve.tick`` in this
#: cell; an untraced run prices the same spans in ``notes``
TICK_UNNAMED = "tick_unnamed_ms.k2"


def build_engine(cfg: dict, p: dict, tracer, seq_spec: dict):
    from anomod.replay import ReplayConfig
    from anomod.serve.engine import ServeEngine
    from anomod.serve.queues import TenantSpec

    fleet = cfg["fleet"]
    rates = traffic.fleet_rates(p, int(fleet["n_tenants"]))
    specs = [TenantSpec(tenant_id=t, name=f"tenant{t:05d}",
                        priority=t % int(fleet["n_priorities"]),
                        rate_spans_per_s=float(r))
             for t, r in enumerate(rates)]
    services = tuple(f"svc{i:02d}" for i in range(int(fleet["n_services"])))
    rcfg = ReplayConfig(n_services=fleet["n_services"],
                        n_windows=fleet["n_windows"],
                        n_hist_buckets=fleet["n_hist_buckets"],
                        chunk_size=fleet["chunk_size"],
                        window_us=fleet["window_us"])
    declared = float(p["offered_spans_per_s"]) * DECLARED_OVER_OFFERED
    engine = ServeEngine(
        specs, services, rcfg, capacity_spans_per_s=declared,
        tick_s=float(fleet["tick_s"]),
        max_backlog=int(float(fleet["max_backlog_s"]) * declared),
        baseline_windows=int(fleet["baseline_windows"]),
        z_threshold=float(fleet["z_threshold"]),
        min_count=float(fleet["min_count"]), tracer=tracer,
        shards=int(fleet["shards"]), state=fleet["state"],
        ckpt_every=int(fleet["ckpt_every"]),
        flight_digest_every=int(fleet["flight_digest_every"]),
        seq_model=seq_spec)
    return engine, services


def sample_tenants(seed: int, arrivals, first_tick: int, n: int,
                   busiest: int) -> list:
    """The ``busiest`` first ranks and a seeded draw over the other
    tenants the window sends spans of."""
    sent = sorted({t for tick in arrivals.ticks[first_tick:]
                   for t, _ in tick})
    head = [t for t in sent if t < busiest]
    rest = [t for t in sent if t >= busiest]
    drawn = traffic.rng_for(seed, 4).choice(
        len(rest), size=min(max(n - len(head), 0), len(rest)), replace=False)
    return head + sorted(rest[i] for i in drawn)


def control_sessions(touched: dict, seed: int, busiest: int,
                     budget: int, least: int) -> list:
    """The sessions the control reads, of the window's own lengths: the
    longest session of each of the ``busiest`` tenants first, then a
    seeded order of the others of at least ``least`` spans (those with a
    mean of their own), each taken while ``budget`` tokens last."""
    keys = sorted(touched)
    head = [max((k for k in keys if k[0] == t),
                key=lambda k: len(touched[k]))
            for t in range(busiest) if any(k[0] == t for k in keys)]
    rest = [k for k in keys if k not in head
            and len(touched[k]) >= least]
    out = []
    for k in head + [rest[i] for i in
                     traffic.rng_for(seed, 6).permutation(len(rest))]:
        if len(touched[k]) <= budget:
            out.append(k)
            budget -= len(touched[k])
    return out


def replay_policy(served_log: list, cfg: dict, n_pre_ticks: int):
    """The session policy replayed from the served log.  Returns ``(per
    tick segments, policy, (blocks held, sessions begun per tenant) at the
    window's start)``."""
    policy = ref.SessionPolicy(
        int(cfg["assumed"]["pool_tokens"]) // int(
            cfg["assumed"]["block_tokens"]) - 1,
        int(cfg["assumed"]["context_tokens"]),
        int(cfg["assumed"]["block_tokens"]))
    ticks, at_start = [], None
    for k, served in enumerate(served_log):
        if k == n_pre_ticks:
            at_start = (policy.blocks_held, dict(policy.begun))
        counts = {}
        for qb in served:
            counts[qb.tenant_id] = counts.get(qb.tenant_id, 0) + qb.n_spans
        chunks = [(t, n) for t, n in counts.items() if n]
        ticks.append(policy.step(chunks) if chunks else [])
    return ticks, policy, at_start


def sessions_of(served_log: list, segments: list, tenants: list,
                n_pre_ticks: int, n_hist: int) -> dict:
    """``(tenant, session number) -> (token ids of the whole session,
    touched by the window?)`` for the sampled tenants, token ids by the
    reference's tokeniser over the served spans."""
    want = set(tenants)
    out = {}
    for k, (served, segs) in enumerate(zip(served_log, segments)):
        spans = {}
        for qb in served:
            if qb.tenant_id in want and qb.n_spans:
                spans.setdefault(qb.tenant_id, []).append(qb.spans)
        ids, at = {}, {}
        for t, batches in spans.items():
            cat = lambda key: np.concatenate(
                [getattr(b, key) for b in batches])
            ids[t] = ref.tokenise(cat("service"), cat("duration_us"),
                                  cat("status"), cat("kind"), n_hist)
            at[t] = 0
        for t, number, start, n in segs:
            if t not in want:
                continue
            entry = out.setdefault((t, number), [[], False])
            assert sum(map(len, entry[0])) == start
            entry[0].append(ids[t][at[t]:at[t] + n])
            at[t] += n
            entry[1] = entry[1] or k >= n_pre_ticks
    return {key: (np.concatenate(parts), touched)
            for key, (parts, touched) in out.items()}


def program_sessions(plane) -> tuple:
    """What the timed path produced for the audit tenants: ``(session ->
    (tokens, surprisals), session -> {position: logits row}, segment
    list)``."""
    parts, segs = {}, []
    for t, number, start, tokens, surprisal in plane.audit_segments:
        segs.append((t, number, start, len(tokens)))
        entry = parts.setdefault((t, number), ([], []))
        entry[0].append(tokens)
        entry[1].append(surprisal)
    rows = {}
    for t, number, pos, row in plane.audit_logits:
        rows.setdefault((t, number), {})[pos] = row
    return ({k: (np.concatenate(a), np.concatenate(b))
             for k, (a, b) in parts.items()}, rows, segs)


def run(cell: dict, seed: int, seconds: float, traced: bool, t_start: float,
        meter: harness.CompileMeter, trace_dir: str,
        control: bool = False) -> dict:
    # a program without the plane fails here, at once
    from anomod.serve import seqplane
    base = harness.module_for("drivers", "fleet-open")
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
    tenants = sample_tenants(seed, arrivals, n_pre,
                             int(wl["sample_tenants"]),
                             int(wl["sample_busiest"]))
    tracer = harness.SpanTracer()
    engine, _ = build_engine(cfg, p, tracer, dict(
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
                             f"{n_pre // merge}, blocks held "
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
    for gauge in ("sessions_rolled", "sessions_evicted", "pool_blocks_held"):
        delta[gauge] = counters1[gauge]
    t_phase = time.perf_counter()

    n_due = sum(1 for j in range(n_win) if (j + 1) * tick_s < seconds)
    due_batches = sum(len(arrivals.ticks[n_pre + j]) for j in range(n_due))
    attempted = sum(len(arrivals.ticks[n_pre + j]) for j, *_ in rows)
    window_log = served_log[n_pre_ticks:]
    served_spans = sum(qb.n_spans for served in window_log for qb in served)
    served_batches = sum(len(served) for served in window_log)
    late = np.asarray([start - due for _, due, start, _ in rows]) * 1e3
    walls = np.asarray([ret - start for _, _, start, ret in rows])

    # the program's answers are read and its state is freed, the pool
    # first; of the weights it served with only the digests stay
    got_sessions, got_rows, got_segments = program_sessions(plane)
    n_hist = int(fleet["n_hist_buckets"])
    blocks_total = plane.table.usable
    spans = base.served_spans_of(served_log, tenants)
    not_as_sent = base.served_not_as_sent(
        spans, base.sent_spans_of(arrivals, tenants))
    plane.pool = plane.h_last = None
    served_with = ref.digests(plane.params)
    engine.close()
    del engine, arrivals

    segments, policy, at_start = replay_policy(served_log, cfg, n_pre_ticks)
    held0, begun0 = at_start
    want_segments = [s for tick in segments for s in tick
                     if s[0] in set(tenants)]
    bounds_differing = len(set(want_segments) ^ set(got_segments))
    sessions = sessions_of(served_log, segments, tenants, n_pre_ticks,
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
    params = ref.draw_params(cfg, seed)
    own = ref.digests(params)
    weights_differing = sum(served_with.get(k) != own.get(k)
                            for k in set(served_with) | set(own))
    phase("reference_weights_s")
    runner = ref.SessionRunner(cfg, params, lengths)
    reference, program = {}, {}
    for key, tok in sorted(touched.items()):
        rows_at = sorted(got_rows.get(key, {}))[-runner.max_rows:]
        s, logits = runner.run(tok, rows_at)
        reference[key] = (s, dict(zip(rows_at, logits)))
        if key in got_sessions:
            program[key] = (got_sessions[key][1], got_rows.get(key, {}))
    phase("reference_s")
    ctl, least = {}, int(wl["own_mean_least_spans"])
    for key in control_sessions(touched, seed, int(wl["sample_busiest"]),
                                int(wl["control_tokens"]), least):
        rows_at = sorted(reference[key][1])
        s, logits = runner.run(touched[key], rows_at, control=True)
        ctl[key] = (s, dict(zip(rows_at, logits)))
    phase("control_s")
    compare = lambda got: ref.compare(
        got, {k: reference[k] for k in got}, least)
    ctl_numbers = compare(ctl)
    same_numbers = compare({k: program[k] for k in ctl if k in program})
    missing = [k for k in reference if k not in program]
    numbers = ctl_numbers if control else compare(program)
    absorbed_pct = 100.0 * delta["seq_absorbed_tokens"] \
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
        harness.Check("pool_blocks_unheld_pct",
                      100.0 - 100.0 * held0 / blocks_total,
                      100.0 - BLOCKS_HELD_PCT),
        harness.Check("busiest_unrolled", sum(
            begun0.get(t, 0) < 2 for t in range(BUSIEST_ROLLED)), 0),
        harness.Check("forms_unreached",
                      int(not 0.0 < absorbed_pct < 100.0), 0),
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
            pool_blocks=blocks_total, pool_blocks_held_at_start=held0,
            pool_blocks_held_at_end=policy.blocks_held,
            sessions_rolled=policy.rolled, sessions_evicted=policy.evicted,
            absorbed_token_share=absorbed_pct,
            seq_tokens=delta["seq_tokens"], seq_pairs=delta["seq_pairs"],
            seq_pad_tokens=delta["seq_pad_tokens"],
            seq_steps=delta["seq_steps"],
            tenants_compared=len(tenants),
            sessions_compared=len(program),
            spans_compared=numbers["spans_compared"],
            rows_compared=numbers["rows_compared"],
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
                            "surprisal_gap_p99", "logit_gap",
                            "logit_gap_max")},
            backlog_spans=int(totals.offered_spans - totals.served_spans
                              - totals.shed_spans)),
        "ticks": ticks, "tick_wall_s": float(walls.sum()),
        "counters": delta, "tracer": tracer, "window_t0": t0,
        "trace_dir": trace_dir,
    }
