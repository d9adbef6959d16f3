"""Driver ``fleet-seq-swa-open``: ``fleet-seq-open``'s cell for a model
whose sessions hold blocks of TWO K/V pools (the window-and-full
attention decoder: a pool that grows with a session for the full layers,
a trailing ring of a window pool for the sliding ones): a tenant fleet
served in real time, open loop at a rate fixed in the cell, every served
span scored by the sequence model in the serve tick
(``ServeEngine(seq_model=)``).

From the program: what ``fleet-seq-open`` takes (whose engine builder,
tenant sample, control draw, served-log and audit helpers are used as
they are, as it uses ``fleet-open``'s), and the plane's counters of the
two layer kinds and of the window pool.  Set-up, the timed window, the
reference's runs and the family's counts and notes are
``benchmark/seq_cell.py``'s; here are the two-pool policy's replay, this
cell's checks and its notes.

Set-up feeds a pre-window of the same per-tenant rates, ``pre_merge``
virtual intervals to an engine tick; three checks hold it to what it is
for: at the window's start at least 80% of EACH pool's blocks are held by
sessions, each of the ten busiest tenants has ended a session, and at
least one live session is longer than the rotary scaling's original
length (4,096 tokens at published sizes).

``correct``, after the window: the program's state is freed (the pools
first), the two-pool session policy is replayed from the served log by
the reference's own code, and for the sampled tenants every session that
the window touched is run WHOLE through the float32 reference (the
window a mask over positions), layer by layer on the device; compared are
every span's surprisal (the mean over all of them, and the worst
session's or tenant's own mean), the kept logits rows, the session
boundaries and the token ids; both layer kinds have to have run, the
sliding one on fewer keys than the full one.  The control (the same
reference with every key and value, and the last hidden state a segment
hands on, rounded to float8 where a cache would hold them) runs over
sessions of the window's own lengths and stands in the program's place
under ``--control 1``; such a run also reads, over the control's
sessions, how far the float32 reference moves when what the program holds
in bfloat16 is rounded so, with every layer's own choice of experts and
with the float32 run's (``bf16_acts_*`` and ``bf16_acts_forced_*`` in
``notes``): what of the program's distance is arithmetic and what is a
near-tie of two router scores going the other way.
"""

from __future__ import annotations

# a program without the model fails here, at once
from anomod.models import swa_moe  # noqa: F401

import numpy as np

from benchmark import harness, seq_cell
from benchmark.reference import swa_moe_decoder as ref

BLOCKS_HELD_PCT = 80.0
BUSIEST_ROLLED = 10
#: the metric whose file lists the leaf spans of ``serve.tick`` in this
#: cell; an untraced run prices the same spans in ``notes``
TICK_UNNAMED = "tick_unnamed_ms.lxs2"
#: counters that hold the table's present count, not a sum over steps
GAUGES = ("sessions_rolled", "sessions_evicted", "pool_blocks_held",
          "win_blocks_held")
#: the limits of ``correct``, in the order the line gives them
GAPS = ("surprisal_gap_mean", "surprisal_gap_group_max", "logit_gap",
        "surprisal_gap_p50")


def replay_policy(served_log: list, cfg: dict, n_pre_ticks: int):
    """The two-pool session policy replayed from the served log.  Returns
    ``(per tick segments, policy, (blocks held, window blocks held,
    sessions begun per tenant, the longest live session) at the window's
    start)``."""
    a = cfg["assumed"]
    policy = ref.SessionPolicy(
        int(a["pool_tokens"]) // int(a["block_tokens"]) - 1,
        int(a["window_blocks"]) - 1, int(a["context_tokens"]),
        int(a["block_tokens"]), int(cfg["sliding_window"]))
    ticks, at_start = [], None
    for k, served in enumerate(served_log):
        if k == n_pre_ticks:
            at_start = (policy.blocks_held, policy.win_blocks_held,
                        dict(policy.begun),
                        max((s[0] for s in policy.live.values()),
                            default=0))
        counts = {}
        for qb in served:
            counts[qb.tenant_id] = counts.get(qb.tenant_id, 0) + qb.n_spans
        chunks = [(t, n) for t, n in counts.items() if n]
        ticks.append(policy.tick(chunks) if chunks else [])
    return ticks, policy, at_start


def bf16_readings(w, runner, keys: list) -> None:
    """Over the control's sessions: the reference with bfloat16
    activations against itself in float32, free and with the float32
    run's experts forced."""
    free, forced, moved, took = {}, {}, 0, 0
    for key in keys:
        tok, rows_at = w.touched[key], sorted(w.reference[key][1])
        *_, experts = runner.run(tok, chosen=True)
        s, logits, own = runner.run(tok, rows_at, acts=True, chosen=True)
        free[key] = (s, dict(zip(rows_at, logits)))
        s, logits = runner.run(tok, rows_at, acts=True, forced=experts)
        forced[key] = (s, dict(zip(rows_at, logits)))
        moved += int((np.sort(own, axis=-1)
                      != np.sort(experts, axis=-1)).any(axis=(0, 2)).sum())
        took += len(tok)
    for who, got in (("bf16_acts", free), ("bf16_acts_forced", forced)):
        numbers = w.gaps(got)
        w.notes.update({f"{who}_{name}": numbers[name]
                        for name in seq_cell.GAP_NOTES})
    w.notes["bf16_acts_tokens_rerouted_pct"] = 100.0 * moved / max(took, 1)
    seq_cell.phase(w, "bf16_readings_s")


def run(cell: dict, seed: int, seconds: float, traced: bool, t_start: float,
        meter: harness.CompileMeter, trace_dir: str,
        control: bool = False) -> dict:
    cfg = cell["config"]
    w = seq_cell.serve(
        cell, seed, seconds, traced, t_start, meter, trace_dir, GAUGES,
        lambda table: f"blocks held {table.blocks_held}, window blocks "
                      f"held {table.win_blocks_held}")
    blocks_total, win_total = w.plane.table.usable, w.plane.table.usable_win
    seq_cell.compare(w, ref, replay_policy, control,
                     bf16_readings if control else None)
    blocks0, win0, begun0, longest0 = w.at_start
    delta, policy = w.delta, w.policy
    long_enough = int(cfg["rope_parameters"]["full_attention"].get(
        "original_max_position_embeddings", 0))
    checks = seq_cell.common_checks(w) + [
        harness.Check("blocks_unheld_pct",
                      100.0 - 100.0 * blocks0 / blocks_total,
                      100.0 - BLOCKS_HELD_PCT),
        harness.Check("window_blocks_unheld_pct",
                      100.0 - 100.0 * win0 / win_total,
                      100.0 - BLOCKS_HELD_PCT),
        harness.Check("busiest_unrolled", sum(
            begun0.get(t, 0) < 2 for t in range(BUSIEST_ROLLED)), 0),
        harness.Check("no_session_past_original_length",
                      int(longest0 <= long_enough), 0),
        harness.Check("layer_kinds_unreached", int(
            not 0 < delta["swa_keys"] < delta["full_keys"]
            or not 0 < delta["swa_pairs"] < delta["full_pairs"]), 0),
    ] + seq_cell.gap_checks(w, GAPS)
    return seq_cell.result(w, checks, TICK_UNNAMED, dict(
        pool_blocks=blocks_total, pool_blocks_held_at_start=blocks0,
        pool_blocks_held_at_end=policy.blocks_held,
        window_blocks=win_total, window_blocks_held_at_start=win0,
        window_blocks_held_at_end=policy.win_blocks_held,
        longest_session_at_start=longest0,
        sessions_begun_by_busiest=[begun0.get(t, 0)
                                   for t in range(BUSIEST_ROLLED)],
        sessions_rolled=policy.rolled, sessions_evicted=policy.evicted,
        sessions_evicted_by_window=policy.evicted_by_window,
        steps_split_by_window=policy.steps_split,
        window_blocks_freed=policy.win_freed,
        window_evictions=delta["sessions_evicted"]
        - w.counters0["sessions_evicted"],
        window_evictions_by_window=delta["sessions_evicted_by_window"],
        full_pairs=delta["full_pairs"], full_keys=delta["full_keys"],
        swa_pairs=delta["swa_pairs"], swa_keys=delta["swa_keys"],
        swa_key_share=100.0 * delta["swa_keys"]
        / max(delta["full_keys"], 1),
        expert_rounds_a_layer_a_step=1))
