"""``lxs2-fleet-overload`` rehearsed at a tiny size on the CPU (its twin is
added to a temp copy of the benchmark by files and entries alone), the
window-and-full model's FLOP and byte counts against a counted toy
forward, the configuration's keys against the catalog row's values, and
the guard of PR 33's refusal for this cell: no metric it brought is due in
another cell and every reader it brought returns ``None`` where its
counters, spans, trace or configuration family are absent."""

import json
import os
import types

import bm_tiny
import bm_tiny_swa
from bm_tiny_swa import tiny_swa_root  # noqa: F401  (the fixture)
import numpy as np
import pytest

from benchmark import contract, harness, swa_work

ROOT = bm_tiny.ROOT
CELL = bm_tiny_swa.CELL
SHARES = ("surprisal_gap_mean", "logit_gap", "surprisal_gap_group_max",
          "surprisal_gap_p50")
NEW = ["mfu.lxs2_step", "swa_append_roofline", "full_append_roofline",
       "moe_small_grouped_roofline", "expert_load_max_over_mean.lxs2",
       "swa_key_share", "window_evictions_per_tick", "seq_model_ms.lxs2",
       "seq_stage_ms.lxs2", "seq_score_ms.lxs2", "admit_drain_ms.lxs2",
       "coalesce_plan_ms.lxs2", "lane_issue_ms.lxs2", "fold_retire_ms.lxs2",
       "score_windows_ms.lxs2", "barrier_ms.lxs2", "tick_unnamed_ms.lxs2",
       "idle_pct.admission.lxs2", "idle_pct.staging.lxs2",
       "idle_pct.fold_retire.lxs2", "idle_pct.commit.lxs2",
       "idle_pct.seq.lxs2", "idle_pct.unnamed.lxs2",
       "idle_pct.outside_tick.lxs2", "device_idle_pct.lxs2"]


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_twin_prints_the_contracts_line(tiny_swa_root, trace):
    rc, line, err = bm_tiny.run_cell(
        tiny_swa_root, bm_tiny_swa.TINY_CELL, trace, seed=5000000011)
    assert rc == 0, err
    assert line["correct"] is True, err
    assert line["failed"] == 0 and line["attempted"] > 0
    bench = bm_tiny._load(tiny_swa_root, "BENCHMARK.json")
    assert contract.check_last_line(line, bench, bm_tiny_swa.TINY_CELL,
                                    bool(trace)) == []
    got = set(line["metrics"])
    if trace:
        # the rooflines read a device trace's op metadata: none on the CPU
        assert set(NEW) - got == {
            "swa_append_roofline", "full_append_roofline",
            "moe_small_grouped_roofline"}
        assert 0 < line["metrics"]["swa_key_share"]["value"] < 100
        assert line["metrics"]["window_evictions_per_tick"]["value"] > 0
        assert line["metrics"]["expert_load_max_over_mean.lxs2"][
            "value"] >= 1
    else:
        assert got == {"served_spans_per_s", "setup_s"}
    notes = line["notes"]
    assert notes["sessions_rolled"] > 0
    assert notes["sessions_evicted_by_window"] > 0
    assert notes["steps_split_by_window"] > 0
    assert notes["window_blocks_freed"] > 0
    assert notes["pool_blocks_held_at_start"] >= 0.8 * notes["pool_blocks"]
    assert notes["window_blocks_held_at_start"] \
        >= 0.8 * notes["window_blocks"]
    assert notes["longest_session_at_start"] > 16
    assert 0 < notes["swa_keys"] < notes["full_keys"]
    assert {c["name"] for c in line["checks"]} >= {
        "blocks_unheld_pct", "window_blocks_unheld_pct",
        "layer_kinds_unreached", "weights_differing", "busiest_unrolled",
        "no_session_past_original_length", "session_bounds_differing"}


def test_tiny_twins_control_is_not_correct(tiny_swa_root):
    rc, sound, err = bm_tiny.run_cell(
        tiny_swa_root, bm_tiny_swa.TINY_CELL, 0, seed=91)
    assert rc == 0 and sound["correct"] is True, err
    rc, line, err = bm_tiny.run_cell(
        tiny_swa_root, bm_tiny_swa.TINY_CELL, 0, seed=91, control=1)
    assert rc == 0 and line["correct"] is False, err
    failed = {c["name"] for c in line["checks"] if not c["ok"]}
    assert "surprisal_gap_p50" in failed and failed <= set(SHARES)
    # the limit lies between the program's reading and the control's
    # (tiny twin on the CPU, seeds 91, 92, 93 and 5000000011: the median
    # gap reads 0.0075-0.0111 / 0.0241-0.0358; the three means swing with
    # one near-tie of two of the 16 experts and do not separate here)
    limit = {c["name"]: c for c in sound["checks"]}["surprisal_gap_p50"]
    assert limit["value"] < limit["limit"] \
        < sound["notes"]["control_surprisal_gap_p50"]
    # the control's run also reads what bfloat16 activations alone cost
    # the reference, with its own experts and with the float32 run's
    notes = line["notes"]
    assert "bf16_acts_surprisal_gap_mean" not in sound["notes"]
    assert 0 < notes["bf16_acts_forced_surprisal_gap_mean"] \
        <= notes["bf16_acts_surprisal_gap_mean"]
    assert 0 <= notes["bf16_acts_tokens_rerouted_pct"] <= 100


def _not_correct(root, monkeypatch, step):
    from anomod.models import swa_moe as wm
    monkeypatch.setattr(wm, "append_step", step(wm.append_step))
    rc, line, err = bm_tiny.run_cell(root, bm_tiny_swa.TINY_CELL, 0,
                                     seed=91)
    assert rc == 0 and line["correct"] is False, err
    return {c["name"] for c in line["checks"] if not c["ok"]}


def test_a_stale_window_block_is_not_correct(tiny_swa_root, monkeypatch):
    # the step leaves its window pool unwritten: every later chunk's
    # sliding layers read stale keys where its session's past should be
    def stale(real):
        def step(cfg, params, state, plan):
            out = real(cfg, params, state, plan)
            return (dict(out[0], wpool=state["wpool"]),) + tuple(out[1:])
        return step

    assert _not_correct(tiny_swa_root, monkeypatch, stale) & set(SHARES)


def test_a_window_off_by_one_is_not_correct(tiny_swa_root, monkeypatch):
    # the sliding layers see one key more than sliding_window
    import dataclasses

    def wide(real):
        return lambda cfg, params, state, plan: real(
            dataclasses.replace(cfg, sliding_window=cfg.sliding_window + 1),
            params, state, plan)

    assert _not_correct(tiny_swa_root, monkeypatch, wide) & set(SHARES)


def test_a_program_that_draws_other_weights_is_not_correct(
        tiny_swa_root, monkeypatch):
    # one gate weight of one layer a hundredth off: far too little for the
    # gaps to tell, and the reference's own draw does not share it
    from anomod.models import swa_moe as wm
    real = wm.init_params

    def off(cfg, seed, dtype=None):
        params = real(cfg, seed, dtype)
        layer = dict(params["layer02"])
        layer["mlp_norm"] = layer["mlp_norm"].at[1].mul(1.01)
        return dict(params, layer02=layer)

    monkeypatch.setattr(wm, "init_params", off)
    rc, line, err = bm_tiny.run_cell(
        tiny_swa_root, bm_tiny_swa.TINY_CELL, 0, seed=91)
    assert rc == 0 and line["correct"] is False, err
    assert {c["name"]: c["value"] for c in line["checks"]
            if not c["ok"]} == {"weights_differing": 1.0}


#: the catalog row's ``config`` (``Laguna-XS.2``, model-configs guide),
#: every key at its published value; the per-layer lists by their period
CATALOG = dict(
    model_type="laguna", vocab_size=100352, hidden_size=2048,
    intermediate_size=8192, num_hidden_layers=40, num_attention_heads=48,
    num_key_value_heads=8, head_dim=128, max_position_embeddings=262144,
    attention_bias=False, rms_norm_eps=1e-06, num_experts=256,
    num_experts_per_tok=8, moe_intermediate_size=512,
    shared_expert_intermediate_size=512, tie_word_embeddings=False,
    gating=True, sliding_window=512,
    rope_parameters={
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096},
    layer_types=["full_attention"] + ["sliding_attention"] * 3,
    moe_apply_router_weight_on_input=False, partial_rotary_factor=0.5,
    mlp_layer_types=["dense"] + ["sparse"] * 39,
    moe_routed_scaling_factor=2.5,
    num_attention_heads_per_layer=[48, 64, 64, 64])


def test_the_config_holds_every_key_of_the_catalog_row():
    bench = bm_tiny._load(ROOT, "BENCHMARK.json")
    assert contract.check_benchmark_json(bench) == []
    entry = next(c for c in bench["configs"]
                 if c["name"] == bm_tiny_swa.CONFIG)
    assert entry["source"] == "https://huggingface.co/poolside/" \
        "Laguna-XS.2/blob/main/config.json"
    cfg = bm_tiny._load(ROOT, entry["file"])
    want = dict(CATALOG, num_hidden_layers=5,
                layer_types=CATALOG["layer_types"] * 10,
                num_attention_heads_per_layer=CATALOG[
                    "num_attention_heads_per_layer"] * 10)
    assert {k: cfg[k] for k in want} == want
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"]["num_hidden_layers"] == 40
    assert (cfg["experts_held"], cfg["experts_lo"], cfg["vocab_held"]) \
        == (256, 0, 100352)
    a = cfg["assumed"]
    assert (a["context_tokens"], a["block_tokens"], a["token_grid"]) \
        == (8192, 128, [4096, 8192])
    assert a["pool_tokens"] % 128 == 0 and a["window_blocks"] > 0
    assert set(a["equations"]) == {"gate", "router", "shared_expert",
                                   "qk_norm"}
    assert cfg["fleet"]["n_tenants"] == 2048
    k2 = bm_tiny._load(ROOT, "benchmark", "configs",
                       "kimi-k2-ep32-share.json")["fleet"]
    assert {k: v for k, v in cfg["fleet"].items() if k != "why"} \
        == {k: v for k, v in k2.items() if k != "why"}
    assert len(cfg["guarantees"]) == 4 and "both pools" in cfg[
        "guarantees"][3]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) \
        == (1, bm_tiny_swa.CONFIG, "overload")
    wl = bm_tiny._load(ROOT, "benchmark", "workloads", CELL + ".json")
    p = wl["params"]
    k2p = bm_tiny._load(ROOT, "benchmark", "workloads",
                        "k2-fleet-overload.json")["params"]
    assert {k: p[k] for k in p
            if k not in ("offered_spans_per_s", "pre_merge")} \
        == {k: k2p[k] for k in k2p
            if k not in ("offered_spans_per_s", "pre_merge")}
    assert (p["alpha"], p["batch_cap"], p["structure_seed"],
            p["pre_window_s"]) == (1.2, 512, 1, 84)
    assert p["offered_spans_per_s"] == 1.5 * wl["sweep"]["knee_spans_per_s"]
    assert wl["driver"] == "fleet-seq-swa-open"
    assert len(wl["sweep"]["rows"]) >= 4


# -- the guard of PR 33's refusal, for this PR --------------------------------

#: what each older cell reported at the parent (commit 3788070), traced:
#: the per-layer metric names of the ledger's PR 35 lines
DUE_AT_PARENT = {
    "tt-replay-staged": ["fold_roofline", "device_idle_pct.replay"],
    "tt-fleet-overload": [
        "admit_drain_ms", "dispatch_ms", "dispatches_per_tick",
        "fold_wait_ms", "pool_copy_device_ms", "lane_fold_device_ms",
        "score_ms", "tick_other_ms", "device_idle_pct.fleet",
        "coalesce_plan_ms", "lane_fill_ms", "lane_issue_ms",
        "fold_retire_ms", "score_bookkeep_ms", "score_windows_ms",
        "barrier_ms", "tick_unnamed_ms", "post_tick_drain_ms",
        "idle_pct.admission", "idle_pct.staging", "idle_pct.fold_retire",
        "idle_pct.commit", "idle_pct.unnamed", "idle_pct.outside_tick"],
    "k2-fleet-overload": [
        "mfu.k2_step", "mla_append_roofline", "moe_grouped_roofline",
        "seq_model_ms", "seq_stage_ms", "seq_score_ms",
        "expert_load_max_over_mean", "absorbed_token_share",
        "device_idle_pct.k2", "admit_drain_ms.k2", "coalesce_plan_ms.k2",
        "lane_issue_ms.k2", "fold_retire_ms.k2", "score_windows_ms.k2",
        "barrier_ms.k2", "tick_unnamed_ms.k2", "idle_pct.admission.k2",
        "idle_pct.staging.k2", "idle_pct.fold_retire.k2",
        "idle_pct.commit.k2", "idle_pct.seq.k2", "idle_pct.unnamed.k2",
        "idle_pct.outside_tick.k2"],
    "n3s-fleet-overload": [
        "mfu.n3s_step", "ssm_scan_roofline", "gqa_append_roofline",
        "moe_latent_grouped_roofline", "ssm_recurrent_token_share",
        "slot_evictions_per_tick", "expert_load_max_over_mean.n3s",
        "seq_model_ms.n3s", "seq_stage_ms.n3s", "seq_score_ms.n3s",
        "admit_drain_ms.n3s", "coalesce_plan_ms.n3s", "lane_issue_ms.n3s",
        "fold_retire_ms.n3s", "score_windows_ms.n3s", "barrier_ms.n3s",
        "tick_unnamed_ms.n3s", "idle_pct.admission.n3s",
        "idle_pct.staging.n3s", "idle_pct.fold_retire.n3s",
        "idle_pct.commit.n3s", "idle_pct.seq.n3s", "idle_pct.unnamed.n3s",
        "idle_pct.outside_tick.n3s", "device_idle_pct.n3s"]}


def test_no_metric_of_the_new_cell_is_due_in_an_older_cell():
    """Also what ``test_benchmark_n3s_cell``'s test of this name asserts
    apart from the list of cells that report ``served_spans_per_s``
    (which this PR appends to, so that test is expected to fail:
    ``tests/conftest.OVERTAKEN``)."""
    bench = bm_tiny._load(ROOT, "BENCHMARK.json")
    new = [m for m in bench["per_layer"] if CELL in m.get("workloads", [])]
    assert [m["name"] for m in new] == NEW
    assert all(m["workloads"] == [CELL] for m in new)
    assert bench["per_layer"][-len(new):] == new      # appended at the end
    assert all("workloads" in m for m in bench["per_layer"])
    for cell, names in DUE_AT_PARENT.items():
        assert list(contract.metrics_due(bench, cell, True)) == names
        assert set(contract.metrics_due(bench, cell, False)) == {
            "setup_s", "replay_spans_per_s" if cell == "tt-replay-staged"
            else "served_spans_per_s"}
    n3s = [m for m in bench["per_layer"]
           if "n3s-fleet-overload" in m.get("workloads", [])]
    assert len(n3s) == 25
    assert all(m["workloads"] == ["n3s-fleet-overload"] for m in n3s)
    assert set(contract.metrics_due(bench, CELL, True)) == set(NEW)
    assert set(contract.metrics_due(bench, CELL, False)) \
        == {"served_spans_per_s", "setup_s"}
    served = next(m for m in bench["end_to_end"]
                  if m["name"] == "served_spans_per_s")
    assert served["workloads"] == ["tt-fleet-overload", "k2-fleet-overload",
                                   "n3s-fleet-overload", CELL]
    assert [w["name"] for w in bench["workloads"]] == list(
        DUE_AT_PARENT) + [CELL]
    assert bench["run_seconds"] == 51


def _older_context(trace_dir, cell):
    """What a traced run of an older model cell on the PARENT's program
    hands a reader: the parent plane's counters (none of this PR's) and
    spans only."""
    parent = ("seq_tokens", "seq_pairs", "seq_absorbed_tokens",
              "seq_absorbed_pairs", "seq_absorbed_group_blocks",
              "seq_expanded_keys", "seq_keys", "seq_pad_tokens",
              "seq_steps", "expert_tokens_max", "expert_tokens_mean",
              "sessions_rolled", "sessions_evicted", "pool_blocks_held",
              "ssm_recurrent_tokens", "ssm_scan_tokens", "ssm_scan_blocks",
              "ssm_scan_pairs", "ssm_state_rows", "gqa_pairs", "gqa_keys",
              "state_slots_held", "sessions_evicted_by_slots",
              "steps_split_by_slots")
    tracer = types.SimpleNamespace(
        spans=[["serve.tick", 1.0, 1.4], ["serve.seq_model", 1.1, 1.3]],
        seconds=lambda names, since=0.0: 0.2 * ("serve.seq_model" in names))
    trace = types.SimpleNamespace(
        window_s=1.0, busy_s=0.5, window=(0, 10 ** 9),
        devices={"/device:TPU:0": [("%fusion.1", 10, 20)]}, host=[])
    bench = bm_tiny._load(ROOT, "BENCHMARK.json")
    return {"counters": dict.fromkeys(parent, 7.0), "ticks": 4,
            "tracer": tracer, "window_t0": 0.5, "trace": trace,
            "trace_dir": trace_dir, "peaks": {"flops_per_s": 1e12,
                                              "hbm_bytes_per_s": 1e11},
            "cell": harness.load_cell(bench, cell, ROOT)}


@pytest.mark.parametrize("cell", ["k2-fleet-overload", "n3s-fleet-overload"])
@pytest.mark.parametrize("metric", [
    "mfu.lxs2_step", "swa_append_roofline", "full_append_roofline",
    "moe_small_grouped_roofline", "swa_key_share",
    "window_evictions_per_tick"])
def test_a_new_reader_finds_nothing_in_another_programs_run(metric, cell,
                                                            tmp_path):
    spec = bm_tiny._load(ROOT, "benchmark", "metrics", metric + ".json")
    read = harness.module_for("readers", spec["reader"]).read
    older = _older_context(str(tmp_path), cell)
    assert read(older, **spec["args"]) is None
    # the parent's counters under the new cell's own configuration, the
    # new plane's counters under the older cell's configuration (all
    # zero, as that model counts them), a plane that counted nothing, and
    # a context with nothing in it at all
    new = harness.load_cell(bm_tiny._load(ROOT, "BENCHMARK.json"), CELL,
                            ROOT)
    assert read(dict(older, cell=new), **spec["args"]) is None
    from anomod.serve import seqplane
    zeros = dict.fromkeys(seqplane.COUNTERS, 0.0)
    assert read(dict(older, counters=dict(zeros, **older["counters"])),
                **spec["args"]) is None
    assert read(dict(older, cell=new, counters=zeros), **spec["args"]) \
        is None
    assert read({}, **spec["args"]) is None


def _metric_args(name: str) -> dict:
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           name + ".json")) as f:
        return json.load(f)["args"]


def test_the_step_share_reads_the_window_planes_counters(tmp_path):
    ctx = _older_context(str(tmp_path), CELL)
    ctx["counters"] = dict(
        ctx["counters"], seq_tokens=4000.0, seq_steps=1.0,
        full_pairs=6.0e6, full_keys=90000.0, swa_pairs=1.5e6,
        swa_keys=30000.0, expert_tokens_mean=4 * 125.0)
    # the readers are any family's: the metric's file names the work module
    # and the key that tells the family
    family = _metric_args("mfu.lxs2_step")
    assert family == {"work_module": "swa_work",
                      "family_key": "num_attention_heads_per_layer"}
    read = harness.module_for("readers", "mfu-step-family").read
    c = ctx["cell"]["config"]
    assert read(ctx, **dict(family, family_key="no_such_key")) is None
    assert read(ctx, **family) == pytest.approx(
        100.0 * swa_work.step_flops(c, ctx["counters"]) / 1e12)
    # a step at the published widths: the head is nearly half of the
    # dense work a token (0.41 of 0.88 GFLOP)
    s = swa_work.sizes(c)
    assert 2 * s["head"] == 411_041_792
    assert 2 * s["per_token"] == pytest.approx(0.474e9, rel=0.01)
    # no xplane file under the directory: the scope's reader has no trace
    roof = harness.module_for("readers", "scope-roofline-family").read
    assert _metric_args("swa_append_roofline") == dict(
        family, work="swa", scope="anomod_seq_swa")
    assert roof(ctx, **_metric_args("swa_append_roofline")) is None


class Counted:
    """A toy forward that does the algorithm's arithmetic and counts the
    multiply-adds of every product it takes."""

    def __init__(self):
        self.macs = 0

    def mm(self, a, b):
        self.macs += a.shape[0] * a.shape[1] * b.shape[1]
        return a @ b


def test_swa_work_counts_equal_a_counted_toy_forward():
    c = dict(bm_tiny_swa.PRESET, vocab_held=96, experts_held=16,
             layer_types=CATALOG["layer_types"] * 2,
             mlp_layer_types=["dense"] + ["sparse"] * 7)
    D, kv, hd, W = (c["hidden_size"], c["num_key_value_heads"],
                    c["head_dim"], c["sliding_window"])
    I, F, Fs, R = (c["intermediate_size"], c["moe_intermediate_size"],
                   c["shared_expert_intermediate_size"], c["num_experts"])
    rng = np.random.default_rng(0)
    r = lambda *s: rng.standard_normal(s)
    # three chunks of one step: (cached tokens, new tokens); 9 token-expert
    # pairs land here a sparse layer
    chunks = [(30, 1), (7, 19), (0, 5)]
    pairs_here = 9
    k = Counted()
    layers = list(zip(c["layer_types"], c["num_attention_heads_per_layer"],
                      c["mlp_layer_types"]))[:c["num_hidden_layers"]]
    for kind, H, mlp in layers:
        for cached, n in chunks:
            x = r(n, D)
            k.mm(x, r(D, H * hd + 2 * kv * hd + H))     # q, k, v, the gate
            for i in range(n):
                seen = cached + i + 1
                if kind == "sliding_attention":
                    seen = min(seen, W)
                for _ in range(H):
                    k.mm(r(1, hd), r(hd, seen))
                    k.mm(r(1, seen), r(seen, hd))
            k.mm(r(n, H * hd), r(H * hd, D))
            if mlp == "dense":
                k.mm(k.mm(x, r(D, 2 * I))[:, :I], r(I, D))
            else:
                k.mm(x, r(D, R))
                k.mm(k.mm(x, r(D, 2 * Fs))[:, :Fs], r(Fs, D))
        if mlp == "sparse":
            k.mm(k.mm(r(pairs_here, D), r(D, 2 * F))[:, :F], r(F, D))
    for _, n in chunks:
        k.mm(r(n, D), r(D, c["vocab_held"]))
    n_sparse, n_full, n_swa = 4, 2, 3
    pos = np.concatenate([cached + np.arange(n) for cached, n in chunks])
    n = {"seq_tokens": 25, "seq_steps": 1,
         "full_pairs": int((pos + 1).sum()),
         "swa_pairs": int(np.minimum(pos + 1, W).sum()),
         "full_keys": 31 + 26 + 5,
         "swa_keys": 20 + 26 + 5,
         "expert_tokens_mean": n_sparse * pairs_here / c["experts_held"]}
    assert n["swa_pairs"] < n["full_pairs"]
    assert swa_work.step_flops(c, n) == 2 * k.macs
    full = swa_work.full_work(c, n)
    assert full["flops"] == 2 * n["full_pairs"] * 2 * hd * 6 * n_full
    assert full["bytes"] == 2 * n_full * (62 * 2 * kv * hd
                                          + 25 * 2 * 6 * hd)
    swa = swa_work.swa_work(c, n)
    assert swa["flops"] == 2 * n["swa_pairs"] * 2 * hd * 8 * n_swa
    assert swa["bytes"] == 2 * n_swa * (51 * 2 * kv * hd + 25 * 2 * 8 * hd)
    grouped = swa_work.grouped_work(c, n)
    assert grouped["flops"] == 2 * n_sparse * pairs_here * 3 * D * F
    assert grouped["bytes"] == 2 * (n_sparse * 16 * 3 * D * F
                                    + n_sparse * pairs_here * 2 * (D + F))
