"""``n3s-fleet-overload`` rehearsed at a tiny size on the CPU (its twin is
added to a temp copy of the benchmark by files and entries alone), the
hybrid model's FLOP and byte counts against a counted toy forward, the
configuration's published widths, and the guard of PR 33's refusal: no
metric this cell brought is due in another cell and every reader it
brought returns ``None`` where its counters, spans or trace are absent."""

import json
import os
import types

import bm_tiny
import bm_tiny_hybrid
from bm_tiny_hybrid import tiny_hybrid_root  # noqa: F401  (the fixture)
import numpy as np
import pytest

from benchmark import contract, harness, hybrid_work

ROOT = bm_tiny.ROOT
CELL = bm_tiny_hybrid.CELL
SHARES = ("surprisal_gap_mean", "logit_gap", "surprisal_gap_group_max")


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_twin_prints_the_contracts_line(tiny_hybrid_root, trace):
    rc, line, err = bm_tiny.run_cell(
        tiny_hybrid_root, bm_tiny_hybrid.TINY_CELL, trace, seed=5000000011)
    assert rc == 0, err
    assert line["correct"] is True, err
    assert line["failed"] == 0 and line["attempted"] > 0
    bench = bm_tiny._load(tiny_hybrid_root, "BENCHMARK.json")
    assert contract.check_last_line(line, bench, bm_tiny_hybrid.TINY_CELL,
                                    bool(trace)) == []
    got = set(line["metrics"])
    if trace:
        # the rooflines read a device trace's op metadata: none on the CPU
        assert {"mfu.n3s_step", "seq_model_ms.n3s", "seq_stage_ms.n3s",
                "seq_score_ms.n3s", "expert_load_max_over_mean.n3s",
                "ssm_recurrent_token_share", "slot_evictions_per_tick",
                "device_idle_pct.n3s", "tick_unnamed_ms.n3s",
                "idle_pct.seq.n3s"} <= got
        assert 0 < line["metrics"]["ssm_recurrent_token_share"]["value"] \
            < 100
        assert line["metrics"]["slot_evictions_per_tick"]["value"] > 0
    else:
        assert got == {"served_spans_per_s", "setup_s"}
    notes = line["notes"]
    assert notes["sessions_rolled"] > 0
    assert notes["sessions_evicted_by_slots"] > 0
    assert notes["steps_split_by_slots"] > 0
    assert notes["state_slots_held_at_start"] >= 0.9 * notes["state_slots"]
    assert {c["name"] for c in line["checks"]} >= {
        "slots_unheld_pct", "forms_unreached", "weights_differing",
        "busiest_unrolled", "session_bounds_differing"}


def test_tiny_twins_control_is_not_correct(tiny_hybrid_root):
    rc, sound, err = bm_tiny.run_cell(
        tiny_hybrid_root, bm_tiny_hybrid.TINY_CELL, 0, seed=91)
    assert rc == 0 and sound["correct"] is True, err
    rc, line, err = bm_tiny.run_cell(
        tiny_hybrid_root, bm_tiny_hybrid.TINY_CELL, 0, seed=91, control=1)
    assert rc == 0 and line["correct"] is False, err
    failed = {c["name"] for c in line["checks"] if not c["ok"]}
    assert failed and failed <= set(SHARES)
    # each limit lies between the program's reading and the control's
    limit = {c["name"]: c for c in sound["checks"]}
    for name in failed:
        assert limit[name]["value"] < limit[name]["limit"] \
            < sound["notes"]["control_" + name]


def test_a_plane_that_scores_wrongly_is_not_correct(tiny_hybrid_root,
                                                    monkeypatch):
    # the step leaves its slot pool unwritten: every later chunk goes on
    # from a stale state where its session's past should be
    from anomod.models import hybrid_ssm_moe as hm
    real = hm.append_step

    def stale(cfg, params, state, plan):
        out = real(cfg, params, state, plan)
        return (dict(out[0], ssm=state["ssm"]),) + tuple(out[1:])

    monkeypatch.setattr(hm, "append_step", stale)
    rc, line, err = bm_tiny.run_cell(
        tiny_hybrid_root, bm_tiny_hybrid.TINY_CELL, 0, seed=91)
    assert rc == 0 and line["correct"] is False, err
    assert {c["name"] for c in line["checks"] if not c["ok"]} & set(SHARES)


def test_a_program_that_draws_other_weights_is_not_correct(
        tiny_hybrid_root, monkeypatch):
    # one norm weight of one layer a hundredth off: far too little for the
    # gaps to tell, and the reference's own draw does not share it
    from anomod.models import hybrid_ssm_moe as hm
    real = hm.init_params

    def off(cfg, seed, dtype=None):
        params = real(cfg, seed, dtype)
        layer = dict(params["layer02"])
        layer["gate_norm"] = layer["gate_norm"].at[1].mul(1.01)
        return dict(params, layer02=layer)

    monkeypatch.setattr(hm, "init_params", off)
    rc, line, err = bm_tiny.run_cell(
        tiny_hybrid_root, bm_tiny_hybrid.TINY_CELL, 0, seed=91)
    assert rc == 0 and line["correct"] is False, err
    assert {c["name"]: c["value"] for c in line["checks"]
            if not c["ok"]} == {"weights_differing": 1.0}


def test_the_config_keeps_every_published_width():
    bench = bm_tiny._load(ROOT, "BENCHMARK.json")
    assert contract.check_benchmark_json(bench) == []
    entry = next(c for c in bench["configs"]
                 if c["name"] == bm_tiny_hybrid.CONFIG)
    cfg = bm_tiny._load(ROOT, entry["file"])
    published = dict(
        hidden_size=4096, mamba_num_heads=128, mamba_head_dim=64,
        ssm_state_size=128, n_groups=8, conv_kernel=4, chunk_size=128,
        expand=2, num_attention_heads=32, num_key_value_heads=2,
        head_dim=128, n_routed_experts=512, num_experts_per_tok=22,
        moe_latent_size=1024, moe_intermediate_size=2688,
        intermediate_size=2688, moe_shared_expert_intermediate_size=5376,
        n_shared_experts=1, routed_scaling_factor=5, vocab_size=131072,
        layer_norm_epsilon=1e-05, norm_eps=1e-05, n_group=1, topk_group=1,
        max_position_embeddings=262144, rope_theta=10000,
        time_step_min=0.001, time_step_max=0.1, time_step_floor=0.0001,
        mlp_hidden_act="relu2", mamba_hidden_act="silu",
        model_type="nemotron_h", norm_topk_prob=True,
        tie_word_embeddings=False, residual_in_fp32=False)
    assert {k: cfg[k] for k in published} == published
    pattern = cfg["hybrid_override_pattern"]
    assert (len(pattern), pattern.count("M"), pattern.count("E"),
            pattern.count("*")) == (88, 40, 40, 8)
    assert pattern[:11] == "MEMEMEM*EME"
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "experts_held", "vocab_held",
        "num_nextn_predict_layers"]
    assert (cfg["num_hidden_layers"], cfg["experts_held"],
            cfg["vocab_held"], cfg["num_nextn_predict_layers"]) \
        == (11, 64, 16384, 0)
    assert cfg["published"]["num_hidden_layers"] == 88
    a = cfg["assumed"]
    assert (a["context_tokens"], a["block_tokens"], a["pool_tokens"],
            a["state_slots"], a["state_dtype"], a["token_grid"]) \
        == (8192, 128, 524288, 640, "bfloat16", [4096, 8192])
    assert cfg["fleet"]["n_tenants"] == 1024
    assert "two pools" in cfg["guarantees"][3]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["config"]) == (1, bm_tiny_hybrid.CONFIG)
    wl = bm_tiny._load(ROOT, "benchmark", "workloads", CELL + ".json")
    p = wl["params"]
    assert (p["alpha"], p["batch_cap"], p["structure_seed"],
            p["pre_window_s"], p["pre_merge"], p["error_rate"],
            p["service_mix_dirichlet"], p["latency_scale_us"],
            p["latency_sigma"], p["fault_tenants"]) \
        == (1.2, 512, 1, 84, 4, 0.01, 2.0, [800, 6000], 0.35, 0)
    assert p["offered_spans_per_s"] == 1.5 * wl["sweep"]["knee_spans_per_s"]
    assert wl["driver"] == "fleet-seq-hybrid-open"


def test_the_k2_configuration_is_as_pr_28_left_it():
    """What ``test_benchmark_k2_cell``'s published-widths test asserts
    apart from the list of cells that report ``served_spans_per_s`` (which
    this PR appends to, so that test is expected to fail:
    ``tests/conftest.OVERTAKEN``)."""
    bench = bm_tiny._load(ROOT, "BENCHMARK.json")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "kimi-k2-ep32-share")
    cfg = bm_tiny._load(ROOT, entry["file"])
    published = dict(
        hidden_size=7168, intermediate_size=18432, kv_lora_rank=512,
        q_lora_rank=1536, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, moe_intermediate_size=2048, n_routed_experts=384,
        n_shared_experts=1, num_experts_per_tok=8, num_attention_heads=64,
        vocab_size=163840, first_k_dense_replace=1, rope_theta=50000,
        routed_scaling_factor=2.827, max_position_embeddings=131072)
    assert {k: cfg[k] for k in published} == published
    assert cfg["rope_scaling"]["factor"] == 32
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "experts_held", "vocab_held"]
    assert (cfg["num_hidden_layers"], cfg["experts_held"],
            cfg["vocab_held"]) == (7, 12, 20480)
    from anomod.models import latent_moe as lm
    assert lm.param_count(lm.DecoderConfig.from_dict(cfg)) == 4_849_591_552
    cell = next(w for w in bench["workloads"]
                if w["name"] == "k2-fleet-overload")
    assert cell["chips"] == 1


# -- the guard of PR 33's refusal ---------------------------------------------

#: what each older cell reported at the parent (commit 21976d1), traced
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
        "idle_pct.outside_tick.k2"]}


def test_no_metric_of_the_new_cell_is_due_in_an_older_cell():
    bench = bm_tiny._load(ROOT, "BENCHMARK.json")
    new = [m for m in bench["per_layer"] if CELL in m.get("workloads", [])]
    assert len(new) == 25
    assert all(m["workloads"] == [CELL] for m in new)
    assert all("workloads" in m for m in bench["per_layer"])
    for cell, names in DUE_AT_PARENT.items():
        assert list(contract.metrics_due(bench, cell, True)) == names
    assert set(contract.metrics_due(bench, CELL, True)) \
        == {m["name"] for m in new}
    assert set(contract.metrics_due(bench, CELL, False)) \
        == {"served_spans_per_s", "setup_s"}
    served = next(m for m in bench["end_to_end"]
                  if m["name"] == "served_spans_per_s")
    assert served["workloads"] == ["tt-fleet-overload", "k2-fleet-overload",
                                   CELL]


def _k2_context(trace_dir):
    """What a traced run of ``k2-fleet-overload`` on the parent's program
    hands a reader: K2's counters and spans only."""
    k2 = ("seq_tokens", "seq_pairs", "seq_absorbed_tokens",
          "seq_absorbed_pairs", "seq_absorbed_group_blocks",
          "seq_expanded_keys", "seq_keys", "seq_pad_tokens", "seq_steps",
          "expert_tokens_max", "expert_tokens_mean", "sessions_rolled",
          "sessions_evicted", "pool_blocks_held")
    tracer = types.SimpleNamespace(
        spans=[["serve.tick", 1.0, 1.4], ["serve.seq_model", 1.1, 1.3]],
        seconds=lambda names, since=0.0: 0.2 * ("serve.seq_model" in names))
    trace = types.SimpleNamespace(
        window_s=1.0, busy_s=0.5, window=(0, 10 ** 9),
        devices={"/device:TPU:0": [("%fusion.1", 10, 20)]}, host=[])
    bench = bm_tiny._load(ROOT, "BENCHMARK.json")
    return {"counters": dict.fromkeys(k2, 7.0), "ticks": 4,
            "tracer": tracer, "window_t0": 0.5, "trace": trace,
            "trace_dir": trace_dir, "peaks": {"flops_per_s": 1e12,
                                              "hbm_bytes_per_s": 1e11},
            "cell": harness.load_cell(bench, "k2-fleet-overload", ROOT)}


@pytest.mark.parametrize("metric", [
    "mfu.n3s_step", "ssm_scan_roofline", "gqa_append_roofline",
    "moe_latent_grouped_roofline", "ssm_recurrent_token_share",
    "slot_evictions_per_tick"])
def test_a_new_reader_finds_nothing_in_another_programs_run(metric,
                                                            tmp_path):
    spec = bm_tiny._load(ROOT, "benchmark", "metrics", metric + ".json")
    read = harness.module_for("readers", spec["reader"]).read
    k2 = _k2_context(str(tmp_path))
    assert read(k2, **spec["args"]) is None
    # K2's counters under the new cell's own configuration, a plane that
    # counted nothing, and a context with nothing in it at all
    new = harness.load_cell(bm_tiny._load(ROOT, "BENCHMARK.json"), CELL,
                            ROOT)
    assert read(dict(k2, cell=new), **spec["args"]) is None
    from anomod.serve import seqplane
    zeros = dict.fromkeys(seqplane.COUNTERS, 0.0)
    assert read(dict(k2, cell=new, counters=zeros), **spec["args"]) is None
    assert read({}, **spec["args"]) is None


def test_the_step_share_reads_the_hybrid_planes_counters(tmp_path):
    ctx = _k2_context(str(tmp_path))
    ctx["cell"] = harness.load_cell(bm_tiny._load(ROOT, "BENCHMARK.json"),
                                    CELL, ROOT)
    ctx["counters"] = dict(
        ctx["counters"], seq_tokens=4000.0, ssm_recurrent_tokens=400.0,
        ssm_scan_tokens=3600.0, ssm_scan_pairs=90000.0, gqa_pairs=2.0e6,
        ssm_state_rows=2500.0, gqa_keys=50000.0, seq_steps=1.0)
    read = harness.module_for("readers", "mfu-step-hybrid").read
    c = ctx["cell"]["config"]
    assert read(ctx) == pytest.approx(
        100.0 * hybrid_work.step_flops(c, ctx["counters"]) / 1e12)
    # no xplane file under the directory: the scope's reader has no trace
    roof = harness.module_for("readers", "scope-roofline-hybrid").read
    assert roof(ctx, work="ssm", scope="anomod_seq_ssm") is None


class Counted:
    """A toy forward that does the algorithm's arithmetic and counts the
    multiply-adds of every product it takes."""

    def __init__(self):
        self.macs = 0

    def mm(self, a, b):
        self.macs += a.shape[0] * a.shape[1] * b.shape[1]
        return a @ b


def test_hybrid_work_counts_equal_a_counted_toy_forward():
    c = dict(bm_tiny_hybrid.PRESET, conv_kernel=4, vocab_held=96)
    D = c["hidden_size"]
    H, P, N, G = (c["mamba_num_heads"], c["mamba_head_dim"],
                  c["ssm_state_size"], c["n_groups"])
    di, Q = H * P, c["chunk_size"]
    C = di + 2 * G * N
    Hq, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    L, F = c["moe_latent_size"], c["moe_intermediate_size"]
    Fs, R = c["moe_shared_expert_intermediate_size"], c["n_routed_experts"]
    rng = np.random.default_rng(0)
    r = lambda *s: rng.standard_normal(s)
    # three chunks of one step: 1 token recurrent onto a carried state,
    # 19 tokens chunked (blocks of 8, 8 and 3), 5 tokens chunked; the
    # attention layers see 20, 7 and 0 cached tokens; 9 token-expert
    # pairs land here a layer
    chunks = [("recurrent", 20, 1), ("chunked", 7, 19), ("chunked", 0, 5)]
    pairs_here, scan_pairs = 9, 0
    k = Counted()
    for ch in c["hybrid_override_pattern"][:c["num_hidden_layers"]]:
        for form, cached, n in chunks:
            x = r(n, D)
            if ch == "M":
                k.mm(x, r(D, di + C + H))
                k.mm(r(C, c["conv_kernel"]), r(c["conv_kernel"], n))
                k.mm(r(n, di), r(di, D))
                if form == "recurrent":
                    for _ in range(n * H):
                        k.mm(r(P, 1), r(1, N))       # x (x) B into S
                        k.mm(r(P, N), r(N, 1))       # S C
                else:
                    for q in [Q] * (n // Q) + [n % Q] * bool(n % Q):
                        tri = q * (q + 1) // 2
                        for _ in range(G):           # C B^T, a group
                            k.mm(r(1, N), r(N, tri))
                        for _ in range(H):
                            k.mm(r(1, tri), r(tri, P))   # scores times x
                            k.mm(r(q, N), r(N, P))       # C S
                            k.mm(r(P, q), r(q, N))       # x^T B into S
                        scan_pairs += tri
            elif ch == "*":
                k.mm(x, r(D, Hq * hd + 2 * kv * hd))
                for i in range(n):
                    seen = cached + i + 1
                    for _ in range(Hq):
                        k.mm(r(1, hd), r(hd, seen))
                        k.mm(r(1, seen), r(seen, hd))
                k.mm(r(n, Hq * hd), r(Hq * hd, D))
            else:
                k.mm(x, r(D, R))
                k.mm(k.mm(x, r(D, L)), r(L, D))
                k.mm(k.mm(x, r(D, Fs)), r(Fs, D))
        if ch == "E":
            k.mm(k.mm(r(pairs_here, L), r(L, F)), r(F, L))
    for _, _, n in chunks:
        k.mm(r(n, D), r(D, c["vocab_held"]))
    n_m, n_a, n_e = 3, 1, 3
    n = {"seq_tokens": 25, "seq_steps": 1, "ssm_recurrent_tokens": 1,
         "ssm_scan_tokens": 24, "ssm_scan_blocks": 4,
         "ssm_scan_pairs": scan_pairs // n_m, "ssm_state_rows": 3 * n_m,
         "gqa_pairs": sum(m * cached + m * (m + 1) // 2
                          for _, cached, m in chunks),
         "gqa_keys": 21 + 26 + 5,
         "expert_tokens_mean": n_e * pairs_here / c["experts_held"]}
    assert scan_pairs // n_m == 2 * 36 + 6 + 15
    assert hybrid_work.step_flops(c, n) == 2 * k.macs
    ssm = hybrid_work.ssm_work(c, n)
    assert ssm["flops"] == 2 * n_m * (25 * 2 * H * P * N
                                      + n["ssm_scan_pairs"] * (G * N + H * P))
    assert ssm["bytes"] == 3 * n_m * 2 * H * P * N * 2 \
        + 25 * n_m * ((2 * di + 2 * G * N) * 2 + H * 4)
    gqa = hybrid_work.gqa_work(c, n)
    assert gqa["flops"] == 2 * n["gqa_pairs"] * 2 * Hq * hd
    assert gqa["bytes"] == 2 * (52 * 2 * kv * hd + 25 * 2 * Hq * hd)
    grouped = hybrid_work.grouped_work(c, n)
    assert grouped["flops"] == 2 * n_e * pairs_here * 2 * L * F
    assert grouped["bytes"] == 2 * (n_e * c["experts_held"] * 2 * L * F
                                    + n_e * pairs_here * 2 * (L + F))
