"""``k2-fleet-overload`` rehearsed at a tiny size on the CPU (its twin is
added to a temp copy of the benchmark by files and entries alone), the
reduction that finds the attention kernels' ops by their scope, and the
model's FLOP and byte counts against a counted toy forward."""

import json
import os

import bm_tiny
import bm_tiny_seq
from bm_tiny_seq import tiny_seq_root  # noqa: F401  (the fixture)
import numpy as np
import pytest

from benchmark import contract, harness, model_work

ROOT = bm_tiny.ROOT


@pytest.fixture
def small_tiles(monkeypatch):
    """Attention tiles cut to the tiny preset's sizes, so that both forms
    are reached by size alone as at published widths."""
    from anomod.ops import latent_attention as la
    for name, value in (("Q_TILE", 8), ("KV_BLOCKS", 1), ("GROUP", 2),
                        ("BATCH", 8)):
        monkeypatch.setattr(la, name, value)


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_twin_prints_the_contracts_line(tiny_seq_root, small_tiles,
                                             trace):
    rc, line, err = bm_tiny.run_cell(tiny_seq_root, bm_tiny_seq.TINY_CELL,
                                     trace, seed=5000000011)
    assert rc == 0, err
    assert line["correct"] is True, err
    assert line["failed"] == 0 and line["attempted"] > 0
    bench = bm_tiny._load(tiny_seq_root, "BENCHMARK.json")
    assert contract.check_last_line(line, bench, bm_tiny_seq.TINY_CELL,
                                    bool(trace)) == []
    got = set(line["metrics"])
    if trace:
        assert {"mfu.k2_step", "seq_model_ms", "seq_stage_ms",
                "seq_score_ms", "expert_load_max_over_mean",
                "absorbed_token_share", "device_idle_pct.k2"} <= got
        assert 0 < line["metrics"]["absorbed_token_share"]["value"] < 100
        assert line["metrics"]["expert_load_max_over_mean"]["value"] >= 1
    else:
        assert got == {"served_spans_per_s", "setup_s"}
    notes = line["notes"]
    assert notes["sessions_rolled"] > 0 and notes["sessions_evicted"] > 0
    assert notes["pool_blocks_held_at_start"] >= 0.8 * notes["pool_blocks"]


def test_tiny_twins_control_is_not_correct(tiny_seq_root, small_tiles):
    rc, sound, err = bm_tiny.run_cell(tiny_seq_root, bm_tiny_seq.TINY_CELL,
                                      0, seed=91)
    assert rc == 0 and sound["correct"] is True, err
    rc, line, err = bm_tiny.run_cell(tiny_seq_root, bm_tiny_seq.TINY_CELL,
                                     0, seed=91, control=1)
    assert rc == 0 and line["correct"] is False, err
    failed = {c["name"] for c in line["checks"] if not c["ok"]}
    assert failed and failed <= {"surprisal_gap_mean", "logit_gap",
                                 "surprisal_gap_group_max"}
    # each limit lies between the program's reading and the control's
    limit = {c["name"]: c for c in sound["checks"]}
    for name in failed:
        assert limit[name]["value"] < limit[name]["limit"] \
            < sound["notes"]["control_" + name]


def test_a_plane_that_scores_wrongly_is_not_correct(tiny_seq_root,
                                                    small_tiles,
                                                    monkeypatch):
    # the step leaves its cache unwritten: every later chunk attends to
    # zeros where its session's past should be
    from anomod.models import latent_moe as lm
    real = lm.append_step

    def stale(cfg, params, pool, h_last, plan):
        out = real(cfg, params, pool, h_last, plan)
        return (pool,) + tuple(out[1:])

    monkeypatch.setattr(lm, "append_step", stale)
    rc, line, err = bm_tiny.run_cell(tiny_seq_root, bm_tiny_seq.TINY_CELL,
                                     0, seed=92)
    assert rc == 0 and line["correct"] is False, err
    assert "surprisal_gap_mean" in {c["name"] for c in line["checks"]
                                    if not c["ok"]}


def test_a_program_that_draws_other_weights_is_not_correct(tiny_seq_root,
                                                           small_tiles,
                                                           monkeypatch):
    # one norm weight of one layer a hundredth off: far too little for the
    # gaps to tell, and the reference's own draw does not share it
    from anomod.models import latent_moe as lm
    real = lm.init_params

    def off(cfg, seed, dtype=None):
        params = real(cfg, seed, dtype)
        params["moe"]["kv_norm"] = params["moe"]["kv_norm"].at[1, 0].mul(1.01)
        return params

    monkeypatch.setattr(lm, "init_params", off)
    rc, line, err = bm_tiny.run_cell(tiny_seq_root, bm_tiny_seq.TINY_CELL,
                                     0, seed=93, seconds=1.0)
    assert rc == 0 and line["correct"] is False, err
    assert {c["name"]: c["value"] for c in line["checks"]
            if not c["ok"]} == {"weights_differing": 1.0}


def test_one_slots_fault_fails_its_own_mean_not_the_windows():
    from benchmark.reference import latent_moe_decoder as ref
    rng = np.random.default_rng(0)
    lengths = [8192, 4000, 100] + [300] * 40 + [10] * 12
    tenants = [0, 0, 0] + list(range(1, 41)) + [41] * 12
    reference = {(t, i): (rng.uniform(2, 9, n), {})
                 for i, (t, n) in enumerate(zip(tenants, lengths))}
    sound = {k: (s + 0.01, {}) for k, (s, _) in reference.items()}
    got = ref.compare(sound, reference)
    assert got["surprisal_gap_group_max"] == pytest.approx(0.01)
    assert got["groups_with_a_mean"] == 43 + 42   # sessions + tenants

    def off_by_5(k):
        return ref.compare({**sound, k: (reference[k][0] + 5.0, {})},
                           reference)

    # the busiest tenant's last, 100-span session scored against another
    # session's blocks (about 5 nat a span): lost in the window's mean
    # and in its tenant's, plain in its own
    got = off_by_5((0, 2))
    assert got["surprisal_gap_mean"] < 0.035
    assert got["surprisal_gap_group_max"] == pytest.approx(5.0)
    # one 10-span session of a small tenant: too short for a mean of its
    # own, plain in its tenant's 120 spans
    got = off_by_5((41, 50))
    assert got["surprisal_gap_mean"] < 0.0125
    assert got["surprisal_gap_group_max"] == pytest.approx(
        (5.0 * 10 + 0.01 * 110) / 120)
    # under the least count nothing has a mean of its own
    assert ref.compare(sound, reference, least=20000)[
        "surprisal_gap_group_max"] == 0.0


def test_the_control_reads_sessions_of_the_windows_own_lengths():
    drv = harness.module_for("drivers", "fleet-seq-open")
    touched = {(0, 3): np.zeros(8192), (0, 4): np.zeros(500),
               (1, 1): np.zeros(8192), (1, 2): np.zeros(7000),
               (5, 0): np.zeros(12), (7, 0): np.zeros(300),
               (9, 0): np.zeros(90), (9, 1): np.zeros(40)}
    got = drv.control_sessions(touched, 2147483650, 2, 17000, 16)
    assert got[:2] == [(0, 3), (1, 1)]          # the busiest's longest first
    assert (5, 0) not in got and (1, 2) not in got   # too short; over budget
    assert sum(len(touched[k]) for k in got) <= 17000
    assert set(got[2:]) <= {(0, 4), (7, 0), (9, 0), (9, 1)} and got[2:]
    assert got == drv.control_sessions(touched, 2147483650, 2, 17000, 16)


def test_benchmark_json_is_sound_and_the_config_keeps_published_widths():
    bench = bm_tiny._load(ROOT, "BENCHMARK.json")
    assert contract.check_benchmark_json(bench) == []
    entry = next(c for c in bench["configs"]
                 if c["name"] == bm_tiny_seq.CONFIG)
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
                if w["name"] == bm_tiny_seq.CELL)
    assert cell["chips"] == 1
    served = next(m for m in bench["end_to_end"]
                  if m["name"] == "served_spans_per_s")
    assert served["workloads"] == ["tt-fleet-overload", bm_tiny_seq.CELL]


def test_scoped_ops_reads_the_metadata_of_a_recorded_v5e_trace():
    mod = harness.module_for("readers", "scope-roofline")
    with open(os.path.join(ROOT, "benchmark", "testdata",
                           "replay-v5e.xplane.pb"), "rb") as f:
        raw = memoryview(f.read())
    names = mod.scoped_ops(raw, "jit")
    assert len(names) == 4
    assert any(n.startswith("%run.1 = ") and "custom-call" in n
               for n in names)
    assert mod.scoped_ops(raw, "anomod_seq_mla") == set()


class Counted:
    """A toy forward that does the algorithm's arithmetic and counts the
    multiply-adds of every product it takes."""

    def __init__(self):
        self.macs = 0

    def mm(self, a, b):
        self.macs += a.shape[0] * a.shape[1] * b.shape[1]
        return a @ b


def test_model_work_counts_equal_a_counted_toy_forward():
    c = dict(bm_tiny_seq.PRESET, first_k_dense_replace=1, vocab_held=96,
             n_shared_experts=1)
    D, H = c["hidden_size"], c["num_attention_heads"]
    nope, rope, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    R, Q = c["kv_lora_rank"], c["q_lora_rank"]
    rng = np.random.default_rng(0)
    # two chunks of one step: 5 tokens absorbed onto 20 cached, 12 tokens
    # expanded onto 7 cached; 9 token-expert pairs land here per layer
    chunks = [("absorbed", 20, 5), ("expanded", 7, 12)]
    pairs_here = 9
    k = Counted()
    r = lambda *s: rng.standard_normal(s)
    for kind in ["dense"] + ["moe"] * 2:
        for form, cached, n in chunks:
            x = r(n, D)
            k.mm(k.mm(x, r(D, Q)), r(Q, H * (nope + rope)))
            k.mm(x, r(D, R + rope))
            total = cached + n
            if form == "expanded":
                k.mm(r(total, R), r(R, H * (nope + v)))
            for i in range(n):
                seen = cached + i + 1
                for _ in range(H):
                    if form == "absorbed":
                        q_lat = k.mm(r(1, nope), r(nope, R))
                        k.mm(np.hstack([q_lat, r(1, rope)]),
                             r(R + rope, seen))
                        k.mm(k.mm(r(1, seen), r(seen, R)), r(R, v))
                    else:
                        k.mm(r(1, nope + rope), r(nope + rope, seen))
                        k.mm(r(1, seen), r(seen, v))
            k.mm(r(n, H * v), r(H * v, D))
            if kind == "dense":
                mid = k.mm(x, r(D, 2 * c["intermediate_size"]))
                k.mm(mid[:, :c["intermediate_size"]],
                     r(c["intermediate_size"], D))
            else:
                F = c["moe_intermediate_size"]
                k.mm(x, r(D, c["n_routed_experts"]))
                k.mm(k.mm(x, r(D, 2 * F))[:, :F], r(F, D))
        if kind == "moe":
            F = c["moe_intermediate_size"]
            k.mm(k.mm(r(pairs_here, D), r(D, 2 * F))[:, :F], r(F, D))
    for _, _, n in chunks:
        k.mm(r(n, D), r(D, c["vocab_held"]))
    n = {"seq_tokens": 17, "seq_steps": 1,
         "seq_pairs": sum(m * cached + m * (m + 1) // 2
                          for _, cached, m in chunks),
         "seq_absorbed_pairs": 5 * 20 + 15, "seq_absorbed_tokens": 5,
         "seq_expanded_keys": 19, "seq_keys": 25 + 19,
         "expert_tokens_mean": 2 * pairs_here / c["experts_held"]}
    assert model_work.step_flops(c, n) == 2 * k.macs
    att = model_work.attention_work(c, n)
    assert att["flops"] == model_work.attention_flops(c, n) > 0
    assert att["bytes"] == 3 * 2 * ((25 + 19) * (R + rope)
                                    + 17 * H * (nope + rope + v))
    grouped = model_work.grouped_work(c, n)
    assert grouped["flops"] == 2 * 2 * pairs_here * 3 * D \
        * c["moe_intermediate_size"]
    assert grouped["bytes"] == 2 * (
        2 * c["experts_held"] * 3 * D * c["moe_intermediate_size"]
        + 2 * pairs_here * (2 * D + 3 * c["moe_intermediate_size"]))
