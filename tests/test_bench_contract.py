"""Driver-contract tests for bench.py in its explicit-CPU mode
(``JAX_PLATFORMS=cpu``): exit 0 and exactly one JSON line carrying the
schema the driver records, the device fields, and a unit that is never a
chip unit off-chip.  Without the explicit variable — or when a Pallas
kernel is asked for off-TPU — the bench must exit non-zero instead."""

import json
import os
import subprocess
import sys
from pathlib import Path

BENCH = str(Path(__file__).parent.parent / "bench.py")


def _env(tmp_path, **extra):
    env = dict(os.environ)
    for name in ("ANOMOD_BENCH_KERNEL", "ANOMOD_BENCH_REPLICATE"):
        env.pop(name, None)
    env["JAX_PLATFORMS"] = "cpu"
    # keep the provenance record out of the repo's bench_runs/
    env["ANOMOD_BENCH_RUNS_DIR"] = str(tmp_path / "runs")
    # fresh ingest cache: the run must be cold-then-self-warming
    env["ANOMOD_CACHE_DIR"] = str(tmp_path / "cache")
    env.update(extra)
    return env


def _json_line(stdout):
    lines = [l for l in stdout.strip().splitlines() if l.startswith("{")]
    assert len(lines) == 1, stdout
    return json.loads(lines[0])


def test_bench_explicit_cpu_contract(tmp_path):
    r = subprocess.run([sys.executable, BENCH, "200"], capture_output=True,
                       text=True, timeout=420, env=_env(tmp_path))
    assert r.returncode == 0, r.stderr[-500:]
    out = _json_line(r.stdout)
    assert out["metric"] == "tt_replay_throughput"
    # every line names its device; a CPU rate never carries a chip unit
    assert out["platform"] == "cpu" and out["device_kind"]
    assert out["n_devices"] >= 1
    assert out["unit"] == "spans/sec/cpu-host"
    assert out["value"] > 0 and out["vs_baseline"] > 0
    assert out["kernel"] == "numpy"        # the host engine on a host
    assert out["jit_cache_dir"]
    # median-of-N: the recorded wall is the median of >=3 raw repeats
    assert len(out["raw_wall_s"]) >= 3
    assert out["wall_s"] == sorted(out["raw_wall_s"])[len(out["raw_wall_s"]) // 2]
    # ingest split: a fresh cache dir means a cold first load, an honest
    # recorded parse_s, and a warm-vs-cold throughput metric in the line
    assert out["cache_hit"] is False
    assert out["parse_s"] > 0
    tp = out["tt_ingest_throughput"]
    assert tp["unit"] == "experiments/sec"
    assert tp["warm"] > 0 and tp["cold"] > 0
    assert tp["speedup"] > 1.0, \
        "warm columnar read must beat cold synth+concat"
    # provenance record: committed-capture schema with device + versions + SHA
    runs = list((tmp_path / "runs").glob("*.json"))
    assert len(runs) == 1
    rec = json.loads(runs[0].read_text())
    for field in ("metric", "value", "unit", "timestamp_utc", "git_sha",
                  "jax_version", "device", "platform", "device_kind",
                  "n_devices", "kernel", "raw_wall_s"):
        assert field in rec, field
    assert rec["device"] == out["device"]


def test_bench_refuses_to_run_off_tpu(tmp_path):
    """No fallback that hides the device: without an EXPLICIT
    JAX_PLATFORMS=cpu a chipless box is an error in both modes, and a
    Pallas kernel asked for off-TPU is an error, not a downgrade."""
    for argv in (["200"], ["--mode", "serve"]):
        # JAX_PLATFORMS empty: JAX picks whatever it finds (here: no TPU)
        r = subprocess.run([sys.executable, BENCH, *argv],
                           capture_output=True, text=True, timeout=420,
                           env=_env(tmp_path, JAX_PLATFORMS=""))
        assert r.returncode != 0
        assert "no TPU" in r.stderr
        assert not [l for l in r.stdout.splitlines() if l.startswith("{")]
    for kernel in ("pallas", "pallas-sorted"):
        r = subprocess.run(
            [sys.executable, BENCH, "200"], capture_output=True, text=True,
            timeout=420, env=_env(tmp_path, ANOMOD_BENCH_KERNEL=kernel))
        assert r.returncode != 0
        assert "needs a TPU backend" in r.stderr
    assert not list((tmp_path / "runs").glob("*.json"))


def test_bench_replicate_override_contract(tmp_path):
    """ANOMOD_BENCH_REPLICATE: a valid override resizes the dispatch and
    is recorded in replicate_used; a malformed value is an error."""
    r = subprocess.run(
        [sys.executable, BENCH, "200"], capture_output=True, text=True,
        timeout=420, env=_env(tmp_path, ANOMOD_BENCH_REPLICATE="3"))
    assert r.returncode == 0, r.stderr[-500:]
    out = _json_line(r.stdout)
    assert out["replicate_used"] == 3
    assert out["n_spans"] % 3 == 0

    r = subprocess.run(
        [sys.executable, BENCH, "200"], capture_output=True, text=True,
        timeout=420, env=_env(tmp_path, ANOMOD_BENCH_REPLICATE="4k"))
    assert r.returncode != 0
    assert "ANOMOD_BENCH_REPLICATE" in r.stderr


def test_bench_serve_mode_contract(tmp_path):
    """`bench.py --mode serve` in the explicit-CPU mode: exit 0, one JSON
    line with sustained spans/sec, p99 admission->scored latency and the
    shed fraction under the seeded 2x overload, plus a provenance
    record."""
    env = _env(
        tmp_path,
        # tiny fleet keeps the tier-1 contract fast; the protocol (2x
        # overload, seeded) is what's under test, not the absolute number
        ANOMOD_SERVE_BENCH_CAPACITY="1500",
        ANOMOD_SERVE_BENCH_DURATION="45",
        ANOMOD_SERVE_BENCH_TENANTS="12",
        # small registered-fleet sweep keeps the census probe fast; the
        # default is 1e3/1e4/1e5
        ANOMOD_CENSUS_SWEEP="400,1600,6400")
    r = subprocess.run([sys.executable, BENCH, "--mode", "serve"],
                       capture_output=True, text=True, timeout=420, env=env)
    assert r.returncode == 0, r.stderr[-800:]
    out = _json_line(r.stdout)
    assert out["metric"] == "serve_sustained_throughput"
    assert out["unit"] == "spans/sec"
    assert out["platform"] == "cpu" and out["device_kind"]
    assert out["n_devices"] >= 1
    assert out["value"] > 0
    assert out["overload"] == 2.0
    # 2x overload against a bounded backlog MUST shed
    assert 0.2 < out["shed_fraction"] < 0.8
    assert out["p99_admission_to_scored_latency_s"] is not None
    assert out["served_spans"] > 0
    assert out["offered_spans"] > out["served_spans"]
    assert out["device"]
    # telemetry pair (observability PR): same seed off/on, overhead
    # fraction recorded, the enabled leg's registry snapshotted inline
    tel = out["telemetry"]
    assert tel["spans_per_sec_off"] > 0 and tel["spans_per_sec_on"] > 0
    assert 0.0 <= tel["overhead_fraction"] < 1.0
    assert tel["journal_samples"] > 0
    assert out["obs_snapshot"]["anomod_serve_served_spans_total"][
        "value"] == out["served_spans"]
    # shard-scaling legs (scale-out PR): 2/4 workers then a warm
    # 1-shard reference, all on the same seed; shedding and p99 are
    # shard-count-invariant by construction
    scaling = out["shard_scaling"]
    assert set(scaling) == {"1", "2", "4"}
    assert scaling["1"]["speedup_vs_1_shard"] == 1.0
    for leg in scaling.values():
        assert leg["spans_per_sec"] > 0
        assert leg["shed_fraction"] == out["shed_fraction"]
        assert leg["p99_latency_s"] == \
            out["p99_admission_to_scored_latency_s"]
        assert leg["shard_imbalance"] >= 1.0
    # the compile-cache block: where it lives, one grid wall per leg
    assert out["jit_cache"]["dir"]
    assert len(out["jit_cache"]["grid_compile_s_per_runner"]) == 3
    runs = list((tmp_path / "runs").glob("*.json"))
    assert len(runs) == 1
    rec = json.loads(runs[0].read_text())
    assert rec["metric"] == "serve_sustained_throughput"
    assert rec["shed_fraction"] == out["shed_fraction"]
    # the committed self-scrape capture: TT-CSV sidecar next to the
    # record, loadable by the framework's own loader
    scrape = out["self_scrape"]
    csvs = list((tmp_path / "runs").glob("*_selfscrape.csv"))
    assert len(csvs) == 1
    assert scrape["samples"] > 0
    from anomod.io.metrics import load_tt_metric_csv
    batch = load_tt_metric_csv(csvs[0])
    assert batch is not None and batch.n_samples == scrape["samples"]
    # fused-vs-unfused on the same seed (PR-4): the tenant-fused
    # lane-stacked path is the headline, the unfused leg rides along
    fd = out["fused_dispatch"]
    assert fd["fused"] is True
    assert fd["spans_per_sec_fused"] == out["value"]
    assert fd["spans_per_sec_unfused"] > 0
    assert fd["speedup"] > 0
    assert fd["fused_dispatches"] > 0
    assert fd["lane_buckets"]
    assert 0.0 <= fd["lane_pad_waste"] < 1.0
    # staging decomposition (ISSUE-7, five-legged since ISSUE-8):
    # stage/dispatch/fold/score/other walls on the native AND
    # interpreter-staging legs of the same seed, plus the byte-parity
    # bits the native path is pinned to
    st = out["staging"]
    assert st["native_mode"] in ("auto", "on", "off")
    assert st["native_available"] in (True, False)
    for leg in ("wall_s_native", "wall_s_python"):
        walls = st[leg]
        assert set(walls) == {"stage", "dispatch", "fold", "score",
                              "other", "serve"}
        assert all(v >= 0 for v in walls.values())
        assert walls["stage"] + walls["dispatch"] + walls["fold"] \
            + walls["score"] <= walls["serve"] + 1e-6
    assert st["spans_per_sec_native"] > 0
    assert st["spans_per_sec_python"] > 0
    if st["native_available"] and st["native_mode"] != "off":
        assert st["native_staging_headline"] is True
        assert st["native_staged_dispatches"] > 0
    par = st["parity"]
    assert par["alerts_identical"] is True
    assert par["states_identical"] is True
    assert par["p99_identical"] is True
    assert par["shed_identical"] is True
    # tenant-state residency (ISSUE-8): the device-pool headline vs the
    # host-seam reference on the same seed, five-leg decompositions, the
    # fold+score+other share, and the pool's byte-parity bits
    ss = out["serve_state"]
    assert ss["headline"] == "device"
    assert ss["pool_engine"] in ("numpy", "jax")
    for leg in ("wall_s_device", "wall_s_host_seam"):
        assert set(ss[leg]) == {"stage", "dispatch", "fold", "score",
                                "other", "serve"}
    for share in ("fold_score_other_share_device",
                  "fold_score_other_share_host_seam"):
        assert 0.0 <= ss[share] <= 1.0
    assert ss["spans_per_sec_device"] > 0
    assert ss["spans_per_sec_host_seam"] > 0
    par = ss["parity"]
    assert par["alerts_identical"] is True
    assert par["states_identical"] is True
    assert par["p99_identical"] is True
    assert par["shed_identical"] is True
    # online-RCA block (ISSUE-6): alert→culprit numbers on the same
    # seed plus the determinism pins the capture must carry
    rca = out["rca"]
    assert rca["enabled"] is True
    assert rca["n_rca_runs"] > 0
    assert set(rca["topk_hits"]) == set(rca["topk_hit_rate"]) \
        == set(rca["topk_hit_rate_given_detected"]) == {"1", "3", "5"}
    assert rca["n_fault_tenants"] == 2
    assert 0 <= rca["eligible_fault_tenants"] <= rca["n_fault_tenants"]
    for k in ("1", "3", "5"):
        rate = rca["topk_hit_rate"][k]
        assert rate is not None and 0.0 <= rate <= 1.0
    # hit-rate is monotone in k by construction
    assert rca["topk_hit_rate"]["1"] <= rca["topk_hit_rate"]["3"] \
        <= rca["topk_hit_rate"]["5"]
    assert rca["alert_to_culprit_latency_s"]["p99_s"] is not None
    assert rca["queue_delay_virtual_s"]["p50_s"] is not None
    assert rca["rca_wall_s"] > 0
    assert rca["spans_per_sec_rca_on"] > 0
    par = rca["parity"]
    assert par["alerts_identical_to_rca_off"] is True
    assert par["states_identical_to_rca_off"] is True
    assert par["p99_identical_to_rca_off"] is True
    assert par["shed_identical_to_rca_off"] is True
    assert par["verdicts_identical_1_vs_2_shards"] is True
    # flight-recorder block (ISSUE-9): the always-on tick journal's
    # overhead leg, zero ring drops (no silent loss), and the read-side
    # byte-parity bits against the no-recorder leg
    fl = out["flight"]
    assert fl["enabled_headline"] is True
    assert fl["recorded_ticks"] > 0
    assert fl["dropped_ticks"] == 0
    assert fl["digest_every"] >= 1
    assert fl["spans_per_sec_on"] == out["value"]
    assert fl["spans_per_sec_off"] > 0
    assert 0.0 <= fl["overhead_fraction"] < 1.0
    par = fl["parity"]
    assert par["alerts_identical"] is True
    assert par["states_identical"] is True
    assert par["p99_identical"] is True
    assert par["shed_identical"] is True
    # recovery block (ISSUE-10): the checkpoint cadence priced in-run
    # on the headline (ckpt_wall / serve_wall — no A/B leg by design),
    # the chaos leg's crash/restore counts, and the no-score-gap
    # parity bits (byte-identical decisions + equal canonical flight
    # journals)
    rc = out["recovery"]
    assert rc["supervised_headline"] is True
    assert rc["ckpt_every"] >= 1
    assert rc["n_checkpoints"] >= 1
    assert rc["ckpt_wall_s"] >= 0
    assert 0.0 <= rc["ckpt_overhead_fraction"] < 1.0
    assert rc["chaos_script"]
    assert rc["n_shard_crashes"] == 3          # the scripted campaign
    assert rc["n_restored_ticks"] >= rc["n_shard_crashes"]
    assert rc["n_quarantined"] == 0            # repeat=1 faults recover
    assert rc["n_migrated_tenants"] == 0
    assert rc["mttr_ticks"] >= 1
    assert rc["recovery_wall_s"] >= 0
    par = rc["parity"]
    assert par["alerts_identical"] is True
    assert par["states_identical"] is True
    assert par["p99_identical"] is True
    assert par["shed_identical"] is True
    assert par["journal_canonical_identical"] is True
    # performance-observatory block (ISSUE-14): the dispatch-lifecycle
    # timeline's overlap-headroom bound, the measured fold WAIT, the
    # per-tick raw_wall_s samples `anomod perf diff` bootstraps over,
    # the on/off overhead fraction, and the read-side parity bits
    pf = out["perf"]
    assert pf["enabled_headline"] is False     # deep-dive opt-in, off
    assert pf["events_recorded"] > 0
    assert pf["events_dropped"] == 0
    assert pf["overlap_headroom_s"] >= 0.0
    assert pf["fold_wait_s"] >= 0.0
    assert pf["fold_wait_s"] <= pf["fold_wall_s"] + 1e-6
    # the headroom bound can never exceed the wait it would hide
    assert pf["overlap_headroom_s"] <= pf["fold_wait_s"] + 1e-6
    bf = pf["bubble_fractions"]
    assert set(bf) == {"stage", "dispatch", "score",
                       "fold_wait_of_fold", "fold_wait_of_serve",
                       "headroom_of_fold", "headroom_of_serve"}
    assert all(0.0 <= v <= 1.0 for v in bf.values())
    # one serve-wall sample per headline tick: the bootstrap's input
    assert len(pf["raw_wall_s"]) > 0
    assert all(t >= 0 for t in pf["raw_wall_s"])
    assert len(pf["perf_leg"]["raw_wall_s"]) > 0
    assert pf["noise_floor"] > 0
    assert pf["spans_per_sec_on"] > 0
    assert pf["spans_per_sec_off"] == out["value"]
    assert 0.0 <= pf["overhead_fraction"] < 1.0
    par = pf["parity"]
    assert par["alerts_identical"] is True
    assert par["states_identical"] is True
    assert par["p99_identical"] is True
    assert par["shed_identical"] is True
    # a self-diff of the finished capture must be clean: decisions
    # byte-exact by identity, walls trivially within the noise model
    from anomod.obs.perf import diff_captures
    self_diff = diff_captures(out, json.loads(json.dumps(out)))
    assert self_diff["status"] == "ok"
    assert self_diff["decisions"]["identical"] is True
    # fleet-census block (ISSUE-15): the deterministic resident-bytes
    # census, the hot-set/Zipf census, the registered-fleet sweep's
    # fitted O(registered) baseline slopes, one informational RSS
    # sample (never a pin), and the read-side parity bits
    cn = out["census"]
    assert cn["enabled_headline"] is False     # deep-dive opt-in, off
    assert cn["census_ticks"] >= 1
    rb = cn["resident_bytes"]
    assert rb["total"] > 0
    assert rb["pool_reconciled"] is True
    assert rb["by_plane"]["pool"] > 0
    assert rb["by_plane"]["admission"] > 0
    assert rb["total"] == sum(rb["by_plane"].values())
    hs = cn["hot_set"]
    assert hs["registered"] == out["n_tenants"]
    assert 0 < hs["ever_served"] <= hs["registered"]
    assert 0.0 < hs["occupancy_vs_registered"] <= 1.0
    assert hs["hot_by_decay"]
    assert hs["zipf_alpha"] is None or hs["zipf_alpha"] > 0
    assert len(hs["coldest"]) >= 1
    # informational cross-check only: present, never compared
    assert cn["process_resident_memory_bytes"] is None \
        or cn["process_resident_memory_bytes"] > 0
    sweep = cn["sweep"]
    assert sweep["sizes"] == [400, 1600, 6400]     # the env override
    assert len(sweep["rows"]) == 3
    bytes_by_size = [r["resident_bytes"] for r in sweep["rows"]]
    assert bytes_by_size == sorted(bytes_by_size)  # O(registered) grows
    assert all(r["pool_reconciled"] is True for r in sweep["rows"])
    assert sweep["bytes_slope_per_registered"] > 0
    assert "wall_slope_s_per_registered" in sweep
    assert cn["spans_per_sec_on"] > 0
    assert cn["spans_per_sec_off"] == out["value"]
    # the overhead is measured IN-RUN (the ckpt_wall idiom); a wall
    # ratio on a shared CPU is not a contract — presence and range only
    assert cn["census_wall_s"] >= 0
    assert 0.0 <= cn["census_overhead_in_run"] < 1.0
    assert 0.0 <= cn["overhead_fraction"] < 1.0
    par = cn["parity"]
    assert par["alerts_identical"] is True
    assert par["states_identical"] is True
    assert par["p99_identical"] is True
    assert par["shed_identical"] is True
    assert par["journal_canonical_identical"] is True
    # live-feed block (ISSUE-18): the closed telemetry loop — the
    # self-scrape leg's throughput/poll counters, the feed-lag
    # histogram, and the five live-vs-replay parity bits (the
    # --from-live reproducibility pin the capture carries)
    lf = out["live_feed"]
    assert lf["spans_per_s"] > 0
    assert lf["served_spans"] > 0
    assert lf["n_polls"] >= 1
    assert lf["n_samples"] >= 1
    assert lf["gaps"] >= 0
    assert lf["journal_entries"] >= lf["n_polls"]
    assert set(lf["feed_lag"]) == {"p50", "p99"}
    # the scrape path observes the effective ingest lag per poll, so a
    # consuming leg always populates the histogram
    assert lf["feed_lag"]["p50"] is not None and lf["feed_lag"]["p50"] >= 0
    assert lf["feed_lag"]["p99"] is not None and lf["feed_lag"]["p99"] >= 0
    par = lf["parity"]
    assert par["alerts_identical"] is True
    assert par["states_identical"] is True
    assert par["p99_identical"] is True
    assert par["shed_identical"] is True
    assert par["journal_canonical_identical"] is True
    # a census self-diff of the finished capture must be clean (the
    # tiering before/after judge's identity case)
    from anomod.obs.census import diff_census
    cen_diff = diff_census(out, json.loads(json.dumps(out)))
    assert cen_diff["status"] == "ok"
    assert cen_diff["sweep_comparable"] is True
    # state-tiering block (ISSUE-19): the tiered registered-fleet
    # sweep (one extra 10x top point past the census sweep — the
    # committed capture's 1e6-registered / 1e3-hot mode), the
    # demote/spill/promote/miss counters and prefetch-hidden fraction
    # from the sub-capacity parity pair, and the parity bits — every
    # decision plane identical to the never-evicted twin, the journal
    # byte-equal across the same-config rerun
    tr = out["tiering"]
    assert tr["tier_hot"] > 0
    tsw = tr["sweep"]
    assert tsw["sizes"] == sweep["sizes"] + [10 * max(sweep["sizes"])]
    assert len(tsw["rows"]) == len(tsw["sizes"])
    assert all(r["pool_reconciled"] is True for r in tsw["rows"])
    assert tr["bytes_slope_per_registered"] \
        == tsw["bytes_slope_per_registered"]
    assert tr["bytes_slope_per_registered"] > 0
    assert tr["baseline_bytes_slope_per_registered"] \
        == sweep["bytes_slope_per_registered"]
    # tiering must never COST resident bytes per registered tenant
    assert tr["bytes_slope_per_registered"] \
        <= tr["baseline_bytes_slope_per_registered"]
    assert "wall_slope_s_per_registered" in tr
    ctr = tr["counters"]
    assert ctr["demotions_warm"] >= 1
    assert ctr["demotions_cold"] >= 1
    assert ctr["promotions"] >= 1
    assert ctr["tier_misses"] >= 1
    assert tr["prefetch_joins"] >= 1
    assert 0.0 <= tr["prefetch_hidden_fraction"] <= 1.0
    assert tr["tier_wall_s"] >= 0
    assert tr["tier_empty_at_end"] is True
    par = tr["parity"]
    assert par["alerts_identical"] is True
    assert par["states_identical"] is True
    assert par["p99_identical"] is True
    assert par["shed_identical"] is True
    assert par["served_identical"] is True
    assert par["journal_rerun_identical"] is True
    # elasticity block (ISSUE-13): the policy leg under the scripted
    # surge must complete a full scaling episode (>=1 up AND >=1 down)
    # and carry the elastic determinism parity bits — byte-identical
    # decisions and an equal canonical journal vs the static leg
    el = out["elasticity"]
    assert el["policy"] == "auto"
    assert el["chaos_script"].startswith("surge@")
    assert el["min_shards"] == 1 and el["max_shards"] == 2
    assert el["n_scale_ups"] >= 1
    assert el["n_scale_downs"] >= 1
    assert el["n_policy_migrations"] >= 1
    assert el["migrated_spans"] >= 0
    assert el["peak_shards"] == 2
    assert el["policy_wall_s"] >= 0
    assert el["shard_imbalance_static"] >= 1.0
    assert el["shard_imbalance_elastic"] >= 1.0
    kinds = [ev["kind"] for ev in el["episodes"]]
    assert "scale_up" in kinds and "scale_down" in kinds
    assert el["spans_per_sec_static"] > 0
    assert el["spans_per_sec_elastic"] > 0
    par = el["parity"]
    assert par["alerts_identical"] is True
    assert par["states_identical"] is True
    assert par["p99_identical"] is True
    assert par["shed_identical"] is True
    assert par["journal_canonical_identical"] is True
    # process-shard block (ISSUE-20): the GIL-free worker quartet —
    # thread-vs-process and N-vs-1-process parity bits, the sparse
    # barrier fold's payload bytes against the dense walk, and the
    # honesty bit that gates throughput-scaling claims on core count
    ps = out["proc_shard"]
    assert ps["worker_headline"] == "thread"
    assert ps["fold_headline"] in ("dense", "sparse")
    assert ps["n_cores"] >= 1
    assert ps["scaling_quotable"] is (ps["n_cores"] >= 4)
    if not ps["scaling_quotable"]:
        assert ps["speedup_process_vs_thread"] is None
    assert ps["spans_per_sec_thread_2shard"] > 0
    assert ps["spans_per_sec_process_2shard"] > 0
    assert ps["spans_per_sec_process_1shard"] > 0
    for leg in ("wall_s_thread", "wall_s_process"):
        walls = ps[leg]
        assert set(walls) == {"stage", "dispatch", "fold", "score",
                              "other", "serve"}
        assert all(v >= 0 for v in walls.values())
    # the sparse fold must shrink the barrier payload vs the dense walk
    assert ps["fold_payload_bytes_dense"] > 0
    assert 0 < ps["fold_payload_bytes_sparse"] \
        < ps["fold_payload_bytes_dense"]
    assert ps["fold_payload_ratio"] <= 0.5
    assert len(ps["thread_leg"]["raw_wall_s"]) > 0
    assert len(ps["process_leg"]["raw_wall_s"]) > 0
    par = ps["parity"]
    assert par["alerts_identical_thread_vs_process"] is True
    assert par["alerts_identical_2_vs_1_process"] is True
    assert par["p99_identical"] is True
    assert par["shed_identical"] is True
    assert par["served_identical"] is True
    assert par["journal_canonical_identical_thread_vs_process"] is True
    assert par["journal_canonical_identical_2_vs_1_process"] is True
    assert par["journal_canonical_identical_sparse_vs_dense"] is True


def test_pre_bench_exit_codes_named_and_unique():
    """The gate's exit-code table (accreted 3/4/5/6/7/8 across PRs 5–10)
    lives as named EXIT_* constants in ONE place; the constants are
    collected by prefix (a new one joins the pin automatically), every
    code is distinct, and the documented values are pinned so drivers
    parsing return codes never see a silent renumbering."""
    import sys as _sys
    _sys.path.insert(0, str(Path(__file__).parent.parent / "scripts"))
    try:
        import pre_bench_check as pbc
    finally:
        _sys.path.pop(0)
    codes = {name: getattr(pbc, name) for name in dir(pbc)
             if name.startswith("EXIT_")}
    assert len(set(codes.values())) == len(codes)
    assert codes == {
        "EXIT_READY": 0, "EXIT_COLD_CACHE": 1, "EXIT_CACHE_DISABLED": 2,
        "EXIT_SERVE_PRECONDITION": 3, "EXIT_ENV_CONTRACT": 4,
        "EXIT_NATIVE_UNUSABLE": 5, "EXIT_STATE_POOL_UNUSABLE": 6,
        "EXIT_FLIGHT_DIVERGENCE": 7, "EXIT_RECOVERY_DIVERGENCE": 8,
        "EXIT_LINT": 9, "EXIT_POLICY_DIVERGENCE": 10,
        "EXIT_PERF_DIVERGENCE": 11, "EXIT_CENSUS_DIVERGENCE": 12,
        "EXIT_ASYNC_DIVERGENCE": 13, "EXIT_FEED_DIVERGENCE": 14,
        "EXIT_TIERING_DIVERGENCE": 15,
        "EXIT_PROCSHARD_DIVERGENCE": 16,
    }
    # every literal return in the gate's source goes through a constant
    src = (Path(__file__).parent.parent / "scripts"
           / "pre_bench_check.py").read_text()
    import re
    assert not re.search(r"return [0-9]", src), \
        "pre_bench_check must return named EXIT_* constants, not literals"
