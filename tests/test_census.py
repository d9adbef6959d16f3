"""Fleet census observatory (anomod.obs.census): read-side byte-parity,
deterministic census streams, hot-set shard invariance, pool-bytes
reconciliation, the registered-fleet probe, the census diff judge, and
the scrape-path export of the census gauges."""

import dataclasses
import json

import numpy as np
import pytest

from anomod.obs.census import (CENSUS_PLANES, collect_resident_bytes,
                               diff_census, fit_slope, fit_zipf,
                               fleet_probe, plane_nbytes,
                               pool_row_nbytes, pool_slot_nbytes,
                               span_batch_nbytes)
from anomod.serve.engine import run_power_law

#: the tiny seeded run every engine-level census pin shares (window 2 s
#: so the scripted fault fires inside the run — the alert stream is
#: LIVE, not vacuously equal)
KW = dict(n_tenants=5, n_services=4, capacity_spans_per_s=1000,
          overload=2.0, duration_s=20, tick_s=1.0, seed=9,
          window_s=2.0, baseline_windows=4, fault_tenants=2,
          buckets=(64, 256), lane_buckets=(1, 2, 4), max_backlog=1500,
          n_windows=16, shards=1, pipeline=2)


def _census_stream(eng):
    """The journal's census variant stream (census ticks only),
    serialized deterministically — the byte-equality surface."""
    docs = [rec["census"] for rec in eng.flight_recorder.records()
            if rec["census"]["planes"]]
    return json.dumps(docs, sort_keys=True, separators=(",", ":"))


@pytest.fixture(scope="module")
def census_pair():
    eng_off, rep_off = run_power_law(**KW)
    eng_on, rep_on = run_power_law(census=True, census_every=4, **KW)
    return eng_off, rep_off, eng_on, rep_on


# ---------------------------------------------------------------------------
# the read-side contract + determinism pins
# ---------------------------------------------------------------------------

def test_census_read_side_byte_parity(census_pair):
    """Census on/off leaves every decision byte-identical: per-tenant
    alert streams, replay states, SLO quantiles, shed, and the
    CANONICAL flight journal."""
    eng_off, rep_off, eng_on, rep_on = census_pair
    assert rep_on.n_alerts > 0            # the pin is live, not vacuous
    assert rep_off.census_enabled is False and rep_on.census_enabled
    for tid in eng_off._tenant_det:
        assert [dataclasses.asdict(a) for a in eng_off.alerts_for(tid)] \
            == [dataclasses.asdict(a) for a in eng_on.alerts_for(tid)]
        s1 = eng_off._tenant_replay[tid].state
        s2 = eng_on._tenant_replay[tid].state
        assert np.array_equal(np.asarray(s1.agg), np.asarray(s2.agg))
        assert np.array_equal(np.asarray(s1.hist), np.asarray(s2.hist))
    assert rep_off.latency == rep_on.latency
    assert rep_off.shed_fraction == rep_on.shed_fraction
    assert eng_off.flight_recorder.canonical_bytes() \
        == eng_on.flight_recorder.canonical_bytes()


def test_census_off_report_fields_empty(census_pair):
    _, rep_off, _, _ = census_pair
    assert rep_off.census_ticks == 0
    assert rep_off.census_hot_set == {}
    assert rep_off.census_resident_bytes == {}


def test_census_stream_byte_equal_across_reruns(census_pair):
    """Same seed ⇒ the census VARIANT stream is byte-equal across
    reruns — unlike walls/perf, census records carry no wall clocks."""
    _, _, eng_on, _ = census_pair
    eng2, _ = run_power_law(census=True, census_every=4, **KW)
    assert _census_stream(eng_on) == _census_stream(eng2)


def test_census_hot_set_invariant_across_shards(census_pair):
    """The hot-set/Zipf census derives from coordinator admission
    decisions alone: a 2-shard census-on run reports the SAME
    census_hot_set and census_ticks as the 1-shard run (the canonical
    half of the census report; resident bytes are consciously
    variant)."""
    _, _, eng_on, rep_on = census_pair
    kw = dict(KW)
    kw["shards"] = 2
    eng2, rep2 = run_power_law(census=True, census_every=4, **kw)
    assert rep2.census_hot_set == rep_on.census_hot_set
    assert rep2.census_ticks == rep_on.census_ticks
    # resident bytes exist on both, and the 2-shard run censuses
    # per-shard pool/scratch planes for BOTH shards
    doc = [rec["census"] for rec in eng2.flight_recorder.records()
           if rec["census"]["planes"]][-1]
    pool_shards = {p["shard"] for p in doc["planes"]
                   if p["plane"] == "pool"}
    assert pool_shards == {0, 1}
    # the canonical report surface stays equal (the fan-out contract,
    # census-on this time)
    from anomod.serve.engine import SHARD_VARIANT_REPORT_FIELDS
    a = {k: v for k, v in rep_on.to_dict().items()
         if k not in SHARD_VARIANT_REPORT_FIELDS}
    b = {k: v for k, v in rep2.to_dict().items()
         if k not in SHARD_VARIANT_REPORT_FIELDS}
    assert a == b


def test_census_planes_schema_and_reconciliation(census_pair):
    """Per-(shard, plane) records drain in (shard, plane) order; the
    pool total reconciles EXACTLY with (capacity + 1) × per-slot
    nbytes; the by_plane totals sum to the census total."""
    _, _, eng_on, rep_on = census_pair
    docs = [rec["census"] for rec in eng_on.flight_recorder.records()
            if rec["census"]["planes"]]
    assert len(docs) == rep_on.census_ticks
    last = docs[-1]
    order = [(p["shard"], p["plane"]) for p in last["planes"]]
    assert order == sorted(order)
    # CENSUS_PLANES is the one plane inventory: this RCA-off,
    # tiering-off run emits exactly the other planes, and nothing
    # outside the inventory
    assert {p["plane"] for p in last["planes"]} \
        == set(CENSUS_PLANES) - {"rca", "tier"}
    assert last["pool_reconciled"] is True
    by_plane = {}
    for p in last["planes"]:
        by_plane[p["plane"]] = by_plane.get(p["plane"], 0) + p["bytes"]
    assert last["total_bytes"] == sum(by_plane.values())
    pool = [p for p in last["planes"] if p["plane"] == "pool"][0]
    assert pool["mode"] == "device"
    assert pool["bytes"] == (pool["capacity"] + 1) * pool["slot_bytes"]
    assert pool["slot_bytes"] == pool_slot_nbytes(eng_on.cfg)
    assert 0 < pool["slots_used"] <= pool["capacity"]
    adm = [p for p in last["planes"] if p["plane"] == "admission"][0]
    assert adm["registered"] == KW["n_tenants"]
    # report mirror
    rb = rep_on.census_resident_bytes
    assert rb["total"] == last["total_bytes"]
    assert rb["pool_reconciled"] is True
    assert rb["peak_total"] >= rb["total"]
    # hot-set doc sanity
    hs = rep_on.census_hot_set
    assert hs["registered"] == KW["n_tenants"]
    assert 0 < hs["ever_served"] <= hs["registered"]
    assert hs["resident"] == len(eng_on._tenant_replay)
    assert all(v <= hs["ever_served"]
               for v in hs["hot_by_decay"].values())
    ticks = [c["last_served_tick"] for c in hs["coldest"]]
    assert ticks == sorted(ticks)          # coldest first


def test_census_survives_elastic_scaling():
    """An elastic census-on run (scale 1→2→1 under a scripted surge)
    keeps censusing through the topology changes — per-shard planes
    appear for the appended shard — and its hot-set census equals the
    static run's (scaling moves capacity, never an admission
    decision)."""
    kw = dict(n_tenants=6, n_services=4, capacity_spans_per_s=1000,
              overload=0.6, duration_s=24, tick_s=1.0, seed=5,
              window_s=5.0, baseline_windows=4, fault_tenants=0,
              buckets=(64, 256), lane_buckets=(1, 2, 4),
              max_backlog=1500, n_windows=16,
              flight_digest_every=4, chaos="surge@6:factor=6:ticks=6")
    eng_s, rep_s = run_power_law(shards=1, census=True, census_every=4,
                                 **kw)
    eng_e, rep_e = run_power_law(shards=1, policy="auto", min_shards=1,
                                 max_shards=2, cooldown_ticks=3,
                                 census=True, census_every=4, **kw)
    assert rep_e.n_scale_ups >= 1 and rep_e.n_scale_downs >= 1
    assert rep_e.census_ticks == rep_s.census_ticks
    assert rep_e.census_hot_set == rep_s.census_hot_set
    docs = [rec["census"] for rec in eng_e.flight_recorder.records()
            if rec["census"]["planes"]]
    peak_shards = max(max(p["shard"] for p in d["planes"]
                          if p["plane"] == "pool") for d in docs)
    assert peak_shards == 1            # shard 1 was censused at peak
    assert all(d["pool_reconciled"] is True for d in docs)


def test_census_audit_replay_byte_equal():
    """`anomod audit replay` of a census-on journal re-records the
    SAME census stream: the census knobs ride the flight header
    resolved, and the stream carries no wall clock."""
    kw = dict(KW)
    kw["duration_s"] = 12.0
    eng, _ = run_power_law(census=True, census_every=4, **kw)
    run = dict(eng.flight_recorder.header["run"])
    assert run["census"] is True and run["census_every"] == 4
    run["buckets"] = tuple(run["buckets"])
    run["lane_buckets"] = tuple(run["lane_buckets"])
    eng2, _ = run_power_law(**run)
    assert _census_stream(eng) == _census_stream(eng2)


# ---------------------------------------------------------------------------
# byte-accounting helpers
# ---------------------------------------------------------------------------

def test_span_batch_nbytes_exact():
    """The O(1) fixed-width fast path equals the per-array sum — the
    pin that keeps SPAN_ROW_BYTES honest against the real schema."""
    from anomod import labels, synth
    batch = synth.generate_spans(labels.ALL_LABELS[0], n_traces=5)
    want = sum(arr.nbytes for arr in (
        batch.trace, batch.parent, batch.service, batch.endpoint,
        batch.start_us, batch.duration_us, batch.is_error,
        batch.status, batch.kind))
    assert span_batch_nbytes(batch) == want
    assert want == batch.n_spans * 36      # the schema's 36 B/span


def test_pool_reconciliation_survives_growth():
    """The (capacity + 1) × per-row pin holds through pool doubling
    (growth concatenates zero rows — the shape algebra must follow).
    This shape's agg row (192 floats) is held at 256: the pool's row is
    the host pytree's bytes plus that padding."""
    from anomod.replay import TenantStatePool
    from anomod.serve.engine import serve_plane_cfg
    cfg = serve_plane_cfg(4, 5.0, 8)
    pool = TenantStatePool(cfg, capacity=2)
    for _ in range(6):
        pool.acquire()                     # forces two doublings
    got = plane_nbytes(pool.agg) + plane_nbytes(pool.hist)
    assert got == (pool.capacity + 1) * pool_row_nbytes(cfg)
    assert pool_row_nbytes(cfg) == pool_slot_nbytes(cfg) + 4 * (256 - 192)
    assert pool.capacity >= 6


# ---------------------------------------------------------------------------
# estimators
# ---------------------------------------------------------------------------

def test_fit_zipf_recovers_alpha():
    alpha = 1.3
    counts = [int(1e6 / r ** alpha) for r in range(1, 200)]
    got = fit_zipf(counts)
    assert got is not None and abs(got - alpha) < 0.05
    assert fit_zipf([5, 3]) is None        # below 3 points: no fit
    assert fit_zipf([]) is None


def test_fit_slope_linear():
    xs = [1000, 10000, 100000]
    ys = [3e-6 * x + 0.25 for x in xs]
    slope, icpt = fit_slope(xs, ys)
    assert abs(slope - 3e-6) < 1e-12
    assert abs(icpt - 0.25) < 1e-9


# ---------------------------------------------------------------------------
# the registered-fleet probe (cost attribution)
# ---------------------------------------------------------------------------

def test_fleet_probe_scales_with_registered():
    doc = fleet_probe(sizes=(50, 200, 800), hot=20, ticks=4, seed=0)
    assert doc["sizes"] == [50, 200, 800]
    assert len(doc["rows"]) == 3
    by_size = [r["resident_bytes"] for r in doc["rows"]]
    # resident bytes grow strictly with the REGISTERED count even
    # though only 20 tenants ever offer a span — the O(registered)
    # baseline the tiering refactor must flatten
    assert by_size[0] < by_size[1] < by_size[2]
    assert all(r["hot"] == 20 for r in doc["rows"])
    assert all(r["pool_reconciled"] is True for r in doc["rows"])
    assert doc["bytes_slope_per_registered"] > 0
    assert np.isfinite(doc["wall_slope_s_per_registered"])
    assert all(r["median_tick_wall_s"] > 0 for r in doc["rows"])
    # zero measured ticks would fit a slope over NaN walls: refused
    with pytest.raises(ValueError):
        fleet_probe(sizes=(50, 200), hot=10, ticks=0)


# ---------------------------------------------------------------------------
# `anomod census diff` — the before/after judge
# ---------------------------------------------------------------------------

def _capture(pool=1000, sweep_bytes=3.5, wall=2e-7):
    return {"census": {
        "resident_bytes": {"total": pool + 500,
                           "by_plane": {"pool": pool, "slo": 500}},
        "sweep": {"sizes": [1000, 100000], "hot": 50,
                  "bytes_slope_per_registered": sweep_bytes,
                  "wall_slope_s_per_registered": wall,
                  "wall_intercept_s": 0.04}}}


def test_diff_census_identical_ok():
    doc = diff_census(_capture(), _capture(), tolerance=0.35)
    assert doc["status"] == "ok"
    assert doc["bytes_regressions"] == []
    assert doc["slope_regressions"] == []
    assert doc["sweep_comparable"] is True


def test_diff_census_flags_byte_growth_exactly():
    doc = diff_census(_capture(pool=1000), _capture(pool=1001),
                      tolerance=0.35)
    assert doc["status"] == "bytes-regression"
    assert doc["bytes_regressions"][0]["plane"] == "pool"
    assert doc["bytes_regressions"][0]["delta"] == 1
    # shrinkage (the tiering win) is never a regression
    doc = diff_census(_capture(pool=1000), _capture(pool=10),
                      tolerance=0.35)
    assert doc["status"] == "ok"


def test_diff_census_wall_slope_tolerance():
    # within the noise tolerance: ok
    doc = diff_census(_capture(wall=2e-7), _capture(wall=2.4e-7),
                      tolerance=0.35)
    assert doc["status"] == "ok"
    # a 3x wall-slope regression clears any sane tolerance: flagged
    doc = diff_census(_capture(wall=2e-7), _capture(wall=6e-7),
                      tolerance=0.35)
    assert doc["status"] == "slope-regression"
    assert doc["slope_regressions"][0]["slope"] == \
        "wall_slope_s_per_registered"
    # the BYTES slope is deterministic: any growth flags, exactly
    doc = diff_census(_capture(sweep_bytes=3.5),
                      _capture(sweep_bytes=3.6), tolerance=0.35)
    assert doc["status"] == "slope-regression"
    assert doc["slope_regressions"][0]["exact"] is True


def test_diff_census_flat_baseline_still_guards():
    """THE post-tiering scenario: once the baseline wall slope sits at
    ~0 (or dips negative from the fit), a pure ratio test would never
    flag O(registered) cost creeping back — the scale-aware floor
    (tolerance × A's intercept at the sweep's top size) must."""
    for base in (0.0, -1e-8):
        doc = diff_census(_capture(wall=base), _capture(wall=5e-6),
                          tolerance=0.35)
        assert doc["status"] == "slope-regression", base
    # slope noise on a genuinely-flat curve stays under the floor
    doc = diff_census(_capture(wall=0.0), _capture(wall=1e-8),
                      tolerance=0.35)
    assert doc["status"] == "ok"


def test_diff_census_missing_block_and_shape_mismatch():
    doc = diff_census({"metric": "x"}, _capture())
    assert doc["status"] == "census-missing"
    assert doc["missing_in"] == ["a"]
    # mismatched sweep shapes: slope rows become informational, never
    # a verdict
    b = _capture(wall=9e-7)
    b["census"]["sweep"]["sizes"] = [100, 2000]
    doc = diff_census(_capture(), b, tolerance=0.35)
    assert doc["sweep_comparable"] is False
    assert doc["status"] == "ok" and doc["notes"]


# ---------------------------------------------------------------------------
# scrape-path export (satellite: gauges flow through selfscrape/export)
# ---------------------------------------------------------------------------

def test_census_gauges_flow_through_scrape_paths(tmp_path):
    """The census gauges ride the registry scrape journal end to end:
    Prometheus text names them, the TT-CSV export round-trips them,
    and the self-scrape metric→span mapping files them under a
    ``census`` subsystem."""
    from anomod.io.metrics import load_tt_metric_csv
    from anomod.obs.export import export_tt_csv, to_prometheus_text
    from anomod.obs.registry import Registry, set_registry, subsystem_of
    from anomod.obs.selfscrape import spans_from_metrics
    assert subsystem_of("anomod_census_resident_bytes") == "census"
    reg = Registry(enabled=True)
    prev = set_registry(reg)
    try:
        kw = dict(KW)
        kw["duration_s"] = 10.0
        run_power_law(census=True, census_every=4, **kw)
    finally:
        set_registry(prev)
    text = to_prometheus_text(reg)
    for name in ("anomod_census_resident_bytes",
                 "anomod_census_pool_bytes",
                 "anomod_census_registered_tenants",
                 "anomod_census_ticks_total"):
        assert name in text
    csv = tmp_path / "census_scrape.csv"
    n = export_tt_csv(reg, csv)
    assert n > 0
    batch = load_tt_metric_csv(csv)
    assert any(m.startswith("anomod_census_")
               for m in batch.metric_names)
    spans = spans_from_metrics(batch)
    assert "census" in spans.services


# ---------------------------------------------------------------------------
# knob validation + CLI
# ---------------------------------------------------------------------------

def test_census_knob_validation(monkeypatch):
    from anomod.config import Config
    for var, bad in (("ANOMOD_CENSUS_EVERY", "0"),
                     ("ANOMOD_CENSUS_EVERY", "x"),
                     ("ANOMOD_CENSUS_DECAY_TICKS", "16,4"),
                     ("ANOMOD_CENSUS_DECAY_TICKS", "a,b"),
                     ("ANOMOD_CENSUS_SWEEP", "1000"),
                     ("ANOMOD_CENSUS_SWEEP", "1000,1000"),
                     ("ANOMOD_CENSUS_COLDEST_K", "-1")):
        monkeypatch.setenv(var, bad)
        with pytest.raises(ValueError):
            Config()
        monkeypatch.delenv(var)
    monkeypatch.setenv("ANOMOD_CENSUS", "1")
    monkeypatch.setenv("ANOMOD_CENSUS_EVERY", "16")
    monkeypatch.setenv("ANOMOD_CENSUS_DECAY_TICKS", "2,8")
    monkeypatch.setenv("ANOMOD_CENSUS_SWEEP", "100,200")
    monkeypatch.setenv("ANOMOD_CENSUS_COLDEST_K", "3")
    cfg = Config()
    assert cfg.census is True and cfg.census_every == 16
    assert cfg.census_decay_ticks == (2, 8)
    assert cfg.census_sweep == (100, 200)
    assert cfg.census_coldest_k == 3


def test_census_engine_rejects_bad_cadence():
    with pytest.raises(ValueError):
        run_power_law(census=True, census_every=0, **KW)


def test_census_cli_record_probe_diff(tmp_path, capsys):
    from anomod.cli import main
    out = tmp_path / "census.json"
    rc = main(["census", "record", "--out", str(out), "--tenants", "5",
               "--duration", "8", "--capacity", "800", "--tick", "1.0",
               "--every", "4"])
    assert rc == 0
    line = json.loads(capsys.readouterr().out)
    assert line["census_ticks"] >= 1
    assert line["pool_reconciled"] is True
    doc = json.loads(out.read_text())
    assert doc["census_format"] == 1
    assert doc["stream"] and all(d["planes"] for d in doc["stream"])
    rc = main(["census", "probe", "--sizes", "40,160", "--hot", "10",
               "--ticks", "3"])
    assert rc == 0
    probe = json.loads(capsys.readouterr().out)
    assert probe["sweep"]["sizes"] == [40, 160]
    a = tmp_path / "a.json"
    b = tmp_path / "b.json"
    a.write_text(json.dumps(_capture()))
    b.write_text(json.dumps(_capture()))
    assert main(["census", "diff", str(a), str(b)]) == 0
    capsys.readouterr()
    b.write_text(json.dumps(_capture(pool=2000)))
    assert main(["census", "diff", str(a), str(b)]) == 1
    capsys.readouterr()
    b.write_text(json.dumps({"metric": "x"}))
    assert main(["census", "diff", str(a), str(b)]) == 2
    capsys.readouterr()
    # mode-mismatched flags fail loud
    with pytest.raises(SystemExit):
        main(["census", "diff", str(a), str(b), "--out", "x.json"])
    capsys.readouterr()
    with pytest.raises(SystemExit):
        main(["census", "record", "--out", str(out), "--sizes", "1,2"])
    capsys.readouterr()
    with pytest.raises(SystemExit):
        main(["census", "probe", "--tolerance", "0.5"])
    capsys.readouterr()
    with pytest.raises(SystemExit):       # record-only flag on probe
        main(["census", "probe", "--duration", "120"])
    capsys.readouterr()
    with pytest.raises(SystemExit):       # probe-only flag on diff
        main(["census", "diff", str(a), str(b), "--hot", "5"])
    capsys.readouterr()
    with pytest.raises(SystemExit):       # ticks must measure
        main(["census", "probe", "--ticks", "0"])
    capsys.readouterr()
