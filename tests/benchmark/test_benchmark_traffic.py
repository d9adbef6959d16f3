"""The one traffic generator: a pure function of the seed, the program's
``PowerLawTraffic`` distributions, micro-batches that tile the spans."""

import numpy as np
import pytest

from benchmark import traffic

CFG = {"n_tenants": 60, "n_services": 5, "tick_s": 0.5}
P = {"offered_spans_per_s": 3000.0, "alpha": 1.2, "batch_cap": 64,
     "service_mix_dirichlet": 2.0, "latency_scale_us": [800, 6000],
     "latency_sigma": 0.35, "error_rate": 0.01, "fault_tenants": 2,
     "fault_service": 1, "fault_factor": 10.0, "fault_onset_s": 2.0}
BIG = 2**31 + 12345


def _same(a: dict, b: dict) -> bool:
    return all(np.array_equal(a[k], b[k]) for k in a)


@pytest.mark.parametrize("seed", [0, 7, BIG, 2**40 + 3])
def test_schedule_is_a_pure_function_of_the_seed(seed):
    a = traffic.fleet_schedule(P, CFG, seed, 8)
    assert _same(a, traffic.fleet_schedule(P, CFG, seed, 8))
    assert not np.array_equal(
        a["start_us"], traffic.fleet_schedule(P, CFG, seed + 1, 8)["start_us"])


def test_rates_are_the_programs_power_law():
    from anomod.serve.traffic import PowerLawTraffic
    theirs = PowerLawTraffic(60, 3000.0, alpha=1.2, seed=1, n_services=5)
    ours = traffic.fleet_rates(P, 60)
    np.testing.assert_allclose(
        ours, [s.rate_spans_per_s for s in theirs.specs], rtol=1e-12)
    assert abs(ours.sum() - 3000.0) < 1e-6


def test_arrival_counts_follow_the_rates():
    sched = traffic.fleet_schedule(P, CFG, 11, 400)
    per_tenant = np.bincount(sched["tenant"], minlength=60) / (400 * 0.5)
    rates = traffic.fleet_rates(P, 60)
    busy = rates > 20
    assert np.abs(per_tenant[busy] / rates[busy] - 1).max() < 0.1
    assert abs(len(sched["tenant"]) / (400 * 0.5) / 3000.0 - 1) < 0.02


def test_batches_tile_the_spans_in_tenant_order_under_the_cap():
    s = traffic.fleet_schedule(P, CFG, 5, 6)
    lo, hi = s["batch_lo"], s["batch_hi"]
    assert lo[0] == 0 and hi[-1] == len(s["tenant"])
    assert np.array_equal(lo[1:], hi[:-1])
    assert (hi - lo).max() <= 64 and (hi - lo).min() >= 1
    for b in range(len(lo)):
        rows = slice(lo[b], hi[b])
        assert (s["tenant"][rows] == s["batch_tenant"][b]).all()
        assert (s["tick"][rows] == s["batch_tick"][b]).all()
        assert (np.diff(s["start_us"][rows]) >= 0).all()
    ticks = s["start_us"] // 500_000
    assert np.array_equal(ticks, s["tick"])


def test_fault_tenants_slow_down_after_the_onset():
    s = traffic.fleet_schedule(P, CFG, 5, 12)
    hit = (s["tenant"] < 2) & (s["service"] == 1)
    before = s["duration_us"][hit & (s["tick"] < 4)].mean()
    after = s["duration_us"][hit & (s["tick"] >= 4)].mean()
    assert 6 < after / before < 15


def test_roll_call_covers_every_tenant_of_the_fleet():
    sparse = dict(P, offered_spans_per_s=30.0)
    s = traffic.fleet_schedule(dict(sparse, roll_call_s=[1.0, 2.0]), CFG, 5, 8)
    in_call = (s["tick"] >= 2) & (s["tick"] < 4)
    assert set(s["tenant"][in_call].tolist()) == set(range(CFG["n_tenants"]))
    plain = traffic.fleet_schedule(sparse, CFG, 5, 8)
    quiet = (plain["tick"] >= 2) & (plain["tick"] < 4)
    assert len(set(plain["tenant"][quiet].tolist())) < CFG["n_tenants"]
    assert len(s["tenant"]) - len(plain["tenant"]) == CFG["n_tenants"]


def test_baseline_call_covers_whoever_reports_later():
    sparse = dict(P, offered_spans_per_s=30.0)
    s = traffic.fleet_schedule(dict(sparse, baseline_call_s=[1.0, 2.0]), CFG,
                               5, 8)
    called = set(s["tenant"][(s["tick"] >= 2) & (s["tick"] < 4)].tolist())
    later = set(s["tenant"][s["tick"] >= 4].tolist())
    assert later <= called and 5 < len(later) < CFG["n_tenants"]
    plain = traffic.fleet_schedule(sparse, CFG, 5, 8)
    assert len(s["tenant"]) - len(plain["tenant"]) == len(later)


def test_a_structure_seed_gives_every_seed_the_same_sizes():
    p = dict(P, structure_seed=3, roll_call_s=[0.0, 1.0],
             baseline_call_s=[1.0, 2.0])
    a = traffic.fleet_schedule(p, CFG, 5, 8)
    b = traffic.fleet_schedule(p, CFG, BIG, 8)
    for k in ("tick", "tenant", "batch_tick", "batch_tenant", "batch_lo",
              "batch_hi"):
        assert np.array_equal(a[k], b[k]), k
    for k in ("service", "start_us", "duration_us", "is_error"):
        assert not np.array_equal(a[k], b[k]), k
    other = traffic.fleet_schedule(dict(p, structure_seed=4), CFG, 5, 8)
    assert len(other["tenant"]) != len(a["tenant"]) or not np.array_equal(
        other["tenant"], a["tenant"])


REPLAY = {"structure_seed": 1, "base_spans": 3000, "n_services": 5, "campaign_windows": 7,
          "window_us": 60_000_000, "service_mix_dirichlet": 0.6,
          "latency_scale_us": [2000, 30000], "latency_sigma": 0.4,
          "error_rate": 0.02, "latency_jitter": 0.125,
          "error_flip_per_1024": 10}


@pytest.mark.parametrize("seed", [3, BIG])
def test_archive_base_is_a_pure_function_of_the_seed(seed):
    a, b = (traffic.archive_base(REPLAY, seed) for _ in range(2))
    assert _same(a, b) and len(a["service"]) == 3000
    other = traffic.archive_base(REPLAY, seed + 1)
    assert np.array_equal(a["service"], other["service"])
    assert np.array_equal(a["start_us"], other["start_us"])
    assert not np.array_equal(a["duration_us"], other["duration_us"])
    assert len(set(traffic.copy_keys(seed, 16).tolist())) == 16


def test_perturbation_is_bit_equal_in_numpy_and_jax():
    import jax.numpy as jnp
    base = traffic.archive_base(REPLAY, 9)
    raw = base["duration_us"].astype(np.float32)
    dur, err = np.log1p(raw), base["is_error"].astype(np.float32)
    f_tab, l_tab = traffic.jitter_tables(REPLAY)
    key = traffic.copy_keys(9, 4)[2]
    host = traffic.perturb(np, raw.view(np.uint32), key, raw, dur, err, err,
                           np.ones_like(raw), f_tab, l_tab, 10)
    dev = traffic.perturb(jnp, jnp.asarray(raw.view(np.uint32)),
                          jnp.uint32(key), jnp.asarray(raw),
                          jnp.asarray(dur), jnp.asarray(err),
                          jnp.asarray(err), jnp.ones_like(raw),
                          jnp.asarray(f_tab), jnp.asarray(l_tab), 10)
    for h, d in zip(host, dev):
        assert np.array_equal(h, np.asarray(d))
    assert 0 < np.abs(host[0] - err).sum() < 0.05 * len(err)
