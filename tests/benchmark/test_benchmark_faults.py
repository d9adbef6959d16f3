"""``correct`` has to read false when it should: the lower-precision
control in the program's place, and the timed path broken underneath a
whole run — a step that leaves its state unchanged, half of the batch
left out, an answer altered where it is produced.  (One chip: there is no
exchange between chips to leave out.)"""

import bm_tiny
from bm_tiny import tiny_root  # noqa: F401  (the fixture)
import numpy as np
import pytest

from benchmark import harness


def _failed(line) -> set:
    return {c["name"] for c in line["checks"] if not c["ok"]}


@pytest.mark.parametrize("cell", ["tiny-replay", "tiny-fleet-steady"])
def test_control_in_the_programs_place_is_not_correct(tiny_root, cell):
    rc, sound, err = bm_tiny.run_cell(tiny_root, cell, 0, seed=77)
    assert rc == 0 and sound["correct"] is True, err
    rc, line, err = bm_tiny.run_cell(tiny_root, cell, 0, seed=77, control=1)
    assert rc == 0 and line["correct"] is False, err
    assert "moment_gap" in _failed(line)
    gap = {c["name"]: c for c in sound["checks"]}["moment_gap"]
    ctl = sound["notes"]["control_moment_gap"]
    assert ctl >= 3 * gap["value"] and gap["value"] < gap["limit"] < ctl


def _replay_faults(real):
    def unchanged(pfn, archive, sub_passes):
        return np.zeros_like(real(pfn, archive, sub_passes))

    def half(pfn, archive, sub_passes):
        sid, planes, wids = archive
        nb = wids.shape[0] // 2
        n = nb * (sid.shape[0] // wids.shape[0])
        return real(pfn, (sid[:n], planes[:, :n], wids[:nb]), sub_passes)

    def altered(pfn, archive, sub_passes):
        out = np.array(real(pfn, archive, sub_passes))
        row = int(np.argmax(out[:, 0]))
        out[row, 4] *= 1.001
        return out

    return {"unchanged": unchanged, "half": half, "altered": altered}


@pytest.mark.parametrize("fault, number", [
    ("unchanged", "spans_folded_minus_staged"),
    ("half", "exact_cells_differing"), ("altered", "moment_gap")])
def test_a_broken_fold_is_not_correct(tiny_root, monkeypatch, fault, number):
    mod = harness.module_for("drivers", "replay-closed", tiny_root)
    monkeypatch.setattr(mod, "fold_passes",
                        _replay_faults(mod.fold_passes)[fault])
    rc, line, err = bm_tiny.run_cell(tiny_root, "tiny-replay", 0, seed=78)
    assert rc == 0 and line["correct"] is False, err
    assert number in _failed(line)


def _break_fleet(monkeypatch, mod, fault):
    from anomod.replay import TenantStatePool
    from anomod.serve.batcher import BucketRunner
    if fault == "unchanged":
        monkeypatch.setattr(TenantStatePool, "scatter_fold",
                            lambda self, slots, dagg, dhist: None)
    elif fault == "half":
        real = BucketRunner.submit_lanes
        monkeypatch.setattr(
            BucketRunner, "submit_lanes",
            lambda self, width, work: real(
                self, width, work[:max(len(work) // 2, 1)]
                if len(work) > 1 else work))
    else:
        real_read = mod.read_program

        def altered(engine, tenant, spans, cfg):
            agg, hist, alerts = real_read(engine, tenant, spans, cfg)
            agg = np.array(agg)
            agg[int(np.argmax(agg[:, 0])), 0] += 1.0
            return agg, hist, alerts

        monkeypatch.setattr(mod, "read_program", altered)


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
def test_a_broken_tick_is_not_correct(tiny_root, monkeypatch, fault):
    mod = harness.module_for("drivers", "fleet-open", tiny_root)
    _break_fleet(monkeypatch, mod, fault)
    rc, line, err = bm_tiny.run_cell(tiny_root, "tiny-fleet-steady", 0,
                                     seed=79)
    assert rc == 0 and line["correct"] is False, err
    assert "exact_cells_differing" in _failed(line)


def test_a_batch_lost_from_log_and_state_alike_is_not_correct(
        tiny_root, monkeypatch):
    """The reference folds the program's own served log: only the
    comparison of that log with what was sent sees this."""
    from anomod.serve.engine import ServeEngine
    real, calls = ServeEngine.tick, []

    def lossy(self, arrivals):
        calls.append(1)
        if len(calls) == 3:
            arrivals = [a for a in arrivals if a[0] != 0]
        return real(self, arrivals)

    monkeypatch.setattr(ServeEngine, "tick", lossy)
    rc, line, err = bm_tiny.run_cell(tiny_root, "tiny-fleet-steady", 0,
                                     seed=81)
    assert rc == 0 and line["correct"] is False, err
    assert _failed(line) == {"served_not_as_sent"}


def test_a_closed_window_left_unscored_is_not_correct(tiny_root,
                                                      monkeypatch):
    from anomod.stream import OnlineDetector
    monkeypatch.setattr(OnlineDetector, "note_bookkeep",
                        lambda self, n_spans, w_max: None)
    rc, line, err = bm_tiny.run_cell(tiny_root, "tiny-fleet-steady", 0,
                                     seed=82)
    assert rc == 0 and line["correct"] is False, err
    assert "windows_unscored" in _failed(line)


def test_an_alert_with_the_wrong_z_is_not_correct():
    from benchmark.reference import fleet_score
    cfg = {"n_services": 3, "n_windows": 8, "n_hist_buckets": 4,
           "window_us": 1_000_000, "baseline_windows": 2, "min_count": 5.0,
           "z_threshold": 4.0, "alert_margin": 0.25}
    rng = np.random.default_rng(0)
    n = 4000
    start = np.sort(rng.integers(0, 6_000_000, n))
    dur = np.where((start >= 4_000_000), 40_000, 4_000) \
        * rng.lognormal(0, 0.3, n)
    spans = {"service": rng.integers(0, 3, n).astype(np.int32),
             "start_us": start, "duration_us": dur.astype(np.int64),
             "is_error": np.zeros(n, bool),
             "status": np.full(n, 200, np.int16)}
    agg, hist = fleet_score.fold(spans, cfg)
    alerts = fleet_score.alerts_of(agg, fleet_score.last_window(spans, cfg),
                                   cfg)
    assert alerts and {a[0] for a in alerts} == {4}
    sound = fleet_score.compare_tenant(agg, hist, alerts, spans, cfg)
    assert sound["z_gap"] == 0 and sound["alerts_unborne"] == 0
    bent = [(w, s, zl * 1.2, ze, zd, zc) for w, s, zl, ze, zd, zc in alerts]
    assert fleet_score.compare_tenant(agg, hist, bent, spans,
                                      cfg)["z_gap"] > 0.1
    missing = fleet_score.compare_tenant(agg, hist, alerts[1:], spans, cfg)
    assert missing["alerts_unborne"] == 1 and missing["z_gap"] == 1.0
    extra = alerts + [(3, 0, 9.0, 0.0, 0.0, 0.0)]
    raised = fleet_score.compare_tenant(agg, hist, extra, spans, cfg)
    assert raised["alerts_unborne"] == 1 and raised["z_gap"] == 1.0
