"""The readers that attribute time by overlap (``idle_overlap_pct``,
``span_uncovered_ms_per_tick``, ``host_span_diff_ms_per_tick``) on
hand-made traces, and the fleet cell's tiny twin printing every metric
that reads the tick's spans."""

import json
import os

import bm_tiny
from bm_tiny import tiny_root  # noqa: F401  (the fixture)
import pytest

from benchmark import contract, harness, intervals
from benchmark.trace_reduce import Trace

SPAN_METRICS = ("coalesce_plan_ms", "lane_fill_ms", "lane_issue_ms",
                "fold_retire_ms", "score_bookkeep_ms", "score_windows_ms",
                "barrier_ms", "tick_unnamed_ms", "post_tick_drain_ms")
IDLE_SHARES = ("idle_pct.admission", "idle_pct.staging",
               "idle_pct.fold_retire", "idle_pct.commit", "idle_pct.unnamed",
               "idle_pct.outside_tick")


def reader(name):
    return harness.module_for("readers", name, bm_tiny.ROOT).read


def args_of(metric):
    with open(os.path.join(bm_tiny.ROOT, "benchmark", "metrics",
                           metric + ".json")) as f:
        return json.load(f)["args"]


@pytest.mark.parametrize("a, b, both, a_less_b", [
    ([[0, 10]], [[2, 3], [5, 7]], [[2, 3], [5, 7]],
     [[0, 2], [3, 5], [7, 10]]),
    ([[0, 4], [6, 9]], [[3, 7]], [[3, 4], [6, 7]], [[0, 3], [7, 9]]),
    ([[0, 4]], [[4, 8]], [], [[0, 4]]),               # adjacent
    ([[0, 4]], [[0, 4]], [[0, 4]], []),
    ([[1, 2]], [], [], [[1, 2]]),
    ([], [[1, 2]], [], []),
])
def test_interval_algebra(a, b, both, a_less_b):
    assert intervals.intersect(a, b) == both
    assert intervals.intersect(b, a) == both
    assert intervals.subtract(a, b) == a_less_b
    assert intervals.measure(both) + intervals.measure(a_less_b) \
        == intervals.measure(a)


def gap_trace():
    """A 100 ns window, one tick [10, 90]; the device works in [10, 20]
    and [70, 100], so one gap [20, 70] runs through three spans: admit
    [18, 30], stage [30, 62] (the gap's middle, 45, is here) and the
    first lane fill [62, 75].  [0, 10] is idle before the tick."""
    return Trace(
        window=(0, 100),
        devices={"/device:TPU:0": [("%copy.2 = x", 10, 20),
                                   ("%fusion = y", 70, 100)]},
        host=[("bench.window", 0, 100), ("bench.tick", 10, 96),
              ("serve.tick", 10, 90), ("serve.admit", 18, 30),
              ("serve.stage", 30, 62), ("serve.lane_fill", 62, 75),
              ("serve.fold_retire", 76, 88)])


def test_a_gap_is_split_by_overlap_not_charged_to_its_middle():
    trace = gap_trace()
    idle = reader("idle_overlap_pct")
    # by the middle the whole 50 ns gap is serve.stage's
    assert trace.idle_gaps()["serve.stage"] == pytest.approx(50e-9)
    assert "serve.admit" not in trace.idle_gaps()
    # by overlap: 60 idle ns = 10 before the tick + admit 10 + stage 32
    # + lane_fill 8
    got = {m: idle({"trace": trace}, **args_of(m)) for m in IDLE_SHARES}
    assert got["idle_pct.admission"] == pytest.approx(100 * 10 / 60)
    assert got["idle_pct.staging"] == pytest.approx(100 * 40 / 60)
    assert got["idle_pct.fold_retire"] == 0.0    # spans there, no idle
    assert got["idle_pct.unnamed"] == pytest.approx(0.0)
    assert got["idle_pct.outside_tick"] == pytest.approx(100 * 10 / 60)
    # the barrier's spans are not in this trace: nothing to read
    assert got["idle_pct.commit"] is None
    assert sum(v for v in got.values() if v is not None) \
        == pytest.approx(100.0)


def test_idle_shares_sum_to_100_with_every_span_present():
    trace = gap_trace()
    trace.host += [("serve.commit", 88, 89), ("serve.scrape", 89, 90)]
    trace.devices["/device:TPU:0"] = [("%copy.2 = x", 10, 20),
                                      ("%fusion = y", 70, 88.5)]
    idle = reader("idle_overlap_pct")
    got = {m: idle({"trace": trace}, **args_of(m)) for m in IDLE_SHARES}
    assert all(v is not None for v in got.values())
    assert got["idle_pct.commit"] == pytest.approx(100 * 1.5 / 71.5)
    assert got["idle_pct.outside_tick"] == pytest.approx(100 * 20 / 71.5)
    assert sum(got.values()) == pytest.approx(100.0)


@pytest.mark.parametrize("trace", [
    Trace(window=(0, 100), devices={}, host=[("serve.tick", 0, 50)]),
    # never busy, never idle
    Trace(window=(0, 100), devices={"d": [("op", 200, 300)]},
          host=[("serve.tick", 0, 50)]),
    Trace(window=(0, 100), devices={"d": [("op", 0, 100)]},
          host=[("serve.tick", 0, 50)]),
    # the parent: no serve.tick, no serve.stage
    Trace(window=(0, 100), devices={"d": [("op", 0, 40)]},
          host=[("bench.tick", 0, 50)]),
], ids=["no-device", "never-busy", "never-idle", "no-such-span"])
def test_idle_overlap_finds_nothing_to_read(trace):
    idle = reader("idle_overlap_pct")
    for m in ("idle_pct.staging", "idle_pct.unnamed",
              "idle_pct.outside_tick"):
        assert idle({"trace": trace}, **args_of(m)) is None


class Spans:
    def __init__(self, spans):
        self.spans = [list(s) for s in spans]


def test_uncovered_time_counts_nested_and_adjacent_leaves_once():
    uncovered = reader("span_uncovered_ms_per_tick")
    args = args_of("tick_unnamed_ms")
    tracer = Spans([
        ("serve.tick", 0.5, 0.9),                # before the window
        ("serve.tick", 1.0, 2.0),
        ("serve.admit", 1.0, 1.2), ("serve.drain", 1.2, 1.3),  # adjacent
        ("serve.score_shard", 1.3, 1.9),         # no leaf: not subtracted
        ("serve.stage", 1.35, 1.5),
        ("serve.fold_retire", 1.5, 1.7),
        ("serve.commit", 1.7, 1.9),              # no leaf either
        ("serve.bookkeep", 1.72, 1.8), ("serve.score_windows", 1.8, 1.88),
        ("serve.lane_fill", 1.55, 1.6),          # nested in a leaf: once
        ("serve.tick", 3.0, 3.5), ("serve.scrape", 3.4, 3.5),
    ])
    ctx = {"tracer": tracer, "window_t0": 1.0, "ticks": 2}
    # tick 1: 1.0 - (0.3 + 0.15 + 0.2 + 0.16) = 0.19; tick 2: 0.4
    assert uncovered(ctx, **args) == pytest.approx(1e3 * (0.19 + 0.4) / 2)
    assert uncovered(dict(ctx, tracer=Spans([("serve.admit", 1.0, 1.2)])),
                     **args) is None
    assert uncovered(dict(ctx, ticks=0), **args) is None


def test_post_tick_drain_is_bench_tick_less_serve_tick_in_the_window():
    diff = reader("host_span_diff_ms_per_tick")
    args = args_of("post_tick_drain_ms")
    trace = Trace(window=(100, 10_000_100), devices={}, host=[
        ("bench.tick", 0, 90), ("serve.tick", 0, 80),       # before it
        ("bench.tick", 1_000_000, 3_000_000),
        ("serve.tick", 1_000_000, 2_500_000),
        ("bench.tick", 5_000_000, 8_000_000),
        ("serve.tick", 5_000_100, 6_500_100)])
    assert diff({"trace": trace, "ticks": 2}, **args) \
        == pytest.approx((0.5 + 1.5) / 2)
    parent = Trace(window=trace.window, devices={}, host=[
        e for e in trace.host if e[0] == "bench.tick"])
    assert diff({"trace": parent, "ticks": 2}, **args) is None
    assert diff({"trace": trace, "ticks": 0}, **args) is None


def test_the_fleet_twin_prints_every_span_metric(tiny_root):
    rc, line, err = bm_tiny.run_cell(tiny_root, "tiny-fleet-overload", 1,
                                     seed=2147483999)
    assert rc == 0, err
    bench = harness.load_benchmark(tiny_root)
    assert contract.check_benchmark_json(bench) == []
    assert contract.check_last_line(line, bench, "tiny-fleet-overload",
                                    True) == []
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(SPAN_METRICS + IDLE_SHARES) <= set(got)
    assert sum(got[m] for m in IDLE_SHARES) == pytest.approx(100.0, abs=0.5)
    # the old metrics time from outside what the spans time inside
    assert got["fold_retire_ms"] <= got["fold_wait_ms"]
    assert got["score_bookkeep_ms"] + got["score_windows_ms"] \
        <= got["score_ms"]
    assert got["lane_fill_ms"] + got["lane_issue_ms"] <= got["dispatch_ms"] \
        <= (got["coalesce_plan_ms"] + got["lane_fill_ms"]
            + got["lane_issue_ms"])
    # the tick closes: the named parts and the two remainders are the
    # driver's tick (one or two ticks here: the median is the mean)
    assert line["notes"]["ticks"] <= 2
    parts = got["admit_drain_ms"] + sum(got[m] for m in SPAN_METRICS)
    assert parts == pytest.approx(line["notes"]["tick_wall_p50_ms"],
                                  rel=0.05)


def test_the_replay_twin_prints_nothing_new(tiny_root):
    rc, line, err = bm_tiny.run_cell(tiny_root, "tiny-replay", 1,
                                     seed=2147483998)
    assert rc == 0, err
    assert not set(SPAN_METRICS + IDLE_SHARES) & set(line["metrics"])
