"""Operations and bytes from shapes against a counted toy case, the table
of peaks, and the reduction of a trace to busy seconds."""

import glob
import os

import bm_tiny
import numpy as np
import pytest

from benchmark import trace_reduce, work


def test_fold_work_matches_a_counted_toy_fold():
    n, segments, hist = 10, 4, 3
    adds = bytes_read = 0
    state = np.zeros((segments, work.N_FEATS + hist))
    rng = np.random.default_rng(0)
    for _ in range(n):
        row = rng.integers(0, segments)
        bytes_read += 7 * 4                      # the seven staged columns
        for f in range(work.N_FEATS):
            state[row, f] += 1.0
            adds += 1
        state[row, work.N_FEATS + rng.integers(0, hist)] += 1.0
        adds += 1
    got = work.fold_work(n, segments, hist)
    assert got["flops"] == adds
    assert got["bytes"] == bytes_read + state.size * 4


def test_least_seconds_names_the_binding_peak():
    peaks = work.load_peaks("TPU v5 lite")
    assert peaks["flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9 and "source" in peaks
    secs, which = work.least_seconds(work.fold_work(10**9, 1440, 16), peaks)
    assert which == "bytes" and secs == pytest.approx(28e9 / 819e9, rel=1e-3)
    assert work.least_seconds({"flops": 1e15, "bytes": 1.0}, peaks)[1] \
        == "flops"


def test_an_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        work.load_peaks("TPU v9 imaginary")


def test_union_and_clip():
    assert trace_reduce.union([[5, 7], [0, 2], [1, 3], [3, 4]]) == \
        [[0, 4], [5, 7]]
    assert trace_reduce.clip([[0, 4], [5, 7], [9, 12]], 3, 10) == \
        [[3, 4], [5, 7], [9, 10]]


def _toy_trace():
    ops = [("fold", 10, 40), ("fold", 30, 60), ("copy", 80, 90),
           ("late", 95, 130)]
    host = [("bench.window", 20, 100), ("bench.pass", 20, 70),
            ("serve.drain", 60, 80)]
    return trace_reduce.Trace(window=(20, 100), devices={"d0": ops},
                              host=host)


def test_busy_is_the_union_clipped_to_the_window():
    t = _toy_trace()
    assert t.window_s == pytest.approx(80e-9)
    assert t.busy_s == pytest.approx((40 + 10 + 5) * 1e-9)
    assert t.op_seconds("fold") == {"fold": pytest.approx(50e-9)}


def test_idle_gaps_go_to_the_innermost_host_span():
    gaps = _toy_trace().idle_gaps()
    assert gaps["serve.drain"] == pytest.approx(20e-9)
    assert gaps["(none)"] == pytest.approx(5e-9)
    rows = _toy_trace().breakdown()
    assert rows["device_ops"][0][0] == "fold" and len(rows["idle_gaps"]) == 2


RECORDED = sorted(glob.glob(os.path.join(
    bm_tiny.ROOT, "benchmark", "testdata", "*.xplane.pb")))


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_recorded_chip_trace_reduces_to_a_sound_busy_share(path):
    t = trace_reduce.reduce_xplane(path)
    assert list(t.devices) == ["/device:TPU:0"]
    assert 0 < t.busy_s <= t.window_s
    assert sum(t.op_seconds().values()) >= t.busy_s * 0.999
    expected = path[:-len(".xplane.pb")] + ".json"
    import json
    want = json.load(open(expected))
    assert t.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert t.window_s == pytest.approx(want["window_s"], rel=1e-9)


def test_a_recorded_chip_trace_is_checked_in():
    assert RECORDED, "benchmark/testdata holds no recorded .xplane.pb"
