"""A cell, a configuration and a per-layer metric are added as new files
and entries only: the tiny twin of the benchmark is exactly that, a copy
to which files were added and of which none was edited."""

import filecmp
import os

import bm_tiny
from bm_tiny import tiny_root  # noqa: F401  (the fixture)
import pytest

from benchmark import contract, harness


def test_no_file_of_the_copy_was_edited(tiny_root):
    for sub in ("configs", "workloads", "metrics", "readers", "drivers"):
        theirs = os.path.join(bm_tiny.ROOT, "benchmark", sub)
        for name in os.listdir(theirs):
            if name.endswith((".json", ".py")):
                assert filecmp.cmp(os.path.join(theirs, name), os.path.join(
                    tiny_root, "benchmark", sub, name), shallow=False), name


def test_added_entries_still_meet_the_contract(tiny_root):
    bench = harness.load_benchmark(tiny_root)
    assert contract.check_benchmark_json(bench) == []
    cells = {w["name"] for w in bench["workloads"]}
    assert cells >= set(bm_tiny.TINY) | set(bm_tiny.TINY.values()) \
        | {bm_tiny.STEADY}


def test_an_added_cell_and_configuration_load(tiny_root):
    bench = harness.load_benchmark(tiny_root)
    cell = harness.load_cell(bench, "tiny-fleet-overload", tiny_root)
    assert cell["config_name"] == "tiny-tt-fleet"
    assert cell["config"]["n_tenants"] == 24
    with pytest.raises(KeyError):
        harness.load_cell(bench, "no-such-cell", tiny_root)


def test_an_added_metric_is_read_by_its_own_reader(tiny_root):
    rc, line, err = bm_tiny.run_cell(tiny_root, "tiny-replay", 1, seed=80)
    assert rc == 0, err
    assert line["metrics"]["passes_in_trace"]["value"] == line["attempted"]
    assert "passes_in_trace" not in harness.load_benchmark(
        bm_tiny.ROOT)["per_layer"]


def test_a_reader_that_finds_nothing_is_left_out(tiny_root):
    bench = harness.load_benchmark(tiny_root)

    class Nothing:
        window_s = busy_s = 0.0

        def op_seconds(self, pattern=None):
            return {}

    got = harness.layer_metrics(bench, "tiny-replay", {
        "trace": Nothing(), "attempted": 0, "work": None, "peaks": {}},
        tiny_root)
    assert got == {}


def test_the_folds_device_time_leaves_the_pool_copies_out(tiny_root):
    """``lane_fold_device_ms`` reads the ops that are not layout copies,
    ``pool_copy_device_ms`` the copies: together every op, neither both."""
    import json

    class Trace:
        ops = {"%copy.2 = f32[9,1440,16]{1,2,0} copy(%p)": 0.6,
               "%copy = f32[9,1440,6]{1,2,0} copy(%q)": 0.3,
               "%fusion.3 = f32[9,1440,16] fusion(%a), kind=kLoop": 0.002,
               "%scatter.1 = f32[9,1440,6] scatter(%b)": 0.001}

        def op_seconds(self, pattern=None):
            import re
            return {k: v for k, v in self.ops.items()
                    if re.search(pattern, k)}

    def read(metric):
        spec = json.load(open(os.path.join(
            tiny_root, "benchmark", "metrics", metric + ".json")))
        return harness.module_for("readers", spec["reader"], tiny_root).read(
            {"trace": Trace(), "ticks": 2}, **spec["args"])

    assert read("pool_copy_device_ms") == pytest.approx(450.0)
    assert read("lane_fold_device_ms") == pytest.approx(1.5)
