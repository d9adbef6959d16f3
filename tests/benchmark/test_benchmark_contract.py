"""BENCHMARK.json against the contract's rules, and the printed line of a
CPU rehearsal of every cell (tiny twins, ``--trace 0`` and ``--trace 1``)
against ``contract.check_last_line``."""

import copy
import json
import os

import bm_tiny
from bm_tiny import tiny_root  # noqa: F401  (the fixture)
import pytest

from benchmark import contract, harness

BENCH = harness.load_benchmark(bm_tiny.ROOT)
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_benchmark_json_meets_the_contract():
    assert contract.check_benchmark_json(BENCH) == []
    assert len(json.dumps(BENCH)) < 64 * 1024
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) \
        <= max(len(CELLS) // 4, 1)


@pytest.mark.parametrize("group", ["configs", "workloads", "end_to_end",
                                   "per_layer"])
def test_every_name_and_unit_is_well_formed(group):
    for entry in BENCH[group]:
        assert contract.NAME_RE.match(entry["name"]), entry["name"]
        if "unit" in entry:
            assert contract.UNIT_RE.match(entry["unit"]), entry["unit"]
        for key in ("why", "layer", "source"):
            if key in entry and group in ("configs", "workloads",
                                          "per_layer"):
                assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key]


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_has_its_files(name):
    cell = harness.load_cell(BENCH, name, bm_tiny.ROOT)
    assert os.path.exists(os.path.join(
        bm_tiny.ROOT, "benchmark", "drivers",
        cell["traffic"]["driver"].replace("-", "_") + ".py"))
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == cell["config_name"])
    assert cell["config"]["reduced"] == entry["reduced"]
    for m in BENCH["per_layer"]:
        if name in m.get("workloads", [name]):
            spec = json.load(open(os.path.join(
                bm_tiny.ROOT, "benchmark", "metrics", m["name"] + ".json")))
            assert all(spec[k] == v for k, v in m.items()
                       if k != "workloads")
            assert os.path.exists(os.path.join(
                bm_tiny.ROOT, "benchmark", "readers", spec["reader"] + ".py"))


@pytest.mark.parametrize("mutate, reason", [
    (lambda b: b["end_to_end"][0].update(unit="spans per s"), "unit"),
    (lambda b: b["workloads"][0].update(name="a/b"), "not a name"),
    (lambda b: b["end_to_end"][0].update(bound=0.2), "bound"),
    (lambda b: b["per_layer"][0].update(why="x"), "keys"),
    (lambda b: b["per_layer"][0].update(moves="nothing"), "moves"),
    (lambda b: b["workloads"][0].update(chips=2), "chips"),
    (lambda b: b.update(run_seconds=52), "run_seconds"),
    (lambda b: b["end_to_end"].remove(next(
        m for m in b["end_to_end"] if m["name"] == "setup_s")), "setup_s"),
])
def test_a_broken_benchmark_json_is_refused(mutate, reason):
    bench = copy.deepcopy(BENCH)
    mutate(bench)
    assert any(reason in r for r in contract.check_benchmark_json(bench))


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", sorted(bm_tiny.TINY.values())
                         + [bm_tiny.STEADY])
def test_rehearsal_prints_the_contracts_line(tiny_root, cell, trace):
    rc, line, err = bm_tiny.run_cell(tiny_root, cell, trace)
    assert rc == 0, err
    bench = harness.load_benchmark(tiny_root)
    assert contract.check_last_line(line, bench, cell,
                                    bool(trace)) == []
    assert line["correct"] is True, err
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "checks"
    for row in line["checks"]:
        assert f"check {row['name']}:" in err
    due = contract.metrics_due(bench, cell, bool(trace))
    if trace:
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
        assert line["notes"]["cache_misses"] >= 0
    else:
        assert set(line["metrics"]) == set(due)


def test_a_batch_that_waits_at_the_close_has_not_failed(tiny_root):
    """Above capacity the batches whose tick has not come up wait: they
    are in the notes, and neither attempted nor failed."""
    rc, line, err = bm_tiny.run_cell(tiny_root, "tiny-fleet-overload", 0,
                                     seed=83)
    assert rc == 0 and line["failed"] == 0, err
    notes = line["notes"]
    assert 0 < line["attempted"] <= notes["due_batches"]
    assert notes["waiting_batches"] == max(
        notes["due_batches"] - notes["served_batches"], 0)


def _good_line(traced):
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
           "memory_peak_bytes": 5_000_000_000}
    if traced:
        dev.update(busy_s=1.0, window_s=2.0)
        metrics = {"fold_roofline": {"value": 7.0, "unit": "%"}}
    else:
        metrics = {"replay_spans_per_s": {"value": 1e9, "unit": "spans/s"},
                   "setup_s": {"value": 20.0, "unit": "s"}}
    return {"correct": True, "attempted": 3, "failed": 0,
            "metrics": metrics, "device": dev}


@pytest.mark.parametrize("traced, mutate, reason", [
    (False, lambda o: o["metrics"].pop("setup_s"), "setup_s"),
    (False, lambda o: o["metrics"]["setup_s"].pop("unit"), "unit"),
    (False, lambda o: o["metrics"].update(x={"value": 1, "unit": "s"}),
     "not one of"),
    (False, lambda o: o.pop("failed"), "missing"),
    (False, lambda o: o["device"].pop("kind"), "device.kind"),
    (False, lambda o: o["device"].update(memory_peak_bytes=0), "peak"),
    (False, lambda o: o["metrics"]["setup_s"].update(value=float("nan")),
     "finite"),
    (True, lambda o: o["device"].pop("busy_s"), "busy_s"),
    (True, lambda o: o["device"].update(busy_s=0.0), "busy_s"),
    (True, lambda o: o["device"].update(busy_s=3.0), "busy_s"),
    (True, lambda o: o["metrics"].clear(), "no per-layer"),
    (True, lambda o: o["metrics"]["fold_roofline"].update(value=120.0),
     "share"),
    (True, lambda o: o.update(breakdown={"device_ops": [["a", 1.0]] * 11,
                                         "idle_gaps": []}), "breakdown"),
])
def test_a_malformed_line_is_refused(traced, mutate, reason):
    assert contract.check_last_line(_good_line(traced), BENCH,
                                    "tt-replay-staged", traced) == []
    obj = _good_line(traced)
    mutate(obj)
    assert any(reason in r for r in contract.check_last_line(
        obj, BENCH, "tt-replay-staged", traced))


def test_run_refuses_to_print_a_malformed_line(tiny_root, monkeypatch,
                                               capsys):
    from benchmark import run
    monkeypatch.setattr(harness, "memory_peak_bytes", lambda: -1)
    rc = run.main(["--workload", "tiny-replay", "--seed", "3", "--seconds",
                   "0.3", "--trace", "0"], root=tiny_root)
    out = capsys.readouterr()
    assert rc != 0 and "malformed line" in out.err
    assert '"correct"' not in out.out


def test_no_accelerator_is_an_error(tiny_root, monkeypatch, capsys):
    from benchmark import run
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    rc = run.main(["--workload", "tiny-replay", "--seed", "3", "--seconds",
                   "0.3", "--trace", "0"], root=tiny_root)
    out = capsys.readouterr()
    assert rc != 0 and "not a TPU" in out.err and out.out == ""
