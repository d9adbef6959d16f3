"""A temp copy of the benchmark to which cells are ADDED as new files and
entries (no file of the copy is edited): the CPU rehearsal the tests
drive, and the proof that a configuration, a cell, an end-to-end metric
and a per-layer metric can be added by data alone.

Added: a tiny twin of each cell ``TINY`` names, and ``tiny-fleet-steady``,
the issue's steady cell at tiny size (the overload twin's file under
another traffic name, with the two lag metrics as new end-to-end
entries), which ``BENCHMARK.json`` does not hold yet (PERF.md, Open
questions)."""

import contextlib
import io
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY = {"tt-replay-staged": "tiny-replay",
        "tt-fleet-overload": "tiny-fleet-overload"}
STEADY = "tiny-fleet-steady"


def _dump(obj, *path):
    with open(os.path.join(*path), "w") as f:
        json.dump(obj, f, indent=1)


def _load(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


def make_tiny_root(dst: str) -> str:
    """Copy BENCHMARK.json and benchmark/ to ``dst`` and add the twins (of
    the cells ``TINY`` names; cells that later PRs add get none)."""
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    bench = _load(ROOT, "BENCHMARK.json")
    twinned = [w for w in bench["workloads"] if w["name"] in TINY]
    cfg_of = {}
    for c in list(bench["configs"]):
        if c["name"] not in {w["config"] for w in twinned}:
            continue
        cfg = _load(dst, c["file"])
        if "copies" in cfg:
            cfg.update(copies=2, n_services=5, n_windows=8, chunk_size=1024)
        else:
            cfg.update(n_tenants=24, n_services=5)
        name = "tiny-" + c["name"]
        cfg["name"] = name
        file = f"benchmark/configs/{name}.json"
        _dump(cfg, dst, file)
        bench["configs"].append(dict(c, name=name, file=file))
        cfg_of[c["name"]] = name
    for w in twinned:
        wl = _load(dst, "benchmark", "workloads", w["name"] + ".json")
        wl["config"] = cfg_of[w["config"]]
        wl["trace_seconds"] = 1.0
        p = wl["params"]
        if "base_spans" in p:
            p["base_spans"], p["campaign_windows"] = 6000, 7
        else:
            scale = 2000.0 / max(p["offered_spans_per_s"], 1)
            p["offered_spans_per_s"] = p["offered_spans_per_s"] * scale
            wl["sample_tenants"], wl["sample_busiest"] = 8, 2
        _dump(wl, dst, "benchmark", "workloads", TINY[w["name"]] + ".json")
        bench["workloads"].append(dict(w, name=TINY[w["name"]],
                                       config=cfg_of[w["config"]]))
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if "workloads" in m:
                m["workloads"] = m["workloads"] + [
                    TINY[c] for c in m["workloads"] if c in TINY]
    # the steady cell: a workload file, its entry, its name in the lists of
    # the metrics the fleet reports, and two end-to-end metrics of its own
    fleet = TINY["tt-fleet-overload"]
    wl = _load(dst, "benchmark", "workloads", fleet + ".json")
    _dump(dict(wl, traffic="steady"), dst, "benchmark", "workloads",
          STEADY + ".json")
    entry = next(w for w in bench["workloads"] if w["name"] == fleet)
    bench["workloads"].append(dict(entry, name=STEADY, traffic="steady"))
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if fleet in m.get("workloads", []):
                m["workloads"] = m["workloads"] + [STEADY]
    for name in ("scored_lag_p50_ms", "scored_lag_p95_ms"):
        bench["end_to_end"].append({
            "name": name, "unit": "ms", "better": "lower", "bound": 0.1,
            "source": "host_clock", "workloads": [STEADY]})
    # one per-layer metric added by files alone: a reader and its entry
    with open(os.path.join(dst, "benchmark", "readers",
                           "passes_in_trace.py"), "w") as f:
        f.write("def read(ctx):\n    return ctx['attempted'] or None\n")
    new = {"name": "passes_in_trace", "unit": "count", "better": "higher",
           "source": "program_counter", "layer": "fold kernels",
           "moves": "replay_spans_per_s", "workloads": ["tiny-replay"]}
    _dump(dict(new, reader="passes_in_trace", args={}), dst, "benchmark",
          "metrics", "passes_in_trace.json")
    bench["per_layer"].append(new)
    _dump(bench, dst, "BENCHMARK.json")
    return dst


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    """The tiny twin, built once; the test files import this fixture (a
    ``conftest.py`` here would shadow ``tests/conftest.py`` for the test
    files that import names from it)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    return make_tiny_root(str(tmp_path_factory.mktemp("bm_tiny")))


def run_cell(root: str, workload: str, trace: int, seed: int = 5000000011,
             seconds: float = 1.5, control: int = 0):
    """One in-process CPU rehearsal: ``(exit code, last line, stderr)``."""
    from benchmark import run
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace),
                       "--control", str(control)], root=root)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if rc == 0 and lines else None), \
        err.getvalue()
