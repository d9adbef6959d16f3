"""The printed line's contract, and the character rules of BENCHMARK.json.

``check_last_line`` is called by ``run.py`` on the object it is about to
print (a run that would print a partial line exits non-zero with the
reason instead) and by ``tests/benchmark`` on CPU rehearsals of every
cell.  It returns a list of reasons; empty means the line is sound.
"""

from __future__ import annotations

import math
import re

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH_RE = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
LINE_KEYS = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")


def _is_number(x) -> bool:
    return (isinstance(x, (int, float)) and not isinstance(x, bool)
            and math.isfinite(x))


def metrics_due(bench: dict, workload: str, traced: bool) -> dict:
    """name -> unit of every metric a run of ``workload`` may report: the
    cell's end-to-end metrics untraced, its per-layer metrics traced.  A
    metric without a ``workloads`` key is due in every cell."""
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return {m["name"]: m["unit"] for m in group
            if workload in m.get("workloads", [workload])}


def check_last_line(obj, bench: dict, workload: str, traced: bool) -> list:
    """Reasons why ``obj`` is not the contract's result line for a run of
    ``workload`` (``traced``: a ``--trace 1`` run)."""
    if not isinstance(obj, dict):
        return ["the line is not a JSON object"]
    bad = [f"key {k!r} is missing" for k in LINE_KEYS if k not in obj]
    if bad:
        return bad
    if not isinstance(obj["correct"], bool):
        bad.append("correct is not true or false")
    for k in ("attempted", "failed"):
        if not isinstance(obj[k], int) or isinstance(obj[k], bool) \
                or obj[k] < 0:
            bad.append(f"{k} is not a whole number >= 0")
    due = metrics_due(bench, workload, traced)
    metrics = obj["metrics"]
    if not isinstance(metrics, dict):
        return bad + ["metrics is not an object"]
    for name in metrics:
        if name not in due:
            bad.append(f"metric {name!r} is not one of this kind of run "
                       f"in {workload}")
    for name, unit in due.items():
        m = metrics.get(name)
        if m is None:
            if not traced:
                bad.append(f"end-to-end metric {name!r} is missing")
            continue            # a reader that found nothing returns nothing
        if not isinstance(m, dict) or not _is_number(m.get("value")):
            bad.append(f"metric {name!r} has no finite value")
        elif m.get("unit") != unit:
            bad.append(f"metric {name!r} has unit {m.get('unit')!r}, "
                       f"BENCHMARK.json says {unit!r}")
        elif (name.endswith("_roofline") or "mfu" in name.split("_")
              or "mfu" in name.split(".")) and not 0 < m["value"] <= 105:
            bad.append(f"share {name!r} reads {m['value']}")
    if traced and not any(n in metrics for n in due):
        bad.append("a traced run reports no per-layer metric")
    dev = obj["device"]
    if not isinstance(dev, dict):
        return bad + ["device is not an object"]
    for k in DEVICE_KEYS:
        if k not in dev:
            bad.append(f"device.{k} is missing")
    if not bad:
        if not isinstance(dev["count"], int) or dev["count"] < 1:
            bad.append("device.count is not a whole number >= 1")
        peak = dev["memory_peak_bytes"]
        if not isinstance(peak, int) or peak < 0 \
                or (dev["platform"] == "tpu" and peak == 0):
            bad.append(f"device.memory_peak_bytes reads {peak!r}")
    if traced:
        busy, window = dev.get("busy_s"), dev.get("window_s")
        if not (_is_number(busy) and _is_number(window)):
            bad.append("a traced run's device needs busy_s and window_s")
        elif not 0 < busy <= window:
            bad.append(f"busy_s {busy} is not above 0 and at most "
                       f"window_s {window}")
        br = obj.get("breakdown")
        if br is not None:
            for k in ("device_ops", "idle_gaps"):
                rows = br.get(k) if isinstance(br, dict) else None
                if not isinstance(rows, list) or len(rows) > 10 or any(
                        not (isinstance(r, list) and len(r) == 2
                             and isinstance(r[0], str) and _is_number(r[1]))
                        for r in rows):
                    bad.append(f"breakdown.{k} is not a list of at most "
                               "10 [name, seconds] pairs")
    return bad


def check_benchmark_json(bench: dict) -> list:
    """The character and length rules a BENCHMARK.json is refused over
    before any run (names, units, paths, sources, cross-references)."""
    bad = []
    want = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(bench) != want:
        bad.append(f"keys {sorted(set(bench) ^ want)} differ from the "
                   "contract's")
        return bad

    def name_ok(s, what):
        if not isinstance(s, str) or not NAME_RE.match(s):
            bad.append(f"{what} {s!r} is not a name")

    def line_ok(s, what):
        if not isinstance(s, str) or not 1 <= len(s) <= 200 \
                or "\n" in s or "\t" in s:
            bad.append(f"{what} is not 1 to 200 characters on one line")

    for p in bench["paths"]:
        if not PATH_RE.match(p) or p.startswith("/") or ".." in p.split("/"):
            bad.append(f"path {p!r}")
    if not 1 <= len(bench["command"]) <= 32:
        bad.append("command has no 1 to 32 words")
    for word in bench["command"]:
        line_ok(word, f"command word {word!r}")
    if not isinstance(bench["run_seconds"], int) \
            or not 1 <= bench["run_seconds"] <= 51:
        bad.append("run_seconds is not a whole number from 1 to 51")
    cfgs = {}
    for c in bench["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"config keys {sorted(c)}")
            continue
        name_ok(c["name"], "config")
        line_ok(c["source"], f"source of {c['name']}")
        line_ok(c["why"], f"why of {c['name']}")
        if not any(c["file"].startswith(p + "/") for p in bench["paths"]):
            bad.append(f"config file {c['file']!r} lies outside paths")
        for k in c["reduced"]:
            name_ok(k, "reduced key")
        cfgs[c["name"]] = c
    if len(cfgs) != len(bench["configs"]):
        bad.append("two configurations share a name")
    cells, pairs = set(), set()
    for w in bench["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"workload keys {sorted(w)}")
            continue
        for k in ("name", "config", "traffic"):
            name_ok(w[k], f"workload {k}")
        line_ok(w["why"], f"why of {w['name']}")
        if w["config"] not in cfgs:
            bad.append(f"{w['name']} names no configuration")
        if w["chips"] not in (1, 4):
            bad.append(f"{w['name']} asks for {w['chips']} chips")
        pairs.add((w["config"], w["traffic"]))
        cells.add(w["name"])
    if len(cells) != len(bench["workloads"]) \
            or len(pairs) != len(bench["workloads"]):
        bad.append("two cells share a name or a (config, traffic) pair")
    used = {w["config"] for w in bench["workloads"] if "config" in w}
    if used != set(cfgs):
        bad.append(f"configurations {sorted(set(cfgs) - used)} have no cell")
    names = set()
    e2e = {}
    for group, keys in (("end_to_end", {"name", "unit", "better", "bound",
                                        "source"}),
                        ("per_layer", {"name", "unit", "better", "source",
                                       "layer", "moves"})):
        for m in bench[group]:
            if set(m) - {"workloads"} != keys:
                bad.append(f"{group} metric keys {sorted(m)}")
                continue
            name_ok(m["name"], "metric")
            if m["name"] in names:
                bad.append(f"two metrics are named {m['name']}")
            names.add(m["name"])
            if not UNIT_RE.match(m["unit"]):
                bad.append(f"unit {m['unit']!r} of {m['name']}")
            if m["better"] not in ("lower", "higher"):
                bad.append(f"better of {m['name']}")
            if m["source"] not in SOURCES:
                bad.append(f"source of {m['name']}")
            for cell in m.get("workloads", []):
                if cell not in cells:
                    bad.append(f"{m['name']} lists no cell {cell!r}")
            if group == "end_to_end":
                e2e[m["name"]] = m
                if m["source"] not in ("host_clock", "device_trace"):
                    bad.append(f"end-to-end {m['name']} reads a "
                               f"{m['source']}")
                if not _is_number(m["bound"]) or not 0 < m["bound"] <= 0.1:
                    bad.append(f"bound of {m['name']}")
            else:
                line_ok(m["layer"], f"layer of {m['name']}")
                if m["moves"] not in e2e:
                    bad.append(f"{m['name']} moves no end-to-end metric")
                else:
                    reported = set(e2e[m["moves"]].get("workloads", cells))
                    for cell in m.get("workloads", []):
                        if cell not in reported:
                            bad.append(f"{m['name']} lists {cell}, which "
                                       f"does not report {m['moves']}")
    if "setup_s" not in e2e:
        bad.append("no setup_s")
    for cell in cells:
        if len(metrics_due(bench, cell, False)) < 2:
            bad.append(f"{cell} reports no end-to-end metric but setup_s")
        if not metrics_due(bench, cell, True):
            bad.append(f"{cell} reports no per-layer metric")
    return bad
