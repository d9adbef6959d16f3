"""PR 38's sixteen per-layer metrics: the sequence step's device time by
call name (``readers/scope_ms_per_tick.py``), the three leaf spans of
``serve.seq_model`` and the plane's two byte counters.  The new reader on
the recorded v5e trace and on a hand-built plane in the same wire format;
every new metric finding nothing in a run of the PARENT's program (PR 33
was refused for a reader that raised there); the entries' form; the tiny
twins' traced lines; and what ``test_benchmark_lxs2_cell``'s pinned lists
of every cell's due metrics asserted, for the entries that were there
(that test is ``tests/conftest.OVERTAKEN``: this PR's entries list three
cells each, and no twin a cell)."""

import os
import shutil
import types

import bm_tiny
import bm_tiny_hybrid
import bm_tiny_seq
import bm_tiny_swa
from bm_tiny_hybrid import tiny_hybrid_root  # noqa: F401  (the fixtures)
from bm_tiny_seq import tiny_seq_root  # noqa: F401
from bm_tiny_swa import tiny_swa_root  # noqa: F401
import pytest
import test_benchmark_lxs2_cell as lxs2_cell

from benchmark import contract, harness, trace_reduce

ROOT = bm_tiny.ROOT
K2, N3S, LXS2 = bm_tiny_seq.CELL, bm_tiny_hybrid.CELL, bm_tiny_swa.CELL
MODEL = [K2, N3S, LXS2]
DEVICE = ["step_device_ms." + p for p in (
    "proj", "attn", "route", "rounds", "ragged", "mlp", "head")]
N3S_ONLY = ["step_device_ms.ssm", "step_device_ms.conv"]
HOST = ["seq_issue_ms", "seq_wait_ms", "seq_fetch_ms", "seq_issue_idle_pct",
        "seq_plan_mb_per_tick", "seq_fetch_mb_per_tick"]
NEW = DEVICE + N3S_ONLY + ["step_unscoped_pct"] + HOST
#: what a run on the CPU can read of them: the spans and the counters
ON_CPU = {"seq_issue_ms", "seq_wait_ms", "seq_fetch_ms",
          "seq_plan_mb_per_tick", "seq_fetch_mb_per_tick"}
SCOPES = ["anomod_seq_proj", "anomod_seq_conv", "anomod_seq_mla",
          "anomod_seq_gqa", "anomod_seq_swa", "anomod_seq_ssm",
          "anomod_seq_route", "anomod_seq_rounds", "ragged-dot-none",
          "anomod_seq_mlp", "anomod_seq_head"]


def _spec(metric):
    return bm_tiny._load(ROOT, "benchmark", "metrics", metric + ".json")


def _read(ctx, **args):
    return harness.module_for("readers", "scope-ms-per-tick").read(ctx,
                                                                   **args)


# -- the entries --------------------------------------------------------------

def test_sixteen_entries_are_appended_and_each_lists_its_cells():
    bench = bm_tiny._load(ROOT, "BENCHMARK.json")
    assert contract.check_benchmark_json(bench) == []
    added = bench["per_layer"][99:]
    assert [m["name"] for m in added] == NEW and len(NEW) == 16
    served = next(m for m in bench["end_to_end"]
                  if m["name"] == "served_spans_per_s")
    for m in added:
        assert m["workloads"] == ([N3S] if m["name"] in N3S_ONLY else MODEL)
        assert m["moves"] == "served_spans_per_s" and m["better"] == "lower"
        assert set(m["workloads"]) <= set(served["workloads"])
        spec = _spec(m["name"])
        assert {k: spec[k] for k in m if k != "workloads"} \
            == {k: m[k] for k in m if k != "workloads"}
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "readers", spec["reader"] + ".py"))
    # one file a metric: no twin a cell
    files = os.listdir(os.path.join(ROOT, "benchmark", "metrics"))
    assert not [f for f in files if f.startswith(("step_", "seq_issue",
                                                  "seq_wait", "seq_fetch",
                                                  "seq_plan"))
                and f[:-5] not in NEW]
    by = {m["name"]: m for m in added}
    assert {by[n]["layer"] for n in DEVICE + N3S_ONLY
            + ["step_unscoped_pct"]} == {"sequence model step"}
    assert {by[n]["source"] for n in DEVICE + N3S_ONLY
            + ["step_unscoped_pct", "seq_issue_idle_pct"]} \
        == {"device_trace"}
    assert by["seq_issue_idle_pct"]["layer"] == "device"
    assert {by[n]["layer"] for n in HOST if n != "seq_issue_idle_pct"} \
        == {"sequence model plane"}
    # the vocabulary: eleven names, each under exactly one device metric,
    # and the complement over all of them
    named = [s for n in DEVICE + N3S_ONLY for s in _spec(n)["args"]["scopes"]]
    assert sorted(named) == sorted(SCOPES)
    assert _spec("step_unscoped_pct")["args"] == {"scopes": SCOPES,
                                                  "complement": True}
    assert _spec("step_device_ms.attn")["args"]["scopes"] == [
        "anomod_seq_mla", "anomod_seq_gqa", "anomod_seq_swa"]
    assert _spec("seq_issue_idle_pct")["args"] == {
        "spans": ["serve.seq_issue"]}
    assert _spec("seq_plan_mb_per_tick")["args"] == {
        "counters": ["seq_plan_bytes"], "scale": 1e-6}


def test_the_entries_that_were_there_are_as_they_were():
    """What ``test_benchmark_lxs2_cell``'s overtaken test pins, for the 99
    entries of the parent: their order, the cells each is due in, PR 34's
    and PR 36's 25 that list their cell alone; and what each cell reports
    now: those, then this PR's in the order appended."""
    bench = bm_tiny._load(ROOT, "BENCHMARK.json")
    were = dict(bench, per_layer=bench["per_layer"][:99])
    new = [m for m in were["per_layer"] if LXS2 in m.get("workloads", [])]
    assert [m["name"] for m in new] == lxs2_cell.NEW
    assert all(m["workloads"] == [LXS2] for m in new)
    assert were["per_layer"][-len(new):] == new
    assert all("workloads" in m for m in bench["per_layer"])
    n3s = [m for m in were["per_layer"] if N3S in m.get("workloads", [])]
    assert len(n3s) == 25 and all(m["workloads"] == [N3S] for m in n3s)
    due = dict(lxs2_cell.DUE_AT_PARENT, **{LXS2: lxs2_cell.NEW})
    for cell, names in due.items():
        assert list(contract.metrics_due(were, cell, True)) == names
        mine = [n for n in NEW if cell in MODEL
                and (n not in N3S_ONLY or cell == N3S)]
        assert list(contract.metrics_due(bench, cell, True)) == names + mine
        assert len(mine) == {K2: 14, N3S: 16, LXS2: 14}.get(cell, 0)
        assert set(contract.metrics_due(bench, cell, False)) == {
            "setup_s", "replay_spans_per_s" if cell == "tt-replay-staged"
            else "served_spans_per_s"}
    served = next(m for m in bench["end_to_end"]
                  if m["name"] == "served_spans_per_s")
    assert served["workloads"] == ["tt-fleet-overload"] + MODEL
    assert [w["name"] for w in bench["workloads"]] == list(due)
    assert bench["run_seconds"] == 51


# -- the reader on a recorded trace -------------------------------------------

@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The recorded v5e trace where a driver leaves a run's."""
    root = tmp_path_factory.mktemp("recorded")
    run = root / "plugins" / "profile" / "2026_10_05"
    run.mkdir(parents=True)
    src = os.path.join(ROOT, "benchmark", "testdata", "replay-v5e.xplane.pb")
    shutil.copy(src, run / "replay.xplane.pb")
    return {"trace": trace_reduce.reduce_xplane(src), "trace_dir": str(root),
            "ticks": 49}


def test_scope_ms_per_tick_on_the_recorded_v5e_trace(recorded):
    trace = recorded["trace"]
    with open(trace_reduce.find_xplane(recorded["trace_dir"]), "rb") as f:
        names = harness.module_for("readers", "scope-roofline").scoped_ops(
            memoryview(f.read()), "jit")
    assert len(names) == 4
    seconds = trace.op_seconds()
    want = sum(seconds[n] for n in names)
    ctx = dict(recorded)
    assert _read(ctx, scopes=["jit"]) == pytest.approx(1e3 * want / 49)
    # the four ops are all of the 3.96 busy seconds of the 49 passes: no
    # two overlap, so their summed time is the union of their intervals
    assert _read(ctx, scopes=["jit"]) == pytest.approx(
        1e3 * trace.busy_s / 49, rel=1e-6)
    # a scope the program never ran under; two lists of which one is there
    assert _read(ctx, scopes=["anomod_seq_proj"]) is None
    assert _read(ctx, scopes=["anomod_seq_proj", "anomod_seq_head"],
                 complement=True) is None
    assert _read(ctx, scopes=["anomod_seq_proj", "jit"]) \
        == _read(ctx, scopes=["jit"])
    # every op of the recorded run is under ``jit``: nothing is left over
    everything = sum(seconds.values())
    assert _read(ctx, scopes=["jit"], complement=True) == pytest.approx(
        100.0 * (everything - want) / everything, abs=1e-9)
    # the file was read once for all of these, each scope walked once
    kept = ctx["_scope_ms_per_tick"]
    assert set(kept) == {"xplane", "names", "seconds"}
    assert set(kept["names"]) == {"jit", "anomod_seq_proj",
                                  "anomod_seq_head", ""}
    # nothing to divide by, nothing to read
    assert _read(dict(recorded, ticks=0), scopes=["jit"]) is None
    assert _read(dict(recorded, trace_dir="/nonexistent"),
                 scopes=["jit"]) is None
    assert _read(dict(recorded, trace=None), scopes=["jit"]) is None
    assert _read({}, scopes=["jit"]) is None


# -- the reader on a hand-built plane -----------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    """One field in the protobuf wire format: a varint for an int, a
    length-delimited field for bytes."""
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _plane(name, ops):
    """An ``XPlane`` with the metadata tables of ``ops``: ``(trace name,
    tf_op, hlo_category)`` each (field numbers as ``scoped_ops`` gives
    them)."""
    stats = {1: b"tf_op", 2: b"hlo_category"}
    body = _field(2, name)
    for sid, sname in stats.items():
        body += _field(5, _field(1, sid) + _field(
            2, _field(1, sid) + _field(2, sname)))
    for eid, (op, tf_op, category) in enumerate(ops, 1):
        meta = _field(1, eid) + _field(2, op.encode())
        for sid, text in ((1, tf_op), (2, category)):
            meta += _field(5, _field(1, sid) + _field(5, text.encode()))
        body += _field(4, _field(1, eid) + _field(2, meta))
    return _field(1, body)


OPS = [("%while.7 = while(...)", "jit(s)/anomod_seq_rounds/while", "while"),
       ("%fusion.1 = fusion(...)", "jit(s)/anomod_seq_rounds/while/body/mul",
        "loop fusion"),
       ("%custom-call.2 = custom-call(...)", "ragged-dot-none",
        "custom-call"),
       ("%fusion.3 = fusion(...)", "jit(s)/anomod_seq_proj/dot_general",
        "convolution fusion"),
       ("%copy.4 = copy(...)", "", "data formatting"),
       ("%fusion.5 = fusion(...)",
        "jit(s)/anomod_seq_proj/a;jit(s)/anomod_seq_mlp/b", "loop fusion")]


def test_scope_ms_per_tick_counts_a_loops_body_once(tmp_path):
    run = tmp_path / "plugins" / "profile" / "x"
    run.mkdir(parents=True)
    (run / "hand.xplane.pb").write_bytes(
        _plane(b"/host:CPU", [("%fusion.1 = fusion(...)",
                               "anomod_seq_proj", "loop fusion")])
        + _plane(b"/device:TPU:0", OPS))
    ms = 10 ** 6                             # the trace's clock: ns
    events = [(OPS[0][0], 0, 50 * ms),       # the loop: 50 ms around
              (OPS[1][0], 0, 20 * ms),       # its body's 20 ms
              (OPS[2][0], 20 * ms, 50 * ms),  # and 30 ms of ragged dots
              (OPS[3][0], 50 * ms, 60 * ms), (OPS[4][0], 60 * ms, 65 * ms),
              (OPS[5][0], 65 * ms, 70 * ms),
              (OPS[3][0], 95 * ms, 110 * ms)]  # 5 ms of it in the window
    trace = trace_reduce.Trace(window=(0, 100 * ms),
                               devices={"/device:TPU:0": events}, host=[])
    ctx = {"trace": trace, "trace_dir": str(tmp_path), "ticks": 2}
    read = lambda *scopes, **kw: _read(ctx, scopes=list(scopes), **kw)
    assert read("anomod_seq_rounds") == pytest.approx(10.0)   # not 35
    assert read("ragged-dot-none") == pytest.approx(15.0)
    assert read("anomod_seq_proj") == pytest.approx(10.0)     # 10 + 5 + 5
    assert read("anomod_seq_mlp") == pytest.approx(2.5)
    assert read("anomod_seq_head") is None
    # an op under two scopes counts under each, and once in the complement:
    # 75 ms of ops (the loop aside), 5 of them the copy's
    assert read("anomod_seq_proj", "anomod_seq_mlp") == pytest.approx(10.0)
    assert read(*SCOPES) == pytest.approx(35.0)
    assert read(*SCOPES, complement=True) == pytest.approx(100 * 5 / 75)
    assert read("anomod_seq_proj", complement=True) \
        == pytest.approx(100 * 55 / 75)
    # the host plane's metadata names no device op
    assert "%fusion.1 = fusion(...)" not in ctx["_scope_ms_per_tick"][
        "names"]["anomod_seq_proj"]


# -- on the parent's program, the CPU and the tiny twins ----------------------

@pytest.mark.parametrize("metric", NEW)
def test_a_new_metric_finds_nothing_in_a_run_of_the_parents_program(
        metric, tmp_path):
    spec = _spec(metric)
    read = harness.module_for("readers", spec["reader"]).read
    for cell in MODEL + ["tt-fleet-overload"]:
        older = lxs2_cell._older_context(str(tmp_path), cell)
        assert read(older, **spec["args"]) is None
        # a trace directory that holds no device plane's metadata
        assert read(dict(older, ticks=0), **spec["args"]) is None


def test_the_parents_program_reads_high_in_the_complement(tmp_path):
    """Only the kernels are named there: the share no name accounts for
    is a number (and a high one), the rows of the parts are absent."""
    run = tmp_path / "plugins" / "profile" / "x"
    run.mkdir(parents=True)
    ops = [("%k = custom-call(...)", "jit(s)/anomod_seq_gqa/pallas_call",
            "custom-call"),
           ("%f = fusion(...)", "jit(s)/dot_general", "convolution fusion")]
    (run / "p.xplane.pb").write_bytes(_plane(b"/device:TPU:0", ops))
    trace = trace_reduce.Trace(
        window=(0, 100), host=[], devices={"/device:TPU:0": [
            (ops[0][0], 0, 30), (ops[1][0], 30, 100)]})
    ctx = dict(lxs2_cell._older_context(str(tmp_path), LXS2), trace=trace)
    got = {n: harness.module_for("readers", _spec(n)["reader"]).read(
        ctx, **_spec(n)["args"]) for n in NEW}
    assert {n for n, v in got.items() if v is not None} == {
        "step_device_ms.attn", "step_unscoped_pct"}
    assert got["step_unscoped_pct"] == pytest.approx(70.0)


@pytest.mark.parametrize("twin", ["k2", "n3s", "lxs2"])
def test_a_tiny_twins_traced_line_carries_the_spans_and_the_counters(
        twin, request):
    mod, fixture = {"k2": (bm_tiny_seq, "tiny_seq_root"),
                    "n3s": (bm_tiny_hybrid, "tiny_hybrid_root"),
                    "lxs2": (bm_tiny_swa, "tiny_swa_root")}[twin]
    root = request.getfixturevalue(fixture)
    rc, line, err = bm_tiny.run_cell(root, mod.TINY_CELL, 1, seed=5000000029)
    assert rc == 0, err
    bench = bm_tiny._load(root, "BENCHMARK.json")
    assert contract.check_last_line(line, bench, mod.TINY_CELL, True) == []
    # the twin's name was appended to the lists that name its cell
    due = set(contract.metrics_due(bench, mod.TINY_CELL, True))
    assert due >= set(DEVICE + HOST + ["step_unscoped_pct"])
    assert (set(N3S_ONLY) <= due) == (twin == "n3s")
    got = line["metrics"]
    # the CPU's trace names no device op: the scope rows are absent
    assert ON_CPU <= set(got) and not set(got) & set(
        DEVICE + N3S_ONLY + ["step_unscoped_pct"])
    model_ms = got["seq_model_ms" + {"k2": "", "n3s": ".n3s",
                                     "lxs2": ".lxs2"}[twin]]["value"]
    leaves = sum(got[n]["value"] for n in ("seq_issue_ms", "seq_wait_ms",
                                           "seq_fetch_ms"))
    assert 0 < leaves <= model_ms
    assert got["seq_plan_mb_per_tick"]["unit"] == "MB"
    assert 0 < got["seq_plan_mb_per_tick"]["value"] < 1
    # surprisals and expert counts every step; audit rows on some
    assert got["seq_fetch_mb_per_tick"]["value"] > 0
