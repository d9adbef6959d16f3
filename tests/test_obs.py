"""Self-scraping telemetry plane (anomod.obs) + hardened tracer.

The acceptance-critical pin is the full dogfood round trip:
registry → TT-CSV export → ``load_tt_metric_csv`` → ``OnlineDetector``
flags an injected serve-plane stall on the ``serve`` subsystem.  The
rest covers registry semantics (thread safety, kind clash, disabled
nulls), both exporters, the engine's registry wiring, the env-contract
gate, and the tracer's new contracts (thread-local stacks, tags/events,
Jaeger round trip with parents+durations, atomic dump).
"""

import json
import subprocess
import sys
import threading
from pathlib import Path

import numpy as np
import pytest

from anomod.obs import export as obs_export
from anomod.obs.registry import NULL, Registry, set_registry
from anomod.obs.selfscrape import score_self_scrape, spans_from_metrics
from anomod.utils.tracing import Tracer

SCRIPTS = Path(__file__).parent.parent / "scripts"


@pytest.fixture
def registry():
    """A fresh force-enabled registry installed as the process default
    (instrumented call sites record into it), restored afterwards."""
    reg = Registry(enabled=True, max_samples=200_000)
    prev = set_registry(reg)
    yield reg
    set_registry(prev)


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_semantics(registry):
    c = registry.counter("anomod_test_events_total")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)                       # counters are monotone
    g = registry.gauge("anomod_test_depth")
    g.set(7)
    g.inc(2)
    g.dec()
    assert g.value == 8
    h = registry.histogram("anomod_test_wall_seconds")
    rng = np.random.default_rng(0)
    vals = rng.uniform(1.0, 2.0, 1000)
    for v in vals:
        h.observe(float(v))
    assert h.count == 1000
    assert h.sum == pytest.approx(vals.sum(), rel=1e-5)
    assert h.quantile(0.5) == pytest.approx(np.median(vals), rel=0.05)
    assert h.quantile(0.99) == pytest.approx(
        np.quantile(vals, 0.99), rel=0.05)
    # handles are memoized; a kind clash fails loudly
    assert registry.counter("anomod_test_events_total") is c
    with pytest.raises(ValueError, match="already registered"):
        registry.gauge("anomod_test_events_total")


def test_disabled_registry_is_noop():
    reg = Registry(enabled=False, max_samples=100)
    assert reg.counter("anomod_x_total") is NULL
    reg.counter("anomod_x_total").inc()      # all no-ops, never raise
    reg.histogram("anomod_x_seconds").observe(1.0)
    assert reg.scrape(now_s=0.0) == 0
    assert reg.snapshot() == {}
    assert reg.n_samples == 0


def test_counter_thread_safety(registry):
    c = registry.counter("anomod_test_threads_total")

    def work():
        for _ in range(5_000):
            c.inc()

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 40_000


def test_scrape_vs_record_hammer(registry):
    """Scrape-vs-record race hammer (the sharded serving plane's
    regime: worker threads record while the coordinator scrapes).

    Every histogram observation is exactly 1.0, so any scraped
    ``_count`` that disagrees with its ``_sum`` is a TORN read — the
    pre-fix ``Histogram.samples`` read count and sum outside the lock
    and could journal a count from after an observe with the sum from
    before it.  Counters/gauges ride along to shake the registry's
    handle table and journal under the same concurrency."""
    h = registry.histogram("anomod_test_hammer_seconds")
    c = registry.counter("anomod_test_hammer_total")
    g = registry.gauge("anomod_test_hammer_depth")
    N_THREADS, N_OBS = 4, 20_000
    # aggressive GIL churn: make the torn-read window (count read,
    # switch, observe, switch, sum read) actually reachable
    prev_switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)

    def record():
        for k in range(N_OBS):
            h.observe(1.0)
            c.inc()
            g.set(float(k))

    threads = [threading.Thread(target=record)
               for _ in range(N_THREADS)]
    try:
        for t in threads:
            t.start()
        scrapes = 0
        while any(t.is_alive() for t in threads):
            registry.scrape(now_s=float(scrapes))
            scrapes += 1
        for t in threads:
            t.join()
    finally:
        sys.setswitchinterval(prev_switch)
    registry.scrape(now_s=float(scrapes))
    # final totals exact
    assert h.count == N_THREADS * N_OBS
    assert h.sum == pytest.approx(float(N_THREADS * N_OBS))
    assert c.value == N_THREADS * N_OBS
    # every scraped (count, sum) pair is internally consistent
    rows = {}
    for t_s, name, _, val in registry.journal():
        rows.setdefault(t_s, {})[name] = val
    checked = 0
    for t_s, r in rows.items():
        if "anomod_test_hammer_seconds_count" in r:
            assert r["anomod_test_hammer_seconds_count"] == pytest.approx(
                r["anomod_test_hammer_seconds_sum"]), \
                f"torn histogram snapshot at scrape t={t_s}"
            checked += 1
    assert checked >= 2          # the hammer actually overlapped scrapes


def test_registry_fold_from_shard_registries(registry):
    """The sharded engine's merge seam: counters fold as deltas
    (summable fleet totals across repeated folds), gauges land on
    shard-labeled twins, histograms merge once at final through
    merge_digest."""
    shard = Registry(enabled=True, max_samples=1000)
    state = {}
    c = shard.counter("anomod_serve_fused_dispatches_total")
    g = shard.gauge("anomod_serve_lane_pad_waste_fraction")
    h = shard.histogram("anomod_serve_fused_lanes")
    c.inc(3)
    g.set(0.25)
    for v in (1.0, 2.0, 4.0):
        h.observe(v)
    registry.fold_from(shard, state, shard="0")
    assert registry.counter(
        "anomod_serve_fused_dispatches_total").value == 3
    c.inc(2)
    registry.fold_from(shard, state, shard="0")   # delta, not re-total
    assert registry.counter(
        "anomod_serve_fused_dispatches_total").value == 5
    assert registry.gauge("anomod_serve_lane_pad_waste_fraction",
                          shard="0").value == 0.25
    # histograms only at final=True, and they DRAIN: a second final
    # fold (engine run() twice) adds only the new observations
    assert registry.histogram("anomod_serve_fused_lanes").count == 0
    registry.fold_from(shard, state, shard="0", final=True)
    assert registry.histogram("anomod_serve_fused_lanes").count == 3
    assert registry.histogram("anomod_serve_fused_lanes").sum == \
        pytest.approx(7.0)
    registry.fold_from(shard, state, shard="0", final=True)   # drained
    assert registry.histogram("anomod_serve_fused_lanes").count == 3
    h.observe(8.0)
    registry.fold_from(shard, state, shard="0", final=True)
    assert registry.histogram("anomod_serve_fused_lanes").count == 4
    assert registry.histogram("anomod_serve_fused_lanes").sum == \
        pytest.approx(15.0)
    # disabled either side: no-op
    registry.fold_from(Registry(enabled=False, max_samples=10), {},
                       shard="1", final=True)


def test_histogram_merge_digest(registry):
    """The serve plane's fold path: a pre-built t-digest joins the
    histogram weight-preserving, with count/sum bookkeeping."""
    from anomod.ops.tdigest import tdigest_build
    h = registry.histogram("anomod_test_fold_seconds")
    vals = np.linspace(1.0, 3.0, 512).astype(np.float32)
    h.merge_digest(tdigest_build(vals, k=32))
    assert h.count == 512
    assert h.sum == pytest.approx(float(vals.sum()), rel=1e-4)
    assert h.quantile(0.5) == pytest.approx(2.0, rel=0.05)


def test_scrape_journal_bound_and_batch(registry):
    g = registry.gauge("anomod_serve_backlog_spans")
    for t in range(10):
        g.set(t)
        registry.scrape(now_s=float(t))
    assert registry.n_samples == 10
    batch = obs_export.to_metric_batch(registry)
    assert batch.n_samples == 10
    assert batch.metric_names == ("anomod_serve_backlog_spans",)
    assert batch.services == ("serve",)
    # series carry service="<subsystem>" for direct multimodal pushes
    assert 'service="serve"' in batch.series_keys[0]
    assert int(batch.series_service[0]) == 0
    small = Registry(enabled=True, max_samples=5)
    c = small.counter("anomod_x_total")
    for t in range(20):
        c.inc()
        small.scrape(now_s=float(t))
    assert small.n_samples == 5              # bounded journal drops oldest


def test_prometheus_text_format(registry):
    registry.counter("anomod_ingest_cache_hits_total").inc(3)
    h = registry.histogram("anomod_serve_tick_seconds")
    for v in np.linspace(0.01, 0.02, 300):
        h.observe(float(v))
    text = obs_export.to_prometheus_text(registry)
    assert "# HELP anomod_ingest_cache_hits_total " in text
    assert "# TYPE anomod_ingest_cache_hits_total counter" in text
    assert "anomod_ingest_cache_hits_total 3" in text
    assert "# HELP anomod_serve_tick_seconds " in text
    assert "# TYPE anomod_serve_tick_seconds summary" in text
    assert 'anomod_serve_tick_seconds{quantile="0.99"}' in text
    assert "anomod_serve_tick_seconds_count 300" in text


def _parse_prom(text):
    """A tiny exposition-format parser (unescaping label values per the
    grammar) — what the adversarial-label pin re-reads the export with."""
    samples = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        head, value = line.rsplit(" ", 1)
        labels = {}
        if "{" in head:
            name, rest = head.split("{", 1)
            body = rest[:rest.rindex("}")]
            i = 0
            while i < len(body):
                eq = body.index("=", i)
                key = body[i:eq]
                assert body[eq + 1] == '"'
                j = eq + 2
                val = []
                while body[j] != '"':
                    if body[j] == "\\":
                        val.append({"\\": "\\", '"': '"',
                                    "n": "\n"}[body[j + 1]])
                        j += 2
                    else:
                        val.append(body[j])
                        j += 1
                labels[key] = "".join(val)
                i = j + 1
                if i < len(body) and body[i] == ",":
                    i += 1
            head = name
        samples[(head, tuple(sorted(labels.items())))] = float(value)
    return samples


def test_prometheus_escaping_adversarial_labels(registry):
    """Exposition-format hardening: backslash, double-quote and newline
    in label values must escape per the grammar and round-trip through a
    parser; HELP lines appear exactly once per metric family even with
    label variants (the shard-labeled gauge shape)."""
    evil = 'C:\\temp\n"quoted",comma'
    registry.gauge("anomod_test_evil", path=evil).set(7)
    registry.gauge("anomod_test_evil", path="plain").set(8)
    registry.counter("anomod_test_total", reason="a\\b").inc(2)
    text = obs_export.to_prometheus_text(registry)
    # raw control characters never leak into the wire format
    for line in text.splitlines():
        assert "\r" not in line
    assert '\\n' in text and '\\"' in text and "\\\\" in text
    samples = _parse_prom(text)
    assert samples[("anomod_test_evil",
                    (("path", evil),))] == 7
    assert samples[("anomod_test_evil",
                    (("path", "plain"),))] == 8
    assert samples[("anomod_test_total",
                    (("reason", "a\\b"),))] == 2
    # one HELP + one TYPE per family, label variants notwithstanding
    assert text.count("# HELP anomod_test_evil ") == 1
    assert text.count("# TYPE anomod_test_evil ") == 1
    # every family has a HELP line
    names = {line.split(" ", 3)[2] for line in text.splitlines()
             if line.startswith("# TYPE ")}
    helped = {line.split(" ", 3)[2] for line in text.splitlines()
              if line.startswith("# HELP ")}
    assert names == helped


# ---------------------------------------------------------------------------
# instrumented layers record into the registry
# ---------------------------------------------------------------------------

def test_cache_instrumentation_mirrors_stats(tmp_path, registry):
    import dataclasses

    from anomod.config import Config
    from anomod.io import cache
    cfg = dataclasses.replace(Config(), cache_dir=tmp_path / "cache")
    calls = []

    def compute():
        calls.append(1)
        return np.arange(4)

    # miss + store, then a hit — wrong-kind arg keeps the helper honest
    from anomod.schemas import ApiBatch
    value = ApiBatch(endpoint=np.zeros(2, np.int32),
                     t_s=np.array([1.0, 2.0]),
                     status=np.array([200, 200], np.int16),
                     latency_ms=np.array([1.0, 2.0]),
                     content_length=np.zeros(2, np.int64),
                     endpoints=("/a",))
    cache.cached("api", {"k": 1}, lambda: value, cfg=cfg)
    cache.cached("api", {"k": 1}, lambda: value, cfg=cfg)
    assert registry.counter("anomod_ingest_cache_misses_total").value >= 1
    assert registry.counter("anomod_ingest_cache_hits_total").value >= 1
    assert registry.counter("anomod_ingest_cache_stores_total").value >= 1
    assert registry.counter(
        "anomod_ingest_cache_written_bytes_total").value > 0
    assert registry.counter(
        "anomod_ingest_cache_read_bytes_total").value > 0


def test_prefetch_instrumentation(registry):
    from anomod.io.prefetch import Pipeline
    pipe = Pipeline(range(10), lambda x: x * 2, depth=2)
    assert list(pipe) == [2 * i for i in range(10)]
    h = registry.histogram("anomod_prefetch_stage_seconds")
    assert h.count == 10


def test_serve_engine_registry_wiring(registry):
    """A small seeded serve run populates every serve-plane metric and
    scrapes on the virtual clock (deterministic timeline)."""
    from anomod.serve.engine import run_power_law
    eng, rep = run_power_law(
        n_tenants=6, n_services=4, capacity_spans_per_s=1200,
        overload=1.5, duration_s=12, tick_s=1.0, seed=5,
        window_s=4.0, baseline_windows=2, fault_tenants=0)
    assert rep.served_spans > 0
    served = registry.counter("anomod_serve_served_spans_total").value
    assert served == rep.served_spans
    offered = registry.counter("anomod_serve_offered_spans_total").value
    assert offered == rep.offered_spans
    assert registry.counter("anomod_serve_ticks_total").value == rep.ticks
    lat = registry.histogram("anomod_serve_admit_to_scored_seconds")
    lat_total = sum(s.n_samples for s in eng._slo.values())
    eng_report_fold = lat.count            # report() folded every tenant
    assert eng_report_fold == lat_total
    # bucket-pad waste is derivable and bounded
    staged = registry.counter("anomod_serve_staged_rows_total").value
    live = registry.counter("anomod_serve_live_rows_total").value
    assert live == rep.served_spans and staged >= live
    assert 0.0 <= registry.gauge(
        "anomod_serve_pad_waste_fraction").value < 1.0
    # one scrape per virtual second, on the virtual clock
    ts = {t for t, _, _, _ in registry.journal()}
    assert ts and max(ts) <= 12.0 + 1.0
    # tracer on by default (gated on the enabled registry)
    assert eng.tracer is not None and eng.tracer.n_spans > 0


# ---------------------------------------------------------------------------
# the acceptance round trip: injected serve-plane stall
# ---------------------------------------------------------------------------

def _simulated_stalled_run(stall_after_s: float = 140.0,
                           end_s: float = 200.0) -> Registry:
    """A hand-driven registry timeline: healthy serve telemetry for the
    baseline phase, then a stall (tick walls and queue depth jump 30x)."""
    reg = Registry(enabled=True, max_samples=100_000)
    tick = reg.histogram("anomod_serve_tick_seconds")
    lat = reg.histogram("anomod_serve_admit_to_scored_seconds")
    backlog = reg.gauge("anomod_serve_backlog_spans")
    served = reg.counter("anomod_serve_served_spans_total")
    rng = np.random.default_rng(7)
    for t in range(int(end_s)):
        stalled = t >= stall_after_s
        scale = 30.0 if stalled else 1.0
        tick.observe(float(rng.uniform(0.009, 0.011) * scale))
        lat.observe(float(rng.uniform(0.4, 0.6) * scale))
        backlog.set(float(rng.uniform(900, 1100) * scale))
        served.inc(0 if stalled else 500)
        reg.scrape(now_s=float(t))
    return reg


def test_self_scrape_flags_injected_serve_stall(tmp_path):
    """registry → TT-CSV → load_tt_metric_csv → OnlineDetector: the
    stall localizes to the `serve` subsystem, after its onset."""
    from anomod.io.metrics import load_tt_metric_csv
    reg = _simulated_stalled_run()
    csv_path = tmp_path / "selfscrape.csv"
    n = obs_export.export_tt_csv(reg, csv_path)
    assert n == reg.n_samples
    assert load_tt_metric_csv(csv_path).n_samples == n   # loader contract
    report = score_self_scrape(csv_path, window_s=10.0,
                               baseline_windows=4, z_threshold=4.0)
    assert "serve" in report["subsystems"]
    assert report["n_alerts"] > 0
    assert report["alerted_subsystems"] == ["serve"]
    onset_window = int(140.0 // 10.0)
    assert all(a["window"] >= onset_window for a in report["alerts"])
    assert report["ranked_subsystems"][0] == "serve"


def test_self_scrape_healthy_run_stays_quiet(tmp_path):
    reg = _simulated_stalled_run(stall_after_s=1e9)     # never stalls
    csv_path = tmp_path / "healthy.csv"
    obs_export.export_tt_csv(reg, csv_path)
    report = score_self_scrape(csv_path, window_s=10.0,
                               baseline_windows=4, z_threshold=4.0)
    assert report["n_alerts"] == 0


def test_spans_from_metrics_counter_differencing():
    """Cumulative *_total streams must contribute rates, not their
    monotone raw values (which would fake a latency trend)."""
    reg = Registry(enabled=True, max_samples=10_000)
    c = reg.counter("anomod_serve_served_spans_total")
    for t in range(50):
        c.inc(100)                       # perfectly steady rate
        reg.scrape(now_s=float(t))
    spans = spans_from_metrics(obs_export.to_metric_batch(reg))
    # first sample has no predecessor and is dropped; the rest are the
    # constant per-scrape delta (normalized to the series' own scale,
    # so steady rate -> the 1e6 anchor), never the growing cumulative
    assert spans.n_spans == 49
    assert set(spans.duration_us.tolist()) == {1_000_000}


# ---------------------------------------------------------------------------
# env contract gate
# ---------------------------------------------------------------------------

def test_obs_env_contract(monkeypatch):
    from anomod.config import Config
    monkeypatch.setenv("ANOMOD_OBS_ENABLED", "0")
    assert Config().obs_enabled is False
    monkeypatch.setenv("ANOMOD_OBS_ENABLED", "1")
    assert Config().obs_enabled is True
    monkeypatch.setenv("ANOMOD_OBS_MAX_SAMPLES", "nope")
    with pytest.raises(ValueError, match="ANOMOD_OBS_MAX_SAMPLES"):
        Config()
    monkeypatch.setenv("ANOMOD_OBS_MAX_SAMPLES", "0")
    with pytest.raises(ValueError, match="ANOMOD_OBS_MAX_SAMPLES"):
        Config()


def test_env_contract_script_passes_on_repo():
    r = subprocess.run(
        [sys.executable, str(SCRIPTS / "check_env_contract.py")],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stdout + r.stderr
    out = json.loads(r.stdout)
    assert out["status"] == "ok"
    # the PINNED inventory size: a new ANOMOD_* knob must land here and
    # in the docs in the same PR (PR 21 took it from 79 to 72: the probe /
    # platform-pin / jit-cache names went with the machinery they set;
    # PR 31 to 61: the perf observatory's three and the eight that the
    # deleted pre-chip benchmark and its fold sweep read)
    assert out["n_vars"] == 61


def test_env_contract_script_catches_rogue_var(tmp_path):
    """A fixture tree with an undocumented ANOMOD_* read must fail."""
    (tmp_path / "anomod").mkdir()
    (tmp_path / "scripts").mkdir()
    (tmp_path / "docs").mkdir()
    (tmp_path / "anomod" / "config.py").write_text(
        'X = _env("ANOMOD_KNOWN_KNOB", "1")\n')
    (tmp_path / "anomod" / "rogue.py").write_text(
        'import os\nY = os.environ.get("ANOMOD_ROGUE_KNOB")\n')
    (tmp_path / "README.md").write_text("no knobs documented here\n")
    r = subprocess.run(
        [sys.executable, str(SCRIPTS / "check_env_contract.py"),
         "--root", str(tmp_path)],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 1
    out = json.loads(r.stdout)
    assert "ANOMOD_ROGUE_KNOB" in out["missing"]
    assert "ANOMOD_KNOWN_KNOB" not in out.get("missing", {})


# ---------------------------------------------------------------------------
# tracer: thread safety, tags/events, round trip, atomic dump
# ---------------------------------------------------------------------------

def test_tracer_thread_local_stacks():
    """Spans opened from worker threads must not corrupt the main
    thread's parent links (the old shared-stack bug)."""
    tr = Tracer("anomod-test")
    stop = threading.Event()

    def worker():
        while not stop.is_set():
            with tr.span("worker.stage"):
                pass

    threads = [threading.Thread(target=worker) for _ in range(4)]
    with tr.span("main.pipeline"):
        for t in threads:
            t.start()
        for _ in range(200):
            with tr.span("main.step"):
                pass
        stop.set()
        for t in threads:
            t.join()
    doc = tr.to_jaeger()["data"][0]
    by_id = {s["spanID"]: s for s in doc["spans"]}
    for s in doc["spans"]:
        if s["operationName"] == "main.step":
            # every main.step's parent is main.pipeline, never a worker
            assert len(s["references"]) == 1
            parent = by_id[s["references"][0]["spanID"]]
            assert parent["operationName"] == "main.pipeline"
        elif s["operationName"] == "worker.stage":
            assert s["references"] == []       # thread roots, not children


def test_tracer_jaeger_roundtrip_parents_and_durations(tmp_path):
    """Docstring-promised round trip: to_jaeger() parses through
    spans_from_jaeger with parent references and durations intact."""
    import time

    from anomod.io.sn_traces import spans_from_jaeger
    tr = Tracer("anomod-test")
    with tr.span("pipeline", phase="bench"):
        with tr.span("load"):
            time.sleep(0.01)
        with tr.span("detect") as sp:
            sp.event("windows-scored", n=7)
    batch = spans_from_jaeger(tr.to_jaeger())
    assert batch.n_spans == 3
    assert batch.services == ("anomod-test",)
    names = [batch.endpoints[int(e)] for e in batch.endpoint]
    root = names.index("pipeline")
    assert (batch.parent == -1).sum() == 1
    assert int(batch.parent[names.index("load")]) == root
    assert int(batch.parent[names.index("detect")]) == root
    assert int(batch.duration_us[names.index("load")]) >= 10_000
    # tags + events survive in the Jaeger shape
    doc = tr.to_jaeger()["data"][0]["spans"]
    root_span = next(s for s in doc if s["operationName"] == "pipeline")
    assert {"key": "phase", "value": "bench"} in root_span["tags"]
    detect_span = next(s for s in doc if s["operationName"] == "detect")
    assert detect_span["logs"] and detect_span["logs"][0]["fields"]


def test_tracer_chrome_roundtrip(tmp_path):
    """Chrome trace-event exporter: the event array loads as plain JSON
    (the chrome://tracing / Perfetto shape — complete "X" events on the
    microsecond clock) and round-trips through spans_from_chrome with
    names, parents, durations and tags intact."""
    import time

    from anomod.utils.tracing import spans_from_chrome
    tr = Tracer("anomod-test")
    with tr.span("pipeline", phase="bench"):
        with tr.span("load"):
            time.sleep(0.01)
        with tr.span("detect"):
            pass
    events = tr.to_chrome()
    assert all(e["ph"] == "X" for e in events)
    assert all(isinstance(e["ts"], int) and isinstance(e["dur"], int)
               for e in events)
    # foreign events (another producer's metadata rows) are skipped, and
    # a Perfetto-style re-sort by timestamp still parses losslessly
    shuffled = sorted(events, key=lambda e: e["ts"], reverse=True)
    spans = spans_from_chrome(
        [{"ph": "M", "name": "process_name"}] + shuffled)
    assert [s["name"] for s in spans] == ["pipeline", "load", "detect"]
    root = spans[0]
    assert root["parent"] is None
    assert spans[1]["parent"] == 0 and spans[2]["parent"] == 0
    assert spans[1]["dur"] >= 0.01
    assert root["tags"] == {"phase": "bench"}
    # atomic publish, same contract as the jaeger dump
    path = tmp_path / "trace_chrome.json"
    path.write_text("[]")
    tr.dump_chrome(path)
    assert list(tmp_path.glob("*.tmp")) == []
    assert json.loads(path.read_text()) == events


def test_obs_export_chrome_cli(tmp_path):
    """`anomod obs export --format chrome`: the self-exercise engine's
    own trace lands as a loadable trace-event array."""
    from anomod.cli import main
    from anomod.utils.tracing import spans_from_chrome
    out = tmp_path / "serve_trace.json"
    rc = main(["obs", "export", "--format", "chrome", "--out", str(out),
               "--serve-seconds", "4", "--tenants", "4",
               "--capacity", "1000"])
    assert rc == 0
    events = json.loads(out.read_text())
    spans = spans_from_chrome(events)
    names = {s["name"] for s in spans}
    assert "serve.run" in names and "serve.admit" in names


def test_tracer_dump_atomic(tmp_path):
    tr = Tracer("anomod-test")
    with tr.span("only"):
        pass
    path = tmp_path / "trace.json"
    path.write_text("{\"stale\": true}")     # replace, never append/truncate
    tr.dump(path)
    doc = json.loads(path.read_text())
    assert doc["data"][0]["spans"][0]["operationName"] == "only"
    # no tmp litter left beside the published file
    assert list(tmp_path.glob("*.tmp")) == []
