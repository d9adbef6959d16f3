"""Ingest fast-path tests: content-addressed cache correctness (warm ==
cold bit-identical, invalidation on source change and loader-version bump,
corrupt-entry fallback), parallel-loader parity, the double-buffered
prefetcher and the env contract."""

import json
import os
from pathlib import Path

import numpy as np
import pytest

from anomod import labels, synth
from anomod.config import Config
from anomod.io import cache, dataset
from anomod.io import metrics as met_io


def _cfg(tmp_path, **kw):
    kw.setdefault("data_root", tmp_path / "data")
    kw.setdefault("cache_dir", tmp_path / "cache")
    return Config(**kw)


def _assert_batches_equal(a, b, ctx=""):
    if a is None or b is None:
        assert a is b, ctx
        return
    for f in a._fields:
        x, y = getattr(a, f), getattr(b, f)
        if isinstance(x, np.ndarray):
            assert x.dtype == y.dtype, (ctx, f)
            np.testing.assert_array_equal(x, y, err_msg=f"{ctx}.{f}")
        else:
            assert x == y, (ctx, f)


def _assert_experiments_equal(e1, e2):
    assert e1.name == e2.name and e1.testbed == e2.testbed
    assert e1.synthetic == e2.synthetic
    _assert_batches_equal(e1.spans, e2.spans, "spans")
    _assert_batches_equal(e1.metrics, e2.metrics, "metrics")
    _assert_batches_equal(e1.logs, e2.logs, "logs")
    _assert_batches_equal(e1.api, e2.api, "api")
    _assert_batches_equal(e1.coverage, e2.coverage, "coverage")
    assert e1.log_summaries == e2.log_summaries


def test_warm_load_bit_identical_all_modalities(tmp_path):
    """Warm load == cold load, bit for bit, for all five modalities
    (synth-fallback corpus: the shipped checkout's situation)."""
    cfg = _cfg(tmp_path)
    cold = dataset.load_experiment("Lv_P_CPU_preserve", cfg=cfg,
                                   n_synth_traces=20)
    cache.reset_stats()
    warm = dataset.load_experiment("Lv_P_CPU_preserve", cfg=cfg,
                                   n_synth_traces=20)
    assert cache.stats().hits == 5 and cache.stats().misses == 0
    _assert_experiments_equal(cold, warm)
    assert warm.synthetic


def _write_tt_metric_tree(cfg, label, value_shift=0.0):
    d = (cfg.tt_data / "metric_data"
         / f"{label.experiment}_20251103T185917Z_em")
    d.mkdir(parents=True, exist_ok=True)
    m = synth.generate_metrics(label, duration_s=120)
    if value_shift:
        m = m._replace(value=m.value + value_shift)
    met_io.write_metric_batch_tt_csv(m, d / "exp_metrics_1.csv")
    return d / "exp_metrics_1.csv"


def test_invalidation_on_source_file_change(tmp_path):
    """Rewriting a source artifact (new size/mtime) must invalidate the
    entry: the reload parses the NEW content instead of serving stale."""
    cfg = _cfg(tmp_path)
    label = labels.label_for("Lv_D_cachelimit")
    art = _write_tt_metric_tree(cfg, label)
    m1 = dataset.load_experiment(label.experiment, cfg=cfg,
                                 modalities=["metrics"]).metrics
    cache.reset_stats()
    m1b = dataset.load_experiment(label.experiment, cfg=cfg,
                                  modalities=["metrics"]).metrics
    assert cache.stats().hits == 1
    _assert_batches_equal(m1, m1b, "metrics")

    _write_tt_metric_tree(cfg, label, value_shift=100.0)
    os.utime(art, ns=(1, 1))     # force a distinct mtime_ns fingerprint
    cache.reset_stats()
    m2 = dataset.load_experiment(label.experiment, cfg=cfg,
                                 modalities=["metrics"]).metrics
    assert cache.stats().misses >= 1
    assert float(np.nanmean(m2.value)) > float(np.nanmean(m1.value)) + 50


def test_invalidation_on_loader_version_bump(tmp_path, monkeypatch):
    cfg = _cfg(tmp_path)
    label = labels.label_for("Lv_D_cachelimit")
    _write_tt_metric_tree(cfg, label)
    dataset.load_experiment(label.experiment, cfg=cfg,
                            modalities=["metrics"])
    monkeypatch.setattr(met_io, "LOADER_VERSION",
                        met_io.LOADER_VERSION + 1)
    cache.reset_stats()
    dataset.load_experiment(label.experiment, cfg=cfg,
                            modalities=["metrics"])
    assert cache.stats().misses >= 1, \
        "a loader-version bump must invalidate that modality's entries"


def test_synth_version_bump_invalidates_synth_entries(tmp_path, monkeypatch):
    cfg = _cfg(tmp_path)
    dataset.load_experiment("Lv_P_CPU_preserve", cfg=cfg,
                            modalities=["traces"], n_synth_traces=10)
    monkeypatch.setattr(synth, "SYNTH_VERSION", synth.SYNTH_VERSION + 1)
    cache.reset_stats()
    dataset.load_experiment("Lv_P_CPU_preserve", cfg=cfg,
                            modalities=["traces"], n_synth_traces=10)
    assert cache.stats().misses >= 1


def test_corrupt_cache_entry_falls_back_to_reparse(tmp_path):
    """A truncated/garbage payload is a miss, not a crash — and the reload
    re-publishes a good entry."""
    cfg = _cfg(tmp_path)
    cold = dataset.load_experiment("Lv_S_KILLPOD_preserve", cfg=cfg,
                                   n_synth_traces=15)
    payloads = sorted((tmp_path / "cache").glob("*/*.npc"))
    assert payloads
    for p in payloads:
        p.write_bytes(p.read_bytes()[: max(8, p.stat().st_size // 3)])
    cache.reset_stats()
    again = dataset.load_experiment("Lv_S_KILLPOD_preserve", cfg=cfg,
                                    n_synth_traces=15)
    assert cache.stats().errors >= 1 and cache.stats().hits == 0
    _assert_experiments_equal(cold, again)
    cache.reset_stats()
    dataset.load_experiment("Lv_S_KILLPOD_preserve", cfg=cfg,
                            n_synth_traces=15)
    assert cache.stats().hits == 5, "re-parse must re-publish the entries"


def test_cache_disabled_still_loads(tmp_path):
    cfg = _cfg(tmp_path, cache_dir=None)
    exp = dataset.load_experiment("Lv_P_CPU_preserve", cfg=cfg,
                                  n_synth_traces=10)
    assert exp.spans is not None and exp.spans.n_spans > 0
    assert cache.entry_count(tmp_path / "cache") == 0


def test_parallel_loader_matches_serial(tmp_path):
    """Pool-loaded corpus == serial corpus (same Experiment fields, same
    synthetic flags), including the LFS-stub + synth-fallback path."""
    cfg = _cfg(tmp_path)
    # one experiment gets an LFS-pointer trace artifact: the loader must
    # see the stub, fall back to synth, and still match across pool/serial
    label = labels.label_for("Lv_P_CPU_preserve")
    d = (cfg.tt_data / "trace_data"
         / f"{label.experiment}_20251103T185917Z_em")
    d.mkdir(parents=True)
    (d / f"{label.experiment}_skywalking_traces_x.json").write_text(
        "version https://git-lfs.github.com/spec/v1\n"
        "oid sha256:deadbeef\nsize 12345\n")
    serial = dataset.load_corpus("TT", cfg=cfg, n_synth_traces=10,
                                 workers=0)
    cache.reset_stats()
    pooled = dataset.load_corpus("TT", cfg=cfg, n_synth_traces=10,
                                 workers=2)
    assert len(serial) == len(pooled) == 13
    for e1, e2 in zip(serial, pooled):
        _assert_experiments_equal(e1, e2)
    assert any(e.synthetic for e in pooled)
    # worker-process cache counters must merge back into this process
    assert cache.stats().hits >= 65


def test_prefetch_pipeline_preserves_order_and_values():
    from anomod.io.prefetch import Pipeline, iter_chunk_dicts
    chunks = {"a": np.arange(12).reshape(3, 4),
              "b": np.arange(12, 24).reshape(3, 4)}
    staged = list(Pipeline(iter_chunk_dicts(chunks), fn=lambda d: d))
    assert len(staged) == 3
    for i, d in enumerate(staged):
        np.testing.assert_array_equal(d["a"], chunks["a"][i])
        np.testing.assert_array_equal(d["b"], chunks["b"][i])


def test_prefetch_pipeline_propagates_worker_errors():
    from anomod.io.prefetch import Pipeline

    def bad():
        yield 1
        raise RuntimeError("boom")

    it = Pipeline(bad(), fn=lambda x: x * 2)
    assert next(it) == 2
    with pytest.raises(RuntimeError, match="boom"):
        list(it)


def test_device_put_columns_matches_direct_put():
    from anomod.io.prefetch import device_put_columns
    cols = {"x": np.arange(100, dtype=np.int32),
            "y": np.linspace(0, 1, 50, dtype=np.float32)}
    staged = device_put_columns(cols)
    assert set(staged) == {"x", "y"}
    for k in cols:
        np.testing.assert_array_equal(np.asarray(staged[k]), cols[k])


def test_env_contract(monkeypatch):
    monkeypatch.setenv("ANOMOD_CACHE_DIR", "off")
    assert Config().cache_dir is None
    monkeypatch.setenv("ANOMOD_CACHE_DIR", "/tmp/somewhere")
    assert Config().cache_dir == Path("/tmp/somewhere")
    monkeypatch.setenv("ANOMOD_INGEST_WORKERS", "4")
    assert Config().ingest_workers == 4
    monkeypatch.setenv("ANOMOD_INGEST_WORKERS", "many")
    with pytest.raises(ValueError, match="ANOMOD_INGEST_WORKERS"):
        Config()
    monkeypatch.setenv("ANOMOD_INGEST_WORKERS", "-2")
    with pytest.raises(ValueError, match="ANOMOD_INGEST_WORKERS"):
        Config()


def test_ingest_cli_warm_cache(tmp_path, capsys):
    from anomod.cli import main
    rc = main(["ingest", "--warm-cache", "--testbed", "TT",
               "--traces", "8", "--bench-traces", "0",
               "--cache-dir", str(tmp_path / "c"),
               "--data-root", str(tmp_path / "d")])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["entries"] == out["stores"] > 0
    assert out["warmed"] == ["TT"]
    # second warm pass: all hits, no new stores
    rc = main(["ingest", "--warm-cache", "--testbed", "TT",
               "--traces", "8", "--bench-traces", "0",
               "--cache-dir", str(tmp_path / "c"),
               "--data-root", str(tmp_path / "d")])
    assert rc == 0
    out2 = json.loads(capsys.readouterr().out)
    assert out2["misses"] == 0 and out2["hits"] >= 65


def test_bench_corpus_cold_warm_accounting(tmp_path):
    cfg = _cfg(tmp_path)
    b1, cold = dataset.load_bench_corpus("TT", 60, cfg)
    assert not cold["cache_hit"] and cold["parse_s"] > 0
    b2, warm = dataset.load_bench_corpus("TT", 60, cfg)
    assert warm["cache_hit"]
    assert warm["parse_s"] == pytest.approx(cold["parse_s"])
    _assert_batches_equal(b1, b2, "bench-corpus")
