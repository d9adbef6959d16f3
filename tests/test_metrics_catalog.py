"""Catalog-name parity with the reference collection scripts.

These tests parse the reference files at test time and assert our catalog
constants match name-for-name, so catalog drift is caught mechanically:

- SN: the ``--output .../<name>.csv`` targets of
  SN_collection-scripts/Dataset/metric_data/collect_metric.sh
- TT: the ``metric_categories`` level groups and the TT-specific query list
  of TT_collection-scripts/T-Dataset/metric_collector.py
"""


import re
from pathlib import Path

import numpy as np
import pytest

from anomod import metrics_catalog as mc

_REF = Path("/root/reference")
_SN_SH = _REF / "SN_collection-scripts/Dataset/metric_data/collect_metric.sh"
_TT_PY = _REF / "TT_collection-scripts/T-Dataset/metric_collector.py"

needs_ref = pytest.mark.skipif(not _REF.is_dir(),
                               reason="reference checkout not present")


@needs_ref
def test_sn_catalog_matches_collect_metric_sh():
    text = _SN_SH.read_text()
    ref_files = re.findall(r'--output\s+"\$OUTPUT_DIR/([\w.]+)\.csv"', text)
    assert ref_files, "no --output targets parsed from collect_metric.sh"
    assert list(mc.SN_METRIC_FILES) == ref_files


@needs_ref
def test_tt_catalog_matches_metric_collector_py():
    text = _TT_PY.read_text()
    # pull each level group's metrics list out of the metric_categories dict
    # (entries contain brackets/braces, so match to the ]-on-its-own-line
    # that closes the list, then collect the quoted strings)
    groups = {}
    for level in ("performance", "service", "database"):
        m = re.search(
            rf"'{level}':\s*{{.*?'metrics':\s*\[(.*?)\n\s*\]", text, re.S)
        assert m, f"level {level} not found in metric_collector.py"
        groups[level] = re.findall(r"'([^']+)'", m.group(1))
    for level, ref_list in groups.items():
        assert list(mc.TT_METRIC_CATEGORIES[level]) == ref_list, level


@needs_ref
def test_tt_specific_queries_match_reference():
    text = _TT_PY.read_text()
    m = re.search(r"train_ticket_queries\s*=\s*\[(.*?)\n\s*\]", text, re.S)
    assert m
    ref_queries = re.findall(r"'([^']+)'", m.group(1))
    assert list(mc.TT_SPECIFIC_QUERIES) == ref_queries


def test_normalize_metric_name():
    assert mc.normalize_metric_name("node_load5") == "node_load5"
    assert mc.normalize_metric_name(
        "rate(node_cpu_seconds_total[5m])") == "node_cpu_seconds_total"
    assert mc.normalize_metric_name(
        'kube_pod_status_phase{namespace="default"}') == "kube_pod_status_phase"
    assert mc.normalize_metric_name(
        'rate(container_network_receive_bytes_total{namespace="default"}[5m])'
    ) == "container_network_receive_bytes_total"
    with pytest.raises(ValueError):
        mc.normalize_metric_name("sum(foo) by (bar)")


def test_level_groups_cover_union():
    union = set()
    for level in ("performance", "service", "database"):
        union.update(mc.metrics_for_level(level))
    assert union == set(mc.TT_METRIC_NAMES)
    # ~31 unique metrics in the three groups
    assert len(mc.TT_METRIC_NAMES) >= 30


def test_experiment_window_clamp_semantics():
    now = 2_000_000.0
    # normal: earliest pod start within 24 h
    s, e = mc.experiment_window([now - 3600.0, now - 7200.0], now)
    assert (s, e) == (now - 7200.0, now)
    # clamp: pod older than 24 h
    s, e = mc.experiment_window([now - 48 * 3600.0], now)
    assert (s, e) == (now - 24 * 3600.0, now)
    # discovery returned nothing: 2 h safe window
    s, e = mc.experiment_window([], now)
    assert (s, e) == (now - 2 * 3600.0, now)
    # discovery errored: 1 h fallback
    s, e = mc.experiment_window(None, now, discovery_failed=True)
    assert (s, e) == (now - 3600.0, now)


def test_synth_emits_full_catalogs():
    from anomod import labels, synth
    sn = synth.generate_metrics(labels.label_for("Normal_Baseline"))
    assert sn.metric_names == mc.SN_METRIC_FILES
    tt = synth.generate_metrics(labels.label_for("Normal_case"))
    assert tt.metric_names == mc.TT_ALL_METRIC_NAMES
    # per-service families carry one series per service
    for name in ("microservice_error_rate",):
        mi = sn.metric_names.index(name)
        n_series = len(np.unique(sn.series[sn.metric == mi]))
        assert n_series == len(sn.services)
    for name in ("kube_pod_status_phase", "process_open_fds"):
        mi = tt.metric_names.index(name)
        n_series = len(np.unique(tt.series[tt.metric == mi]))
        assert n_series == len(tt.services)


def test_fault_conditioning_new_families():
    """The newly-modeled families must carry their fault's signature."""
    from anomod import labels, synth

    def series_values(batch, metric, svc=None):
        mi = batch.metric_names.index(metric)
        rows = batch.metric == mi
        if svc is not None:
            svc_i = batch.services.index(svc)
            s_ids = np.flatnonzero(
                np.asarray(batch.series_service) == svc_i)
            rows &= np.isin(batch.series, s_ids)
        return batch.value[rows], batch.t_s[rows]

    # SN: service-kill fault raises the target's error rate and drops its
    # request rate inside the anomaly window
    lab = labels.label_for("Svc_Kill_UserTimeline")
    m = synth.generate_metrics(lab)
    tgt = lab.target_service
    assert tgt in m.services
    v, _ = series_values(m, "microservice_error_rate", tgt)
    assert v.max() > 0.2
    v, _ = series_values(m, "microservice_request_rate", tgt)
    assert v.min() < 0.5 * np.median(v)
    # TT: pod-kill flips kube_pod_status_phase and bumps restarts
    lab = labels.label_for("Lv_S_KILLPOD_preserve")
    m = synth.generate_metrics(lab)
    v, _ = series_values(m, "kube_pod_status_phase", lab.target_service)
    assert (v == 0).any() and (v == 1).any()
    v, _ = series_values(m, "kube_pod_container_status_restarts_total",
                         lab.target_service)
    assert v.max() > 0
    # TT: connection-pool exhaustion spikes fds on the target
    lab = labels.label_for("Lv_D_CONNECTION_POOL_exhaustion")
    m = synth.generate_metrics(lab)
    v, _ = series_values(m, "process_open_fds", lab.target_service)
    assert v.max() > 3 * np.median(v)


def test_detector_level_features_populated():
    from anomod import labels, synth
    from anomod.detect import FEATURES, extract_features
    exp = synth.generate_experiment("Lv_D_TRANSACTION_timeout", n_traces=30)
    feats = extract_features(exp, exp.spans.services)
    i = FEATURES.index("metric_perf_log")
    assert feats.x[:, i:i + 3].max() > 0


def test_sn_store_families_per_owner_and_db_feature_fires():
    """SN store families are per-instance series attributed to the owning
    service (per-service Redis/Mongo in the compose stack), so the database
    level-keyed detector feature is live on SN."""
    from anomod import labels, synth
    from anomod.detect import FEATURES, extract_features
    lab = labels.label_for("DB_Redis_CacheLimit_HomeTimeline")
    m = synth.generate_metrics(lab)
    mi = m.metric_names.index("redis_memory_used")
    s_ids = np.unique(m.series[m.metric == mi])
    owners = {m.services[m.series_service[s]] for s in s_ids}
    assert lab.target_service in owners and len(owners) >= 3
    # target's redis shows the plateau drop; others don't
    tgt_i = m.services.index(lab.target_service)
    for s in s_ids:
        v = m.value[(m.metric == mi) & (m.series == s)]
        if m.series_service[s] == tgt_i:
            assert v.min() < 0.5 * np.median(v)
        else:
            assert v.min() > 0.5 * np.median(v)
    exp = synth.generate_experiment(lab.experiment, n_traces=30)
    x = extract_features(exp, exp.spans.services).x
    db_col = FEATURES.index("metric_db_log")
    assert x[:, db_col].max() > 0
