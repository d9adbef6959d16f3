"""CPU rehearsal of chip_smoke.py: the phases are IMPORTED and run at a
tiny size (Pallas kernels interpreted, the CPU branches of the serve
engines), so the script cannot rot between chip runs; and the gate —
no TPU means a non-zero exit before any work — is run for real."""

import importlib.util
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def tmp_ingest_cache(tmp_path, monkeypatch):
    from anomod.config import Config, get_config, set_config
    old = get_config()
    monkeypatch.setenv("ANOMOD_CACHE_DIR", str(tmp_path / "cache"))
    set_config(Config())
    yield
    set_config(old)


def test_gate_exits_nonzero_without_tpu_before_any_work():
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    lines = r.stdout.strip().splitlines()
    assert len(lines) == 1 and lines[0].startswith("device: platform=cpu")
    assert '"ok"' not in r.stdout


def test_replay_phase_rehearsal(smoke, tmp_ingest_cache):
    info = smoke.phase_replay(n_traces=8, replicate=2)
    assert set(info) >= {"xla", "pallas", "pallas-sorted"}


def test_replay_parity_check_catches_a_wrong_plane(smoke):
    import numpy as np
    want = np.full((4, 22), 1000.0, np.float32)
    smoke.assert_replay_parity(want.copy(), want)
    miscounted = want.copy()
    miscounted[2, 0] += 1.0                 # one span too many
    with pytest.raises(AssertionError):
        smoke.assert_replay_parity(miscounted, want)
    bf16_only = want.copy()
    bf16_only[1, 4] *= 1.003                # a moment with no lo term
    with pytest.raises(AssertionError):
        smoke.assert_replay_parity(bf16_only, want)


def test_serve_phase_rehearsal(smoke):
    kw = dict(smoke.serve_run_kw(capacity=1500, duration=45, tenants=12),
              buckets=(64, 256), lane_buckets=(1, 2, 4))
    # at this size the second fault tenant is served in time to alert
    info = smoke.phase_serve(kw, expect_engines=("scatter", "numpy"),
                             expect_alerted=(0, 1))
    assert info["served_spans"] > 0
    # tier-1's pins, re-read through the smoke's own report: the run's
    # own served log re-scored sequentially, every tenant of it
    fused = info["bit_parity"]["fused_eq_sequential"]
    assert fused["alerts"] and fused["states"]
    assert fused["tenants"] == 12 and fused["pushes"] > fused["tenants"]
    assert info["bit_parity"]["device_eq_host_state"] == {
        "alerts": True, "states": True}
    # the asserts are live: the chip's expectations fail here
    with pytest.raises(AssertionError, match="engines"):
        smoke.phase_serve(kw, expect_alerted=(0, 1))
    with pytest.raises(AssertionError, match="fault tenants"):
        smoke.phase_serve(kw, expect_engines=("scatter", "numpy"))


def test_fused_vs_sequential_reads_the_runs_own_served_log(smoke):
    """The parity report has teeth: the served log ``run_power_law``
    hands back re-scores to the engine's states, and the same log with
    one tick's batches withheld does not."""
    from anomod.serve.engine import run_power_law
    log = []
    eng, rep = run_power_law(
        shards=1, served_log=log, buckets=(64, 256), lane_buckets=(1, 2, 4),
        **smoke.serve_run_kw(capacity=1500, duration=20, tenants=6))
    assert len(log) == 40                      # one entry per tick
    assert sum(qb.spans.n_spans for served in log for qb in served) \
        == rep.served_spans
    whole = smoke.fused_vs_sequential(eng, log)
    assert whole["alerts"] and whole["states"]
    busiest = max(range(len(log)), key=lambda k: len(log[k]))
    assert not smoke.fused_vs_sequential(
        eng, log[:busiest] + log[busiest + 1:])["states"]


def test_train_phase_rehearsal(smoke):
    info = smoke.phase_train(epochs=6, train_seeds=2, n_traces=10,
                             platform="cpu")
    assert info["loss_last"] < info["loss_first"]


def test_four_chip_phase_never_passes_silently(smoke, tmp_ingest_cache):
    assert smoke.phase_four_chips(8, n_devices=64) \
        == "not run: 8 device(s)"
    info = smoke.phase_four_chips(8, n_devices=4)   # the virtual mesh
    assert set(info) == {"xla", "pallas", "train_step"}
