"""A tiny twin of ``k2-fleet-overload`` added to a temp copy of the
benchmark as new files and entries (``bm_tiny.py`` is not edited): the
decoder at the tests' tiny preset under a 24-tenant fleet."""

import json
import os
import shutil

import pytest

from bm_tiny import ROOT, _dump, _load

CELL, TINY_CELL = "k2-fleet-overload", "tiny-k2-fleet-overload"
CONFIG, TINY_CONFIG = "kimi-k2-ep32-share", "tiny-k2-share"

#: the tier-1 preset: hidden 64, 4 heads, 16 routed experts top-2 of which
#: 4 held, 3 layers
PRESET = dict(
    hidden_size=64, num_attention_heads=4, q_lora_rank=32, kv_lora_rank=64,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=128, moe_intermediate_size=32, n_routed_experts=16,
    n_shared_experts=1, num_experts_per_tok=2, num_hidden_layers=3,
    experts_held=4, experts_lo=4)


def make_tiny_seq_root(dst: str) -> str:
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    bench = _load(ROOT, "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    cfg = dict(_load(dst, entry["file"]), **PRESET)
    cfg.update(name=TINY_CONFIG, vocab_size=1024, vocab_held=1024)
    cfg["assumed"] = dict(cfg["assumed"], context_tokens=64, block_tokens=8,
                          pool_tokens=1024, token_grid=[64, 256])
    cfg["fleet"] = dict(cfg["fleet"], n_tenants=24, n_services=5)
    file = f"benchmark/configs/{TINY_CONFIG}.json"
    _dump(cfg, dst, file)
    bench["configs"].append(dict(entry, name=TINY_CONFIG, file=file))
    wl = _load(dst, "benchmark", "workloads", CELL + ".json")
    wl.update(config=TINY_CONFIG, trace_seconds=1.0, sample_tenants=8,
              sample_busiest=2, control_tokens=400,
              own_mean_least_spans=16,
              reference_lengths=[64])
    wl["params"].update(offered_spans_per_s=400, pre_window_s=14,
                        pre_merge=2)
    # the tiny preset's own readings on the CPU (seeds 91-93, 5000000011,
    # windows of 1 and 1.5 s), program at most / control at least: mean
    # surprisal gap 0.0081 / 0.0314, the worst session's or tenant's own
    # mean 0.0236 / 0.0497, mean logit-row gap 0.0176 / 0.0412 (limit 0.03
    # between them, 0.035 before the control read whole-length sessions).
    # With 16 experts, a
    # quarter of them held, a near-tie of two scores moves a token's row
    # far more often than among 384.  The cell's own limits come from the
    # chip's readings
    wl["limits"] = dict(surprisal_gap_mean=0.02, logit_gap=0.03,
                        surprisal_gap_group_max=0.035)
    _dump(wl, dst, "benchmark", "workloads", TINY_CELL + ".json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    bench["workloads"].append(dict(cell, name=TINY_CELL, config=TINY_CONFIG))
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if CELL in m.get("workloads", []):
                m["workloads"] = m["workloads"] + [TINY_CELL]
    _dump(bench, dst, "BENCHMARK.json")
    return dst


@pytest.fixture(scope="session")
def tiny_seq_root(tmp_path_factory):
    os.environ["JAX_PLATFORMS"] = "cpu"
    return make_tiny_seq_root(str(tmp_path_factory.mktemp("bm_tiny_seq")))
