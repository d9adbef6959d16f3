"""A tiny twin of ``lxs2-fleet-overload`` added to a temp copy of the
benchmark as new files and entries (``bm_tiny.py``, ``bm_tiny_seq.py`` and
``bm_tiny_hybrid.py`` are not edited): the window-and-full attention
decoder at the tests' tiny preset under a 24-tenant fleet whose window
pool is smaller than its tenants' rings."""

import os
import shutil

import pytest

from bm_tiny import ROOT, _dump, _load

CELL, TINY_CELL = "lxs2-fleet-overload", "tiny-lxs2-fleet-overload"
CONFIG, TINY_CONFIG = "laguna-xs2-pp8-stage", "tiny-lxs2-stage"

#: the tier-1 preset: hidden 64; 6 | 8 query heads over 2 key-value heads
#: of 32; a window of 20 keys over blocks of 8; YaRN from 16 positions on
#: half a head; 16 experts top-2, all held; F S S S F with a dense layer
#: first
PRESET = dict(
    hidden_size=64, num_hidden_layers=5,
    num_attention_heads_per_layer=[6, 8, 8, 8] * 10,
    num_attention_heads=6, num_key_value_heads=2, head_dim=32,
    sliding_window=20, intermediate_size=128, num_experts=16,
    num_experts_per_tok=2, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, experts_held=16, experts_lo=0)


#: the pools: 58 usable blocks of 8 tokens, 51 usable window blocks; the
#: pre-window's ticks (``pre_merge`` 4, ~800 tokens of 24 tenants) are
#: wider than either and are cut into policy steps
POOL_TOKENS, WINDOW_BLOCKS = 472, 52


def make_tiny_swa_root(dst: str) -> str:
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    bench = _load(ROOT, "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    cfg = dict(_load(dst, entry["file"]), **PRESET)
    cfg.update(name=TINY_CONFIG, vocab_size=1024, vocab_held=1024)
    rope = cfg["rope_parameters"]
    cfg["rope_parameters"] = dict(rope, full_attention=dict(
        rope["full_attention"], original_max_position_embeddings=16,
        beta_fast=4))
    cfg["assumed"] = dict(cfg["assumed"], context_tokens=64, block_tokens=8,
                          pool_tokens=POOL_TOKENS,
                          window_blocks=WINDOW_BLOCKS,
                          token_grid=[64, 256])
    cfg["fleet"] = dict(cfg["fleet"], n_tenants=24, n_services=5)
    file = f"benchmark/configs/{TINY_CONFIG}.json"
    _dump(cfg, dst, file)
    bench["configs"].append(dict(entry, name=TINY_CONFIG, file=file))
    wl = _load(dst, "benchmark", "workloads", CELL + ".json")
    wl.update(config=TINY_CONFIG, trace_seconds=1.0, sample_tenants=8,
              sample_busiest=2, control_tokens=400,
              own_mean_least_spans=16, reference_lengths=[64])
    wl["params"].update(offered_spans_per_s=400, pre_window_s=14)
    wl["limits"] = dict(surprisal_gap_mean=LIMITS[0],
                        surprisal_gap_group_max=LIMITS[1],
                        logit_gap=LIMITS[2], surprisal_gap_p50=LIMITS[3])
    _dump(wl, dst, "benchmark", "workloads", TINY_CELL + ".json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    bench["workloads"].append(dict(cell, name=TINY_CELL, config=TINY_CONFIG))
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if CELL in m.get("workloads", []):
                m["workloads"] = m["workloads"] + [TINY_CELL]
    _dump(bench, dst, "BENCHMARK.json")
    return dst


#: surprisal_gap_mean, surprisal_gap_group_max, logit_gap of the tiny twin:
#: between its own readings on the CPU, program / control (the test file
#: gives them); the cell's own limits come from the chip's readings
LIMITS = (0.08, 0.25, 0.2, 0.017)


@pytest.fixture(scope="session")
def tiny_swa_root(tmp_path_factory):
    os.environ["JAX_PLATFORMS"] = "cpu"
    return make_tiny_swa_root(str(tmp_path_factory.mktemp("bm_tiny_swa")))
