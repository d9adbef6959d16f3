"""Operations and bytes of the ALGORITHM, from shapes only.

The fold is a segment sum: every span adds its six features into its
(service, window) segment and one count into that segment's latency
histogram.  The count reads the same whatever implements the fold — a
one-hot matmul's multiply-adds are the implementation's, not the
algorithm's, so they are not counted here.
"""

from __future__ import annotations

import json
import os

N_FEATS = 6                      # count, err, 5xx, lat, loglat, loglat^2
STAGED_BYTES_PER_SPAN = 7 * 4    # replay.STAGE_KEYS: seven 4-byte columns


def fold_work(n_spans: int, n_segments: int, n_hist: int) -> dict:
    """One pass over ``n_spans`` staged spans into a fresh state."""
    state = n_segments * (N_FEATS + n_hist) * 4
    return {"flops": n_spans * (N_FEATS + 1),
            "bytes": n_spans * STAGED_BYTES_PER_SPAN + state}


def load_peaks(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "benchmark/peaks.json; add it with its source")
    return table[device_kind]


def least_seconds(work: dict, peaks: dict) -> tuple:
    """``(seconds, which)``: the least time the chip could take, and
    whether compute (``flops``) or memory (``bytes``) binds it."""
    by_flops = work["flops"] / peaks["flops_per_s"]
    by_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    return (by_flops, "flops") if by_flops > by_bytes else (by_bytes, "bytes")
