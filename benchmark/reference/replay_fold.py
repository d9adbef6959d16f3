"""The plain reference of the archive fold: numpy, float64 sums, nothing of
the program.  Segment ids, staged column values and the perturbation are
all computed here from the raw spans; the only shared code is
``benchmark.traffic`` (the benchmark's own input generator).

The latency moments are SUMMED FROM float32 column values in wide sums
(the reference); the control rounds each value to bfloat16 first — one
bf16 MXU pass where the configuration states the hi/lo pair.
"""

from __future__ import annotations

import numpy as np

from benchmark import traffic


def segment_ids(service, start_us, n_windows: int, window_us: int):
    """(service, window) segment of every span; the window grid starts at
    the first span and the last window takes everything later."""
    t0 = int(start_us.min())
    w = np.clip((start_us - t0) // window_us, 0, n_windows - 1)
    return (service.astype(np.int64) * n_windows + w).astype(np.int64)


def _round(x: np.ndarray, moments: str) -> np.ndarray:
    if moments == "float32":
        return x.astype(np.float64)
    if moments == "bfloat16":
        import ml_dtypes
        return x.astype(ml_dtypes.bfloat16).astype(np.float64)
    raise ValueError(moments)


def fold_archive(base: dict, cfg: dict, params: dict, seed: int,
                 control: bool = False):
    """``(reference, control)``, each [S*W, 6+H] float64: the state one
    pass over the whole archive leaves — count, errors, 5xx, sum lat, sum
    loglat, sum loglat^2, then the H-bucket histogram of floor(loglat).
    The control (None unless asked for) differs in its latency moments
    alone, summed from bfloat16-rounded values."""
    S, W, H = cfg["n_services"], cfg["n_windows"], cfg["n_hist_buckets"]
    sw = S * W
    sid = segment_ids(base["service"], base["start_us"], W, cfg["window_us"])
    dur_raw = base["duration_us"].astype(np.float32)
    dur = np.log1p(dur_raw)
    err = base["is_error"].astype(np.float32)
    valid = np.ones_like(dur_raw)
    f_tab, l_tab = traffic.jitter_tables(params)
    out = np.zeros((sw, 6 + H), np.float64)
    ctl = np.zeros((sw, 3), np.float64) if control else None
    bits = dur_raw.view(np.uint32)
    for key in traffic.copy_keys(seed, int(params["copies"])):
        e, s5, raw, d, d2 = traffic.perturb(
            np, bits, key, dur_raw, dur, err, err, valid, f_tab, l_tab,
            int(params["error_flip_per_1024"]))
        out[:, 0] += np.bincount(sid, minlength=sw)
        out[:, 1] += np.bincount(sid, weights=e, minlength=sw)
        out[:, 2] += np.bincount(sid, weights=s5, minlength=sw)
        for col, x in ((3, raw), (4, d), (5, d2)):
            out[:, col] += np.bincount(sid, weights=_round(x, "float32"),
                                       minlength=sw)
            if control:
                ctl[:, col - 3] += np.bincount(
                    sid, weights=_round(x, "bfloat16"), minlength=sw)
        bucket = np.clip(d.astype(np.int32), 0, H - 1)
        out[:, 6:] += np.bincount(sid * H + bucket,
                                  minlength=sw * H).reshape(sw, H)
    if control:
        ctl = np.concatenate([out[:, :3], ctl, out[:, 6:]], axis=1)
    return out, ctl


def compare(state: np.ndarray, want: np.ndarray) -> dict:
    """The numbers compared: cells of the exact planes (count, errors,
    5xx, histogram) that differ at all, and the widest relative gap of a
    latency moment — against the reference's value or the median
    segment's, whichever is larger, since a near-empty segment's sum is
    all rounding."""
    state = np.asarray(state, np.float64)
    exact = [0, 1, 2] + list(range(6, want.shape[1]))
    mismatch = int((state[:, exact] != want[:, exact]).sum())
    gap = 0.0
    for col in (3, 4, 5):
        ref = np.abs(want[:, col])
        floor = np.median(ref[ref > 0]) if (ref > 0).any() else 1.0
        gap = max(gap, float((np.abs(state[:, col] - want[:, col])
                              / np.maximum(ref, floor)).max()))
    return {"exact_cells_differing": mismatch, "moment_gap": gap}
