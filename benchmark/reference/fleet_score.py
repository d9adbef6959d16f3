"""The plain reference of one served tenant: numpy, float64, nothing of the
program.  Given the tenant's served spans in served order it folds them
into per-(service, window) aggregates and a latency histogram, then
scores every closed window the way the fleet configuration states:

- the first ``baseline_windows`` windows calibrate, per service, the
  pooled log-latency mean and variance, a Laplace-smoothed error rate,
  the span rate, and the between-window variances of the window means;
- a later window ``w`` is scored once a span of a later window has been
  served: latency z (standard error of the window's log-latency mean),
  error z (binomial against the pooled rate), drop z (Poisson deficit)
  and a recovery-resetting CUSUM of the deficit; a window in which the
  tenant reported nothing resets the CUSUM and is not scored;
- a service alerts in ``w`` when the largest of the four z's reaches
  ``z_threshold``.

``moments`` is the precision the latency moments are SUMMED FROM:
``"float32"`` is the reference; ``"bfloat16"`` rounds every value to
bfloat16 first — the control, one bf16 pass where the configuration
states the hi/lo pair.
"""

from __future__ import annotations

import numpy as np

COUNT, ERR, S5, LAT, LOGLAT, LOGLAT2 = range(6)
DROP_MEMORY = 8


def _round(x: np.ndarray, moments: str) -> np.ndarray:
    if moments == "float32":
        return x.astype(np.float64)
    if moments == "bfloat16":
        import ml_dtypes
        return x.astype(ml_dtypes.bfloat16).astype(np.float64)
    raise ValueError(moments)


def fold(spans: dict, cfg: dict, moments: str = "float32"):
    """``(agg [S, W, 6], hist [S, W, H])`` float64 of one tenant's served
    spans (``service``, ``start_us``, ``duration_us``, ``is_error``,
    ``status``); window ``w`` holds starts in ``[w, w+1) * window_us``."""
    S, W, H = cfg["n_services"], cfg["n_windows"], cfg["n_hist_buckets"]
    w = np.clip(spans["start_us"] // int(cfg["window_us"]), 0, W - 1)
    sid = spans["service"].astype(np.int64) * W + w
    raw = spans["duration_us"].astype(np.float32)
    log = np.log1p(raw)
    agg = np.zeros((S * W, 6), np.float64)
    for col, x in ((COUNT, None), (ERR, spans["is_error"]),
                   (S5, spans["status"] >= 500),
                   (LAT, _round(raw, moments)), (LOGLAT, _round(log, moments)),
                   (LOGLAT2, _round(log * log, moments))):
        agg[:, col] = np.bincount(
            sid, weights=None if x is None else x.astype(np.float64),
            minlength=S * W)
    bucket = np.clip(log.astype(np.int32), 0, H - 1)
    hist = np.bincount(sid * H + bucket, minlength=S * W * H)
    return agg.reshape(S, W, 6), hist.reshape(S, W, H).astype(np.float64)


def calibrate(agg: np.ndarray, B: int, min_count: float) -> dict:
    cnt = agg[:, :B, COUNT]
    c0 = np.maximum(cnt.sum(axis=1), 1.0)
    mu = agg[:, :B, LOGLAT].sum(axis=1) / c0
    var_span = np.maximum(agg[:, :B, LOGLAT2].sum(axis=1) / c0 - mu ** 2,
                          1e-4)
    p_err = (agg[:, :B, ERR].sum(axis=1) + 1.0) / (c0 + 2.0)
    rate0 = cnt.mean(axis=1)
    safe = np.maximum(cnt, 1.0)
    valid = cnt >= min_count
    nb = np.maximum(valid.sum(axis=1), 1)

    def between(per_window):
        m = (per_window * valid).sum(axis=1) / nb
        return ((per_window - m[:, None]) ** 2 * valid).sum(axis=1) / nb

    return {"mu": mu, "var_span": var_span, "p_err": p_err,
            "err_var": np.maximum(p_err * (1.0 - p_err), 1e-6),
            "rate0": rate0,
            "var_bl": between(agg[:, :B, LOGLAT] / safe),
            "var_be": between(agg[:, :B, ERR] / safe),
            "active": rate0 >= min_count, "cum_active": rate0 >= 1.0,
            "calibrated": c0 >= 2.0 * min_count,
            "sd_cnt": np.sqrt(np.maximum(cnt.var(axis=1),
                                         np.maximum(rate0, 1.0)))}


def score(agg: np.ndarray, last_window: int, cfg: dict) -> dict:
    """``{(window, service): (z_latency, z_error, z_drop, z_cusum)}`` for
    every scored window below ``last_window`` (the newest window a served
    span fell into)."""
    B = int(cfg["baseline_windows"])
    min_count = float(cfg["min_count"])
    if last_window <= B:
        return {}
    b = calibrate(agg, B, min_count)
    S = agg.shape[0]
    cusum, run = np.zeros(S), np.zeros(S, np.int64)
    out = {}
    for w in range(B, last_window):
        col = agg[:, w]
        n = col[:, COUNT]
        if n.sum() <= 0:
            cusum[:], run[:] = 0.0, 0
            continue
        safe = np.maximum(n, 1.0)
        ok = (n >= min_count) & b["calibrated"]
        zl = np.where(ok, (col[:, LOGLAT] / safe - b["mu"])
                      / np.sqrt(b["var_span"] / safe + b["var_bl"]), 0.0)
        ze = np.where(ok, (col[:, ERR] / safe - b["p_err"])
                      / np.sqrt(b["err_var"] / safe + b["var_be"]), 0.0)
        zd = np.where(b["active"], (b["rate0"] - n) / b["sd_cnt"], 0.0)
        cusum = np.where(n >= b["rate0"], 0.0, np.maximum(
            0.0, cusum + b["rate0"] - n - 0.25 * b["sd_cnt"]))
        run = np.where(cusum > 0, np.minimum(run + 1, DROP_MEMORY), 0)
        zc = np.where(b["cum_active"], cusum
                      / (b["sd_cnt"] * np.sqrt(np.maximum(run, 1))), 0.0)
        for s in range(S):
            out[(w, s)] = (zl[s], ze[s], zd[s], zc[s])
    return out


def alerts_of(agg: np.ndarray, last_window: int, cfg: dict) -> list:
    """The alerts ``score`` bears out, as ``(window, service, z...)``."""
    thr = float(cfg["z_threshold"])
    return [(w, s) + tuple(z) for (w, s), z in
            score(agg, last_window, cfg).items() if max(z) >= thr]


def last_window(spans: dict, cfg: dict) -> int:
    return int(spans["start_us"].max() // int(cfg["window_us"])) \
        if len(spans["start_us"]) else -1


def _gap(got, want) -> float:
    return max(abs(g - w) / max(1.0, abs(w)) for g, w in zip(got, want))


def compare_tenant(state_agg, state_hist, alerts, spans: dict,
                   cfg: dict) -> dict:
    """One tenant's numbers: cells of the exact planes (count, errors,
    5xx, histogram) that differ at all; the widest relative gap of a
    latency moment (against the reference's value or the median
    segment's, whichever is larger); and the widest gap of a z, against
    max(1, |reference z|), taken two ways over every scored (window,
    service): the z's this file scores from the program's STATE against
    those it scores from the reference's, and the z's the program's
    ALERTS carry against the reference's — where an alert the reference
    does not bear out counts as a gap of 1: one raised where the
    reference's largest z lies more than ``alert_margin`` under the
    threshold, or one missing where it lies more than that over."""
    S, W, H = cfg["n_services"], cfg["n_windows"], cfg["n_hist_buckets"]
    want_agg, want_hist = fold(spans, cfg)
    got_agg = np.asarray(state_agg, np.float64).reshape(S, W, 6)
    got_hist = np.asarray(state_hist, np.float64).reshape(S, W, H)
    differing = int((got_agg[..., :3] != want_agg[..., :3]).sum()
                    + (got_hist != want_hist).sum())
    gap = 0.0
    for col in (LAT, LOGLAT, LOGLAT2):
        ref = np.abs(want_agg[..., col])
        floor = np.median(ref[ref > 0]) if (ref > 0).any() else 1.0
        gap = max(gap, float((np.abs(got_agg[..., col] - want_agg[..., col])
                              / np.maximum(ref, floor)).max()))
    last = last_window(spans, cfg)
    zs = score(want_agg, last, cfg)
    from_state = score(got_agg, last, cfg) if not differing else {}
    z_gap = max((_gap(from_state[key], ref) for key, ref in zs.items()
                 if key in from_state), default=0.0)
    thr, margin = float(cfg["z_threshold"]), float(cfg["alert_margin"])
    unborne, seen = 0, set()
    for (w, s, *got) in alerts:
        ref = zs.get((w, s))
        seen.add((w, s))
        if ref is None or max(ref) < thr - margin:
            unborne += 1
        else:
            z_gap = max(z_gap, _gap(got, ref))
    unborne += sum(1 for key, ref in zs.items()
                   if max(ref) >= thr + margin and key not in seen)
    return {"exact_cells_differing": differing, "moment_gap": gap,
            "z_gap": max(z_gap, 1.0) if unborne else z_gap,
            "alerts_unborne": unborne, "alerts_compared": len(alerts),
            "windows_scored": len({w for w, _ in zs})}
