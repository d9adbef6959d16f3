"""The benchmark's one traffic generator: every mix is a data file of
parameters read here, and everything is a pure function of the seed.

Two families:

- ``archive_base`` / ``copy_keys`` / ``jitter_tables`` / ``perturb``: the
  replay archive.  A base campaign of raw spans is drawn on the host; the
  archive is ``K`` copies of it, each with its own seeded perturbation of
  the latency and error columns.  The perturbation is integer hashing and
  single IEEE float32 operations only, so the device (``jax.numpy``) and
  the plain reference (``numpy``) compute bit-equal inputs from the same
  ``perturb`` code, whatever the layout the program stages them in.
- ``fleet_rates`` / ``fleet_schedule``: the serving fleet.  The
  distributions are ``anomod/serve/traffic.PowerLawTraffic``'s (power-law
  tenant rates, Poisson arrivals per tenant and tick, a Dirichlet service
  mix and a latency scale per tenant, lognormal latencies, a 1% error
  floor, scripted latency faults, micro-batches capped at ``batch_cap``),
  drawn for the whole fleet at once instead of one Python iteration per
  tenant per tick.
"""

from __future__ import annotations

import numpy as np

MASK32 = 0xFFFFFFFF


def seed_words(seed: int) -> list:
    """``--seed`` (any whole number, beyond 32 bits too) as 32-bit words
    for ``numpy.random.default_rng``."""
    seed = int(seed)
    if seed < 0:
        raise ValueError("--seed must be >= 0")
    return [seed & MASK32, (seed >> 32) & MASK32]


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(seed_words(seed) + [int(stream)])


# -- the replay archive -----------------------------------------------------

def archive_base(p: dict, seed: int) -> dict:
    """One campaign's raw spans: ``service``, ``start_us``, ``duration_us``,
    ``is_error`` (an error is a 5xx).  Which service a span belongs to and
    when it starts come from the parameters alone (``structure_seed``), so
    every seed stages the same sizes — the same rows a segment, the same
    padding, the same compiled shapes; latencies and errors, the values
    that are folded, come from ``seed``."""
    shape = rng_for(int(p["structure_seed"]), 1)
    n, S = int(p["base_spans"]), int(p["n_services"])
    mix = shape.dirichlet(np.full(S, float(p["service_mix_dirichlet"])))
    service = shape.choice(S, size=n, p=mix).astype(np.int32)
    span_us = int(p["campaign_windows"]) * int(p["window_us"])
    start = np.sort(shape.integers(0, span_us, n)).astype(np.int64)
    rng = rng_for(seed, 1)
    scale = rng.uniform(p["latency_scale_us"][0], p["latency_scale_us"][1], S)
    dur = np.maximum(scale[service] * rng.lognormal(
        0.0, float(p["latency_sigma"]), n), 1.0).astype(np.int64)
    err = rng.random(n) < float(p["error_rate"])
    return {"service": service, "start_us": start, "duration_us": dur,
            "is_error": err}


def _lowbias32(x, xp):
    x = x ^ (x >> xp.uint32(16))
    x = x * xp.uint32(0x7FEB352D)
    x = x ^ (x >> xp.uint32(15))
    x = x * xp.uint32(0x846CA68B)
    return x ^ (x >> xp.uint32(16))


def copy_keys(seed: int, k: int) -> np.ndarray:
    """[K] uint32: copy ``c``'s hash key."""
    s = np.uint32((int(seed) ^ (int(seed) >> 32)) & MASK32)
    c = np.arange(k, dtype=np.uint32) * np.uint32(0x9E3779B9)
    return _lowbias32(c ^ s, np)


def jitter_tables(p: dict):
    """256 dyadic latency factors in [1 - a, 1 + a) and their float32
    logarithms: ``dur_raw * f`` and ``log1p(dur_raw) + log f`` are single
    exact-rounded float32 operations on both sides."""
    a = float(p["latency_jitter"])
    f = 1.0 + np.round((np.arange(256) - 128) / 128.0 * a * 1024) / 1024
    return f.astype(np.float32), np.log(f).astype(np.float32)


def perturb(xp, bits, key, dur_raw, dur, err, s5, valid, f_tab, l_tab,
            flip_per_1024):
    """Copy ``key``'s latency and error columns from the base columns
    (float32 arrays; ``bits`` is ``dur_raw`` bit-cast to uint32, ``key`` a
    uint32 scalar or a broadcastable array; ``xp`` is numpy or
    jax.numpy).  Returns ``(err, s5, dur_raw, dur, dur2)``; rows with
    ``valid == 0`` stay all-zero."""
    h = _lowbias32(bits ^ key, xp)
    j = (h & xp.uint32(0xFF)).astype(xp.int32)
    flip = (((h >> xp.uint32(8)) & xp.uint32(0x3FF))
            < xp.uint32(flip_per_1024)).astype(xp.float32) * valid
    log_dur = (dur + l_tab[j]) * valid
    return (xp.abs(err - flip), xp.abs(s5 - flip), dur_raw * f_tab[j],
            log_dur, log_dur * log_dur)


# -- the serving fleet --------------------------------------------------------

def fleet_rates(p: dict, n_tenants: int) -> np.ndarray:
    """[T] offered spans/s per tenant: rank r gets (r+1)^-alpha of the
    cell's fixed total."""
    shares = (1.0 + np.arange(n_tenants)) ** -float(p["alpha"])
    return float(p["offered_spans_per_s"]) * shares / shares.sum()


def fleet_schedule(p: dict, cfg: dict, seed: int, n_ticks: int) -> dict:
    """Arrivals of virtual ticks ``0 .. n_ticks-1`` for the whole fleet.

    Two optional calls, each ``[from, to)`` virtual seconds, each adding
    one span at a seeded tick of its interval: ``roll_call_s`` of EVERY
    tenant of the fleet (the state the pool is sized for is state the
    traffic has written), ``baseline_call_s`` of every tenant that reports
    after it (whoever reports later has a window past its baseline by
    then, so no once-in-a-lifetime calibration falls into what follows).
    Where the mix gives a ``structure_seed``, how many spans each tenant
    sends in each tick, and the calls' ticks, come from it alone: every
    seed then offers the same batches, tenants and lanes a tick, and only
    what is folded and scored (service, start, latency, error) follows
    ``seed`` — as in ``archive_base``.
    Returns flat span columns sorted by (tick, tenant, start) plus the
    micro-batch table: ``batch_tick``, ``batch_tenant``, ``batch_lo``,
    ``batch_hi`` (row ranges into the span columns; no batch is longer
    than ``batch_cap``)."""
    T, S = int(cfg["n_tenants"]), int(cfg["n_services"])
    tick_s = float(cfg["tick_s"])
    tick_us = int(round(tick_s * 1e6))
    rates = fleet_rates(p, T)
    rng = rng_for(seed, 2)
    shape = rng_for(int(p["structure_seed"]), 5) \
        if "structure_seed" in p else rng
    counts = shape.poisson(rates[None, :] * tick_s, size=(n_ticks, T))
    for call in ("roll_call_s", "baseline_call_s"):
        if p.get(call):
            lo, hi = (min(int(round(t / tick_s)), n_ticks) for t in p[call])
            who = np.arange(T) if call == "roll_call_s" \
                else np.nonzero(counts[hi:].sum(axis=0))[0]
            if hi > lo and len(who):
                counts[shape.integers(lo, hi, len(who)), who] += 1
    n = int(counts.sum())
    flat = counts.ravel()
    cell = np.repeat(np.arange(n_ticks * T, dtype=np.int64), flat)
    tick = (cell // T).astype(np.int32)
    tenant = (cell % T).astype(np.int32)
    # per-tenant service mix and latency scale: from the tenant id alone
    mix_rng = rng_for(seed, 3)
    mix = mix_rng.dirichlet(np.full(S, float(p["service_mix_dirichlet"])),
                            size=T)
    lat = mix_rng.uniform(p["latency_scale_us"][0], p["latency_scale_us"][1],
                          size=(T, S))
    cdf = np.cumsum(mix, axis=1)
    cdf[:, -1] = 1.0
    table = (cdf + np.arange(T)[:, None]).ravel()
    service = (np.searchsorted(table, tenant + rng.random(n), side="right")
               - tenant.astype(np.int64) * S).astype(np.int32)
    np.clip(service, 0, S - 1, out=service)
    start = tick.astype(np.int64) * tick_us + rng.integers(0, tick_us, n)
    order = np.lexsort((start, cell))
    start, service = start[order], service[order]
    dur = lat[tenant, service] * rng.lognormal(
        0.0, float(p["latency_sigma"]), n)
    fault = np.zeros(n, bool)
    onset_us = int(round(float(p["fault_onset_s"]) * 1e6))
    for t in range(int(p["fault_tenants"])):
        fault |= (tenant == t) & (service == int(p["fault_service"])) \
            & (tick.astype(np.int64) * tick_us >= onset_us)
    dur = np.where(fault, dur * float(p["fault_factor"]), dur)
    dur = np.maximum(dur.astype(np.int64), 1)
    err = rng.random(n) < float(p["error_rate"])
    trace = rng.integers(0, 64, n).astype(np.int32)
    # micro-batches: each (tick, tenant) run cut at batch_cap
    cap = int(p["batch_cap"])
    live = np.nonzero(flat)[0]
    run_n = flat[live]
    run_lo = np.concatenate([[0], np.cumsum(run_n)[:-1]])
    n_b = -(-run_n // cap)
    b_run = np.repeat(np.arange(len(live)), n_b)
    b_idx = np.arange(int(n_b.sum())) - np.repeat(
        np.concatenate([[0], np.cumsum(n_b)[:-1]]), n_b)
    b_lo = run_lo[b_run] + b_idx * cap
    b_hi = np.minimum(b_lo + cap, run_lo[b_run] + run_n[b_run])
    return {"n_ticks": n_ticks, "tick": tick, "tenant": tenant,
            "service": service,
            "start_us": start, "duration_us": dur, "is_error": err,
            "trace": trace, "rates": rates,
            "batch_tick": (live[b_run] // T).astype(np.int32),
            "batch_tenant": (live[b_run] % T).astype(np.int32),
            "batch_lo": b_lo.astype(np.int64),
            "batch_hi": b_hi.astype(np.int64)}
