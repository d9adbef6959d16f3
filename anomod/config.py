"""Configuration: env-var contract + dataclass config.

Mirrors the reference's ``${VAR:-default}`` env contract style
(SN_collection-scripts/README.md:38-53, collect_all_data.sh:37-54) but as a
typed, non-interactive config object.  Placeholder values of the form
``{SOMETHING}`` are treated as unset, matching the reference's anonymization
placeholder policy (``ensure_path_var``, collect_all_data.sh:37-44).
"""

from __future__ import annotations

import dataclasses
import os
from pathlib import Path
from typing import Optional


def _env(name: str, default: str) -> str:
    """Read an env var; reference-style ``{PLACEHOLDER}`` values count as unset."""
    val = os.environ.get(name, "").strip()
    if not val or (val.startswith("{") and val.endswith("}")):
        return default
    return val


# Default data roots: the reference checkout mounted read-only, and this repo.
_DEFAULT_REFERENCE_ROOT = "/root/reference"

# Sentinel values that disable the ingest cache entirely.
_CACHE_OFF = ("0", "off", "none", "disabled", "false")


def _cache_dir_env() -> Optional[Path]:
    """ANOMOD_CACHE_DIR: ingest-cache root; "0"/"off"/"none" disables it.

    Unset means the default user cache location — the cache is on by
    default so repeat runs measure the kernel, not host parsing.
    """
    raw = _env("ANOMOD_CACHE_DIR", "")
    if raw.lower() in _CACHE_OFF:
        return None
    if raw:
        return Path(raw).expanduser()
    return Path(os.path.expanduser("~/.cache/anomod"))


def _ingest_workers_env() -> int:
    """ANOMOD_INGEST_WORKERS: corpus-loader process-pool size (0/1 = serial).

    Validated here so a typo fails loudly at config construction instead of
    silently falling back to the serial path.
    """
    raw = _env("ANOMOD_INGEST_WORKERS", "0")
    try:
        n = int(raw)
    except ValueError:
        raise ValueError(
            f"ANOMOD_INGEST_WORKERS must be a non-negative integer "
            f"(0/1 = serial), got {raw!r}")
    if n < 0:
        raise ValueError(
            f"ANOMOD_INGEST_WORKERS must be >= 0, got {n}")
    return n


#: default serving-plane micro-batch bucket widths (spans) — one XLA
#: compile per width (anomod.serve.batcher re-exports this and the
#: validator below as its contract; they live HERE so Config()
#: construction never pays the serve/stream import chain).  The 64
#: bucket joined with the tenant-fused dispatch path: a power-law
#: fleet's tail tenants flush a handful of spans per tick, and staging
#: them 256-wide was ~80% of all staged rows as padding — narrow
#: buckets only became affordable once lane stacking amortized the
#: per-dispatch cost across tenants.
DEFAULT_SERVE_BUCKETS = (64, 256, 1024, 4096, 16384)


def validate_serve_buckets(buckets) -> tuple:
    """The one bucket-set contract: positive, strictly ascending ints."""
    try:
        out = tuple(int(b) for b in buckets)
    except (TypeError, ValueError):
        raise ValueError(f"bucket set must be integers, got {buckets!r}")
    if not out:
        raise ValueError("bucket set must not be empty")
    if any(b < 1 for b in out):
        raise ValueError(f"bucket widths must be >= 1, got {out}")
    if any(b >= c for b, c in zip(out, out[1:])):
        raise ValueError(f"bucket widths must be strictly ascending: {out}")
    return out


def _serve_buckets_env() -> tuple:
    """ANOMOD_SERVE_BUCKETS: comma-separated micro-batch bucket widths
    (spans) for the serving plane's dynamic batcher.

    Validated at config construction (positive, strictly ascending ints)
    so a typo'd bucket set fails loudly instead of compiling garbage
    shapes mid-serve.
    """
    raw = _env("ANOMOD_SERVE_BUCKETS", "")
    if not raw:
        return DEFAULT_SERVE_BUCKETS
    parts = [p.strip() for p in raw.split(",") if p.strip()]
    try:
        return validate_serve_buckets(parts)
    except ValueError as e:
        raise ValueError(f"ANOMOD_SERVE_BUCKETS: {e}") from e


#: default serving-plane lane-bucket set for the FUSED dispatch path
#: (anomod.serve.batcher): per tick, same-width staged chunks from many
#: tenants stack into [lanes, width] dispatches, lanes padded up to the
#: smallest bucket here (one XLA compile per (width, lane-bucket) shape).
DEFAULT_SERVE_LANE_BUCKETS = (1, 2, 4, 8, 16, 32)


def validate_lane_buckets(lanes) -> tuple:
    """The lane-bucket contract: positive, strictly ascending ints —
    the same shape discipline as the width buckets (every (width,
    lane-bucket) pair is one compiled executable, so the set must be
    small and fixed)."""
    try:
        out = tuple(int(b) for b in lanes)
    except (TypeError, ValueError):
        raise ValueError(f"lane-bucket set must be integers, got {lanes!r}")
    if not out:
        raise ValueError("lane-bucket set must not be empty")
    if any(b < 1 for b in out):
        raise ValueError(f"lane buckets must be >= 1, got {out}")
    if any(b >= c for b, c in zip(out, out[1:])):
        raise ValueError(f"lane buckets must be strictly ascending: {out}")
    return out


def _serve_lane_buckets_env() -> tuple:
    """ANOMOD_SERVE_LANE_BUCKETS: comma-separated lane counts for the
    serving plane's fused (lane-stacked) dispatch.

    Validated at config construction, same contract as
    ``ANOMOD_SERVE_BUCKETS`` — a typo'd set fails loudly instead of
    compiling garbage lane shapes mid-serve.
    """
    raw = _env("ANOMOD_SERVE_LANE_BUCKETS", "")
    if not raw:
        return DEFAULT_SERVE_LANE_BUCKETS
    parts = [p.strip() for p in raw.split(",") if p.strip()]
    try:
        return validate_lane_buckets(parts)
    except ValueError as e:
        raise ValueError(f"ANOMOD_SERVE_LANE_BUCKETS: {e}") from e


def _serve_fuse_env() -> bool:
    """ANOMOD_SERVE_FUSE: serving-plane fused-dispatch switch.

    Default ON; "0"/"false"/"off" is the escape hatch back to one
    dispatch per tenant micro-batch.  The fused path is pinned
    bit-identical on CPU to SEQUENTIAL scoring of the same per-tick
    COALESCED batches — coalescing itself regroups a tenant's same-tick
    micro-batches into one staging, so flipping this switch can move
    borderline f32 bits (and admission/SLO numbers are byte-identical
    either way); see docs/SERVING.md "Fused dispatch" for the exact
    contract."""
    return _env("ANOMOD_SERVE_FUSE", "1").strip().lower() \
        not in ("0", "false", "off", "no")


def _serve_shards_env() -> int:
    """ANOMOD_SERVE_SHARDS: serving-plane engine-worker shard count.

    ``1`` (the default) is the single-threaded engine, output
    bit-identical to the pre-sharding serving plane (its DISPATCH may
    still pipeline per ``ANOMOD_SERVE_PIPELINE``; set that to 1 for the
    exact synchronous code path).  ``N > 1`` partitions tenants across
    N worker threads (anomod.serve.shard), each owning its tenants'
    scoring plane end to end; admission/shedding stay on the
    coordinator, so every decision is identical to the 1-shard engine on
    the same seed.  Validated here so a typo fails loudly at config
    construction instead of silently serving unsharded.
    """
    raw = _env("ANOMOD_SERVE_SHARDS", "1")
    try:
        n = int(raw)
    except ValueError:
        raise ValueError(
            f"ANOMOD_SERVE_SHARDS must be a positive integer, got {raw!r}")
    if not 1 <= n <= 256:
        raise ValueError(
            f"ANOMOD_SERVE_SHARDS must be in [1, 256], got {n}")
    return n


def _serve_pipeline_env() -> int:
    """ANOMOD_SERVE_PIPELINE: in-flight fused dispatches per runner
    (the inline 1-shard engine and every shard worker alike).

    Depth ``1`` is synchronous (each lane-stacked dispatch materializes
    before the next stages); depth ``d > 1`` double-buffers — a shard
    stages and dispatches batch t+1 while batch t's XLA dispatch is
    still in flight, deferring readback/fold by up to ``d-1`` dispatches
    (drained at tick end).  Per-slot pinned scratch keeps reuse safe:
    a slot refills only after its dispatch's outputs materialized.
    Bit-identical at any depth (folds apply in dispatch order).
    """
    raw = _env("ANOMOD_SERVE_PIPELINE", "2")
    try:
        n = int(raw)
    except ValueError:
        raise ValueError(
            f"ANOMOD_SERVE_PIPELINE must be a positive integer, got {raw!r}")
    if not 1 <= n <= 64:
        raise ValueError(
            f"ANOMOD_SERVE_PIPELINE must be in [1, 64], got {n}")
    return n


def _serve_lane_engine_env() -> str:
    """ANOMOD_SERVE_LANE_ENGINE: the serving plane's fused lane-dispatch
    formulation (anomod.replay.make_lane_delta).

    ``auto`` (the default) follows :func:`anomod.replay.
    default_step_engine` — scatter on XLA:CPU, the one-hot matmul on
    accelerators — so the fused path stays BIT-identical to the
    single-chunk step on every backend and the serving plane's
    fused==sequential parity pins hold unconditionally.  ``pallas`` is
    the deliberate TPU opt-in: the whole per-lane score chain as ONE
    Mosaic kernel launch per fused shape (ops.pallas_replay.
    make_pallas_lane_delta_fn) — alert/histogram planes exact vs the
    other engines, latency moments within the bf16 hi/lo envelope (the
    compiled-replay tolerance contract), which is exactly why it is NOT
    the hands-off default.  ``matmul``/``scatter`` pin one exact
    formulation explicitly.  Validated here so a typo fails loudly at
    config construction instead of silently serving the wrong kernel.
    """
    raw = _env("ANOMOD_SERVE_LANE_ENGINE", "auto").strip().lower()
    if raw in ("auto", ""):
        return "auto"
    if raw in ("matmul", "scatter", "pallas"):
        return raw
    raise ValueError(
        "ANOMOD_SERVE_LANE_ENGINE must be auto, matmul, scatter or "
        f"pallas, got {raw!r}")


def _serve_state_env() -> str:
    """ANOMOD_SERVE_STATE: where the serving plane keeps tenant replay
    states between ticks (anomod.serve.batcher).

    ``host`` is the pre-device-pool seam: per-tenant numpy state pytrees,
    the lane fold materializes every dispatch's deltas to host and adds
    them per lane.  ``device`` keeps every shard's tenant states in ONE
    device-resident pool (agg + hist planes of flat tenant rows, tenants
    mapped to slots at first service) and folds lane deltas with an
    on-device scatter-add in dispatch order — pinned BIT-identical to
    the host seam (an XLA f32 scatter-add with unique per-dispatch slots
    performs exactly the same elementwise adds), with
    ``get_state``/``set_state`` surviving as the on-demand gather seam
    for parity checks, checkpoints and migration.  ``auto`` (the
    default) resolves to ``device`` for the bucket-runner serve plane on
    every backend (the pool is exact, not a tolerance trade) and to
    ``host`` where a pool cannot apply (the mesh plane manages its own
    sharded state).  Validated here so a typo fails loudly at config
    construction instead of silently serving the slow seam.
    """
    raw = _env("ANOMOD_SERVE_STATE", "auto").strip().lower()
    if raw in ("auto", ""):
        return "auto"
    if raw in ("host", "device"):
        return raw
    raise ValueError(
        f"ANOMOD_SERVE_STATE must be auto, host or device, got {raw!r}")


def _serve_async_commit_env() -> bool:
    """ANOMOD_SERVE_ASYNC_COMMIT: deferred-commit serve tick
    (anomod.serve.engine).

    Default OFF — the synchronous engine stays the parity oracle.  When
    on, tick N's fold+score dispatch is issued but NOT waited on; the
    XLA execute wait runs concurrent with tick N+1's coordinator phases
    (admission, drain, shed, SLO accounting) and tick N's results drain
    at a commit barrier placed just before they are first read.  Every
    decision is a function of seed+config alone, so states, alerts,
    SLO, shed and the canonical flight journal are pinned byte-identical
    to the synchronous engine (``anomod audit replay`` crosses the two
    freely); only the wall-time attribution moves — the hidden wait is
    reported as ``ServeReport.commit_defer_wall_s``.

    Validated against the explicit token sets (not the legacy
    anything-truthy bool idiom): the knob silently flips the engine's
    whole tick structure, so ``ANOMOD_SERVE_ASYNC_COMMIT=treu`` must
    fail at config construction, not serve synchronously all night.
    """
    raw = _env("ANOMOD_SERVE_ASYNC_COMMIT", "0").strip().lower()
    if raw in ("1", "on", "true", "yes"):
        return True
    if raw in ("0", "off", "false", "no", ""):
        return False
    raise ValueError(
        f"ANOMOD_SERVE_ASYNC_COMMIT must be 0/off/false/no or "
        f"1/on/true/yes, got {raw!r}")


def _serve_native_drain_env() -> str:
    """ANOMOD_SERVE_NATIVE_DRAIN: the admission plane's SFQ drain/shed
    engine (anomod.serve.queues).

    ``off`` (``0``) is the per-span Python heap — the original drain
    loop, kept as the parity oracle.  ``auto`` (the default) runs the
    COLUMNAR engine: candidate selection over parallel NumPy arrays,
    with the sort/select kernels in the native runtime
    (``anomod_sfq_drain`` / ``anomod_sfq_victim``) when the .so loads
    and a pure-NumPy fallback otherwise.  ``on`` (``1``) requires the
    native kernels — the first drain raises with the recorded
    build-failure reason instead of silently serving the slow path (the
    ``ANOMOD_NATIVE=on`` contract).  All three engines are pinned
    byte-identical: same served order, same shed/evict victims, same
    SFQ virtual-time floats.  Validated here so a typo fails loudly at
    config construction.
    """
    raw = _env("ANOMOD_SERVE_NATIVE_DRAIN", "auto").strip().lower()
    if raw in ("auto", ""):
        return "auto"
    if raw in ("1", "on", "true", "yes"):
        return "on"
    if raw in ("0", "off", "false", "no"):
        return "off"
    raise ValueError(
        f"ANOMOD_SERVE_NATIVE_DRAIN must be auto, on/1 or off/0, "
        f"got {raw!r}")


def _serve_worker_env() -> str:
    """ANOMOD_SERVE_WORKER: the serving plane's shard-worker kind
    (anomod.serve.shard / anomod.serve.procshard).

    ``thread`` (the default) is the PR-5 in-process worker — shared
    memory, GIL-bound, the byte-parity oracle.  ``process`` hosts each
    shard's scoring plane (detectors, replays, BucketRunner, RCA plane,
    obs registry) in a spawn-context worker PROCESS driven by a
    picklable per-tick command protocol — the GIL leaves the dispatch
    path entirely.  States, alerts, SLO, shed and the canonical flight
    journal are pinned byte-identical across the two (and across
    process counts); only wall attribution moves.  Validated here so a
    typo fails at config construction, not after a fleet spawn.
    """
    raw = _env("ANOMOD_SERVE_WORKER", "thread").strip().lower()
    if raw in ("thread", ""):
        return "thread"
    if raw == "process":
        return "process"
    raise ValueError(
        f"ANOMOD_SERVE_WORKER must be thread or process, got {raw!r}")


def _serve_worker_start_timeout_s_env() -> float:
    """ANOMOD_SERVE_WORKER_START_TIMEOUT_S: how long the coordinator
    waits for a spawned process worker's ready handshake (spawn +
    imports + sub-plane construction) before failing the run loudly.
    Generous default — a cold jax import on a busy box is slow — but
    bounded, so a wedged child can never hang a serve run forever."""
    raw = _env("ANOMOD_SERVE_WORKER_START_TIMEOUT_S", "120")
    try:
        v = float(raw)
    except ValueError:
        raise ValueError(
            f"ANOMOD_SERVE_WORKER_START_TIMEOUT_S must be a number, "
            f"got {raw!r}")
    if not 1 <= v <= 3600:
        raise ValueError(
            f"ANOMOD_SERVE_WORKER_START_TIMEOUT_S must be in [1, 3600], "
            f"got {v}")
    return v


def _serve_fold_env() -> str:
    """ANOMOD_SERVE_FOLD: the tick barrier's cross-shard registry merge
    mode (anomod.obs.registry.Registry.delta_snapshot).

    ``sparse`` (the default) serializes only families TOUCHED since the
    previous barrier — Zipf traffic leaves most families idle most
    ticks, so barrier payload follows active tenants, not registered
    fleet size (the Sparse Allreduce observation, PAPERS.md).  ``dense``
    walks and serializes every registered family every barrier — the
    payload-accounting oracle the sparse win is measured against.  The
    two are pinned byte-identical on every scrape surface; only
    ``fold_payload_bytes`` moves.  Validated here so a typo fails at
    config construction.
    """
    raw = _env("ANOMOD_SERVE_FOLD", "sparse").strip().lower()
    if raw in ("sparse", ""):
        return "sparse"
    if raw == "dense":
        return "dense"
    raise ValueError(
        f"ANOMOD_SERVE_FOLD must be dense or sparse, got {raw!r}")


def _serve_rca_env() -> bool:
    """ANOMOD_SERVE_RCA: online root-cause inference in the serve tick.

    Default OFF — RCA rides inside the serve SLO, so enabling it is an
    operator decision.  When on (and scoring is on), a tenant's detector
    firing queues incremental GNN culprit inference over that tenant's
    live service graph (anomod.serve.rca); detector states, alerts,
    admission and shedding are byte-identical either way (RCA is a pure
    read-side consumer of the alert stream).
    """
    return _env("ANOMOD_SERVE_RCA", "0").strip().lower() \
        not in ("0", "false", "off", "no", "")


#: default online-RCA bucket grid: (nodes, sampled neighbors) shapes the
#: culprit scorer compiles once each (anomod.serve.rca — the same fixed-
#: shape discipline as the serve width/lane buckets).  A tenant's live
#: graph pads into the smallest bucket whose node count holds its
#: service table; neighbor lists sample down (seeded) / dead-pad up to
#: the bucket's neighbor width.
DEFAULT_SERVE_RCA_BUCKETS = ((16, 8), (64, 16))


def validate_rca_buckets(buckets) -> tuple:
    """The RCA bucket-grid contract: (nodes, neighbors) int pairs with
    strictly ascending node counts, every dimension >= 1 — each pair is
    one compiled executable, so the grid must be small and fixed."""
    try:
        out = tuple((int(n), int(k)) for n, k in buckets)
    except (TypeError, ValueError):
        raise ValueError(
            f"RCA bucket grid must be (nodes, neighbors) integer pairs, "
            f"got {buckets!r}")
    if not out:
        raise ValueError("RCA bucket grid must not be empty")
    if any(n < 1 or k < 1 for n, k in out):
        raise ValueError(f"RCA bucket dims must be >= 1, got {out}")
    if any(a[0] >= b[0] for a, b in zip(out, out[1:])):
        raise ValueError(
            f"RCA bucket node counts must be strictly ascending: {out}")
    return out


def _serve_rca_buckets_env() -> tuple:
    """ANOMOD_SERVE_RCA_BUCKETS: comma-separated ``NODESxNEIGHBORS``
    pairs (e.g. ``16x8,64x16``) for the online-RCA scorer's fixed
    compile grid.  Validated at config construction, same fail-loud
    contract as ``ANOMOD_SERVE_BUCKETS``.
    """
    raw = _env("ANOMOD_SERVE_RCA_BUCKETS", "")
    if not raw:
        return DEFAULT_SERVE_RCA_BUCKETS
    pairs = []
    for part in (p.strip() for p in raw.split(",") if p.strip()):
        dims = part.lower().split("x")
        if len(dims) != 2:
            raise ValueError(
                f"ANOMOD_SERVE_RCA_BUCKETS entries must be NODESxNEIGHBORS "
                f"pairs, got {part!r}")
        pairs.append(dims)
    try:
        return validate_rca_buckets(pairs)
    except ValueError as e:
        raise ValueError(f"ANOMOD_SERVE_RCA_BUCKETS: {e}") from e


def _serve_rca_int_env(name: str, default: str, lo: int, hi: int) -> int:
    """Shared validator for the bounded integer RCA knobs."""
    raw = _env(name, default)
    try:
        n = int(raw)
    except ValueError:
        raise ValueError(f"{name} must be an integer, got {raw!r}")
    if not lo <= n <= hi:
        raise ValueError(f"{name} must be in [{lo}, {hi}], got {n}")
    return n


def _serve_rca_topk_env() -> int:
    """ANOMOD_SERVE_RCA_TOPK: ranked culprit list length per verdict."""
    return _serve_rca_int_env("ANOMOD_SERVE_RCA_TOPK", "5", 1, 64)


def _serve_rca_budget_env() -> int:
    """ANOMOD_SERVE_RCA_BUDGET: max RCA runs per serve tick — the
    per-tick SLO budget; alerts past it queue to later ticks (the RCA
    queue drains FIFO, so verdict order stays deterministic)."""
    return _serve_rca_int_env("ANOMOD_SERVE_RCA_BUDGET", "4", 1, 4096)


def _serve_rca_windows_env() -> int:
    """ANOMOD_SERVE_RCA_WINDOWS: windowed-feature reach (windows) of the
    online extractor — also bounds each tenant's RCA span buffer."""
    return _serve_rca_int_env("ANOMOD_SERVE_RCA_WINDOWS", "8", 2, 128)


def _flight_env() -> bool:
    """ANOMOD_FLIGHT: the serve plane's black-box flight recorder
    (anomod.obs.flight).

    Default ON — the recorder is the always-on tick journal every
    determinism contract replays against (bounded ring, bounded
    per-tick cost) — "0"/"false"/"off" disables it end to end.
    """
    return _env("ANOMOD_FLIGHT", "1").strip().lower() \
        not in ("0", "false", "off", "no")


def _flight_digest_every_env() -> int:
    """ANOMOD_FLIGHT_DIGEST_EVERY: tenant-state digest cadence (ticks).

    Every Nth tick the flight recorder folds a crc32 over every live
    tenant's replay state (through the ``get_state``/pool-gather seam)
    into the tick record's fold plane — the cheap end-state parity
    anchor ``anomod audit diff`` bisects state divergence with.  Small
    values localize tighter; 1 digests every tick.  Validated here so a
    typo fails loudly at config construction.
    """
    raw = _env("ANOMOD_FLIGHT_DIGEST_EVERY", "16")
    try:
        n = int(raw)
    except ValueError:
        raise ValueError(
            f"ANOMOD_FLIGHT_DIGEST_EVERY must be a positive integer, "
            f"got {raw!r}")
    if not 1 <= n <= 1_000_000:
        raise ValueError(
            f"ANOMOD_FLIGHT_DIGEST_EVERY must be in [1, 1000000], got {n}")
    return n


def _flight_max_ticks_env() -> int:
    """ANOMOD_FLIGHT_MAX_TICKS: flight-recorder ring capacity (ticks).

    The journal is a bounded ring — oldest tick records drop past this
    (counted, never silent: ``anomod_flight_dropped_ticks_total``), so
    an unbounded serve run cannot grow host memory without bound.
    """
    raw = _env("ANOMOD_FLIGHT_MAX_TICKS", "65536")
    try:
        n = int(raw)
    except ValueError:
        raise ValueError(
            f"ANOMOD_FLIGHT_MAX_TICKS must be a positive integer, "
            f"got {raw!r}")
    if not 1 <= n <= 10_000_000:
        raise ValueError(
            f"ANOMOD_FLIGHT_MAX_TICKS must be in [1, 10000000], got {n}")
    return n


def _flight_dump_dir_env() -> Optional[Path]:
    """ANOMOD_FLIGHT_DUMP_DIR: alert-triggered forensic-dump directory.

    When set, the first serve tick that raises a new detector alert
    publishes ONE forensic bundle there (flight ring + registry scrape +
    tracer spans, atomically — anomod.obs.flight.forensic_bundle).
    Unset (the default) disables the dump; the in-memory ring and the
    ``anomod audit`` dump path are unaffected.
    """
    raw = _env("ANOMOD_FLIGHT_DUMP_DIR", "")
    if not raw or raw.lower() in _CACHE_OFF:
        return None
    return Path(raw).expanduser()


#: serve-chaos fault taxonomy (anomod.serve.chaos — the framework analog
#: of the paper's injected-fault campaigns, aimed at the serve plane
#: itself): ``crash`` kills the shard WORKER THREAD mid-tick, ``except``
#: raises a plain exception at a score-path phase, ``stall`` sleeps
#: (slow-shard), ``poolput`` fails the state-pool fold, ``surge``
#: multiplies the fleet's offered arrivals for a window of ticks (the
#: load-shift taxonomy — what forces elastic-policy scaling episodes).
#: Phases are the score path's five injection points; a surge has no
#: phase (it acts on admission input, before the score path exists).
CHAOS_KINDS = ("crash", "except", "stall", "poolput", "surge")
CHAOS_PHASES = ("stage", "dispatch", "fold", "score", "commit")
_CHAOS_DEFAULT_PHASE = {"crash": "dispatch", "except": "dispatch",
                        "stall": "stage", "poolput": "fold",
                        "surge": "stage"}


def validate_chaos_script(script: str) -> list:
    """Parse/validate an ``ANOMOD_SERVE_CHAOS`` fault script.

    Grammar: semicolon-separated ``KIND@TICK[:key=value]*`` items, e.g.
    ``crash@5:shard=1;stall@8:ms=20;except@12:phase=score:repeat=2``.
    Keys: ``shard`` (default 0), ``phase`` (one of
    :data:`CHAOS_PHASES`; per-kind default), ``ms`` (stall wall
    milliseconds, default 10), ``repeat`` (how many ATTEMPTS of that
    tick's slice the fault fires on — 1 by default so a recovery retry
    succeeds; ``-1`` = every attempt forever, the quarantine probe).
    A ``surge`` item instead takes ``factor`` (arrival multiplier,
    default 4) and ``ticks`` (duration, default 10): from its origin
    tick, every tenant's offered arrivals are replicated ``factor``×
    for ``ticks`` ticks — a deterministic fleet-wide load shift (the
    elastic-policy episode probe).  Score-path keys on a surge (and
    surge keys on a score-path fault) are refused: a silently-inert
    knob is worse than an error.
    Returns the parsed fault dicts; raises ``ValueError`` with the
    offending item on any malformed script — the same fail-loud contract
    as every other serve knob.  Lives HERE (pure string parsing) so
    Config() never pays the serve import chain.
    """
    faults = []
    for item in (p.strip() for p in str(script).split(";") if p.strip()):
        head, _, tail = item.partition(":")
        kind, at, tick = head.partition("@")
        kind = kind.strip().lower()
        if kind not in CHAOS_KINDS or not at:
            raise ValueError(
                f"chaos item {item!r}: expected KIND@TICK with KIND in "
                f"{'/'.join(CHAOS_KINDS)}")
        try:
            tick_i = int(tick)
        except ValueError:
            raise ValueError(f"chaos item {item!r}: tick must be an "
                             f"integer, got {tick!r}")
        if tick_i < 0:
            raise ValueError(f"chaos item {item!r}: tick must be >= 0")
        fault = {"kind": kind, "tick": tick_i, "shard": 0,
                 "phase": _CHAOS_DEFAULT_PHASE[kind], "ms": 10.0,
                 "repeat": 1, "factor": 4, "ticks": 10}
        allowed = (("factor", "ticks") if kind == "surge"
                   else ("shard", "phase", "ms", "repeat"))
        for kv in (p.strip() for p in tail.split(":") if p.strip()):
            key, eq, val = kv.partition("=")
            key = key.strip().lower()
            if not eq or key not in allowed:
                raise ValueError(
                    f"chaos item {item!r}: unknown key {kv!r} (want "
                    + "/".join(f"{k}=" for k in allowed) + ")")
            try:
                if key == "phase":
                    val = val.strip().lower()
                    if val not in CHAOS_PHASES:
                        raise ValueError
                    fault["phase"] = val
                elif key == "ms":
                    fault["ms"] = float(val)
                    # capped like the backoff knob: a stall is a fault
                    # INJECTION, not a way to park the scoring thread
                    # for minutes inside the measured wall
                    if not 0 <= fault["ms"] <= 10_000:
                        raise ValueError
                else:
                    fault[key] = int(val)
            except ValueError:
                raise ValueError(
                    f"chaos item {item!r}: bad value for {key!r}: {val!r}")
        if fault["shard"] < 0:
            raise ValueError(f"chaos item {item!r}: shard must be >= 0")
        if fault["repeat"] < -1 or fault["repeat"] == 0:
            raise ValueError(f"chaos item {item!r}: repeat must be a "
                             "positive count or -1 (forever)")
        if not 2 <= fault["factor"] <= 64:
            raise ValueError(f"chaos item {item!r}: surge factor must "
                             f"be in [2, 64], got {fault['factor']}")
        if not 1 <= fault["ticks"] <= 1_000_000:
            raise ValueError(f"chaos item {item!r}: surge ticks must "
                             f"be in [1, 1000000], got {fault['ticks']}")
        faults.append(fault)
    return faults


def _serve_chaos_env() -> str:
    """ANOMOD_SERVE_CHAOS: scripted fault injection aimed at the serve
    plane ITSELF (anomod.serve.chaos) — the framework analog of the
    paper's chaos campaigns, behind the supervised engine's
    checkpoint/restore recovery (anomod.serve.supervise).

    Empty (the default) = off.  Otherwise a semicolon-separated fault
    script (``crash@5:shard=1;stall@8:ms=20`` — see
    :func:`validate_chaos_script` for the grammar), validated here so a
    typo fails loudly at config construction instead of silently
    injecting nothing.
    """
    raw = _env("ANOMOD_SERVE_CHAOS", "").strip()
    if raw:
        validate_chaos_script(raw)
    return raw


#: elastic-policy decision taxonomy (anomod.serve.policy): ``up`` grows
#: the shard set by one worker, ``down`` drains and retires the highest
#: shard, ``rebalance`` moves the top-K hottest tenants off the most-
#: loaded shard, ``brownout`` forces a degradation-ladder level.
POLICY_ACTIONS = ("up", "down", "rebalance", "brownout")


def validate_policy_script(script: str) -> list:
    """Parse/validate an ``ANOMOD_SERVE_POLICY_SCRIPT`` scaling script.

    Grammar: semicolon-separated ``ACTION@TICK[:key=value]`` items with
    ACTION in :data:`POLICY_ACTIONS`, e.g.
    ``up@10;rebalance@25:k=2;down@40;brownout@50:level=1``.  Keys:
    ``k`` (rebalance move count, default 1), ``level`` (brownout ladder
    level 0..2, default 1); any key on the wrong action is refused (a
    silently-inert knob is worse than an error).  The engine executes
    each action at its tick (clamped by the min/max-shards envelope,
    journaled either way).  Same fail-loud contract as the chaos
    grammar; lives HERE (pure string parsing) so Config() never pays
    the serve import chain.
    """
    actions = []
    for item in (p.strip() for p in str(script).split(";") if p.strip()):
        head, _, tail = item.partition(":")
        act, at, tick = head.partition("@")
        act = act.strip().lower()
        if act not in POLICY_ACTIONS or not at:
            raise ValueError(
                f"policy item {item!r}: expected ACTION@TICK with "
                f"ACTION in {'/'.join(POLICY_ACTIONS)}")
        try:
            tick_i = int(tick)
        except ValueError:
            raise ValueError(f"policy item {item!r}: tick must be an "
                             f"integer, got {tick!r}")
        if tick_i < 0:
            raise ValueError(f"policy item {item!r}: tick must be >= 0")
        entry = {"action": act, "tick": tick_i, "k": 1, "level": 1}
        allowed = {"rebalance": ("k",), "brownout": ("level",)} \
            .get(act, ())
        for kv in (p.strip() for p in tail.split(":") if p.strip()):
            key, eq, val = kv.partition("=")
            key = key.strip().lower()
            if not eq or key not in allowed:
                raise ValueError(
                    f"policy item {item!r}: unknown key {kv!r}"
                    + (f" (want {'/'.join(f'{k}=' for k in allowed)})"
                       if allowed else f" ({act} takes no keys)"))
            try:
                entry[key] = int(val)
            except ValueError:
                raise ValueError(
                    f"policy item {item!r}: bad value for {key!r}: "
                    f"{val!r}")
        if not 1 <= entry["k"] <= 1024:
            raise ValueError(f"policy item {item!r}: k must be in "
                             f"[1, 1024], got {entry['k']}")
        if not 0 <= entry["level"] <= 2:
            raise ValueError(f"policy item {item!r}: level must be in "
                             f"[0, 2], got {entry['level']}")
        actions.append(entry)
    return actions


def _serve_policy_env() -> str:
    """ANOMOD_SERVE_POLICY: the serving plane's elastic scaling policy
    (anomod.serve.policy).

    ``off`` (the default) is the static engine — the shard count never
    changes and the policy plane costs nothing.  ``auto`` evaluates the
    signal-fed autoscaler at every tick boundary on the coordinator:
    scale-up / scale-down / rebalance / brownout decisions with
    hysteresis and cooldown, fed ONLY canonical (seed-deterministic)
    signals, executed through the live-migration seams — tenant states,
    alerts, SLO and shed stay byte-identical to a static run of the
    same seed.  ``script`` executes a fixed scaling schedule from
    ``ANOMOD_SERVE_POLICY_SCRIPT`` instead of the signals (the
    episode-replay probe).  Validated here so a typo fails loudly at
    config construction instead of silently serving static.
    """
    raw = _env("ANOMOD_SERVE_POLICY", "off").strip().lower()
    if raw in ("off", ""):
        return "off"
    if raw in ("auto", "script"):
        return raw
    raise ValueError(
        f"ANOMOD_SERVE_POLICY must be off, auto or script, got {raw!r}")


def _serve_policy_script_env() -> str:
    """ANOMOD_SERVE_POLICY_SCRIPT: the fixed scaling schedule
    ``ANOMOD_SERVE_POLICY=script`` executes (anomod.serve.policy).

    Empty (the default) = no schedule — the script MODE then refuses at
    the engine (an empty scripted policy is a misconfiguration, not a
    quiet static run).  Otherwise a semicolon-separated action script
    (``up@10;down@40;rebalance@25:k=2`` — see
    :func:`validate_policy_script`), validated here so a typo fails
    loudly at config construction.
    """
    raw = _env("ANOMOD_SERVE_POLICY_SCRIPT", "").strip()
    if raw:
        validate_policy_script(raw)
    return raw


def _serve_policy_int_env(name: str, default: str, lo: int,
                          hi: int) -> int:
    """Shared validator for the bounded integer policy knobs."""
    raw = _env(name, default)
    try:
        n = int(raw)
    except ValueError:
        raise ValueError(f"{name} must be an integer, got {raw!r}")
    if not lo <= n <= hi:
        raise ValueError(f"{name} must be in [{lo}, {hi}], got {n}")
    return n


def _serve_policy_min_shards_env() -> int:
    """ANOMOD_SERVE_POLICY_MIN_SHARDS: the elastic policy's scale-down
    floor — ``down`` decisions never shrink the shard set below it."""
    return _serve_policy_int_env("ANOMOD_SERVE_POLICY_MIN_SHARDS", "1",
                                 1, 256)


def _serve_policy_max_shards_env() -> int:
    """ANOMOD_SERVE_POLICY_MAX_SHARDS: the elastic policy's scale-up
    ceiling — ``up`` decisions never grow the shard set past it (the
    brownout ladder takes over once load persists at the ceiling)."""
    return _serve_policy_int_env("ANOMOD_SERVE_POLICY_MAX_SHARDS", "8",
                                 1, 256)


def _serve_policy_target_imbalance_env() -> float:
    """ANOMOD_SERVE_POLICY_TARGET_IMBALANCE: the max-shard-load /
    mean-shard-load ratio (over the live served-rate EWMAs) past which
    the auto policy triggers a rebalance pass.  1.0 would rebalance on
    any skew; the default tolerates the skew a power-law head tenant
    makes unavoidable."""
    raw = _env("ANOMOD_SERVE_POLICY_TARGET_IMBALANCE", "1.5")
    try:
        v = float(raw)
    except ValueError:
        raise ValueError(
            f"ANOMOD_SERVE_POLICY_TARGET_IMBALANCE must be a number, "
            f"got {raw!r}")
    if not 1.0 <= v <= 100.0:
        raise ValueError(
            f"ANOMOD_SERVE_POLICY_TARGET_IMBALANCE must be in "
            f"[1.0, 100.0], got {v}")
    return v


def _serve_policy_cooldown_env() -> int:
    """ANOMOD_SERVE_POLICY_COOLDOWN_TICKS: minimum ticks between
    executed scaling decisions (scale-up/down/rebalance) — the
    anti-thrash half of the hysteresis contract.  Brownout ladder
    steps pace on the same cooldown."""
    return _serve_policy_int_env("ANOMOD_SERVE_POLICY_COOLDOWN_TICKS",
                                 "8", 1, 100_000)


def _serve_ckpt_every_env() -> int:
    """ANOMOD_SERVE_CKPT_EVERY: shard-checkpoint cadence in ticks
    (anomod.serve.supervise) — the flight-digest cadence idiom, at
    twice the digest period (the snapshot is ~10x a digest's cost:
    state copies + detector bookkeeping, not one crc sweep).

    Every Nth tick each shard snapshots its tenants' detector/replay
    state through the ``get_state``/pool-gather seam (plus the runner's
    dispatch book), and the coordinator retains the ticks' served-batch
    slices since the last snapshot — together that makes any mid-tick
    shard failure recoverable with NO score gap: restore the checkpoint,
    re-execute the retained slices deterministically, and the recovered
    run's states/alerts/SLO/shed are byte-identical to a fault-free run
    of the same seed.  ``0`` disables supervision entirely (a shard
    fault fails the tick, the pre-supervision behavior).  Snapshots are
    pure reads, so the cadence only trades recovery-log memory against
    snapshot wall — decisions are byte-identical at every value.
    """
    raw = _env("ANOMOD_SERVE_CKPT_EVERY", "32")
    try:
        n = int(raw)
    except ValueError:
        raise ValueError(
            f"ANOMOD_SERVE_CKPT_EVERY must be a non-negative integer "
            f"(0 = supervision off), got {raw!r}")
    if not 0 <= n <= 1_000_000:
        raise ValueError(
            f"ANOMOD_SERVE_CKPT_EVERY must be in [0, 1000000], got {n}")
    return n


def _serve_retries_env() -> int:
    """ANOMOD_SERVE_RETRIES: consecutive recovery failures of ONE tick
    slice before that slice is QUARANTINED (anomod.serve.supervise).

    A batch that kills its shard K consecutive times is dropped from the
    recovery log (counted + journaled, never retried forever) and the
    shard recovers without it — bounded unavailability over livelock.
    """
    raw = _env("ANOMOD_SERVE_RETRIES", "3")
    try:
        n = int(raw)
    except ValueError:
        raise ValueError(
            f"ANOMOD_SERVE_RETRIES must be a positive integer, got {raw!r}")
    if not 1 <= n <= 64:
        raise ValueError(
            f"ANOMOD_SERVE_RETRIES must be in [1, 64], got {n}")
    return n


def _serve_retry_backoff_s_env() -> float:
    """ANOMOD_SERVE_RETRY_BACKOFF_S: wall-clock backoff before each
    recovery attempt, doubling per consecutive attempt (capped 5 s).

    ``0`` (the default) retries immediately — recovery stays
    deterministic either way (backoff is wall time, never virtual
    time); a positive value spaces respawn storms on a genuinely sick
    host the way the paper's recovery controllers do.
    """
    raw = _env("ANOMOD_SERVE_RETRY_BACKOFF_S", "0")
    try:
        v = float(raw)
    except ValueError:
        raise ValueError(
            f"ANOMOD_SERVE_RETRY_BACKOFF_S must be a number, got {raw!r}")
    if not 0 <= v <= 60:
        raise ValueError(
            f"ANOMOD_SERVE_RETRY_BACKOFF_S must be in [0, 60], got {v}")
    return v


def _serve_max_respawns_env() -> int:
    """ANOMOD_SERVE_MAX_RESPAWNS: per-shard worker respawns per run
    before the shard is declared DEAD and its tenants migrate to the
    surviving shards through the ``set_state`` seam
    (anomod.serve.supervise — the elastic-tenancy migration step).
    """
    raw = _env("ANOMOD_SERVE_MAX_RESPAWNS", "8")
    try:
        n = int(raw)
    except ValueError:
        raise ValueError(
            f"ANOMOD_SERVE_MAX_RESPAWNS must be a non-negative integer, "
            f"got {raw!r}")
    if not 0 <= n <= 4096:
        raise ValueError(
            f"ANOMOD_SERVE_MAX_RESPAWNS must be in [0, 4096], got {n}")
    return n


def _census_env() -> bool:
    """ANOMOD_CENSUS: the fleet census observatory (anomod.obs.census).

    Default OFF — it is a deep-dive instrument
    (the flight recorder stays the always-on journal); when on, every
    ``ANOMOD_CENSUS_EVERY``-th tick takes a deterministic resident-
    bytes census (per-(shard, plane) byte counts from array shapes and
    container lengths — never an RSS wall) plus the hot-set/Zipf
    census, exported as registry gauges and the flight journal's
    ``census`` VARIANT key.  A pure read-side consumer: decisions are
    byte-identical on or off (pinned); its in-run cost is
    ``ServeReport.census_wall_s``.
    """
    return _env("ANOMOD_CENSUS", "0").strip().lower() \
        not in ("0", "false", "off", "no", "")


def _census_every_env() -> int:
    """ANOMOD_CENSUS_EVERY: census cadence in ticks (the flight
    digest-cadence idiom).  Every Nth tick the census drains at the
    tick barrier; a census is also always forced into the run-end
    settlement record.  1 censuses every tick."""
    raw = _env("ANOMOD_CENSUS_EVERY", "8")
    try:
        n = int(raw)
    except ValueError:
        raise ValueError(
            f"ANOMOD_CENSUS_EVERY must be a positive integer, got {raw!r}")
    if not 1 <= n <= 1_000_000:
        raise ValueError(
            f"ANOMOD_CENSUS_EVERY must be in [1, 1000000], got {n}")
    return n


#: default hot-set decay thresholds (ticks): the census reports the
#: hot-set size at each — how many tenants were served within the last
#: N ticks (anomod.obs.census.CensusTracker.hot_doc)
DEFAULT_CENSUS_DECAY_TICKS = (4, 16, 64, 256)


def _census_int_tuple_env(name: str, default: tuple, lo: int,
                          hi: int) -> tuple:
    """Shared validator for the census's ascending-int-list knobs
    (decay thresholds, sweep sizes): comma-separated positive ints,
    strictly ascending — the bucket-set contract."""
    raw = _env(name, "")
    if not raw:
        return default
    parts = [p.strip() for p in raw.split(",") if p.strip()]
    try:
        out = tuple(int(p) for p in parts)
    except ValueError:
        raise ValueError(f"{name} must be comma-separated integers, "
                         f"got {raw!r}")
    if not out:
        raise ValueError(f"{name} must not be empty")
    if any(not lo <= v <= hi for v in out):
        raise ValueError(f"{name} entries must be in [{lo}, {hi}], "
                         f"got {out}")
    if any(a >= b for a, b in zip(out, out[1:])):
        raise ValueError(f"{name} must be strictly ascending: {out}")
    return out


def _census_decay_ticks_env() -> tuple:
    """ANOMOD_CENSUS_DECAY_TICKS: comma-separated hot-set decay
    thresholds in ticks, strictly ascending (e.g. ``4,16,64``) — the
    hot-set-size-at-decay-threshold curve's x axis."""
    return _census_int_tuple_env("ANOMOD_CENSUS_DECAY_TICKS",
                                 DEFAULT_CENSUS_DECAY_TICKS,
                                 1, 10_000_000)


#: default registered-fleet sweep sizes for the census cost-attribution
#: probe (anomod.obs.census.fleet_probe): tick wall + resident bytes
#: measured at each registered count (fixed ~1e3-hot traffic), slopes
#: fitted vs registered — the O(registered) baseline the ROADMAP's
#: tiering refactor must flatten
DEFAULT_CENSUS_SWEEP = (1_000, 10_000, 100_000)


def _census_sweep_env() -> tuple:
    """ANOMOD_CENSUS_SWEEP: comma-separated registered-fleet sizes for
    the census probe sweep, strictly ascending; at least two sizes (a
    slope needs two points)."""
    out = _census_int_tuple_env("ANOMOD_CENSUS_SWEEP",
                                DEFAULT_CENSUS_SWEEP, 1, 10_000_000)
    if len(out) < 2:
        raise ValueError(
            f"ANOMOD_CENSUS_SWEEP needs >= 2 sizes (a slope fit needs "
            f"two points), got {out}")
    return out


def _census_coldest_k_env() -> int:
    """ANOMOD_CENSUS_COLDEST_K: coldest-K eviction-candidate preview
    length per census tick — since the tiering plane landed this is
    ALSO the demotion policy's candidate-batch size (one ordering,
    :meth:`anomod.obs.census.CensusTracker.coldest_candidates`, shared
    by the preview and the policy so they can never disagree)."""
    raw = _env("ANOMOD_CENSUS_COLDEST_K", "8")
    try:
        n = int(raw)
    except ValueError:
        raise ValueError(
            f"ANOMOD_CENSUS_COLDEST_K must be a positive integer, "
            f"got {raw!r}")
    if not 1 <= n <= 4096:
        raise ValueError(
            f"ANOMOD_CENSUS_COLDEST_K must be in [1, 4096], got {n}")
    return n


def _serve_tier_hot_env() -> int:
    """ANOMOD_SERVE_TIER_HOT: tenant-state tiering hot capacity — the
    max tenants resident in the device ``TenantStatePool`` before the
    decay-driven demotion plane starts spilling the coldest to the host
    warm tier (anomod.serve.tiering).  ``0`` (the default) disables
    tiering entirely: every ever-served tenant stays pool-resident, the
    pre-tiering engine byte-for-byte."""
    raw = _env("ANOMOD_SERVE_TIER_HOT", "0")
    try:
        n = int(raw)
    except ValueError:
        raise ValueError(
            f"ANOMOD_SERVE_TIER_HOT must be a non-negative integer "
            f"(0 = tiering off), got {raw!r}")
    if n < 0:
        raise ValueError(
            f"ANOMOD_SERVE_TIER_HOT must be >= 0, got {n}")
    return n


def _serve_tier_demote_after_env() -> int:
    """ANOMOD_SERVE_TIER_DEMOTE_AFTER: idle ticks (since a tenant's
    last served batch, the census ``last_served`` signal) before a
    pool-resident tenant is eligible for demotion.  The decay knob of
    the demotion plane — small values demote aggressively, large ones
    keep bursty tenants hot across their gaps."""
    raw = _env("ANOMOD_SERVE_TIER_DEMOTE_AFTER", "8")
    try:
        n = int(raw)
    except ValueError:
        raise ValueError(
            f"ANOMOD_SERVE_TIER_DEMOTE_AFTER must be a positive "
            f"integer (idle ticks), got {raw!r}")
    if n < 1:
        raise ValueError(
            f"ANOMOD_SERVE_TIER_DEMOTE_AFTER must be >= 1, got {n}")
    return n


def _serve_tier_warm_bytes_env() -> int:
    """ANOMOD_SERVE_TIER_WARM_BYTES: host warm-tier state-bytes budget.
    Past it the warm tier spills its coldest entries' state arrays to
    the content-addressed disk cold tier — which only acts when
    ``ANOMOD_SERVE_TIER_COLD_DIR`` is set; without a cold dir the warm
    tier is terminal and the budget is advisory (documented in
    SERVING.md, never a silent data drop)."""
    raw = _env("ANOMOD_SERVE_TIER_WARM_BYTES", str(64 * 1024 * 1024))
    try:
        n = int(raw)
    except ValueError:
        raise ValueError(
            f"ANOMOD_SERVE_TIER_WARM_BYTES must be a non-negative "
            f"integer (bytes), got {raw!r}")
    if n < 0:
        raise ValueError(
            f"ANOMOD_SERVE_TIER_WARM_BYTES must be >= 0, got {n}")
    return n


def _serve_tier_cold_dir_env() -> Optional[Path]:
    """ANOMOD_SERVE_TIER_COLD_DIR: content-addressed disk cold-tier
    root for demoted tenant state (anomod.serve.tiering; the
    io/cache.py atomic tmp-rename publish idiom).  Unset or
    "0"/"off"/"none" disables the cold tier — the warm tier is then
    terminal regardless of its bytes budget."""
    raw = _env("ANOMOD_SERVE_TIER_COLD_DIR", "")
    if not raw or raw.lower() in _CACHE_OFF:
        return None
    return Path(raw).expanduser()


def _serve_tier_prefetch_env() -> int:
    """ANOMOD_SERVE_TIER_PREFETCH: cold-tier prefetch lane depth — max
    concurrent disk fetches issued at offer time so the read overlaps
    the tick's admission/drain/SLO phases (the PR-16 deferred-commit
    overlap idiom).  Promotion from cold always defers exactly one tick
    (a counted, journaled ``tier_miss``) so the hot loop never blocks
    on disk and the deferral count stays seed-deterministic."""
    raw = _env("ANOMOD_SERVE_TIER_PREFETCH", "4")
    try:
        n = int(raw)
    except ValueError:
        raise ValueError(
            f"ANOMOD_SERVE_TIER_PREFETCH must be a positive integer, "
            f"got {raw!r}")
    if not 1 <= n <= 256:
        raise ValueError(
            f"ANOMOD_SERVE_TIER_PREFETCH must be in [1, 256], got {n}")
    return n


def _native_env() -> str:
    """ANOMOD_NATIVE: the C++ native runtime switch (anomod.io.native) —
    ingest scanning AND the serving plane's GIL-free lane staging.

    ``auto`` (the default) uses the native .so when it loads (building it
    on first use if a toolchain is present) and degrades to the pure-
    Python paths otherwise; ``on`` (``1``) REQUIRES it — the first native
    consumer raises with the recorded build-failure reason instead of
    silently serving the slow path, and ``anomod validate`` surfaces
    the same reason; ``off`` (``0``) forces
    the pure-Python paths even when the .so is fine.  Validated here so a
    typo fails loudly at config construction.
    """
    raw = _env("ANOMOD_NATIVE", "auto").strip().lower()
    if raw in ("auto", ""):
        return "auto"
    if raw in ("1", "on", "true", "yes"):
        return "on"
    if raw in ("0", "off", "false", "no"):
        return "off"
    raise ValueError(
        f"ANOMOD_NATIVE must be auto, on/1 or off/0, got {raw!r}")


def _obs_http_env() -> bool:
    """ANOMOD_OBS_HTTP: embedded /metrics endpoint plane
    (anomod.obs.http).

    Default OFF — serving HTTP from a benchmark process is opt-in.
    When on, ``anomod serve`` starts a localhost-bound stdlib
    ``http.server`` thread exposing ``/metrics`` (Prometheus text
    exposition), ``/healthz`` and ``/flight``.  Scrapes are pure
    registry reads, so every decision plane stays byte-identical
    endpoint-on vs off.  Validated against the explicit token sets:
    a typo must fail at config construction, not silently skip the
    endpoint all night.
    """
    raw = _env("ANOMOD_OBS_HTTP", "0").strip().lower()
    if raw in ("1", "on", "true", "yes"):
        return True
    if raw in ("0", "off", "false", "no", ""):
        return False
    raise ValueError(
        f"ANOMOD_OBS_HTTP must be 0/off/false/no or "
        f"1/on/true/yes, got {raw!r}")


def _obs_http_port_env() -> int:
    """ANOMOD_OBS_HTTP_PORT: TCP port for the embedded endpoint plane.

    ``9464`` (the OpenMetrics convention neighborhood) by default; ``0``
    asks the OS for an ephemeral port — the test/dogfood mode, where the
    bound port is read back off the server object rather than assumed.
    """
    raw = _env("ANOMOD_OBS_HTTP_PORT", "9464")
    try:
        n = int(raw)
    except ValueError:
        raise ValueError(
            f"ANOMOD_OBS_HTTP_PORT must be an integer port, got {raw!r}")
    if not 0 <= n <= 65535:
        raise ValueError(
            f"ANOMOD_OBS_HTTP_PORT must be in [0, 65535], got {n}")
    return n


def _serve_feed_lag_s_env() -> float:
    """ANOMOD_SERVE_FEED_LAG_S: live-feed wall->virtual lag budget in
    seconds (anomod.serve.feed).

    A sample collected at wall time ``w`` maps to virtual time
    ``w - t0_wall + lag``; the budget keeps the feed's virtual arrival
    times ahead of the poll that discovers them, so a tick never asks
    for spans the pollers have not fetched yet.  Walls are measured,
    never consulted for decisions — the bridge itself is recorded in
    the wire journal so replay reuses the live run's anchor.
    """
    raw = _env("ANOMOD_SERVE_FEED_LAG_S", "2.0")
    try:
        v = float(raw)
    except ValueError:
        raise ValueError(
            f"ANOMOD_SERVE_FEED_LAG_S must be a number, got {raw!r}")
    if not 0 <= v <= 3600:
        raise ValueError(
            f"ANOMOD_SERVE_FEED_LAG_S must be in [0, 3600], got {v}")
    return v


def _feed_journal_env() -> Optional[Path]:
    """ANOMOD_FEED_JOURNAL: live-feed wire-journal path.

    When set, every HTTP response the live feed consumes is recorded in
    sequence and published atomically to this path at the end of the
    run (anomod.serve.feed.FeedJournal); ``anomod serve --live-replay``
    re-serves it through a replay transport, reproducing the live run's
    states/alerts/SLO/shed byte-for-byte with no network.  Unset (the
    default) disables recording.
    """
    raw = _env("ANOMOD_FEED_JOURNAL", "")
    if not raw or raw.lower() in _CACHE_OFF:
        return None
    return Path(raw).expanduser()


def _serve_max_backlog_env() -> int:
    """ANOMOD_SERVE_MAX_BACKLOG: global admission backlog bound (spans) —
    the serving plane's backpressure/shed budget."""
    raw = _env("ANOMOD_SERVE_MAX_BACKLOG", "200000")
    try:
        n = int(raw)
    except ValueError:
        raise ValueError(
            f"ANOMOD_SERVE_MAX_BACKLOG must be a positive integer, "
            f"got {raw!r}")
    if n < 1:
        raise ValueError(
            f"ANOMOD_SERVE_MAX_BACKLOG must be >= 1, got {n}")
    return n


def _obs_enabled_env() -> bool:
    """ANOMOD_OBS_ENABLED: process-wide metrics registry switch.

    Default ON — the hot-path cost of a disabled-check-free counter bump
    is nanoseconds — "0"/"false"/"off" turns every metric handle into a
    shared no-op object (anomod.obs.registry)."""
    return _env("ANOMOD_OBS_ENABLED", "1").strip().lower() \
        not in ("0", "false", "off", "no")


def _obs_max_samples_env() -> int:
    """ANOMOD_OBS_MAX_SAMPLES: scrape-journal bound (samples).

    The registry's time-series journal (what the TT-CSV self-scrape
    export reads) is a bounded deque — oldest samples drop past this, so
    an unbounded run cannot grow host memory without bound.  Validated
    here so a typo fails loudly at config construction."""
    raw = _env("ANOMOD_OBS_MAX_SAMPLES", "500000")
    try:
        n = int(raw)
    except ValueError:
        raise ValueError(
            f"ANOMOD_OBS_MAX_SAMPLES must be a positive integer, "
            f"got {raw!r}")
    if n < 1:
        raise ValueError(
            f"ANOMOD_OBS_MAX_SAMPLES must be >= 1, got {n}")
    return n


@dataclasses.dataclass(frozen=True)
class Config:
    """Global framework configuration.

    Attributes mirror the reference env contract where one exists:
      - ``data_root``     ~ DATA_ARCHIVE_ROOT (collect_all_data.sh:207-211)
      - ``sn_data``/``tt_data`` ~ the shipped SN_data/ and TT_data/ trees
      - ``backend``       ~ the BASELINE.json {cpu, jax-tpu} switch
    """

    data_root: Path = dataclasses.field(
        default_factory=lambda: Path(_env("ANOMOD_DATA_ROOT", _DEFAULT_REFERENCE_ROOT)))
    backend: str = dataclasses.field(
        default_factory=lambda: _env("ANOMOD_BACKEND", "cpu"))  # "cpu" | "jax" | "jax-tpu"
    synth_on_lfs: bool = dataclasses.field(
        default_factory=lambda: _env("ANOMOD_SYNTH_ON_LFS", "1") not in ("0", "false"))
    # init_social_graph.py:149 seeds with 1
    seed: int = dataclasses.field(default_factory=lambda: int(_env("ANOMOD_SEED", "1")))
    # ANOMOD_CACHE_DIR — content-addressed ingest cache root (anomod.io.cache);
    # None disables caching entirely ("0"/"off"/"none" in the env).
    cache_dir: Optional[Path] = dataclasses.field(
        default_factory=_cache_dir_env)
    # ANOMOD_INGEST_WORKERS — load_corpus process-pool size (0/1 = serial).
    ingest_workers: int = dataclasses.field(
        default_factory=_ingest_workers_env)
    # ANOMOD_SERVE_BUCKETS — serving-plane micro-batch bucket widths
    # (anomod.serve.batcher; one XLA compile per width).
    serve_buckets: tuple = dataclasses.field(
        default_factory=_serve_buckets_env)
    # ANOMOD_SERVE_LANE_BUCKETS — fused-dispatch lane counts
    # (anomod.serve.batcher; one XLA compile per (width, lane-bucket)).
    serve_lane_buckets: tuple = dataclasses.field(
        default_factory=_serve_lane_buckets_env)
    # ANOMOD_SERVE_FUSE — serving-plane fused-dispatch switch
    # (anomod.serve.engine; off = one dispatch per tenant micro-batch).
    serve_fuse: bool = dataclasses.field(default_factory=_serve_fuse_env)
    # ANOMOD_SERVE_SHARDS — serving-plane engine-worker shard count
    # (anomod.serve.shard; 1 = the single-threaded engine, bit-identical
    # to the pre-sharding plane).
    serve_shards: int = dataclasses.field(default_factory=_serve_shards_env)
    # ANOMOD_SERVE_PIPELINE — in-flight fused dispatches per shard worker
    # (anomod.serve.batcher; 1 = synchronous, d > 1 = double-buffered
    # staging under in-flight XLA dispatches, per-slot pinned scratch).
    serve_pipeline: int = dataclasses.field(
        default_factory=_serve_pipeline_env)
    # ANOMOD_SERVE_LANE_ENGINE — fused lane-dispatch formulation: auto
    # (= the step engine, bit-parity backend-stable), pallas (single
    # Mosaic kernel, TPU opt-in), matmul/scatter (explicit pin).
    serve_lane_engine: str = dataclasses.field(
        default_factory=_serve_lane_engine_env)
    # ANOMOD_SERVE_STATE — tenant replay state residency: auto (default,
    # = device for the bucket-runner plane), device (shard-owned
    # device-resident pool, scatter-add fold, bit-identical), host (the
    # per-tenant numpy seam; anomod.serve.batcher).
    serve_state: str = dataclasses.field(default_factory=_serve_state_env)
    # ANOMOD_SERVE_ASYNC_COMMIT — deferred-commit serve tick
    # (anomod.serve.engine; off = the synchronous parity oracle, on =
    # tick N's fold/score commit drains under tick N+1's coordinator
    # work, decisions pinned byte-identical either way).
    serve_async_commit: bool = dataclasses.field(
        default_factory=_serve_async_commit_env)
    # ANOMOD_SERVE_WORKER — shard-worker kind: thread (in-process, the
    # byte-parity oracle) or process (spawn-context worker processes
    # behind the same submit/join seam; anomod.serve.procshard).
    serve_worker: str = dataclasses.field(default_factory=_serve_worker_env)
    # ANOMOD_SERVE_WORKER_START_TIMEOUT_S — process-worker ready
    # handshake deadline in seconds (spawn + imports + plane build).
    serve_worker_start_timeout_s: float = dataclasses.field(
        default_factory=_serve_worker_start_timeout_s_env)
    # ANOMOD_SERVE_FOLD — tick-barrier registry merge mode: sparse
    # (touched-family deltas, payload follows active tenants) or dense
    # (full-registry walk, the payload oracle; anomod.obs.registry).
    serve_fold: str = dataclasses.field(default_factory=_serve_fold_env)
    # ANOMOD_SERVE_NATIVE_DRAIN — SFQ drain/shed engine: auto (columnar,
    # native kernels when the .so loads, NumPy fallback), on (native
    # required, fail loud), off (the Python heap parity oracle;
    # anomod.serve.queues).
    serve_native_drain: str = dataclasses.field(
        default_factory=_serve_native_drain_env)
    # ANOMOD_SERVE_RCA — online root-cause inference in the serve tick
    # (anomod.serve.rca; off = the serving plane stops at alerts).
    serve_rca: bool = dataclasses.field(default_factory=_serve_rca_env)
    # ANOMOD_SERVE_RCA_BUCKETS — (nodes, neighbors) compile grid for the
    # online-RCA culprit scorer (anomod.serve.rca; one XLA compile per
    # pair, AOT like the serve lane grid).
    serve_rca_buckets: tuple = dataclasses.field(
        default_factory=_serve_rca_buckets_env)
    # ANOMOD_SERVE_RCA_TOPK — ranked culprit list length per verdict.
    serve_rca_topk: int = dataclasses.field(
        default_factory=_serve_rca_topk_env)
    # ANOMOD_SERVE_RCA_BUDGET — max RCA runs per serve tick (queued past
    # it; the per-tick SLO budget).
    serve_rca_budget: int = dataclasses.field(
        default_factory=_serve_rca_budget_env)
    # ANOMOD_SERVE_RCA_WINDOWS — windowed-feature reach of the online
    # extractor (also bounds the per-tenant RCA span buffer).
    serve_rca_windows: int = dataclasses.field(
        default_factory=_serve_rca_windows_env)
    # ANOMOD_SERVE_CHAOS — scripted serve-plane fault injection
    # (anomod.serve.chaos; "" = off, else a validated fault script).
    serve_chaos: str = dataclasses.field(default_factory=_serve_chaos_env)
    # ANOMOD_SERVE_POLICY — elastic scaling policy: off (static), auto
    # (signal-fed autoscaler), script (fixed schedule from
    # ANOMOD_SERVE_POLICY_SCRIPT; anomod.serve.policy).
    serve_policy: str = dataclasses.field(default_factory=_serve_policy_env)
    # ANOMOD_SERVE_POLICY_SCRIPT — the scripted scaling schedule
    # ("" = none; validated action grammar, see validate_policy_script).
    serve_policy_script: str = dataclasses.field(
        default_factory=_serve_policy_script_env)
    # ANOMOD_SERVE_POLICY_MIN_SHARDS — elastic scale-down floor.
    serve_policy_min_shards: int = dataclasses.field(
        default_factory=_serve_policy_min_shards_env)
    # ANOMOD_SERVE_POLICY_MAX_SHARDS — elastic scale-up ceiling (past
    # it sustained overload climbs the brownout ladder instead).
    serve_policy_max_shards: int = dataclasses.field(
        default_factory=_serve_policy_max_shards_env)
    # ANOMOD_SERVE_POLICY_TARGET_IMBALANCE — max/mean shard-load ratio
    # past which the auto policy rebalances (live served-rate EWMAs).
    serve_policy_target_imbalance: float = dataclasses.field(
        default_factory=_serve_policy_target_imbalance_env)
    # ANOMOD_SERVE_POLICY_COOLDOWN_TICKS — minimum ticks between
    # executed scaling decisions (the anti-thrash hysteresis half).
    serve_policy_cooldown_ticks: int = dataclasses.field(
        default_factory=_serve_policy_cooldown_env)
    # ANOMOD_SERVE_CKPT_EVERY — shard-checkpoint cadence in ticks
    # (anomod.serve.supervise; 0 = supervision off, faults fail the
    # tick as before).
    serve_ckpt_every: int = dataclasses.field(
        default_factory=_serve_ckpt_every_env)
    # ANOMOD_SERVE_RETRIES — consecutive failures of one tick slice
    # before it is quarantined (anomod.serve.supervise).
    serve_retries: int = dataclasses.field(
        default_factory=_serve_retries_env)
    # ANOMOD_SERVE_RETRY_BACKOFF_S — wall backoff between recovery
    # attempts (0 = immediate; doubling, capped 5 s).
    serve_retry_backoff_s: float = dataclasses.field(
        default_factory=_serve_retry_backoff_s_env)
    # ANOMOD_SERVE_MAX_RESPAWNS — per-shard worker respawn budget per
    # run; past it the shard's tenants migrate to survivors.
    serve_max_respawns: int = dataclasses.field(
        default_factory=_serve_max_respawns_env)
    # ANOMOD_FLIGHT — serve-plane black-box flight recorder switch
    # (anomod.obs.flight; off = no tick journal, no audit surface).
    flight: bool = dataclasses.field(default_factory=_flight_env)
    # ANOMOD_FLIGHT_DIGEST_EVERY — tenant-state digest cadence in ticks
    # (anomod.obs.flight; crc32 over the get_state/pool-gather bytes).
    flight_digest_every: int = dataclasses.field(
        default_factory=_flight_digest_every_env)
    # ANOMOD_FLIGHT_MAX_TICKS — flight-journal ring capacity in ticks
    # (oldest records drop past it, counted in the registry).
    flight_max_ticks: int = dataclasses.field(
        default_factory=_flight_max_ticks_env)
    # ANOMOD_FLIGHT_DUMP_DIR — alert-triggered forensic-bundle directory
    # (anomod.obs.flight.forensic_bundle; None = dumps off).
    flight_dump_dir: Optional[Path] = dataclasses.field(
        default_factory=_flight_dump_dir_env)
    # ANOMOD_CENSUS — fleet census observatory: deterministic
    # resident-bytes + hot-set/Zipf census per cadence tick
    # (anomod.obs.census; off by default, pure read-side).
    census: bool = dataclasses.field(default_factory=_census_env)
    # ANOMOD_CENSUS_EVERY — census cadence in ticks (the flight
    # digest-cadence idiom; a census is always forced at run end).
    census_every: int = dataclasses.field(
        default_factory=_census_every_env)
    # ANOMOD_CENSUS_DECAY_TICKS — hot-set decay thresholds in ticks
    # (the hot-set-size-at-decay-threshold curve's x axis).
    census_decay_ticks: tuple = dataclasses.field(
        default_factory=_census_decay_ticks_env)
    # ANOMOD_CENSUS_SWEEP — registered-fleet sizes for the census
    # cost-attribution probe (anomod.obs.census.fleet_probe).
    census_sweep: tuple = dataclasses.field(
        default_factory=_census_sweep_env)
    # ANOMOD_CENSUS_COLDEST_K — coldest-K eviction-candidate preview
    # length per census tick.
    census_coldest_k: int = dataclasses.field(
        default_factory=_census_coldest_k_env)
    # ANOMOD_SERVE_TIER_HOT — tenant-state tiering hot capacity in
    # tenants; 0 = tiering off (anomod.serve.tiering).
    serve_tier_hot: int = dataclasses.field(
        default_factory=_serve_tier_hot_env)
    # ANOMOD_SERVE_TIER_DEMOTE_AFTER — idle ticks before a resident
    # tenant is demotion-eligible (the census last-served decay signal).
    serve_tier_demote_after: int = dataclasses.field(
        default_factory=_serve_tier_demote_after_env)
    # ANOMOD_SERVE_TIER_WARM_BYTES — host warm-tier state-bytes budget;
    # past it the coldest warm entries spill to the disk cold tier.
    serve_tier_warm_bytes: int = dataclasses.field(
        default_factory=_serve_tier_warm_bytes_env)
    # ANOMOD_SERVE_TIER_COLD_DIR — content-addressed disk cold-tier
    # root (io/cache atomic publish idiom); unset/off = no cold tier.
    serve_tier_cold_dir: Optional[Path] = dataclasses.field(
        default_factory=_serve_tier_cold_dir_env)
    # ANOMOD_SERVE_TIER_PREFETCH — cold-tier prefetch lane depth (max
    # concurrent disk fetches overlapping the admission phases).
    serve_tier_prefetch: int = dataclasses.field(
        default_factory=_serve_tier_prefetch_env)
    # ANOMOD_NATIVE — C++ native runtime switch: auto (use when the .so
    # loads), on (required, fail loud with the build reason), off
    # (pure-Python paths; anomod.io.native).
    native: str = dataclasses.field(default_factory=_native_env)
    # ANOMOD_SERVE_MAX_BACKLOG — global admission backlog bound in spans
    # (anomod.serve.queues; the backpressure/shed budget).
    serve_max_backlog: int = dataclasses.field(
        default_factory=_serve_max_backlog_env)
    # ANOMOD_OBS_ENABLED — process-wide metrics registry switch
    # (anomod.obs.registry; off = shared no-op metric handles).
    obs_enabled: bool = dataclasses.field(default_factory=_obs_enabled_env)
    # ANOMOD_OBS_MAX_SAMPLES — scrape-journal bound in samples
    # (anomod.obs.registry; oldest samples drop past it).
    obs_max_samples: int = dataclasses.field(
        default_factory=_obs_max_samples_env)
    # ANOMOD_OBS_HTTP — embedded /metrics endpoint plane switch
    # (anomod.obs.http; localhost-bound, off by default).
    obs_http: bool = dataclasses.field(default_factory=_obs_http_env)
    # ANOMOD_OBS_HTTP_PORT — endpoint-plane TCP port; 0 = OS-assigned
    # ephemeral (anomod.obs.http).
    obs_http_port: int = dataclasses.field(
        default_factory=_obs_http_port_env)
    # ANOMOD_SERVE_FEED_LAG_S — live-feed wall->virtual lag budget in
    # seconds (anomod.serve.feed; walls measured, never decisive).
    serve_feed_lag_s: float = dataclasses.field(
        default_factory=_serve_feed_lag_s_env)
    # ANOMOD_FEED_JOURNAL — live-feed wire-journal path, or unset/off to
    # disable recording (anomod.serve.feed.FeedJournal).
    feed_journal: Optional[Path] = dataclasses.field(
        default_factory=_feed_journal_env)

    @property
    def sn_data(self) -> Path:
        return self.data_root / "SN_data"

    @property
    def tt_data(self) -> Path:
        return self.data_root / "TT_data"

    def with_backend(self, backend: str) -> "Config":
        return dataclasses.replace(self, backend=backend)


_DEFAULT: Optional[Config] = None


def get_config() -> Config:
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = Config()
    return _DEFAULT


def set_config(cfg: Config) -> None:
    global _DEFAULT
    _DEFAULT = cfg
