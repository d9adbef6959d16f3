"""The serving engine: virtual-clock tick loop over admission → dynamic
batching → the shared jitted chunk step → per-tenant SLO accounting.

Deterministic by construction (the anomod.recovery pattern): a virtual
clock advances in fixed ticks, arrivals come from a seeded traffic
source, and every admission/shedding/serving decision is pure
bookkeeping — a seeded overload replay is bit-reproducible, and the
whole engine unit-tests without a single wall sleep.  Wall time is
measured (never waited on) around the serving path only, for the
sustained spans/sec number the report carries.

Each tenant runs the UNCHANGED detector stack: an
``anomod.stream.OnlineDetector`` whose replay plane is a
:class:`anomod.serve.batcher.BucketedStreamReplay` sharing one compiled
chunk step per bucket across the whole fleet (or, with ``mesh``, an
``anomod.parallel.stream.ShardedStreamReplay`` — the pod-sharded plane,
reused wholesale).  Admission→scored latency per micro-batch folds into
per-tenant t-digests (anomod.ops.tdigest — the repo's one sketch path),
so the ServeReport's p50/p99 are sketch-backed, mergeable across tenants
and priorities.

Scale-out (``ANOMOD_SERVE_SHARDS``): the score plane fans out across
tenant-sharded worker threads (anomod.serve.shard) and joins at a
barrier each tick, while admission/drain/shed/SLO bookkeeping stays on
the coordinator — so an N-shard run's states, alerts and decisions are
IDENTICAL to the 1-shard engine on the same seed.  Within a shard the
fused dispatch pipelines (``ANOMOD_SERVE_PIPELINE``): staging of batch
t+1 overlaps batch t's in-flight XLA dispatch, bit-identically.

Online RCA (``ANOMOD_SERVE_RCA``): a tenant's detector firing queues
incremental GNN culprit inference over that tenant's live service graph
(anomod.serve.rca) — budgeted per tick, run on the shard that owns the
tenant, verdicts folded at the barrier in enqueue order; a pure
read-side consumer, so every decision above stays byte-identical with
RCA on or off.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from anomod import obs
from anomod.ops.tdigest import (TDigest, tdigest_build, tdigest_merge_many,
                                tdigest_quantile)
from anomod.replay import N_FEATS, ReplayConfig
from anomod.schemas import concat_span_batches
from anomod.serve.batcher import (BucketedStreamReplay, BucketRunner,
                                  PooledStreamReplay)
from anomod.serve.queues import (AdmissionController, QueuedBatch,
                                 TenantSpec)
from anomod.utils.tracing import span_of

#: t-digest centroid capacity for the latency sketches (compact enough to
#: keep per tenant, accurate to well under a tick at the tails)
_DIGEST_K = 32
#: latency samples buffered per tenant before folding into the digest
_FOLD_EVERY = 256


class VirtualClock:
    """Tick-based deterministic time (no wall sleeps — recovery.py's
    pattern, shared contract with the chaos/recovery controllers)."""

    def __init__(self, tick_s: float = 1.0, t0_s: float = 0.0):
        if tick_s <= 0:
            raise ValueError("tick_s must be positive")
        self.tick_s = float(tick_s)
        self.now_s = float(t0_s)
        self.ticks = 0

    def advance(self) -> float:
        self.now_s += self.tick_s
        self.ticks += 1
        return self.now_s


class _TenantSLO:
    """Per-tenant latency sketch + alert bookkeeping.

    Every fold ALSO merges the freshly-built digest chunk into the
    process registry's ``anomod_serve_admit_to_scored_seconds`` histogram
    (anomod.obs) — the registry's fleet-wide latency sketch is literally
    the fold of these private per-tenant digests, with no double counting
    and no second pass over raw samples."""

    def __init__(self,
                 hist_name: str = "anomod_serve_admit_to_scored_seconds"):
        self.digest: Optional[TDigest] = None
        self._buf: List[float] = []
        self.n_samples = 0
        self.max_latency_s = 0.0
        self._obs_hist = obs.histogram(hist_name)

    def record(self, latency_s: float) -> None:
        self._buf.append(float(latency_s))
        self.n_samples += 1
        self.max_latency_s = max(self.max_latency_s, float(latency_s))
        if len(self._buf) >= _FOLD_EVERY:
            self.fold()

    def fold(self) -> None:
        if not self._buf:
            return
        d = tdigest_build(np.asarray(self._buf, np.float32), k=_DIGEST_K)
        self._obs_hist.merge_digest(d)
        self.digest = d if self.digest is None else \
            tdigest_merge_many([self.digest, d])
        self._buf = []

    def quantile(self, q: float) -> Optional[float]:
        self.fold()
        if self.digest is None or float(self.digest.weight.sum()) <= 0:
            return None
        return float(tdigest_quantile(self.digest, q))


class _LazySLO(dict):
    """Per-tenant SLO sketches created on first recorded sample — the
    registered fleet never materializes a digest row (the tiering PR's
    O(hot-set) registry contract; the report's priority merge walks the
    rows that exist, and ``_merged_quantiles`` of none is None-safe)."""

    def __missing__(self, tid: int) -> _TenantSLO:
        s = self[tid] = _TenantSLO()
        return s


def _merged_quantiles(slos: Sequence[_TenantSLO],
                      qs=(0.5, 0.99)) -> Dict[str, Optional[float]]:
    digests = []
    for s in slos:
        s.fold()
        if s.digest is not None and float(s.digest.weight.sum()) > 0:
            digests.append(s.digest)
    if not digests:
        return {f"p{int(q * 100)}_latency_s": None for q in qs}
    merged = digests[0] if len(digests) == 1 else \
        tdigest_merge_many(digests)
    return {f"p{int(q * 100)}_latency_s":
            round(float(tdigest_quantile(merged, q)), 6) for q in qs}


#: ServeReport fields that legitimately differ across shard counts /
#: pipeline depths on the same seed: wall-clock measurements and lane
#: GROUPING topology (which lanes share a fused stack depends on shard
#: membership; the resulting per-lane bits do not).  The ONE definition
#: of the shard-determinism contract's exclusion list, read by the
#: parity tests (tests/test_serve.py).
#: ``rca_latency``/``rca_wall_s`` are wall measurements of the RCA runs;
#: the verdict STREAM itself (and every other rca_* field) is pinned
#: identical across shard counts.
SHARD_VARIANT_REPORT_FIELDS = (
    "serve_wall_s", "sustained_spans_per_sec", "compile_s",
    "lane_compile_s", "fused_dispatches", "lanes_by_bucket",
    "lane_pad_waste", "shards", "pipeline", "shard_tenants",
    "shard_spans", "shard_imbalance", "rca_latency", "rca_wall_s",
    # tick-wall decomposition: wall measurements, and the native-staged
    # dispatch count follows the fused-dispatch grouping topology
    "stage_wall_s", "dispatch_wall_s", "fold_wall_s", "score_wall_s",
    "native_staged_dispatches",
    # supervision wall legs: snapshot and recovery time are wall
    # measurements (the decisions they protect are pinned identical)
    "ckpt_wall_s", "recovery_wall_s",
    # elastic topology: how many workers the policy ran at its peak is
    # execution strategy (a policy-off run's peak IS its shard count),
    # and the policy/migration wall is a wall measurement
    "peak_shards", "policy_wall_s",
    # the deferred-commit seam (ANOMOD_SERVE_ASYNC_COMMIT): how long
    # dispatches were left executing under coordinator work is a wall
    # measurement — consciously VARIANT (async_commit, the config bit,
    # and async_ticks, its config-derived tick count, stay canonical)
    "commit_defer_wall_s",
    # the fleet census observatory (anomod.obs.census): resident-bytes
    # totals follow the execution TOPOLOGY (per-shard pool capacity and
    # scratch grids depend on the shard count and residency), so the
    # byte dict is consciously VARIANT — the hot-set census
    # (census_hot_set) and the census tick count derive from
    # coordinator admission decisions alone and stay CANONICAL; the
    # census wall is a wall measurement (the in-run overhead price)
    "census_resident_bytes", "census_wall_s",
    # the state-tiering plane (ANOMOD_SERVE_TIER_HOT): demotions /
    # promotions / misses are functions of seed+config and stay
    # CANONICAL; whether a cold fetch happened to finish before its
    # one-tick deferral elapsed is wall luck, and the gate+demote wall
    # is a wall measurement — consciously VARIANT
    "tier_prefetch_hidden", "tier_wall_s",
    # the worker plane (ANOMOD_SERVE_WORKER / ANOMOD_SERVE_FOLD):
    # thread-vs-process shard execution and dense-vs-sparse barrier
    # deltas are execution topology, and the fold payload byte count
    # follows that topology — a process-worker report must compare
    # equal to the thread oracle on every decision field
    "worker", "fold", "fold_payload_bytes")


def _runner_stats(r) -> dict:
    """One runner's cumulative book + compile/wall legs as a plain
    dict — the ONE shape shared by the report aggregation and the
    retired-runner retention at elastic scale-down, so the "counts
    cover the WHOLE run" invariant cannot drift when a new leg
    lands in one site but not the other."""
    return {"book": r.book_snapshot(),
            "compile_s": r.compile_s,
            "lane_compile_s": r.lane_compile_s,
            "stage_wall_s": r.stage_wall_s,
            "dispatch_wall_s": r.dispatch_wall_s,
            "fold_wall_s": r.fold_wall_s,
            "score_wall_s": r.score_wall_s}


def _plane_col_gather(work):
    """The ``gather_cols`` backend for one batched COMMIT pass
    (:func:`anomod.stream.score_closed_windows_batched`) over the
    engine's replay planes.

    DEVICE path — every requested plane lives in the SAME runner's
    tenant pool (the engine maps a tenant's replay to its owning
    shard's runner, and one commit pass only ever sees one shard's
    tenants): ONE fused pool gather per scored window
    (:meth:`anomod.replay.TenantStatePool.gather_window`), so only the
    small scored columns materialize to host — never the full
    [SW, F] rows.  HOST path (host-seam replays, or mixed callers):
    per-plane host views, cached across the pass's windows (the plane
    is static during scoring — same snapshot discipline as the
    sequential scorer's one ``agg_plane()`` read)."""
    planes: Dict[int, np.ndarray] = {}

    def gather(items):
        reps = [work[i][0].replay for i, _ in items]
        # anomod-lint: disable=S301 — the one blessed fused-gather exception: slots are only COLLECTED here and handed to pool.gather_window, which owns the always-copy contract
        if reps and all(type(r) is PooledStreamReplay for r in reps) \
                and all(r._runner is reps[0]._runner for r in reps):
            return reps[0]._runner.pool.gather_window(
                [r._slot for r in reps], [c for _, c in items])
        out = np.empty((len(items), reps[0].cfg.n_services, N_FEATS),
                       np.float32)
        for j, (i, c) in enumerate(items):
            pl = planes.get(i)
            if pl is None:
                pl = planes[i] = np.asarray(
                    work[i][0].replay.agg_plane(), np.float32)
            out[j] = pl[:, c]
        return out

    return gather


def onset_eligible(window: int, onset_window: int) -> bool:
    """THE pre-onset-noise eligibility rule, in one place: an alert (or
    an RCA verdict, via its triggering alert) at absolute window ``w``
    is attributable to a fault whose onset falls in ``onset_window`` iff
    ``w >= onset_window`` — the boundary window itself counts (it is the
    earliest window the fault can influence), anything earlier is noise
    and must not score as (negative-latency) detection or as an RCA hit.
    Shared by the golden fault-detection metrics, :meth:`ServeEngine.
    alerts_for` and the RCA hit accounting so the three paths can never
    apply different rules."""
    return window >= onset_window


def onset_eligible_alerts(alerts, onset_window: int) -> list:
    """The alerts that pass :func:`onset_eligible`."""
    return [a for a in alerts if onset_eligible(a.window, onset_window)]


@dataclasses.dataclass
class ServeReport:
    """The serving run's quality/throughput document (JSON-able)."""
    n_tenants: int
    duration_s: float
    ticks: int
    capacity_spans_per_s: float
    offered_spans: int
    admitted_spans: int
    served_spans: int
    shed_spans: int
    shed_fraction: float
    served_batches: int
    peak_backlog_spans: int
    max_backlog: int
    buckets: Tuple[int, ...]
    dispatches_by_width: Dict[int, int]
    fused: bool                                  # lane-stacked dispatch on?
    fused_dispatches: int                        # actual fused dispatches
    lane_buckets: Tuple[int, ...]
    lanes_by_bucket: Dict[int, int]              # fused dispatches per bucket
    lane_pad_waste: float                        # dead-lane fraction
    compile_s: float
    lane_compile_s: float
    native_staging: bool                         # GIL-free C++ scratch pack?
    native_staged_dispatches: int                # fused dispatches so packed
    serve_state: str                             # tenant states: host|device
    stage_wall_s: float                          # host packing wall
    dispatch_wall_s: float                       # executable-issue wall
    fold_wall_s: float                           # delta fold wall (device:
    #                                              scatter-add + barrier)
    score_wall_s: float                          # window-scoring wall
    shards: int                                  # engine-worker shard count
    pipeline: int                                # in-flight dispatch depth
    shard_tenants: Dict[int, int]                # tenants owned per shard
    shard_spans: Dict[int, int]                  # spans scored per shard
    shard_imbalance: float                       # max shard load / mean
    latency: Dict[str, Optional[float]]          # aggregate p50/p99
    per_priority: Dict[int, dict]
    modality_events: Dict[str, int]              # multimodal sidecar volume
    n_alerts: int
    n_tenants_alerted: int
    fault_detection: Optional[dict]
    rca_enabled: bool                            # online RCA plane on?
    n_rca_runs: int                              # alert→culprit inferences
    rca_topk_hits: Dict[int, int]                # k -> fault tenants hit@k
    rca_eligible: int                            # fault tenants w/ verdict
    rca_latency: Dict[str, Optional[float]]      # wall p50/p99 per RCA run
    rca_alert_to_culprit_s: Dict[str, Optional[float]]  # virtual queue delay
    rca_wall_s: float                            # total RCA wall
    supervised: bool                             # checkpoint/recovery on?
    ckpt_every: int                              # snapshot cadence (ticks)
    n_checkpoints: int                           # snapshots taken
    ckpt_wall_s: float                           # snapshot wall
    n_shard_crashes: int                         # tick-barrier failures
    n_respawns: int                              # worker threads respawned
    n_restored_ticks: int                        # slices re-executed
    n_quarantined: int                           # batches dropped after K
    #                                              consecutive kill loops
    n_migrated_tenants: int                      # moved off dead shards
    recovery_wall_s: float                       # restore + re-exec wall
    policy: str                                  # elastic mode: off|auto|
    #                                              script
    n_scale_ups: int                             # executed up episodes
    n_scale_downs: int                           # executed down episodes
    n_rebalances: int                            # executed rebalances
    n_policy_migrations: int                     # tenants moved by policy
    brownout_ticks: int                          # ticks at ladder level>=1
    peak_shards: int                             # max workers the run held
    policy_wall_s: float                         # policy eval + migration
    #                                              wall
    flight_enabled: bool                         # black-box recorder on?
    flight_recorded_ticks: int                   # journal records written
    flight_dropped_ticks: int                    # ring evictions (0 = no
    #                                              loss; never silent)
    census_enabled: bool                         # fleet census on?
    census_ticks: int                            # census drains taken
    census_hot_set: Dict[str, object]            # hot-set/Zipf census
    #                                              (canonical: admission-
    #                                              derived, shard-invariant)
    census_resident_bytes: Dict[str, object]     # deterministic resident
    #                                              bytes (variant: follows
    #                                              pool/scratch topology)
    census_wall_s: float                         # census drain wall (the
    #                                              in-run overhead price)
    tier_hot: int                                # hot-pool tenant capacity
    #                                              (0 = tiering off)
    n_tier_demotions_warm: int                   # device→host warm demotions
    n_tier_demotions_cold: int                   # warm→disk cold spills
    n_tier_promotions: int                       # tier→device re-admissions
    n_tier_misses: int                           # deterministic one-tick
    #                                              cold-promotion deferrals
    tier_prefetch_hidden: int                    # cold joins whose disk read
    #                                              had already finished
    #                                              (variant: wall telemetry)
    tier_wall_s: float                           # gate + demote-step wall
    async_commit: bool                           # deferred-commit tick on?
    async_ticks: int                             # ticks whose commit
    #                                              deferred past issue
    commit_defer_wall_s: float                   # wall dispatches spent
    #                                              executing under next-tick
    #                                              coordinator work (the
    #                                              hidden fold wait)
    worker: str                                  # shard engine: thread|
    #                                              process (execution
    #                                              topology — variant)
    fold: str                                    # barrier delta mode:
    #                                              dense|sparse (variant)
    fold_payload_bytes: int                      # structural bytes the tick
    #                                              barrier's registry deltas
    #                                              carried (variant: follows
    #                                              worker/fold topology)
    serve_wall_s: float
    sustained_spans_per_sec: float

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["buckets"] = list(self.buckets)
        d["lane_buckets"] = list(self.lane_buckets)
        d["dispatches_by_width"] = {str(k): v for k, v
                                    in self.dispatches_by_width.items()}
        d["lanes_by_bucket"] = {str(k): v for k, v
                                in self.lanes_by_bucket.items()}
        d["per_priority"] = {str(k): v for k, v
                             in self.per_priority.items()}
        d["shard_tenants"] = {str(k): v for k, v
                              in self.shard_tenants.items()}
        d["shard_spans"] = {str(k): v for k, v
                            in self.shard_spans.items()}
        d["rca_topk_hits"] = {str(k): v for k, v
                              in self.rca_topk_hits.items()}
        return d


def serve_plane_cfg(n_services: int = 12, window_s: float = 5.0,
                    n_windows: int = 32) -> ReplayConfig:
    """The serve plane's replay-plane shape — ONE definition shared by
    ``run_power_law``, the live feed (``serve/feed.py``) and the
    benchmark's fleet configuration (``benchmark/configs/tt-fleet.json``)."""
    return ReplayConfig(n_services=n_services, n_windows=n_windows,
                        window_us=int(window_s * 1e6), chunk_size=4096)


def run_power_law(n_tenants: int = 200, n_services: int = 8,
                  capacity_spans_per_s: float = 20_000.0,
                  overload: float = 1.0, duration_s: float = 120.0,
                  tick_s: float = 1.0, seed: int = 0, alpha: float = 1.2,
                  window_s: float = 5.0, baseline_windows: int = 4,
                  z_threshold: float = 4.0,
                  buckets: Optional[Tuple[int, ...]] = None,
                  max_backlog: Optional[int] = None,
                  fault_tenants: int = 2, score: bool = True,
                  mesh=None, tracer=None, n_windows: int = 32,
                  fuse: Optional[bool] = None,
                  lane_buckets: Optional[Tuple[int, ...]] = None,
                  shards: Optional[int] = None,
                  pipeline: Optional[int] = None,
                  rca: Optional[bool] = None,
                  native: Optional[bool] = None,
                  state: Optional[str] = None,
                  flight: Optional[bool] = None,
                  flight_digest_every: Optional[int] = None,
                  flight_max_ticks: Optional[int] = None,
                  census: Optional[bool] = None,
                  census_every: Optional[int] = None,
                  chaos: Optional[str] = None,
                  ckpt_every: Optional[int] = None,
                  retries: Optional[int] = None,
                  retry_backoff_s: Optional[float] = None,
                  max_respawns: Optional[int] = None,
                  policy: Optional[str] = None,
                  policy_script: Optional[str] = None,
                  min_shards: Optional[int] = None,
                  max_shards: Optional[int] = None,
                  target_imbalance: Optional[float] = None,
                  cooldown_ticks: Optional[int] = None,
                  async_commit: Optional[bool] = None,
                  native_drain: Optional[str] = None,
                  tier_hot: Optional[int] = None,
                  tier_demote_after: Optional[int] = None,
                  tier_warm_bytes: Optional[int] = None,
                  tier_cold_dir=None,
                  tier_prefetch: Optional[int] = None,
                  worker: Optional[str] = None,
                  fold: Optional[str] = None,
                  served_log: Optional[list] = None,
                  seq_model=None
                  ) -> Tuple["ServeEngine", ServeReport]:
    """The canonical seeded serve run shared by ``anomod serve`` and
    ``chip_smoke.py``: a power-law tenant fleet offering
    ``overload``× the engine's capacity, with ``fault_tenants`` busiest
    tenants given a scripted latency fault once calibration is past —
    so one invocation measures sustained throughput, shed behavior AND
    alert latency under load.  ``served_log`` (see
    :meth:`ServeEngine.run`) collects what each tick served."""
    from anomod.serve.traffic import PowerLawTraffic, TenantFault
    onset_s = (baseline_windows + 2) * window_s
    if duration_s <= onset_s + 2 * window_s:
        fault_tenants = 0                 # too short for a fault phase
    faults = {t: TenantFault("latency", service=1, onset_s=onset_s,
                             factor=10.0)
              for t in range(min(fault_tenants, n_tenants))}
    traffic = PowerLawTraffic(
        n_tenants=n_tenants,
        total_rate_spans_per_s=capacity_spans_per_s * overload,
        alpha=alpha, seed=seed, n_services=n_services, faults=faults)
    cfg = serve_plane_cfg(n_services, window_s, n_windows)
    engine = ServeEngine(traffic.specs, traffic.services, cfg,
                         capacity_spans_per_s=capacity_spans_per_s,
                         tick_s=tick_s, buckets=buckets,
                         max_backlog=max_backlog, score=score,
                         baseline_windows=baseline_windows,
                         z_threshold=z_threshold, mesh=mesh,
                         tracer=tracer, fuse=fuse,
                         lane_buckets=lane_buckets, shards=shards,
                         pipeline=pipeline, rca=rca, native=native,
                         state=state, flight=flight,
                         flight_digest_every=flight_digest_every,
                         flight_max_ticks=flight_max_ticks,
                         census=census,
                         census_every=census_every,
                         chaos=chaos, ckpt_every=ckpt_every,
                         retries=retries,
                         retry_backoff_s=retry_backoff_s,
                         max_respawns=max_respawns, policy=policy,
                         policy_script=policy_script,
                         min_shards=min_shards, max_shards=max_shards,
                         target_imbalance=target_imbalance,
                         cooldown_ticks=cooldown_ticks,
                         async_commit=async_commit,
                         native_drain=native_drain,
                         tier_hot=tier_hot,
                         tier_demote_after=tier_demote_after,
                         tier_warm_bytes=tier_warm_bytes,
                         tier_cold_dir=tier_cold_dir,
                         tier_prefetch=tier_prefetch,
                         worker=worker, fold=fold, seq_model=seq_model)
    if engine.flight_recorder is not None:
        # the header's replay contract: `anomod audit replay` re-executes
        # this exact invocation from the journal alone.  Every
        # env-defaulted knob is recorded RESOLVED (what the engine
        # actually served with), never as the raw None the ctor would
        # re-resolve from the REPLAY process's env — otherwise a replay
        # under a different ANOMOD_SERVE_BUCKETS / _MAX_BACKLOG /
        # _FUSE / _RCA would report env drift as plane divergence.
        # ``native`` stays raw on purpose: native-vs-python staging is
        # byte-identical (it cannot move a canonical plane), and a
        # resolved ``True`` would refuse to replay on a box without the
        # toolchain for zero forensic benefit.
        engine.flight_recorder.header["run"] = dict(
            n_tenants=n_tenants, n_services=n_services,
            capacity_spans_per_s=capacity_spans_per_s, overload=overload,
            duration_s=duration_s, tick_s=tick_s, seed=seed, alpha=alpha,
            window_s=window_s, baseline_windows=baseline_windows,
            z_threshold=z_threshold,
            buckets=list(engine.runner.buckets),
            max_backlog=engine.max_backlog, fault_tenants=fault_tenants,
            score=score, n_windows=n_windows, fuse=engine.fuse,
            lane_buckets=list(engine.runner.lane_buckets),
            shards=engine.shards, pipeline=engine.pipeline,
            rca=engine.rca, native=native,
            state=engine.serve_state, flight=True,
            flight_digest_every=engine.flight_recorder.digest_every,
            flight_max_ticks=engine.flight_recorder.max_ticks,
            # the census plane, RESOLVED: a replay of a census-on run
            # re-takes the same deterministic census (the `census`
            # variant stream of a replay is byte-equal to the
            # original's at matching topology — pinned)
            census=engine.census,
            census_every=engine.census_every,
            # the fault-tolerance knobs, RESOLVED: an audit replay of a
            # chaos run re-injects the same script and re-recovers —
            # its canonical journal must equal the original's (the
            # no-score-gap contract makes both equal the fault-free
            # journal)
            chaos=(engine._chaos.script
                   if engine._chaos is not None else ""),
            ckpt_every=engine.ckpt_every, retries=engine.retries,
            retry_backoff_s=engine.retry_backoff_s,
            max_respawns=engine.max_respawns,
            # the elastic-policy knobs, RESOLVED: an audit replay of an
            # elastic run re-evaluates the same policy over the same
            # canonical signals and re-executes the SAME scaling
            # schedule (the episode-determinism pin)
            policy=(engine.policy.mode if engine.policy is not None
                    else "off"),
            policy_script=(engine.policy.script
                           if engine.policy is not None else ""),
            min_shards=(engine.policy.min_shards
                        if engine.policy is not None else None),
            max_shards=(engine.policy.max_shards
                        if engine.policy is not None else None),
            target_imbalance=(engine.policy.target_imbalance
                              if engine.policy is not None else None),
            cooldown_ticks=(engine.policy.cooldown_ticks
                            if engine.policy is not None else None),
            # the deferred-commit seam, RESOLVED: a replay of an
            # async run re-defers and re-commits the same schedule —
            # canonical journal byte-equal to the synchronous
            # engine's (the parity pin), so replaying either mode
            # against either journal matches
            async_commit=engine.async_commit,
            # the state-tiering knobs, RESOLVED: demotions/promotions/
            # misses are functions of these values (warm_bytes and
            # cold_dir decide cold-vs-warm, and a cold promotion's
            # one-tick deferral moves which tick the tenant's canonical
            # fold/score deltas land in), so a replay must serve with
            # the ORIGINAL tiering geometry to reproduce the journal
            tier_hot=engine.tier_hot,
            tier_demote_after=engine.tier_demote_after,
            tier_warm_bytes=engine.tier_warm_bytes,
            tier_cold_dir=(str(engine.tier_cold_dir)
                           if engine.tier_cold_dir is not None else None),
            tier_prefetch=engine.tier_prefetch,
            # ``native_drain`` stays raw — the ``native`` rationale:
            # the columnar/native SFQ drain is byte-identical to the
            # heap (it cannot move a canonical plane), and a resolved
            # "native" would refuse to replay on a toolchain-less box
            # for zero forensic benefit
            native_drain=native_drain,
            # the worker plane, RESOLVED: thread-vs-process shard
            # execution and dense-vs-sparse barrier deltas are
            # byte-parity pinned, so a replay may run either — but the
            # header records what the original actually served with
            # (the forensic record; also what the replay defaults to)
            worker=engine.worker_mode, fold=engine.fold_mode)
    report = engine.run(traffic, duration_s=duration_s,
                        served_log=served_log)
    return engine, report


class ServeEngine:
    """Multi-tenant serving plane over the streaming detectors."""

    def __init__(self, specs: Sequence[TenantSpec], services: Sequence[str],
                 cfg: Optional[ReplayConfig] = None, t0_us: int = 0,
                 capacity_spans_per_s: float = 20_000.0, tick_s: float = 1.0,
                 buckets: Optional[Tuple[int, ...]] = None,
                 max_backlog: Optional[int] = None,
                 max_tenant_backlog: Optional[int] = None,
                 score: bool = True, baseline_windows: int = 4,
                 z_threshold: float = 4.0, consecutive: int = 1,
                 min_count: float = 5.0, mesh=None, tracer=None,
                 multimodal: bool = False, testbed: Optional[str] = None,
                 fuse: Optional[bool] = None,
                 lane_buckets: Optional[Tuple[int, ...]] = None,
                 shards: Optional[int] = None,
                 pipeline: Optional[int] = None,
                 rca: Optional[bool] = None,
                 rca_buckets: Optional[tuple] = None,
                 rca_topk: Optional[int] = None,
                 rca_budget: Optional[int] = None,
                 rca_windows: Optional[int] = None,
                 native: Optional[bool] = None,
                 state: Optional[str] = None,
                 flight: Optional[bool] = None,
                 flight_digest_every: Optional[int] = None,
                 flight_max_ticks: Optional[int] = None,
                 census: Optional[bool] = None,
                 census_every: Optional[int] = None,
                 chaos: Optional[object] = None,
                 ckpt_every: Optional[int] = None,
                 retries: Optional[int] = None,
                 retry_backoff_s: Optional[float] = None,
                 max_respawns: Optional[int] = None,
                 policy: Optional[str] = None,
                 policy_script: Optional[str] = None,
                 min_shards: Optional[int] = None,
                 max_shards: Optional[int] = None,
                 target_imbalance: Optional[float] = None,
                 cooldown_ticks: Optional[int] = None,
                 async_commit: Optional[bool] = None,
                 native_drain: Optional[str] = None,
                 tier_hot: Optional[int] = None,
                 tier_demote_after: Optional[int] = None,
                 tier_warm_bytes: Optional[int] = None,
                 tier_cold_dir=None,
                 tier_prefetch: Optional[int] = None,
                 worker: Optional[str] = None,
                 fold: Optional[str] = None,
                 seq_model=None):
        from anomod.config import get_config
        if capacity_spans_per_s <= 0:
            raise ValueError("capacity must be positive")
        app_cfg = get_config()
        self.specs = list(specs)
        self.services = tuple(services)
        self.cfg = cfg or ReplayConfig(n_services=len(self.services),
                                       chunk_size=4096)
        if self.cfg.n_services != len(self.services):
            raise ValueError("cfg.n_services disagrees with the service "
                             "table")
        self.t0_us = int(t0_us)
        self.capacity_spans_per_s = float(capacity_spans_per_s)
        self.clock = VirtualClock(tick_s)
        self.max_backlog = int(max_backlog if max_backlog is not None
                               else app_cfg.serve_max_backlog)
        self.admission = AdmissionController(
            self.specs, max_backlog=self.max_backlog,
            max_tenant_backlog=max_tenant_backlog,
            drain_engine=native_drain)
        self.score = bool(score)
        self.mesh = mesh
        #: tenant-fused scoring (ANOMOD_SERVE_FUSE): per tick, drained
        #: same-tenant batches coalesce into one staging and same-width
        #: chunks across tenants run as lane-stacked dispatches — pinned
        #: bit-identical on CPU to sequential per-tenant scoring of the
        #: same COALESCED batches (coalescing is the one documented
        #: regrouping vs the unfused per-batch path: docs/SERVING.md).
        #: The mesh plane manages its own sharded dispatch, so fusion
        #: only applies to the bucket-runner plane.
        self.fuse = bool(app_cfg.serve_fuse if fuse is None else fuse)
        self._fused = self.fuse and mesh is None
        #: tenant sharding (ANOMOD_SERVE_SHARDS): the score plane fans
        #: out across worker threads by tenant ownership; admission/
        #: drain/shed/SLO stay on the coordinator, so every decision is
        #: identical to the 1-shard engine on the same seed.  shards=1
        #: (the default) is the exact pre-sharding code path.
        self.shards = int(app_cfg.serve_shards if shards is None
                          else shards)
        if self.shards < 1:
            raise ValueError("shards must be >= 1")
        #: in-flight fused dispatches per runner (ANOMOD_SERVE_PIPELINE):
        #: depth d stages dispatch t+1 while dispatch t's XLA work is in
        #: flight (per-slot pinned scratch; folds in dispatch order, so
        #: any depth is bit-identical).  Applies to the inline 1-shard
        #: fused path AND every shard worker — depth 1 is the exact
        #: synchronous pre-pipelining code path.
        self.pipeline = int(app_cfg.serve_pipeline if pipeline is None
                            else pipeline)
        if self.pipeline < 1:
            raise ValueError("pipeline depth must be >= 1")
        if mesh is not None and self.shards > 1:
            raise ValueError(
                "the mesh plane manages its own sharded dispatch; "
                "run it with shards=1 (ANOMOD_SERVE_SHARDS=1)")
        #: deferred-commit tick (ANOMOD_SERVE_ASYNC_COMMIT): tick t's
        #: fold/score dispatches are ISSUED but not waited on; while
        #: the XLA executes run, the coordinator handles tick t+1's
        #: admission/drain/shed/SLO phases against last-committed
        #: state, and tick t commits at a barrier placed just before
        #: its results are first read.  Every decision input is a
        #: snapshot taken at tick t, so states / alerts / SLO / shed
        #: and the canonical flight journal are byte-identical to the
        #: synchronous engine (=0, the parity oracle) — only walls
        #: move.  The mesh plane manages its own sharded dispatch
        #: (there is no issue/commit seam to split), so the mode
        #: auto-disables there and an explicit request is refused —
        #: the policy/state idiom.
        _async = (app_cfg.serve_async_commit if async_commit is None
                  else bool(async_commit))
        if mesh is not None and _async:
            if async_commit is not None:
                raise ValueError(
                    "the deferred-commit tick splits the bucket-runner "
                    "issue/commit seam; the mesh plane manages its own "
                    "sharded dispatch (ANOMOD_SERVE_ASYNC_COMMIT=0)")
            _async = False
        self.async_commit = bool(_async)
        self._async = self.async_commit
        #: the in-flight deferred tick's snapshotted context (None
        #: when nothing is deferred): every input its commit tail
        #: will read, captured at issue time so the NEXT tick's
        #: admission can never leak into this tick's journal/policy
        self._deferred: Optional[dict] = None
        #: ticks whose commit actually deferred past issue
        self.async_ticks = 0
        #: wall spent with dispatches left executing under coordinator
        #: work before their barrier first read them
        self.commit_defer_wall_s = 0.0
        #: elastic scaling policy (ANOMOD_SERVE_POLICY, anomod.serve.
        #: policy): "off" (the default) is the static engine; "auto"/
        #: "script" evaluate an ElasticPolicy at every tick boundary on
        #: the coordinator and execute scale-up / scale-down /
        #: rebalance / brownout decisions through the live-migration
        #: seams.  Fed ONLY canonical signals, so the scaling schedule
        #: is seed-deterministic (reruns and `anomod audit replay`
        #: reproduce it) and tenant states / alerts / SLO / shed stay
        #: byte-identical to a static run of the same seed.  The mesh
        #: plane keeps state outside the migration seams and the
        #: multimodal sidecar's modality planes have never been
        #: migration-exercised, so the policy auto-disables on both
        #: (an explicit request is refused) — the supervision idiom.
        _policy_mode = (app_cfg.serve_policy if policy is None
                        else str(policy).strip().lower() or "off")
        if _policy_mode not in ("off", "auto", "script"):
            raise ValueError(f"unknown serve policy mode "
                             f"{_policy_mode!r} (off|auto|script)")
        if (mesh is not None or multimodal) and _policy_mode != "off":
            if policy is not None:
                raise ValueError(
                    "the elastic policy migrates tenants through the "
                    "bucket-runner state seams; "
                    + ("the mesh plane manages its own sharded state"
                       if mesh is not None else
                       "the multimodal sidecar planes are not covered "
                       "by the migration seams")
                    + " (ANOMOD_SERVE_POLICY=off)")
            _policy_mode = "off"
        self._elastic = _policy_mode != "off"
        #: elastic engines run the SHARDED machinery at every count
        #: (per-shard registries/runners/workers even at 1 shard), so a
        #: scale-up never has to convert an inline engine mid-run; the
        #: static 1-shard engine keeps the exact inline code path
        self._use_workers = self.shards > 1 or self._elastic
        self.policy = None
        if self._elastic:
            from anomod.serve.policy import ElasticPolicy
            self.policy = ElasticPolicy(
                _policy_mode,
                int(app_cfg.serve_policy_min_shards
                    if min_shards is None else min_shards),
                int(app_cfg.serve_policy_max_shards
                    if max_shards is None else max_shards),
                float(app_cfg.serve_policy_target_imbalance
                      if target_imbalance is None else target_imbalance),
                int(app_cfg.serve_policy_cooldown_ticks
                    if cooldown_ticks is None else cooldown_ticks),
                script=(app_cfg.serve_policy_script
                        if policy_script is None else policy_script))
            if not (self.policy.min_shards <= self.shards
                    <= self.policy.max_shards):
                raise ValueError(
                    f"shards={self.shards} is outside the elastic "
                    f"envelope [{self.policy.min_shards}, "
                    f"{self.policy.max_shards}] "
                    "(ANOMOD_SERVE_POLICY_MIN/MAX_SHARDS)")
        self.policy_wall_s = 0.0
        #: spans resident in the replay states policy migrations moved
        self.policy_migrated_spans = 0
        self._peak_shards = self.shards
        self._policy_events: List[dict] = []
        self._policy_prev_chunks: Optional[List[int]] = None
        self._policy_prev_shed = 0
        #: retired shard runners' cumulative books (scale-down keeps
        #: them so the report's canonical dispatch counts — and its
        #: wall legs — still cover the whole run)
        self._retired_runners: List[dict] = []
        #: tenant-state residency (ANOMOD_SERVE_STATE): "device" keeps
        #: each shard's tenant states in its runner's device-resident
        #: pool (lane folds = on-device scatter-adds in dispatch order,
        #: pinned BIT-identical to the host seam); "host" is the
        #: per-tenant numpy seam.  The mesh plane manages its own
        #: sharded state, so the pool cannot apply there: forcing
        #: "device" with a mesh is refused (auto degrades to host).
        _state = state if state is not None else app_cfg.serve_state
        if _state not in ("auto", "host", "device"):
            raise ValueError(f"unknown serve state mode {_state!r} "
                             "(auto|host|device)")
        if mesh is not None:
            if _state == "device":
                raise ValueError(
                    "the mesh plane manages its own sharded state; "
                    "a device state pool cannot apply "
                    "(ANOMOD_SERVE_STATE=host or auto)")
            _state = "host"
        self.serve_state = "device" if _state == "auto" else _state
        #: tenant-state tiering (ANOMOD_SERVE_TIER_HOT > 0; anomod.
        #: serve.tiering): cold tenants demote out of the device pool
        #: into a host warm tier (and past the warm budget, a
        #: content-addressed disk cold tier), re-admitting transparently
        #: on their next drained batch — pool bytes track the HOT set
        #: while the registered fleet scales to millions.  The mesh
        #: plane keeps state outside the snapshot seams, the multimodal
        #: sidecar's modality planes have no demotion copier, and the
        #: deferred-commit tick would demote states with uncommitted
        #: in-flight folds at tick end — tiering auto-disables on all
        #: three (an explicit request is refused): the policy idiom.
        _tier_hot = (app_cfg.serve_tier_hot if tier_hot is None
                     else int(tier_hot))
        if tier_hot is not None and _tier_hot < 0:
            raise ValueError("tier_hot must be >= 0 (0 = tiering off)")
        if _tier_hot > 0 and (mesh is not None or multimodal
                              or self.async_commit):
            if tier_hot is not None:
                raise ValueError(
                    "state tiering demotes tenants through the "
                    "bucket-runner snapshot seams; "
                    + ("the mesh plane manages its own sharded state"
                       if mesh is not None else
                       "the multimodal sidecar planes are not covered "
                       "by the demotion copier" if multimodal else
                       "the deferred-commit tick leaves folds in "
                       "flight at the demotion point")
                    + " (ANOMOD_SERVE_TIER_HOT=0)")
            _tier_hot = 0
        self.tier_hot = int(_tier_hot)
        self.tier_demote_after = int(
            app_cfg.serve_tier_demote_after if tier_demote_after is None
            else tier_demote_after)
        if self.tier_demote_after < 1:
            raise ValueError("tier_demote_after must be >= 1 tick")
        self.tier_warm_bytes = int(
            app_cfg.serve_tier_warm_bytes if tier_warm_bytes is None
            else tier_warm_bytes)
        if self.tier_warm_bytes < 0:
            raise ValueError("tier_warm_bytes must be >= 0")
        _tier_cold = (app_cfg.serve_tier_cold_dir if tier_cold_dir is None
                      else tier_cold_dir)
        self.tier_cold_dir = (Path(_tier_cold).expanduser()
                              if _tier_cold else None)
        self.tier_prefetch = int(app_cfg.serve_tier_prefetch
                                 if tier_prefetch is None
                                 else tier_prefetch)
        if not 1 <= self.tier_prefetch <= 256:
            raise ValueError("tier_prefetch must be in [1, 256]")
        self._tier = None
        #: a cold-promoting tenant's drained batches, parked exactly
        #: one tick (the deterministic tier_miss deferral) — flushed
        #: FIRST at the next tick's scoring gate, in park order
        self._tier_parked: Dict[int, list] = {}
        self.tier_wall_s = 0.0
        if self.tier_hot:
            from anomod.serve.tiering import TierPlane
            self._tier = TierPlane(
                self.tier_hot, self.tier_demote_after,
                self.tier_warm_bytes, self.tier_cold_dir,
                self.tier_prefetch,
                slot_nbytes=self.cfg.sw
                * (N_FEATS + self.cfg.n_hist_buckets) * 4)
        _buckets = (buckets if buckets is not None
                    else app_cfg.serve_buckets)
        self._proc_registry = obs.get_registry()
        #: the fleet census observatory (ANOMOD_CENSUS, anomod.obs.
        #: census): every ANOMOD_CENSUS_EVERY-th tick (and always at
        #: run end) the coordinator takes a deterministic resident-
        #: bytes census of every plane (state pools, lane scratch,
        #: admission queues/registries, SLO digests, RCA evidence,
        #: recorder retentions — shapes and container lengths, never
        #: an RSS wall) plus the hot-set/Zipf census, exported as
        #: registry gauges, new ServeReport fields and the flight
        #: journal's ``census`` VARIANT key.  A pure read-side
        #: consumer: every decision is byte-identical with the census
        #: on or off (pinned).
        self.census = bool(app_cfg.census if census is None else census)
        self.census_every = int(app_cfg.census_every
                                if census_every is None else census_every)
        if self.census_every < 1:
            raise ValueError("census_every must be >= 1 tick")
        self._census_tracker = None
        self._census_tick_doc: Optional[dict] = None
        self.census_ticks = 0
        self.census_hot_set: Dict[str, object] = {}
        self.census_resident: Dict[str, object] = {}
        self.census_peak_bytes = 0
        self.census_wall_s = 0.0
        self._census_reconciled = True
        if self.census or self.tier_hot:
            # the tracker also runs under a census-off TIERED engine:
            # its last-served/EWMA bookkeeping is the demotion policy's
            # input (coldest_candidates — the eviction preview promoted
            # to policy); the census DRAIN stays gated on self.census
            from anomod.obs.census import CensusTracker
            self._census_tracker = CensusTracker(
                app_cfg.census_decay_ticks,
                app_cfg.census_coldest_k, self.census_every)
        if self.census:
            # metric handles only when the plane is live (the RCA
            # discipline: a census-off run must not register
            # permanently-zero series)
            self._obs_census = {
                "total": obs.gauge("anomod_census_resident_bytes"),
                "pool": obs.gauge("anomod_census_pool_bytes"),
                "scratch": obs.gauge("anomod_census_scratch_bytes"),
                "admission": obs.gauge("anomod_census_admission_bytes"),
                "slo": obs.gauge("anomod_census_slo_bytes"),
                "rca": obs.gauge("anomod_census_rca_bytes"),
                "recorder": obs.gauge("anomod_census_recorder_bytes"),
                "registered": obs.gauge(
                    "anomod_census_registered_tenants"),
                "resident": obs.gauge("anomod_census_resident_tenants"),
                "hot": obs.gauge("anomod_census_hot_tenants"),
                "occupancy": obs.gauge(
                    "anomod_census_slot_occupancy_fraction"),
            }
            self._obs_census_ticks = obs.counter(
                "anomod_census_ticks_total")
        #: worker execution (ANOMOD_SERVE_WORKER): "thread" (the
        #: default, the byte-parity oracle) keeps shard workers as
        #: threads of this interpreter; "process" moves each shard's
        #: WHOLE scoring plane — detectors, replay states, its
        #: BucketRunner, its metrics registry — into a spawn-context
        #: worker process (anomod.serve.procshard) behind the same
        #: ShardWorker seam, so N shards score on N interpreters
        #: instead of time-slicing one GIL.  Each child executes its
        #: slice through the SAME _score_shard code (a 1-shard
        #: sub-engine over its owned tenants), so states / alerts /
        #: SLO / shed and the canonical flight journal are
        #: byte-identical to the thread engine (pinned).  Planes that
        #: share coordinator memory with the score plane cannot cross
        #: the process boundary — the mesh plane, the multimodal
        #: sidecar, the deferred-commit seam, state tiering's demotion
        #: copier and the census observatory — so process mode
        #: auto-degrades to thread under any of them (an explicit
        #: request is refused): the policy/state idiom.
        _worker = (app_cfg.serve_worker if worker is None
                   else str(worker).strip().lower() or "thread")
        if _worker not in ("thread", "process"):
            raise ValueError(f"unknown serve worker mode {_worker!r} "
                             "(thread|process)")
        if _worker == "process":
            import jax
            backend = jax.default_backend()
            blocker = (
                "a chip belongs to one process: each worker child "
                "imports jax and compiles, and cannot get the "
                f"{backend} device this process holds"
                if backend != "cpu" else
                "the mesh plane manages its own sharded dispatch"
                if mesh is not None else
                "the multimodal sidecar planes share coordinator memory"
                if multimodal else
                "the deferred-commit seam keeps folds in flight inside "
                "one interpreter" if self.async_commit else
                "state tiering's demotion copier reads the pool "
                "in-process" if self.tier_hot else
                "the census walks resident planes in-process"
                if self.census else None)
            if blocker is not None:
                if worker is not None:
                    raise ValueError(
                        "process shard workers own their score plane "
                        "in a separate interpreter; " + blocker +
                        " (ANOMOD_SERVE_WORKER=thread)")
                _worker = "thread"
        self.worker_mode = _worker
        self._worker_start_timeout_s = float(
            app_cfg.serve_worker_start_timeout_s)
        #: per-shard chaos fault fired-counts, retained from the last
        #: barrier reply — a respawned worker process resumes its
        #: faults' repeat budgets where the dead one left them (a
        #: one-shot crash fault must not re-trip on recovery
        #: re-execution just because the crash emptied the child)
        self._chaos_fired: Dict[int, list] = {}
        if self.worker_mode == "process":
            # process workers run the sharded machinery at every count
            # (mirrors + command barriers even at 1 shard), exactly the
            # elastic engines' discipline
            self._use_workers = True
        #: tick-barrier fold discipline (ANOMOD_SERVE_FOLD): per-tick
        #: cross-shard merges (registry counter/gauge deltas, t-digest
        #: centroid sets, leg/verdict records) serialize as
        #: "sparse" touched-key deltas (the default — barrier cost
        #: follows ACTIVE tenants, not registered fleet size) or
        #: "dense" full walks (the payload oracle the sparse win is
        #: measured against), combined through a deterministic binary
        #: fold tree in fixed (shard, seq) order either way.  Scrape
        #: output is pinned byte-identical across the two; only the
        #: payload bytes move (counted in fold_payload_bytes).
        _fold = (app_cfg.serve_fold if fold is None
                 else str(fold).strip().lower() or "sparse")
        if _fold not in ("dense", "sparse"):
            raise ValueError(f"unknown serve fold mode {_fold!r} "
                             "(dense|sparse)")
        self.fold_mode = _fold
        #: structural bytes the tick-barrier registry folds shipped
        #: (anomod.obs.registry.delta_nbytes — deterministic, box-
        #: independent accounting, NOT pickle lengths)
        self.fold_payload_bytes = 0
        self._obs_fold_payload = (
            obs.counter("anomod_serve_fold_payload_bytes_total")
            if self._use_workers else None)
        # tracing is ON by default, gated on the one telemetry switch
        # (ANOMOD_OBS_ENABLED) so "telemetry off" means off end to end;
        # pass an explicit Tracer to force it on regardless.  The runners
        # open their per-dispatch spans on the same tracer.
        if tracer is None and obs.get_registry().enabled:
            from anomod.utils.tracing import Tracer
            tracer = Tracer("anomod-serve")
        self.tracer = tracer
        #: the runner recipe a policy-time scale-up rebuilds from (the
        #: same arguments every initial shard runner got)
        self._runner_kw = dict(lane_buckets=lane_buckets,
                               pipeline=self.pipeline,
                               native_stage=native,
                               state=self.serve_state,
                               tracer=tracer)
        self._buckets_arg = _buckets
        if self._use_workers:
            from anomod.serve.shard import plan_shards
            self.shard_of = plan_shards(self.specs, self.shards,
                                        self.capacity_spans_per_s)
            if self.worker_mode == "process":
                # the runners live IN the worker processes; the
                # coordinator keeps per-shard mirrors serving every
                # runner fact its planes read (flight header buckets,
                # leg walls, policy chunk signals, report stats) from
                # the children's barrier replies.  Registry deltas
                # arrive pre-serialized over the pipe, so there are no
                # coordinator-side shard registries to fold from.
                from anomod.serve.procshard import RunnerMirror
                self._shard_regs = []
                self._runners = [
                    RunnerMirror(self.cfg, _buckets,
                                 lane_buckets=lane_buckets,
                                 native_stage=native,
                                 state=self.serve_state)
                    for _ in range(self.shards)]
            else:
                # each shard owns a full scoring plane: its own runner
                # (own jitted executables + pinned scratch slots)
                # recording into its OWN registry — zero cross-thread
                # contention on the dispatch hot path; the coordinator
                # folds shard registries into the process registry at
                # the tick barrier (obs.Registry.fold_from)
                self._shard_regs = [
                    obs.Registry(enabled=self._proc_registry.enabled)
                    for _ in range(self.shards)]
                owned = [sum(1 for t in self.shard_of.values() if t == s)
                         for s in range(self.shards)]
                # with tiering on, each shard's pool sizes to its share
                # of the HOT capacity, not its registered ownership
                # (demotion returns slots; the pool's doubling growth
                # covers transients between demote steps)
                self._runners = [
                    BucketRunner(self.cfg, _buckets, registry=reg,
                                 pool_slots=max(min(owned[s],
                                                    self.tier_hot)
                                                if self.tier_hot
                                                else owned[s], 1),
                                 **self._runner_kw)
                    for s, reg in enumerate(self._shard_regs)]
            self._fold_state = [dict() for _ in range(self.shards)]
            self.runner = self._runners[0]
        else:
            # the inline engine owns every tenant on shard 0: keep the
            # placement map EMPTY (every read is `.get(tid, 0)`) instead
            # of materializing an O(registered) dict — the tiering PR's
            # O(hot-set) registry contract
            self.shard_of = {}
            self.runner = BucketRunner(self.cfg, _buckets,
                                       lane_buckets=lane_buckets,
                                       pipeline=self.pipeline,
                                       native_stage=native,
                                       state=self.serve_state,
                                       pool_slots=max(
                                           min(len(self.specs),
                                               self.tier_hot)
                                           if self.tier_hot
                                           else len(self.specs), 1),
                                       tracer=tracer)
            self._runners = [self.runner]
        self._workers = None
        #: online RCA (ANOMOD_SERVE_RCA): when a tenant's detector fires
        #: inside a tick, incremental GNN culprit inference runs over
        #: that tenant's live service graph (anomod.serve.rca) on the
        #: shard that OWNS the tenant, verdicts folding at the barrier
        #: in enqueue order — a pure read-side consumer of the alert
        #: stream, so detector states / alerts / admission / SLO / shed
        #: are byte-identical with RCA on or off.
        self.rca = bool(app_cfg.serve_rca if rca is None else rca)
        if self.rca and not self.score:
            raise ValueError("online RCA consumes the detectors' alert "
                             "stream; it needs score=True")
        self.rca_budget = int(app_cfg.serve_rca_budget
                              if rca_budget is None else rca_budget)
        if self.rca_budget < 1:
            raise ValueError("rca_budget must be >= 1 run per tick")
        self._rca_planes: list = []
        self._rca_seen: Dict[int, int] = {}
        self._rca_queue: "collections.deque" = collections.deque()
        self._rca_seq = 0
        self.rca_verdicts: list = []
        self.rca_wall_s = 0.0
        # metric handles only when the plane is live: an RCA-off run
        # must not register permanently-zero RCA series in the scrape
        # journal / exports
        #: the sequence-model plane (anomod.serve.seqplane): every served
        #: span an event token of its tenant's session, scored by a
        #: latent-attention routed-expert decoder; a pure consumer of the
        #: served batches beside the RCA step.  ``seq_model`` is the
        #: configuration file's object or its path (None: no plane).
        self._seq = None
        if seq_model is not None:
            for on, what in ((self._tier is not None, "state tiering"),
                             (_worker == "process", "process workers"),
                             (mesh is not None, "a mesh"),
                             (self._async, "the deferred-commit tick")):
                if on:
                    raise ValueError("the sequence-model plane holds its "
                                     "sessions on this process's one chip "
                                     f"and does not run with {what}")
            from anomod.serve.seqplane import SeqPlane
            self._seq = SeqPlane(
                seq_model, [sp.tenant_id for sp in self.specs],
                self.cfg.n_services, self.cfg.n_hist_buckets,
                self.cfg.window_us, self.t0_us, tracer=tracer)
        self._rca_slo = None
        if self.rca:
            self._rca_slo = _TenantSLO("anomod_serve_rca_seconds")
            self._obs_rca_queued = obs.counter(
                "anomod_serve_rca_queued_total")
            from anomod.serve.rca import OnlineRCA, RcaRunner
            _rca_buckets = (rca_buckets if rca_buckets is not None
                            else app_cfg.serve_rca_buckets)
            _topk = int(app_cfg.serve_rca_topk if rca_topk is None
                        else rca_topk)
            _windows = int(app_cfg.serve_rca_windows
                           if rca_windows is None else rca_windows)
            # one plane per shard (shard-private runner + registry, the
            # BucketRunner discipline); the inline 1-shard plane records
            # into the process registry directly.  Process workers keep
            # ONE coordinator-resident plane regardless of shard count:
            # evidence buffering is documented coordinator-side (rca.py
            # — buffer content is shard-count-invariant there), which is
            # also what lets the evidence survive a worker-process crash
            # exactly as it survives a thread crash.
            _regs = (self._shard_regs
                     if self._use_workers and self.worker_mode == "thread"
                     else [self._proc_registry])
            #: the RCA-plane recipe a policy-time scale-up rebuilds from
            self._rca_kw = dict(buckets=_rca_buckets, topk=_topk,
                                windows=_windows)
            self._rca_planes = [
                OnlineRCA(self.services, self.cfg.window_us, self.t0_us,
                          RcaRunner(_rca_buckets, registry=reg),
                          topk=_topk, windows=_windows)
                for reg in _regs]
        self._det_kw = dict(baseline_windows=baseline_windows,
                            z_threshold=z_threshold,
                            consecutive=consecutive, min_count=min_count)
        # per-tenant detector/replay state, built lazily at first served
        # batch (a fleet of mostly-idle tenants must not pay T dead
        # planes up front)
        self.multimodal = bool(multimodal)
        self.testbed = testbed
        #: pushed log/metric/api events per modality (multimodal mode)
        self.modality_events: Dict[str, int] = {}
        self._tenant_replay: Dict[int, object] = {}
        self._tenant_det: Dict[int, object] = {}
        self._shared_sharded_fn = None
        self._slo: Dict[int, _TenantSLO] = _LazySLO()
        self._credit = 0.0
        #: widest batch ever served — the legitimate overdraw envelope
        #: the per-tick credit clamp must respect (a >budget batch's debt
        #: persists across idle ticks; forgiving it would forge capacity)
        self._max_served_batch = 0
        self.serve_wall_s = 0.0
        #: per-tick serve-wall samples (one float per tick, bounded by
        #: the run's tick count) — what the census sweep's wall slope
        #: reads (obs/census.py); wall clock, never a decision input
        self.tick_walls: List[float] = []
        self.n_spans_served = 0
        # self-scrape plumbing (anomod.obs): cached handles for the tick
        # loop, plus a per-tick registry scrape on the VIRTUAL clock so a
        # seeded run's telemetry timeline is deterministic and exports
        # bin cleanly into detector windows
        self._registry = obs.get_registry()
        self._obs_tick = obs.histogram("anomod_serve_tick_seconds")
        self._obs_ticks = obs.counter("anomod_serve_ticks_total")
        self._obs_tenants = obs.gauge("anomod_serve_active_tenants")
        # one scrape per virtual second (not per tick): ~5 samples per
        # detector window at the default 5 s width — plenty for the
        # self-scrape z statistics — at a fraction of the per-tick cost
        self._scrape_every = max(1, int(round(1.0 / self.clock.tick_s)))
        #: black-box flight recorder (ANOMOD_FLIGHT, anomod.obs.flight):
        #: every tick journals its admission decisions, staged dispatch
        #: plan, alert/RCA digests and (at the ANOMOD_FLIGHT_DIGEST_EVERY
        #: cadence) a crc32 tenant-state digest into a bounded ring — the
        #: deterministic record `anomod audit` replays and bisects
        #: against.  A pure read-side consumer: every decision above is
        #: byte-identical with the recorder on or off.
        self.flight = bool(app_cfg.flight if flight is None else flight)
        self.flight_recorder = None
        self._flight_dump_dir = app_cfg.flight_dump_dir
        self._flight_dumped = False
        if self.flight:
            from anomod.obs.flight import (FlightRecorder, config_snapshot,
                                           versions)
            self.flight_recorder = FlightRecorder(
                {"engine": {
                    "n_tenants": len(self.specs),
                    "n_services": len(self.services),
                    "capacity_spans_per_s": self.capacity_spans_per_s,
                    "tick_s": self.clock.tick_s,
                    "max_backlog": self.max_backlog,
                    "buckets": list(self.runner.buckets),
                    "lane_buckets": list(self.runner.lane_buckets),
                    "shards": self.shards,
                    "pipeline": self.pipeline,
                    "serve_state": self.serve_state,
                    "fused": self._fused,
                    "score": self.score,
                    "rca": self.rca,
                    "native_staging": any(r.native_stage
                                          for r in self._runners),
                    "multimodal": self.multimodal,
                    "policy": (self.policy.mode
                               if self.policy is not None else "off"),
                    "census": self.census,
                    "async_commit": self.async_commit,
                    "tier_hot": self.tier_hot,
                    "drain_engine": self.admission.drain_engine,
                    # worker topology: which execution seam scored the
                    # run (thread|process) and which barrier-fold
                    # discipline shipped its metrics (dense|sparse) —
                    # recorded RESOLVED so `anomod audit replay`
                    # re-executes under the same seams
                    "worker": self.worker_mode,
                    "fold": self.fold_mode,
                 },
                 "config": config_snapshot(),
                 "versions": versions()},
                max_ticks=flight_max_ticks,
                digest_every=flight_digest_every)
            #: the brownout ladder's restore point: level 2 coarsens
            #: the live digest cadence 4x, relaxing back to this
            self._flight_digest_base = self.flight_recorder.digest_every
            self._flight_prev_tot = None
            self._flight_prev_legs = None
            self._flight_alert_seen: Dict[int, int] = {}
            self._flight_alert_total = 0
            self._flight_score_crc = 0
            self._flight_rca_seen = 0
            self._flight_rca_crc = 0
        #: scripted serve-plane fault injection (ANOMOD_SERVE_CHAOS,
        #: anomod.serve.chaos) — off by default; a script string or a
        #: prebuilt ServeChaos aims the paper's fault taxonomy at the
        #: framework itself (worker crashes, score-path exceptions,
        #: stalls, pool-put failures) at deterministic (tick, shard,
        #: phase) points.
        _chaos = app_cfg.serve_chaos if chaos is None else chaos
        if isinstance(_chaos, str):
            if _chaos.strip():
                from anomod.serve.chaos import ServeChaos
                _chaos = ServeChaos(_chaos)
            else:
                _chaos = None
        self._chaos = _chaos
        if self._chaos is not None:
            # a fault aimed at a shard this engine doesn't have can
            # never inject — WARN loud (the never-a-silent-no-op
            # contract), but do not refuse: `anomod audit replay
            # --shards 1` deliberately re-executes a 2-shard chaos
            # journal at 1 shard, where the extra faults are inert and
            # the canonical journal still matches (the no-score-gap
            # contract makes every leg equal fault-free).  The CLI's
            # `anomod serve --chaos` validates the range HARD — a typo
            # there is a user error, not a forensic override.
            reachable = (self.policy.max_shards
                         if self.policy is not None else self.shards)
            bad = sorted({f.shard for f in self._chaos.faults
                          if f.kind != "surge" and f.shard >= reachable})
            if bad:
                import warnings
                warnings.warn(
                    f"chaos script targets shard(s) {bad} but the "
                    f"engine has {reachable} shard(s) (ids 0.."
                    f"{reachable - 1}); those faults will never "
                    "fire", RuntimeWarning, stacklevel=2)
        #: shard supervision (ANOMOD_SERVE_CKPT_EVERY > 0, the default;
        #: anomod.serve.supervise): cadenced tenant-state checkpoints
        #: through the get_state/pool-gather seam + a served-batch
        #: recovery log make any mid-tick shard failure recoverable
        #: with NO score gap — restore, re-execute, byte-identical to
        #: fault-free.  Snapshots are pure reads: a chaos-off
        #: supervised run's decisions are byte-identical to the
        #: unsupervised engine (pinned).  The mesh and multimodal
        #: planes keep state outside the snapshot seams, so supervision
        #: auto-disables there (and an explicit request is refused).
        self.ckpt_every = int(app_cfg.serve_ckpt_every
                              if ckpt_every is None else ckpt_every)
        if self.ckpt_every < 0:
            raise ValueError("ckpt_every must be >= 0 (0 = supervision "
                             "off)")
        if (mesh is not None or self.multimodal) and self.ckpt_every:
            if ckpt_every is not None:
                raise ValueError(
                    "shard supervision cannot checkpoint the "
                    + ("mesh plane's sharded" if mesh is not None
                       else "multimodal sidecar") +
                    " state; run with ckpt_every=0 "
                    "(ANOMOD_SERVE_CKPT_EVERY=0)")
            self.ckpt_every = 0
        self.retries = int(app_cfg.serve_retries if retries is None
                           else retries)
        if self.retries < 1:
            raise ValueError("retries must be >= 1")
        self.retry_backoff_s = float(app_cfg.serve_retry_backoff_s
                                     if retry_backoff_s is None
                                     else retry_backoff_s)
        if self.retry_backoff_s < 0:
            raise ValueError("retry_backoff_s must be >= 0")
        self.max_respawns = int(app_cfg.serve_max_respawns
                                if max_respawns is None else max_respawns)
        if self.max_respawns < 0:
            raise ValueError("max_respawns must be >= 0")
        self._supervisor = None
        if self.ckpt_every:
            from anomod.serve.supervise import ShardSupervisor
            self._supervisor = ShardSupervisor(
                self, ckpt_every=self.ckpt_every, retries=self.retries,
                backoff_s=self.retry_backoff_s,
                max_respawns=self.max_respawns)
        self._last_failures = None

    # -- per-tenant plane construction ------------------------------------

    def _replay_for(self, tenant_id: int):
        got = self._tenant_replay.get(tenant_id)
        if got is None:
            if self.mesh is not None:
                from anomod.parallel.stream import ShardedStreamReplay
                got = ShardedStreamReplay(self.cfg, self.t0_us, self.mesh)
                # every tenant's plane runs the IDENTICAL sharded scan;
                # sharing the first plane's jitted fn object gives the
                # fleet one compile instead of T (a fresh closure per
                # tenant would never hit jax's compile cache, and the
                # T-1 redundant compiles would land inside the measured
                # serving wall)
                if self._shared_sharded_fn is None:
                    self._shared_sharded_fn = got._fn
                else:
                    got._fn = self._shared_sharded_fn
            else:
                runner = self._runners[self.shard_of.get(tenant_id, 0)]
                # first service maps the tenant to its shard's pool slot
                # (device mode); the host seam keeps per-tenant pytrees
                cls = (PooledStreamReplay if runner.pool is not None
                       else BucketedStreamReplay)
                got = cls(self.cfg, self.t0_us, runner)
            self._tenant_replay[tenant_id] = got
        return got

    def _detector_for(self, tenant_id: int):
        got = self._tenant_det.get(tenant_id)
        if got is None:
            if self.multimodal:
                from anomod.stream import MultimodalDetector
                got = MultimodalDetector(self.services, self.cfg,
                                         self.t0_us, testbed=self.testbed,
                                         replay=self._replay_for(tenant_id),
                                         **self._det_kw)
            else:
                from anomod.stream import OnlineDetector
                got = OnlineDetector(self.services, self.cfg, self.t0_us,
                                     replay=self._replay_for(tenant_id),
                                     **self._det_kw)
            self._tenant_det[tenant_id] = got
        return got

    # -- modality sidecar (multimodal mode) -------------------------------

    def offer_modality(self, tenant_id: int, kind: str, batch) -> None:
        """Admit a log/metric/api micro-batch for a tenant.

        Modality planes are per-window host aggregates a fraction the
        span volume — control-plane data.  They bypass the weighted-fair
        span queue and push straight into the tenant's MultimodalDetector
        host planes: a window only CLOSES when a later span is pushed, and
        queued spans can only delay that, so a modality batch admitted at
        arrival is always in place before its window scores.
        """
        if not (self.multimodal and self.score):
            raise ValueError("offer_modality needs multimodal=True and "
                             "score=True")
        det = self._detector_for(tenant_id)
        if kind == "logs":
            n = batch.n_lines
            det.push_logs(batch)
        elif kind == "metrics":
            n = batch.n_samples
            det.push_metrics(batch)
        elif kind == "api":
            n = batch.n_records
            det.push_api(batch)
        else:
            raise ValueError(f"unknown modality kind {kind!r}")
        self.modality_events[kind] = self.modality_events.get(kind, 0) + n

    # -- the state-tiering planes (anomod.serve.tiering) ------------------

    def _tier_gate(self, served: List[QueuedBatch]) -> List[QueuedBatch]:
        """The promotion gate between drain and scoring (synchronous
        tick path only — tiering refuses the deferred-commit engine).
        Returns the list that actually scores this tick: last tick's
        parked batches FIRST in park order (their tenants' prefetches
        join here — the one-tick deferral ending), then this tick's
        drained batches, minus any batch whose tenant is still cold
        (parked + prefetch issued + ONE counted `tier_miss` per
        tenant-tick).  Warm tenants promote synchronously in place."""
        tier = self._tier
        score_list: List[QueuedBatch] = []
        if self._tier_parked:
            parked, self._tier_parked = self._tier_parked, {}
            for tid, batches in parked.items():
                # a supervised restore may have re-installed the tenant
                # from a checkpoint (no longer tiered): its batches
                # still score, the promotion is simply a no-op
                if tid in tier:
                    self._tier_promote(tid, deferred=True)
                score_list.extend(batches)
        fresh = self._tier_parked
        for qb in served:
            tid = qb.tenant_id
            if tid in fresh:
                fresh[tid].append(qb)
            elif tid not in tier:
                score_list.append(qb)
            elif tier.status(tid) == "warm":
                self._tier_promote(tid, deferred=False)
                score_list.append(qb)
            else:
                tier.prefetch(tid)
                fresh[tid] = [qb]
        for tid, batches in fresh.items():
            tier.miss(self.clock.ticks, tid, len(batches),
                      sum(qb.n_spans for qb in batches))
        return score_list

    def _tier_promote(self, tid: int, deferred: bool) -> None:
        """Re-admit one demoted tenant through the official seams: take
        its snapshot from the tier (joining the prefetch future for a
        cold entry), rebuild the pool-resident replay via the
        always-copy restore, and repoint the RETAINED detector at the
        new plane — the ``_move_tenant`` discipline, so re-admission
        cannot shift a scored byte."""
        from anomod.serve.supervise import restore_replay
        snap, det = self._tier.take(self.clock.ticks, tid, deferred)
        rep = self._replay_for(tid)
        restore_replay(rep, snap)
        if det is not None:
            det.replay = rep
            self._tenant_det[tid] = det

    def _tier_demote_step(self) -> None:
        """Decay-driven eviction at tick end: while more than
        ``tier_hot`` tenants are pool-resident, demote the coldest
        residents past ``tier_demote_after`` idle ticks — the census
        ``coldest_candidates`` ordering, the PR-15 eviction preview
        promoted from observed-only to policy.  Tenants with queued
        backlog or parked batches are skipped (a demote would promote
        right back next tick — thrash), so every input is coordinator
        state and the demotion schedule is a pure function of
        seed+config."""
        resident = self._tenant_replay
        n_over = len(resident) - self.tier_hot
        if n_over <= 0:
            return
        from anomod.serve.supervise import snapshot_replay
        tracker = self._census_tracker
        t_idx = self.clock.ticks
        for tid in tracker.coldest_candidates(t_idx, resident):
            idle = t_idx - tracker.last_served[tid]
            if idle < self.tier_demote_after:
                break                  # coldest-first: the rest is hotter
            if (self.admission.tenant_backlog(tid)
                    or tid in self._tier_parked):
                continue
            rep = resident.pop(tid)
            snap = snapshot_replay(rep)
            if hasattr(rep, "release"):
                rep.release()          # hand the pool slot back
            det = self._tenant_det.pop(tid, None)
            self._tier.demote(t_idx, tid, snap, det, idle)
            n_over -= 1
            if n_over <= 0:
                return

    # -- the tick loop ----------------------------------------------------

    def _span(self, name: str, **tags):
        return span_of(self.tracer, name, **tags)

    def tick(self, arrivals, modality_arrivals=()) -> List[QueuedBatch]:
        """One virtual tick: admit this tick's arrivals (modality
        sidecar batches first — their windows must be populated before
        any span push can close them), drain up to the tick's capacity
        budget in weighted-fair order, score every drained batch,
        advance the clock.  Returns the served batches.

        Under ANOMOD_SERVE_ASYNC_COMMIT the second half of the tick
        runs the deferred-commit seam instead (``_tick_async_tail``):
        scoring dispatches are issued but not drained, and the
        PREVIOUS tick commits at this tick's barrier — same decisions,
        overlapped walls."""
        with self._span("serve.tick", tick=self.clock.ticks,
                        offers=len(arrivals)):
            return self._tick(arrivals, modality_arrivals)

    def _tick(self, arrivals, modality_arrivals) -> List[QueuedBatch]:
        t_wall = time.perf_counter()
        now = self.clock.now_s + self.clock.tick_s   # decisions at tick end
        if self._chaos is not None:
            # scripted load surge (the chaos 'surge' kind): a pure
            # function of the tick index, so the amplified arrival
            # stream — and everything downstream of it — is identical
            # on every rerun/replay of the same script, at every shard
            # count, with the elastic policy on or off
            factor = self._chaos.surge_factor(self.clock.ticks)
            if factor > 1:
                arrivals = [(tid, concat_span_batches([spans] * factor))
                            for tid, spans in arrivals]
        if modality_arrivals:
            with self._span("serve.modality"):
                for tenant_id, kind, batch in modality_arrivals:
                    self.offer_modality(tenant_id, kind, batch)
        with self._span("serve.admit", offers=len(arrivals)):
            for tenant_id, spans in arrivals:
                # one shared service table per engine: a batch whose ids
                # mean different services would silently corrupt the
                # shared plane rows
                if spans.n_spans and spans.services != self.services:
                    raise ValueError(
                        f"tenant {tenant_id} batch carries a different "
                        "service table than the engine's")
                self.admission.offer(tenant_id, spans, now)
        # capacity credit: unused budget does not bank across idle ticks
        # beyond one tick's worth (no unbounded burst debt)
        budget = self.capacity_spans_per_s * self.clock.tick_s
        self._credit = min(self._credit, 0.0) + budget
        with self._span("serve.drain"):
            served = self.admission.drain(self._credit)
        for qb in served:
            self._credit -= qb.n_spans
        # credit clamp: the residual is physically bounded — at most one
        # tick's unused budget (positive), at most one batch's overdraw
        # (negative) — so anything outside that envelope can only be
        # accumulated float rounding (budget = capacity * tick_s is
        # inexact for most tick widths).  Clamp it, and snap sub-span
        # dust to zero, so a billion-tick run cannot drift phantom
        # capacity or phantom debt into the schedule.  The negative
        # bound uses the widest batch EVER served, not this tick's: a
        # >budget batch's legitimate debt is paid down across several
        # idle ticks, and a floor derived from the (empty) current tick
        # would forgive it mid-repayment.
        for qb in served:
            if qb.n_spans > self._max_served_batch:
                self._max_served_batch = qb.n_spans
        self._credit = min(
            max(self._credit, -max(budget, float(self._max_served_batch))),
            budget)
        if -1e-9 < self._credit < 1e-9:
            self._credit = 0.0
        if self._async:
            # deferred-commit mode: everything above (admission, drain,
            # shed, credit) already ran OVERLAPPED with the previous
            # tick's in-flight XLA work; the tail issues this tick's
            # dispatches and defers their commit to the next barrier
            return self._tick_async_tail(t_wall, now, served)
        # the state-tiering gate (ANOMOD_SERVE_TIER_HOT): any drained
        # tenant the decay plane demoted must be pool-resident before
        # its batches score.  Warm entries re-admit synchronously (a
        # host memcpy through the PR-10 restore seam); cold entries'
        # batches PARK for exactly one tick while the disk fetch runs
        # on the prefetch lane (issued here, joined by the NEXT tick's
        # gate) — a counted, journaled `tier_miss`, never a blocking
        # read in the hot loop.  Only the SCORING list is re-shaped:
        # `served` keeps feeding every admission-time consumer below
        # (SLO, RCA evidence, census, flight, policy), and
        # parked batches score ahead of the next tick's drain in park
        # order, so per-tenant push order — and therefore every final
        # state/alert byte — matches the never-evicted run.
        if self._tier is not None:
            t0 = time.perf_counter()
            with self._span("serve.tier"):
                score_list = self._tier_gate(served)
            self.tier_wall_s += time.perf_counter() - t0
        else:
            score_list = served
        if score_list:
            sup = self._supervisor
            if sup is not None:
                # the recovery log must hold this tick's slices BEFORE
                # scoring: a mid-tick shard failure re-executes them.
                # The log holds what SCORES (score_list), not what
                # drained: a parked batch logs at the tick it actually
                # folds, which is the tick a restore must re-execute.
                sup.begin_tick(score_list)
            self._last_failures = None
            try:
                if self._use_workers:
                    with self._span("serve.score_sharded"):
                        self._score_sharded(score_list)
                elif self._fused:
                    with self._span("serve.score_fused"):
                        self._score_fused(score_list)
                else:
                    # ONE unfused definition (chaos injection ordering
                    # included): _score_shard's unfused branch — the
                    # same unification _score_fused got, so original
                    # execution and recovery re-execution can never
                    # inject or score differently
                    self._score_shard(0, score_list)
            except BaseException as e:
                failures = self._last_failures or [(0, e)]
                self._last_failures = None
                if sup is None or not isinstance(e, Exception):
                    # KeyboardInterrupt / SystemExit are the OPERATOR
                    # stopping the run, not a shard fault — recovery
                    # must never absorb them (re-executing ticks after
                    # a Ctrl-C would make the process uninterruptible)
                    raise
                # supervised recovery: respawn + checkpoint restore +
                # deterministic re-execution — the tick completes as if
                # the fault never happened (or degrades loudly:
                # quarantine / migration / propagation)
                with self._span("serve.recover"):
                    sup.recover(failures)
        if self._supervisor is not None:
            self._supervisor.end_tick()
        # per-batch SLO accounting is DEFERRED past scoring in both paths
        # (the latency samples depend only on admission times and the
        # tick clock, so fused and unfused runs record identical values
        # in identical per-tenant order)
        with self._span("serve.slo"):
            self._slo_record(now, served)
        if self.rca:
            self._rca_step(now, served)
        if self._seq is not None:
            self._seq.step(served)
        with self._span("serve.recorders"):
            if self._census_tracker is not None:
                # hot-set bookkeeping every tick (O(served)); the full
                # resident-bytes census drains on its cadence, INSIDE
                # the measured wall.  The census wall accumulates
                # separately so the overhead is priced IN-RUN
                # (census_wall_s / serve_wall_s — the ckpt_wall idiom:
                # exact, immune to A/B leg noise).
                t0 = time.perf_counter()
                self._census_tracker.observe(self.clock.ticks, served)
                self._census_tick_doc = (
                    self._census_drain()
                    if self.census
                    and self._census_tracker.due(self.clock.ticks)
                    else None)
                if self.census:
                    self.census_wall_s += time.perf_counter() - t0
                else:
                    # the tracker is alive only to feed the tiering
                    # decay plane (coldest_candidates): its bookkeeping
                    # wall is tiering overhead, never a census price
                    self.tier_wall_s += time.perf_counter() - t0
            if self.flight_recorder is not None:
                # the journal entry rides INSIDE the measured wall (the
                # serve_wall_s accumulation below): the recorder's cost
                # is priced, never hidden
                self._flight_tick(now, served,
                                  time.perf_counter() - t_wall)
        if self.policy is not None:
            # the elastic-policy step runs AFTER this tick's journal
            # record (a scale-down must not remove a runner whose
            # tick-t dispatch deltas have not been journaled yet); its
            # events ride the NEXT record's `scaling` variant key, and
            # its wall lands inside the measured tick wall: scaling is
            # priced, never hidden
            t0 = time.perf_counter()
            with self._span("serve.policy"):
                self._policy_step(served)
            self.policy_wall_s += time.perf_counter() - t0
        if self._tier is not None:
            # decay-driven demotion rides the tick END — after this
            # tick's journal record (a demoted tenant's tick-t deltas
            # are already journaled; its demote event rides the NEXT
            # record's `tiering` variant key, the scaling-key idiom)
            # and after the policy step (a migration decision saw the
            # live residency map)
            t0 = time.perf_counter()
            with self._span("serve.tier_demote"):
                self._tier_demote_step()
            self.tier_wall_s += time.perf_counter() - t0
        self.clock.advance()
        # telemetry work stays INSIDE the measured wall: the scrape is
        # priced, not hidden
        with self._span("serve.scrape"):
            self._obs_tick.observe(time.perf_counter() - t_wall)
            self._obs_ticks.inc()
            self._obs_tenants.set(len(self._tenant_det)
                                  or len(self._tenant_replay))
            self.admission.observe_depths()
            if self.clock.ticks % self._scrape_every == 0:
                self._registry.scrape(now_s=now)
        t_tick = time.perf_counter() - t_wall
        self.serve_wall_s += t_tick
        self.tick_walls.append(t_tick)
        return served

    # -- the deferred-commit seam (ANOMOD_SERVE_ASYNC_COMMIT) -------------

    def _tick_async_tail(self, t_wall: float, now: float,
                         served: List[QueuedBatch]) -> List[QueuedBatch]:
        """The deferred-commit second half of one tick.

        Order of operations, and why each placement preserves byte
        parity with the synchronous tick:

        1. SLO accounting moves AHEAD of scoring: the latency samples
           are pure functions of admission times and the tick clock
           (never of scoring results), recorded in the same served
           order — identical values, identical per-tenant sample
           sequence.
        2. THE COMMIT BARRIER (``_commit_deferred``): the PREVIOUS
           tick's in-flight XLA work has been executing under this
           tick's admission/drain/shed/SLO coordinator phases; its
           results are about to be read (folds feed this tick's
           staging), so it commits now, then runs the deferred tick's
           tail (RCA, census drain, flight record, policy)
           against snapshotted inputs.
        3. ISSUE: this tick's fused dispatches stage + submit but do
           NOT drain (``defer=True``); the XLA executes stay in
           flight until the next tick's barrier.  The unfused path
           has no issue/commit seam to split (pushes are synchronous
           host work), so it scores in place and only the tick tail
           defers.
        4. The deferred context snapshots every input the commit tail
           will need — admission totals, backlog, the tick index —
           so the next tick's admission cannot leak into this tick's
           journal or policy view.

        Stage/dispatch-phase faults surface at ISSUE time exactly as
        in the synchronous engine; fold/score/commit-phase faults
        surface one tick later at the barrier, keyed (and recovered)
        at their ORIGIN tick, so chaos scripts and the recovery
        ledger stay deterministic.  Checkpoint ticks force a
        synchronous commit: the supervisor's snapshot must cover this
        tick's folds, or a restore would lose them.
        """
        with self._span("serve.slo"):
            self._slo_record(now, served)
        self._commit_deferred()
        pending = None
        sup = self._supervisor
        if served:
            if sup is not None:
                # the recovery log must hold this tick's slices BEFORE
                # issue: a barrier-time shard failure re-executes them
                sup.begin_tick(served)
            self._last_failures = None
            try:
                if self._fused:
                    pending = self._dispatch_tick(served)
                elif self._use_workers:
                    with self._span("serve.score_sharded"):
                        self._score_sharded(served)
                else:
                    self._score_shard(0, served)
            except BaseException as e:
                failures = self._last_failures or [(0, e)]
                self._last_failures = None
                if sup is None or not isinstance(e, Exception):
                    # operator interrupts are not shard faults — the
                    # synchronous tick's rule, unchanged
                    raise
                with self._span("serve.recover"):
                    sup.recover(failures)
                # recovery re-executed the tick synchronously (restore
                # + full _score_shard replay): it is already committed
                pending = None
        tot = self.admission.totals()
        t_issue = time.perf_counter()
        self._deferred = {
            "tick": self.clock.ticks,
            "now": now,
            "served": served,
            "pending": pending,
            "tot": tot,
            "backlog": self.admission.backlog_spans,
            "t_issue": t_issue,
            "coord_wall": t_issue - t_wall,
        }
        self.async_ticks += 1
        if sup is not None \
                and (self.clock.ticks + 1) % self.ckpt_every == 0:
            # end_tick() checkpoints on this cadence — force the
            # commit so the snapshot covers this tick's folds (the one
            # tick per ckpt_every that pays the synchronous wait)
            self._commit_deferred()
        if sup is not None:
            sup.end_tick()
        self.clock.advance()
        with self._span("serve.scrape"):
            self._obs_tick.observe(time.perf_counter() - t_wall)
            self._obs_ticks.inc()
            self._obs_tenants.set(len(self._tenant_det)
                                  or len(self._tenant_replay))
            self.admission.observe_depths()
            if self.clock.ticks % self._scrape_every == 0:
                self._registry.scrape(now_s=now)
        t_tick = time.perf_counter() - t_wall
        self.serve_wall_s += t_tick
        self.tick_walls.append(t_tick)
        return served

    def _commit_deferred(self) -> None:
        """The deferred tick's COMMIT BARRIER (no-op when nothing is
        deferred): drain the in-flight fold/score/commit phases, then
        run the deferred tick's tail — RCA, census drain, flight
        record, elastic policy — against the exact state, and the
        exact snapshotted inputs, the synchronous engine used at that
        tick.  The tail order mirrors the synchronous tick body
        (RCA → census → flight → policy) line for line.  Chaos
        hooks key on the ORIGIN tick, so scripted fold/score/commit
        faults fire — and recover, via the supervisor's origin-keyed
        retry ledger — exactly as scripted even though they surface
        one tick later.  The policy executing here (not at issue)
        keeps the sync ordering guarantee: a scale-down can never
        remove a runner with un-journaled or in-flight work."""
        d = self._deferred
        if d is None:
            return
        self._deferred = None
        t_barrier = time.perf_counter()
        pending = d["pending"]
        if pending is not None and any(pending):
            # the hidden-wait leg: how long the dispatches were left
            # executing under coordinator work before this barrier
            # first read them
            self.commit_defer_wall_s += max(0.0,
                                            t_barrier - d["t_issue"])
            sup = self._supervisor
            self._last_failures = None
            try:
                if self._use_workers:
                    self._join_commits(pending, d["tick"])
                else:
                    self._commit_shard(0, pending[0], d["tick"])
            except BaseException as e:
                failures = self._last_failures or [(0, e)]
                self._last_failures = None
                if sup is None or not isinstance(e, Exception):
                    raise
                with self._span("serve.recover"):
                    sup.recover(failures, origin_tick=d["tick"])
        now, served = d["now"], d["served"]
        if self.rca:
            self._rca_step(now, served)
        with self._span("serve.recorders"):
            if self._census_tracker is not None:
                t0 = time.perf_counter()
                self._census_tracker.observe(d["tick"], served)
                self._census_tick_doc = (
                    self._census_drain(t_idx=d["tick"])
                    if self._census_tracker.due(d["tick"]) else None)
                self.census_wall_s += time.perf_counter() - t0
            if self.flight_recorder is not None:
                self._flight_tick(now, served,
                                  d["coord_wall"]
                                  + (time.perf_counter() - t_barrier),
                                  t_idx=d["tick"], tot=d["tot"])
        if self.policy is not None:
            t0 = time.perf_counter()
            with self._span("serve.policy"):
                self._policy_step(served, tick=d["tick"],
                                  backlog_spans=d["backlog"],
                                  shed_spans=d["tot"].shed_spans)
            self.policy_wall_s += time.perf_counter() - t0

    def _dispatch_tick(self, served: List[QueuedBatch]) -> list:
        """The ISSUE half of one fused tick: stage + submit every
        shard's lane dispatches and return the per-shard pending work
        lists WITHOUT draining — the XLA executes stay in flight until
        the next barrier first reads them.  The sharded path keeps the
        ``_submit_parts`` discipline (per-shard worker threads, shard
        registries folded at the join, first failure re-raised with
        the full failure list parked for the supervisor)."""
        origin = self.clock.ticks
        if not self._use_workers:
            with self._span("serve.issue_tick"):
                return [self._dispatch_shard(0, served, origin)]
        from functools import partial
        parts: List[List[QueuedBatch]] = [[] for _ in range(self.shards)]
        for qb in served:
            parts[self.shard_of[qb.tenant_id]].append(qb)
        self._ensure_workers()
        pending: list = [None] * self.shards

        def _issue(s: int, part: List[QueuedBatch]) -> None:
            pending[s] = self._dispatch_shard(s, part, origin)

        with self._span("serve.issue_tick"):
            submitted = []
            for s, worker in enumerate(self._workers):
                if parts[s]:
                    worker.submit(partial(_issue, s, parts[s]))
                    submitted.append((s, worker))
            failures = []
            for s, worker in submitted:
                try:
                    worker.join()
                except BaseException as e:
                    failures.append((s, e))
        self._fold_shard_registries()
        if failures:
            self._last_failures = failures
            raise failures[0][1]
        return pending

    def _dispatch_shard(self, shard_id: int, served: List[QueuedBatch],
                        origin_tick: Optional[int] = None) -> list:
        """One shard's stage + submit (phases 1-2 of fused scoring)
        with the drain DEFERRED; returns the pending work list
        ``_commit_shard`` completes at the barrier.  Chaos phases
        ``stage`` and ``dispatch`` fire here, at issue time, exactly
        as in the synchronous ``_score_shard``."""
        runner = self._runners[shard_id]
        chaos = self._chaos
        if chaos is not None:
            tick = (self.clock.ticks if origin_tick is None
                    else origin_tick)
            hook = lambda phase: chaos.hit(phase, tick, shard_id)  # noqa: E731
        else:
            hook = None
        if hook is not None:
            hook("stage")
        with self._span("serve.dispatch_shard", shard=shard_id,
                        pipeline=self.pipeline):
            pending = self._stage_pending(served)
            self._dispatch_rounds(pending, runner, chaos_hook=hook,
                                  defer=True)
        return pending

    def _commit_shard(self, shard_id: int, pending: list,
                      origin_tick: int) -> None:
        """One shard's barrier-time completion: drain the deferred
        dispatches (the fold wait the seam hides), then phase 3
        (window scoring).  Chaos phases ``fold`` / ``score`` /
        ``commit`` fire here keyed on the ORIGIN tick — the same
        injection points, tick keys and ordering the synchronous
        ``_score_shard`` gives them."""
        runner = self._runners[shard_id]
        chaos = self._chaos
        if chaos is not None:
            hook = lambda phase: chaos.hit(phase, origin_tick, shard_id)  # noqa: E731
        else:
            hook = None
        try:
            with self._span("serve.commit_shard", shard=shard_id):
                runner.drain_lanes()
        except BaseException:
            # the abort discipline (_dispatch_rounds): a failed commit
            # must not park issued dispatches for a later drain to
            # fold as stale deltas
            runner.abort_lanes()
            raise
        if hook is not None:
            hook("fold")
        self._commit_pending(pending, runner, chaos_hook=hook)
        if hook is not None:
            hook("commit")

    def _join_commits(self, pending: list, origin_tick: int) -> None:
        """Barrier-time sharded commit: each shard with deferred work
        commits on its own worker (the ``_submit_parts`` discipline —
        join all, fold shard registries, park the failure list and
        re-raise the first)."""
        from functools import partial
        self._ensure_workers()
        submitted = []
        for s, worker in enumerate(self._workers):
            if s < len(pending) and pending[s]:
                worker.submit(partial(self._commit_shard, s,
                                      pending[s], origin_tick))
                submitted.append((s, worker))
        failures = []
        for s, worker in submitted:
            try:
                worker.join()
            except BaseException as e:
                failures.append((s, e))
        self._fold_shard_registries()
        if failures:
            self._last_failures = failures
            raise failures[0][1]

    def _slo_record(self, now: float, served: List[QueuedBatch]) -> None:
        """Per-batch SLO accounting of one tick's served batches."""
        for qb in served:
            self._slo[qb.tenant_id].record(now - qb.enqueued_s)
            self.n_spans_served += qb.n_spans

    def _rca_step(self, now: float, served: List[QueuedBatch]) -> None:
        """One tick's RCA pass: evidence buffering on the COORDINATOR
        (shard-count-invariant content), then the alert→culprit pass;
        both inside the measured tick wall — RCA rides the serve SLO.
        Pruning floors at each tenant's OLDEST queued alert window, so
        a budget-delayed run still finds its full evidence window in
        the buffer (the determinism contract's "delayed run scores the
        same evidence" clause).  THIS tick's new alerts enqueue BEFORE
        the floor is computed: an alert fired across a traffic gap
        longer than the evidence window would otherwise have its
        pre-gap evidence pruned by the same tick's buffering, before
        its run sees it (the enqueue is _rca_seen-guarded, so
        _rca_tick's own enqueue pass below stays a no-op for these).
        Brownout level >= 1 (the elastic policy's degradation ladder)
        tightens the per-tick RCA budget to one run — the item set and
        verdict CONTENT are budget-invariant (the PR-6 pin); only the
        virtual scoring tick moves."""
        self._rca_enqueue(now)
        floor: Dict[int, int] = {}
        for _, tid, w, _ in self._rca_queue:
            floor[tid] = min(floor.get(tid, w), w)
        for qb in served:
            plane = self._rca_planes[
                self.shard_of.get(qb.tenant_id, 0)
                if len(self._rca_planes) > 1 else 0]
            plane.buffer(qb.tenant_id, qb.spans,
                         keep_window=floor.get(qb.tenant_id))
        self._rca_tick(now, budget=(
            1 if self.policy is not None
            and self.policy.brownout_level >= 1 else None))

    def _score_fused(self, served: List[QueuedBatch]) -> None:
        """Tenant-fused scoring of one tick's drained batches.

        Three phases, each pinned bit-identical to the sequential path:

        1. COALESCE (host): same-tenant batches drained this tick
           concatenate in arrival order into ONE staging per tenant —
           one roll, one split plan, one edge pass instead of per batch.
        2. STACK + DISPATCH: per chunk ROUND (a tenant's own chunks must
           apply in order), same-width staged chunks across tenants run
           as lane-stacked fused dispatches (``runner.run_lanes``), lane
           counts padded to the fixed lane-bucket set.  Tenant states
           gather/scatter through the StreamReplay ``get_state`` /
           ``set_state`` seam; dead pad lanes pass through untouched.
        3. COMMIT (host): per tenant, the detector's post-replay half
           (``note_pushed``) scores newly closed windows exactly as a
           sequential push of the coalesced batch would.

        One definition with the sharded path: this IS ``_score_shard``
        on shard 0 (same phases, same chaos injection points), so the
        inline and sharded engines can never drift apart.
        """
        self._score_shard(0, served)

    def _dispatch_rounds(self, pending: list, runner,
                         chaos_hook=None, defer: bool = False) -> None:
        """Phase 2 of fused scoring (STACK + DISPATCH), shared by the
        inline and sharded paths: per chunk round, same-width staged
        chunks lane-stack into fused dispatches through the runner's
        submit/drain path.  At pipeline depth 1 every dispatch retires
        immediately after issue (the exact synchronous fold order);
        depth > 1 stages round r+1's scratch while round r's XLA
        dispatch is still in flight, folding deltas in dispatch order at
        retire (bit-identical at any depth), drained before window
        scoring.  With the device state pool the retire fold is an
        on-device scatter-add — the replay planes ride the submit path
        at EVERY depth so per-tenant host states never materialize in
        the hot loop."""
        try:
            rnd = 0
            while True:
                groups: Dict[int, List[int]] = {}
                for i, (_, _, _, _, plan) in enumerate(pending):
                    if rnd < len(plan):
                        groups.setdefault(plan[rnd][0], []).append(i)
                if not groups:
                    break
                for width in sorted(groups):
                    runner.submit_lanes(
                        width, [(pending[i][1], pending[i][4][rnd][1])
                                for i in groups[width]])
                rnd += 1
            if chaos_hook is not None:
                # the DISPATCH injection point: submits issued, up to
                # pipeline-1 dispatches in flight — a fault here
                # exercises the abort path below with live in-flight
                # work, the nastiest partial-tick state
                chaos_hook("dispatch")
            if not defer:
                runner.drain_lanes()     # tick-end barrier: folds land
            # defer=True (the async-commit issue path) leaves the
            # in-flight dispatches for _commit_shard's barrier drain;
            # the abort discipline below still owns the failure path
        except BaseException:
            # a failed tick must not park its issued dispatches in the
            # runner: a LATER tick's drain would fold the aborted
            # tick's stale deltas into tenant states with no error
            runner.abort_lanes()
            raise

    def _stage_pending(self, served: List[QueuedBatch]) -> list:
        """Phase 1 of fused scoring (COALESCE + plan), shared by the
        inline and sharded paths: same-tenant batches concatenate in
        arrival order into one staging; returns the ordered
        ``(det, replay, n_spans, w_ret, plan)`` work list."""
        with self._span("serve.stage", batches=len(served)):
            per_tenant: Dict[int, List[QueuedBatch]] = {}
            for qb in served:
                per_tenant.setdefault(qb.tenant_id, []).append(qb)
            pending = []
            for tid, qbs in per_tenant.items():
                batch = qbs[0].spans if len(qbs) == 1 else \
                    concat_span_batches([qb.spans for qb in qbs])
                if self.score:
                    det = self._detector_for(tid)
                    replay = det.replay
                else:
                    det = None
                    replay = self._replay_for(tid)
                t0 = time.perf_counter()
                rb = det.replay_batch(batch) if det is not None else batch
                w_ret, plan = replay.plan_push(rb)
                if det is not None:
                    det.push_wall_s += time.perf_counter() - t0
                pending.append((det, replay, batch.n_spans, w_ret, plan))
        return pending

    def _commit_pending(self, pending: list, runner,
                        chaos_hook=None) -> None:
        """Phase 3 of fused scoring (COMMIT), shared by the inline and
        sharded paths: per tenant, the detector's post-replay half
        scores newly closed windows exactly as a sequential push would —
        with every batch-scorable tenant's window scoring VECTORIZED
        into one pass per closed window
        (anomod.stream.score_closed_windows_batched: the sequential
        scorer's own z core with a leading tenant axis, byte-identical
        alerts/streaks/CUSUM — pinned), fed by one fused device-pool
        gather that materializes only the scored columns.  Modality and
        edge-attributing detectors keep the per-tenant sequential path.
        The wall lands in the ``score`` leg of the serve
        decomposition."""
        from anomod.stream import score_closed_windows_batched
        with self._span("serve.commit"):
            t0 = time.perf_counter()
            work = []
            with self._span("serve.bookkeep", tenants=len(pending)):
                for det, replay, n_in, w_ret, plan in pending:
                    if det is None:
                        continue
                    if det.batch_scorable:
                        through = det.note_bookkeep(n_in, w_ret)
                        rng = (det.scoring_window_range(through)
                               if through is not None else None)
                        if rng is not None:
                            work.append((det, rng[0], rng[1]))
                    else:
                        det.note_pushed(n_in, w_ret)
            if chaos_hook is not None:
                # the SCORE injection point: replay folds committed and
                # window bookkeeping advanced, batched scoring not yet
                # run
                chaos_hook("score")
            with self._span("serve.score_windows", tenants=len(work)):
                if work:
                    score_closed_windows_batched(work,
                                                 _plane_col_gather(work))
            dt = time.perf_counter() - t0
            runner.score_wall_s += dt
            runner._obs_score_s.inc(dt)

    # -- the fleet census observatory (anomod.obs.census) -----------------

    def _census_drain(self, t_idx: Optional[int] = None) -> dict:
        """One tick-barrier census: the deterministic resident-bytes
        walk over every plane (shapes and container lengths only — the
        workers are quiescent at the barrier, so the per-shard pool/
        scratch reads race nothing), the hot-set/Zipf doc, the
        registry gauges, and the journal-shaped record the flight
        ``census`` variant key carries.  A pure read of engine state:
        no clocks, no RNG, no mutation of any decision plane.  The
        deferred-commit barrier passes ``t_idx`` (the ORIGIN tick —
        the live clock has already advanced by barrier time); the
        synchronous tick reads the clock."""
        from anomod.obs.census import collect_resident_bytes
        if t_idx is None:
            t_idx = self.clock.ticks
        planes, by_plane, total, reconciled = \
            collect_resident_bytes(self)
        tracker = self._census_tracker
        hot = tracker.hot_doc(t_idx, len(self.specs),
                              list(self._tenant_replay))
        self.census_ticks += 1
        self._census_reconciled = self._census_reconciled and reconciled
        self.census_peak_bytes = max(self.census_peak_bytes, total)
        self.census_hot_set = hot
        self.census_resident = {
            "total": total, "peak_total": self.census_peak_bytes,
            "by_plane": by_plane,
            "pool_reconciled": self._census_reconciled}
        g = self._obs_census
        g["total"].set(total)
        for plane in ("pool", "scratch", "admission", "slo", "rca"):
            g[plane].set(by_plane.get(plane, 0))
        g["recorder"].set(by_plane.get("flight", 0))
        g["registered"].set(len(self.specs))
        g["resident"].set(hot["resident"])
        g["hot"].set(hot["hot_by_decay"].get(
            str(min(tracker.decay_ticks)), 0))
        g["occupancy"].set(hot["occupancy_vs_registered"])
        self._obs_census_ticks.inc()
        return {"tick": t_idx, "planes": planes,
                "total_bytes": total, "pool_reconciled": reconciled,
                "hot": hot}

    # -- the black-box flight recorder (anomod.obs.flight) ----------------

    def _flight_tick(self, now: float, served: List[QueuedBatch],
                     tick_wall_s: float, final: bool = False,
                     t_idx: Optional[int] = None,
                     tot=None) -> None:
        """Journal one tick into the flight recorder.

        The CANONICAL planes hold only seed-determined decisions (the
        parity surface `anomod audit diff` bisects): the admission
        deltas + a crc32 over the served decision set in drain order,
        the staged-chunk counts per width (``stage_plan`` is the one
        staging definition, so the counts are identical at every shard
        count / pipeline depth / residency), the active-plane census +
        the cadenced tenant-state digest, and running digests of the
        alert and RCA-verdict streams.  The VARIANT keys (``walls`` /
        ``topology``) carry the tick's five-leg wall deltas and the
        per-shard leg records, folded at the tick barrier in shard
        order (the ``fold_verdicts`` idiom — every runner's book is
        quiescent here, after the barrier).  ``final=True`` is the
        run-end settlement record: finish() alerts and budget-deferred
        RCA verdicts land in it, and a state digest is forced so every
        journal ends on a full-state parity anchor.

        The deferred-commit barrier passes ``t_idx`` and ``tot``
        snapshots taken at the ORIGIN tick (by barrier time the next
        tick's admission has already mutated the live totals and the
        clock has advanced); the synchronous tick reads them live —
        identical values, so the canonical journal is
        async-invariant."""
        from anomod.obs.flight import crc_text, state_digest
        from anomod.serve.shard import fold_leg_records
        fr = self.flight_recorder
        if t_idx is None:
            t_idx = self.clock.ticks
        if tot is None:
            tot = self.admission.totals()
        prev = self._flight_prev_tot

        def delta(field):
            return getattr(tot, field) - (getattr(prev, field)
                                          if prev is not None else 0)

        crc = 0
        for qb in served:
            crc = crc_text(f"{qb.tenant_id}:{qb.seq}:{qb.n_spans}:"
                           f"{qb.priority}:{qb.enqueued_s!r}", crc)
        admission = {"offered": delta("offered_spans"),
                     "admitted": delta("admitted_spans"),
                     "served": delta("served_spans"),
                     "shed": delta("shed_spans"),
                     "evicted": delta("evicted_batches"),
                     "served_batches": delta("served_batches"),
                     "digest": crc}
        self._flight_prev_tot = tot
        legs = [r.leg_walls() for r in self._runners]
        prev_legs = self._flight_prev_legs or [{} for _ in legs]
        if len(prev_legs) < len(legs):
            # an elastic scale-up appended runners since the last
            # record: the new runners' whole books are this tick's
            # delta (a truncating zip would silently drop their chunks
            # from the canonical dispatch plane)
            prev_legs = prev_legs + [{}] * (len(legs) - len(prev_legs))
        by_width: Dict[int, int] = {}
        chunks = 0
        shard_legs = []
        stage_s = dispatch_s = fold_s = score_s = 0.0
        fused_d = native_staged = 0
        for s, (leg, pleg) in enumerate(zip(legs, prev_legs)):
            pw = pleg.get("by_width", {})
            for w, n in leg["by_width"].items():
                dn = n - pw.get(w, 0)
                if dn:
                    by_width[w] = by_width.get(w, 0) + dn
            dchunks = leg["chunks"] - pleg.get("chunks", 0)
            dstage = leg["stage_s"] - pleg.get("stage_s", 0.0)
            ddisp = leg["dispatch_s"] - pleg.get("dispatch_s", 0.0)
            dfold = leg["fold_s"] - pleg.get("fold_s", 0.0)
            dscore = leg["score_s"] - pleg.get("score_s", 0.0)
            dfused = leg["fused"] - pleg.get("fused", 0)
            dnative = leg["native_staged"] - pleg.get("native_staged", 0)
            chunks += dchunks
            stage_s += dstage
            dispatch_s += ddisp
            fold_s += dfold
            score_s += dscore
            fused_d += dfused
            native_staged += dnative
            shard_legs.append({"shard": s, "chunks": dchunks,
                               "fused": dfused,
                               "native_staged": dnative,
                               "stage_s": round(dstage, 6),
                               "dispatch_s": round(ddisp, 6),
                               "fold_s": round(dfold, 6),
                               "score_s": round(dscore, 6)})
        self._flight_prev_legs = legs
        # the fold plane covers the WHOLE fleet's states: pool-resident
        # replays plus (under tiering) the demoted set, read through
        # the tier's digest shims — warm snapshots by reference, cold
        # entries loaded from disk on digest ticks only.  The merged
        # map is built ONLY when the digest actually runs, so the
        # per-tick cost stays O(resident).
        do_digest = final or fr.digest_tick(t_idx)
        reps = self._tenant_replay
        n_states = len(reps)
        if self._tier is not None and len(self._tier):
            n_states += len(self._tier)
            if do_digest:
                reps = dict(reps)
                for tid_ in self._tier.tids():
                    reps[tid_] = self._tier.state_shim(tid_)
        if do_digest and self.worker_mode == "process":
            # the states live in the children: each ships per-tenant
            # (tid, crc, len) fragments, folded here in global sorted
            # tenant order via crc32_combine — bit-equal to the
            # state_digest walk a thread engine runs (the journal
            # parity anchor survives the process boundary)
            from anomod.obs.flight import fold_digest_parts
            parts = []
            if self._workers is not None:
                for w in self._workers:
                    if not w.alive:
                        continue
                    try:
                        parts.extend(w.call({"op": "digest"})["parts"])
                    except RuntimeError:
                        continue
            digest = fold_digest_parts(parts)
        else:
            digest = state_digest(reps) if do_digest else None
        fold = {"tenants": n_states, "state_digest": digest}
        new_alerts = 0
        crc = self._flight_score_crc
        for tid in sorted(self._tenant_det):
            alerts = getattr(self._tenant_det[tid], "alerts", ())
            seen = self._flight_alert_seen.get(tid, 0)
            for a in alerts[seen:]:
                crc = crc_text(
                    f"{tid}:{a.window}:{a.service}:{a.service_name}:"
                    f"{a.score!r}:{a.z_latency!r}:{a.z_error!r}:"
                    f"{a.z_drop!r}:{a.z_drop_cum!r}:{a.evidence}", crc)
                new_alerts += 1
            self._flight_alert_seen[tid] = len(alerts)
        self._flight_score_crc = crc
        self._flight_alert_total += new_alerts
        score = {"alerts": new_alerts,
                 "alerts_total": self._flight_alert_total,
                 "digest": crc}
        new_verdicts = self.rca_verdicts[self._flight_rca_seen:]
        crc = self._flight_rca_crc
        for v in new_verdicts:
            crc = crc_text(repr(v.to_dict()), crc)
        self._flight_rca_seen = len(self.rca_verdicts)
        self._flight_rca_crc = crc
        rca = {"verdicts": len(new_verdicts),
               "verdicts_total": self._flight_rca_seen,
               "digest": crc}
        rec = {
            "tick": t_idx, "now_s": now,
            "admission": admission,
            "dispatch": {"chunks": chunks,
                         "by_width": {str(w): by_width[w]
                                      for w in sorted(by_width)}},
            "fold": fold, "score": score, "rca": rca,
            "walls": {"tick_s": round(tick_wall_s, 6),
                      "stage_s": round(stage_s, 6),
                      "dispatch_s": round(dispatch_s, 6),
                      "fold_s": round(fold_s, 6),
                      "score_s": round(score_s, 6),
                      "other_s": round(max(0.0, tick_wall_s - stage_s
                                           - dispatch_s - fold_s
                                           - score_s), 6)},
            "topology": {"fused_dispatches": fused_d,
                         "native_staged": native_staged,
                         "shard_legs": fold_leg_records(shard_legs)},
        }
        # recovery events ride the journal's VARIANT tier (the
        # "recovery" key is in FLIGHT_VARIANT_KEYS): what crashed,
        # respawned, quarantined or migrated this tick is forensic
        # topology — the canonical planes above must stay equal to a
        # fault-free run's (the no-score-gap pin), so they never carry
        # recovery marks.  The key is ALWAYS present (usually empty) so
        # every record carries every tier — the self-describing-shape
        # contract the variant-key tests pin.
        rec["recovery"] = (self._supervisor.drain_events()
                           if self._supervisor is not None else [])
        # elastic-policy decisions ride the VARIANT tier too (the
        # "scaling" key in FLIGHT_VARIANT_KEYS): WHAT scaled, when, and
        # which tenants moved is execution topology — the canonical
        # planes stay equal to a static run's (the elastic no-score-gap
        # pin), so scaling marks never touch them.  Always present
        # (usually empty), the recovery-key contract.
        scaling, self._policy_events = self._policy_events, []
        rec["scaling"] = scaling
        # the fleet census rides the VARIANT tier too (the "census"
        # key in FLIGHT_VARIANT_KEYS): per-shard pool/scratch bytes
        # follow the execution topology, so the key is excluded from
        # the canonical surface — but unlike walls its content is
        # wall-free, so the census stream is byte-equal across
        # same-seed reruns of one topology (pinned).  ALWAYS present
        # (empty off-cadence or with the census off) — the
        # every-record-carries-every-tier contract.
        census_doc, self._census_tick_doc = self._census_tick_doc, None
        rec["census"] = census_doc if census_doc is not None else \
            {"planes": [], "hot": {}}
        # the state-tiering plane rides the VARIANT tier too (the
        # "tiering" key in FLIGHT_VARIANT_KEYS): demote/promote/miss
        # events are wall-free functions of seed+config — byte-equal
        # across same-config reruns (pinned), excluded from the
        # canonical surface only because a `tier_miss` legitimately
        # moves WHICH tick a deferred tenant's fold/score deltas land
        # in vs the never-evicted journal.  Demotions ride the record
        # AFTER their tick (the step runs post-journal — the
        # scaling-key placement); promotions/misses ride their own
        # tick's.  ALWAYS present (empty with tiering off) — the
        # every-record-carries-every-tier contract.
        rec["tiering"] = (self._tier.drain_events()
                          if self._tier is not None else [])
        # the sequence-model plane's tick rides the VARIANT tier (the
        # "seq" key): tokens, session-policy counts and a digest of the
        # surprisals.  ALWAYS present (empty with the plane off or on a
        # record that follows no step of its own)
        doc = None
        if self._seq is not None:
            doc, self._seq.tick_doc = self._seq.tick_doc, None
        rec["seq"] = doc if doc is not None else {"tokens": 0}
        if final:
            rec["final"] = True
        fr.record(rec)
        # alert-triggered forensic bundle (ANOMOD_FLIGHT_DUMP_DIR): the
        # first tick that raises a new alert publishes ONE ring+scrape+
        # trace bundle — once per run, so a noisy fleet cannot turn the
        # dump dir into a write amplifier
        if (self._flight_dump_dir is not None and new_alerts
                and not self._flight_dumped):
            self._flight_dumped = True
            from pathlib import Path as _P
            fr.forensic(
                _P(self._flight_dump_dir)
                / f"flight_forensic_tick{t_idx:06d}.json",
                registry=self._registry, tracer=self.tracer,
                reason=f"{new_alerts} new alert(s) at tick {t_idx}")

    # -- the sharded (scale-out) score path -------------------------------

    def _make_worker(self, s: int):
        """One shard worker of the engine's configured kind — the ONE
        construction point the engine, the supervisor's respawn path
        and the elastic policy's scale edges all route through, so a
        process-mode engine can never accidentally respawn a thread."""
        if self.worker_mode == "process":
            from anomod.serve.procshard import ProcShardWorker
            return ProcShardWorker(
                s, self._procshard_init(s),
                start_timeout_s=self._worker_start_timeout_s)
        from anomod.serve.shard import ShardWorker
        return ShardWorker(s)

    def _procshard_init(self, s: int) -> dict:
        """The picklable init payload for shard ``s``'s worker process:
        every knob the child's 1-shard sub-engine needs, passed
        RESOLVED from this engine's values (never re-read from the
        child's env — the child must not drift onto a different
        configuration than the engine that spawned it)."""
        owned = [spec for spec in self.specs
                 if self.shard_of.get(spec.tenant_id, 0) == s]
        chaos_script = None
        if self._chaos is not None:
            chaos_script = getattr(self._chaos, "script", None)
        return {"shard_id": s,
                "specs": owned,
                "services": self.services,
                "cfg": self.cfg,
                "t0_us": self.t0_us,
                "capacity_spans_per_s": self.capacity_spans_per_s,
                "tick_s": self.clock.tick_s,
                "buckets": tuple(self._runners[s].buckets),
                "lane_buckets": tuple(self._runners[s].lane_buckets),
                "max_backlog": self.max_backlog,
                "score": self.score,
                "fuse": self.fuse,
                "pipeline": self.pipeline,
                "native": bool(self._runners[s].native_stage),
                "state": self.serve_state,
                "det_kw": dict(self._det_kw),
                "registry_enabled": bool(self._proc_registry.enabled),
                "chaos_script": chaos_script,
                "chaos_fired": self._chaos_fired.get(s)}

    def _ensure_workers(self) -> None:
        if self._workers is None:
            self._workers = [self._make_worker(s)
                             for s in range(self.shards)]
            return
        if all(w.alive for w in self._workers):
            return
        errs = []
        if self.worker_mode == "process":
            # replace ONLY the dead children: a live worker process
            # holds its shard's tenant states — closing it to respawn a
            # sibling would destroy healthy state.  (A respawned child
            # starts EMPTY: the supervisor's checkpoint/replay path
            # restores it; an unsupervised process engine loses the
            # dead shard's states, exactly like a real process crash
            # without checkpoints — docs/SERVING.md.)
            for s, w in enumerate(self._workers):
                if not w.alive:
                    try:
                        w.close()
                    except BaseException as e:  # noqa: BLE001
                        errs.append(e)
                    self._workers[s] = self._make_worker(s)
        else:
            for w in self._workers:   # no leaked threads on respawn
                try:
                    w.close()
                except BaseException as e:  # noqa: BLE001
                    errs.append(e)
            self._workers = [self._make_worker(s)
                             for s in range(self.shards)]
        if errs:
            # close() re-raises a deferred (never-joined) task
            # error; every sibling still closed before it surfaces
            raise errs[0]

    def close(self) -> None:
        """Stop the shard worker threads (idempotent; the engine remains
        usable — the next sharded tick respawns them).  Every worker
        closes before a deferred task error propagates (the join_all
        discipline).  A close with an uncommitted deferred tick ABORTS
        it (the _dispatch_rounds discipline): in-flight dispatches must
        never park in the runners for a later drain to fold as stale
        deltas — run() always commits before closing, so this only
        fires on direct tick()+close() API use."""
        if self._deferred is not None:
            self._deferred = None
            for r in self._runners:
                r.abort_lanes()
        if self._tier is not None:
            self._tier.close()         # join/park the prefetch lane
        if self._seq is not None:
            self._seq.close()          # the latent pool and the weights
        if self._workers is not None:
            errs = []
            for w in self._workers:
                try:
                    w.close()
                except BaseException as e:      # noqa: BLE001
                    errs.append(e)
            self._workers = None
            if errs:
                raise errs[0]

    def _score_sharded(self, served: List[QueuedBatch]) -> None:
        """Fan one tick's drained batches out to the shard workers by
        tenant ownership and join at the barrier.

        Each worker scores only tenants it owns — detectors, replay
        states, the shard's BucketRunner (pipelined: up to
        ``pipeline - 1`` fused dispatches in flight while the next
        stages) and its metrics registry are all shard-private, so the
        score path takes no cross-shard lock.  Per-tenant results are
        bit-identical to the 1-shard engine: the same coalesced batches
        stage the same chunk plans, lane deltas are bit-equal to
        single-lane dispatches regardless of which lanes share a stack,
        and folds apply in round order per tenant.  After the barrier
        the coordinator folds each shard registry into the process
        registry (counter deltas + shard-labeled gauges)."""
        parts: List[List[QueuedBatch]] = [[] for _ in range(self.shards)]
        for qb in served:
            parts[self.shard_of[qb.tenant_id]].append(qb)
        self._ensure_workers()
        failures = (self._submit_parts_proc(parts)
                    if self.worker_mode == "process"
                    else self._submit_parts(parts))
        if failures:
            # attribution for the supervisor (which shards failed);
            # unsupervised engines keep the historical contract — the
            # barrier completed, registries folded, first error raises
            self._last_failures = failures
            raise failures[0][1]

    def _submit_parts(self, parts: List[List[QueuedBatch]],
                      origin_tick: Optional[int] = None) -> list:
        """Fan per-shard slices out to the workers and join at the
        barrier.  The barrier COMPLETES before anything propagates
        (raising at the first failed join would desynchronize sibling
        done-events — the join_all contract) and the shard registries
        fold either way (counters fold by delta, so folding what the
        shards did record is correct whether or not the tick
        succeeded).  Returns ``[(shard_id, exc), ...]`` in shard
        order."""
        from functools import partial
        submitted = []
        for s, worker in enumerate(self._workers):
            if parts[s]:
                worker.submit(partial(self._score_shard, s, parts[s],
                                      origin_tick))
                submitted.append((s, worker))
        failures = []
        for s, worker in submitted:
            try:
                worker.join()
            except BaseException as e:    # noqa: BLE001 — re-raised
                failures.append((s, e))
        self._fold_shard_registries()
        return failures

    def _submit_parts_proc(self, parts: List[List[QueuedBatch]],
                           origin_tick: Optional[int] = None) -> list:
        """The process-worker barrier: fan per-shard slices out as
        ``score`` commands (all sends complete before any recv — the
        children overlap), then drain the replies in shard order.
        Every reply folds its mirror/alert/registry payloads whether or
        not the slice succeeded (counters the child DID record are
        correct either way — the _submit_parts contract), and a shipped
        error reconstructs into the same exception surface the thread
        worker raises at join().  Returns ``[(shard_id, exc), ...]``
        in shard order."""
        from anomod.serve.procshard import rebuild_exc
        tick = (self.clock.ticks if origin_tick is None else origin_tick)
        submitted = []
        for s, worker in enumerate(self._workers):
            if parts[s]:
                try:
                    worker.send({"op": "score", "served": parts[s],
                                 "origin_tick": tick,
                                 "fold": self.fold_mode})
                    submitted.append((s, worker, None))
                except BaseException as e:      # noqa: BLE001
                    submitted.append((s, worker, e))
        failures = []
        deltas = []
        for s, worker, send_err in submitted:
            if send_err is not None:
                failures.append((s, send_err))
                continue
            try:
                rep = worker.recv()
            except BaseException as e:          # noqa: BLE001
                failures.append((s, e))
                continue
            self._apply_shard_reply(s, rep)
            if rep.get("reg_delta") is not None:
                deltas.append((s, rep["reg_delta"]))
            if rep.get("error") is not None:
                failures.append((s, rebuild_exc(rep["error"])))
        self._fold_shard_registries(deltas=deltas)
        return failures

    def _apply_shard_reply(self, s: int, rep: dict) -> None:
        """Fold one child reply's coordinator-mirror payloads: the
        runner's cumulative book/walls, newly materialized tenant
        planes, the alert suffix protocol, and the shard's chaos
        fired-counts (respawn budget continuity).  Registry deltas are
        NOT applied here — the caller batches them through the fold
        tree (_fold_shard_registries) so payload accounting and combine
        order stay one code path."""
        from anomod.serve.procshard import DetMirror
        if "book" in rep:
            self._runners[s].apply(rep)
        for tid in rep.get("resident_new", ()):
            if tid not in self._tenant_replay:
                # residency stub: the states live in the child; the
                # coordinator only needs the resident SET (census off
                # and tiering off in process mode — nothing walks the
                # values)
                self._tenant_replay[tid] = None
        for tid in rep.get("det_new", ()):
            if tid not in self._tenant_det:
                self._tenant_det[tid] = DetMirror()
        for tid, base, new in rep.get("alerts", ()):
            det = self._tenant_det.get(tid)
            if det is None:
                det = self._tenant_det[tid] = DetMirror()
            del det.alerts[base:]
            det.alerts.extend(new)
        if rep.get("chaos_fired") is not None:
            self._chaos_fired[s] = list(rep["chaos_fired"])

    def _fold_shard_registries(self, final: bool = False,
                               shards: Optional[List[int]] = None,
                               deltas: Optional[list] = None) -> None:
        """The tick barrier's registry merge, one code path for both
        worker kinds: collect per-shard ``(shard, delta)`` payloads —
        snapshotted locally from the shard registries (thread mode) or
        handed in pre-serialized off the pipe (process mode) — combine
        them through the deterministic binary fold tree in fixed
        (shard, seq) order, apply to the process registry, and account
        the structural payload bytes (the sparse-vs-dense win
        criterion: exact and box-independent)."""
        from anomod.obs.registry import delta_nbytes
        from anomod.serve.shard import fold_tree
        if deltas is None:
            idx = range(self.shards) if shards is None else shards
            deltas = []
            for s in idx:
                d = self._shard_regs[s].delta_snapshot(
                    self._fold_state[s], mode=self.fold_mode,
                    final=final)
                deltas.append((s, d))
        parts = [[(s, d)] for s, d in deltas if d is not None]
        merged = fold_tree(parts, lambda a, b: a + b)
        if not merged:
            return
        nbytes = 0
        for s, d in merged:
            self._proc_registry.apply_delta(d, shard=str(s))
            nbytes += delta_nbytes(d)
        self.fold_payload_bytes += nbytes
        if self._obs_fold_payload is not None and nbytes:
            self._obs_fold_payload.inc(nbytes)

    # -- the supervisor's process-mode seams (supervise.py routes here
    # -- when worker_mode == "process"; states live in the children) ------

    def _snapshot_tenants_proc(self) -> dict:
        """Checkpoint gather over the pipes: each child runs the SAME
        snapshot_replay/snapshot_detector seams locally and ships
        ``tid -> (replay_snap, det_snap)``; a dead child's tenants are
        simply absent (their state died with it)."""
        tenants: dict = {}
        if self._workers is None:
            return tenants
        for w in self._workers:
            if not w.alive:
                continue
            try:
                rep = w.call({"op": "snapshot"})
            except RuntimeError:
                continue
            tenants.update(rep["tenants"])
        return tenants

    def _drop_shard_proc(self, s: int) -> None:
        """Restore teardown half, process flavor: clear the
        coordinator's resident stubs/alert mirrors for shard ``s`` and
        tell the child (when one is listening — a freshly respawned
        child is already empty) to drop its planes."""
        for tid in [t for t in list(self._tenant_replay)
                    if self.shard_of.get(t, 0) == s]:
            self._tenant_replay.pop(tid, None)
            self._tenant_det.pop(tid, None)
        if self._workers is not None and self._workers[s].alive:
            try:
                self._workers[s].call({"op": "drop"})
            except RuntimeError:
                pass                 # died on the way out: child gone

    def _restore_book(self, s: int, book: dict) -> None:
        """Install a checkpoint's runner book on shard ``s`` — the
        coordinator mirror AND (process mode) the child's live runner,
        so re-executed slices advance from checkpoint counts in both
        places (the double-count guard must hold where the dispatches
        actually happen)."""
        self._runners[s].book_restore(book)
        if (self.worker_mode == "process" and self._workers is not None
                and self._workers[s].alive):
            try:
                self._workers[s].call({"op": "book_restore",
                                       "book": book})
            except RuntimeError:
                pass

    def _install_tenant_proc(self, tid: int, snap: tuple) -> None:
        """Reinstall one checkpointed tenant into its owning child and
        rewind the coordinator's alert mirror to the checkpoint view
        (restore_detector rewinds the real alert list the same way in
        thread mode)."""
        from anomod.serve.procshard import DetMirror
        rep_snap, det_snap = snap
        s = self.shard_of.get(tid, 0)
        self._ensure_workers()
        rep = self._workers[s].call({"op": "install_tenant", "tid": tid,
                                     "replay": rep_snap,
                                     "det": det_snap})
        self._apply_shard_reply(s, rep)
        self._tenant_replay.setdefault(tid, None)
        if det_snap is not None:
            det = self._tenant_det.get(tid)
            if det is None:
                det = self._tenant_det[tid] = DetMirror()
            det.alerts[:] = list(det_snap.get("alerts", ()))

    def _exec_slice_proc(self, s: int, slice_: list, tick: int) -> None:
        """Supervised re-execution of one logged slice inside shard
        ``s``'s child — the chaos injector keys on ``origin_tick``
        exactly as the thread path does, and a shipped failure raises
        here so the recovery loop charges the slice."""
        from anomod.serve.procshard import rebuild_exc
        w = self._workers[s]
        w.send({"op": "score", "served": slice_, "origin_tick": tick,
                "fold": self.fold_mode})
        rep = w.recv()
        self._apply_shard_reply(s, rep)
        if rep.get("reg_delta") is not None:
            self._fold_shard_registries(deltas=[(s, rep["reg_delta"])])
        if rep.get("error") is not None:
            raise rebuild_exc(rep["error"])

    def _score_shard(self, shard_id: int, served: List[QueuedBatch],
                     origin_tick: Optional[int] = None) -> None:
        """One shard's slice of one tick's served batches — on that
        shard's worker thread in the sharded engine, inline on the
        1-shard fused engine, and during supervised recovery the
        re-execution entry point (``origin_tick`` then names the tick
        the slice was drained on, which is what the chaos injector keys
        on — a re-execution of an older slice must not re-trip a fault
        scripted for the current tick).

        Fused: coalesce + plan (identical at every shard count), then
        pipelined lane-stacked dispatches through the shard's runner
        (``submit_lanes`` — readback and state folds defer behind the
        in-flight window), drained before window scoring.  Unfused: one
        detector/replay push per batch, in served order."""
        runner = self._runners[shard_id]
        chaos = self._chaos
        if chaos is not None:
            tick = self.clock.ticks if origin_tick is None else origin_tick
            hook = lambda phase: chaos.hit(phase, tick, shard_id)  # noqa: E731
        else:
            hook = None
        if hook is not None:
            hook("stage")
        if self._fused:
            # the shard/pipeline tags ride the span into the chrome
            # export's args, and the span opens ON the worker thread —
            # so a sharded trace's Perfetto lanes group by shard
            # instead of collapsing onto the coordinator's lane
            with self._span("serve.score_shard", shard=shard_id,
                            pipeline=self.pipeline):
                pending = self._stage_pending(served)
                self._dispatch_rounds(pending, runner, chaos_hook=hook)
                if hook is not None:
                    hook("fold")
                self._commit_pending(pending, runner, chaos_hook=hook)
            if hook is not None:
                hook("commit")
        else:
            # the unfused path has no phase structure, but every
            # scripted fault must still FIRE somewhere (a silently
            # never-injected fault reads as "the engine survived"):
            # the remaining phases collapse onto the slice's two real
            # boundaries — dispatch before the pushes, fold/score/
            # commit after them (post-mutation, the harder case)
            if hook is not None:
                hook("dispatch")
            for qb in served:
                with self._span("serve.score"):
                    if self.score:
                        self._detector_for(qb.tenant_id).push(qb.spans)
                    else:
                        self._replay_for(qb.tenant_id).push(qb.spans)
            if hook is not None:
                hook("fold")
                hook("score")
                hook("commit")

    # -- the elastic-policy plane (anomod.serve.policy) --------------------

    def _policy_step(self, served: List[QueuedBatch],
                     tick: Optional[int] = None,
                     backlog_spans: Optional[int] = None,
                     shed_spans: Optional[int] = None) -> None:
        """One tick-boundary policy evaluation on the coordinator:
        fold this tick's CANONICAL signals into the policy EWMAs,
        collect its decisions, execute them through the live-migration
        seams, and journal what actually happened.  Every input is a
        function of seed+config (served spans, staged-chunk books,
        backlog, shed — never a wall clock), so the whole scaling
        schedule replays from the flight header.  The deferred-commit
        barrier passes ``tick`` / ``backlog_spans`` / ``shed_spans``
        snapshots taken at the ORIGIN tick (by barrier time the next
        tick's admission has already mutated the live values); the
        synchronous tick reads them live — identical numbers, so the
        scaling schedule is async-invariant."""
        from anomod.serve.policy import TickSignals
        if tick is None:
            tick = self.clock.ticks
        if backlog_spans is None:
            backlog_spans = self.admission.backlog_spans
        if shed_spans is None:
            shed_spans = self.admission.totals().shed_spans
        served_by_tenant: Dict[int, int] = {}
        for qb in served:
            served_by_tenant[qb.tenant_id] = \
                served_by_tenant.get(qb.tenant_id, 0) + qb.n_spans
        chunks = [r.n_dispatches for r in self._runners]
        prev = self._policy_prev_chunks
        if prev is None:
            prev = [0] * len(chunks)
        elif len(prev) != len(chunks):
            prev = (prev + [0] * len(chunks))[:len(chunks)]
        self.policy.observe(TickSignals(
            tick=tick, served_by_tenant=served_by_tenant,
            per_shard_chunks=[c - p for c, p in zip(chunks, prev)],
            backlog_spans=backlog_spans,
            max_backlog=self.max_backlog,
            shed_delta=shed_spans - self._policy_prev_shed,
            budget_spans=self.capacity_spans_per_s
            * self.clock.tick_s))
        self._policy_prev_shed = shed_spans
        topology_changed = False
        for d in self.policy.decide(tick, self.shards):
            topology_changed |= self._execute_decision(d, tick)
        if topology_changed and self._supervisor is not None:
            # the recovery log must never span a topology change: the
            # checkpoint's per-runner books and tenant placements are
            # indexed by the CURRENT shard set, so every scaling action
            # ends on a fresh baseline
            self._supervisor.note_topology_change()
        self._policy_prev_chunks = [r.n_dispatches
                                    for r in self._runners]
        if self.flight_recorder is None and self._policy_events:
            # no journal to drain into: the counters/report carry the
            # story, and the event list must not grow with a
            # flight-off run's episode count
            self._policy_events.clear()

    def _execute_decision(self, d: dict, tick: int) -> bool:
        """Execute one policy decision against the live envelope;
        returns whether the shard topology changed.  A decision the
        envelope refuses (scripted ``up`` at the ceiling) is journaled
        as skipped — never silently dropped, never counted."""
        pol = self.policy
        act = d["action"]
        if act == "up":
            if self.shards >= pol.max_shards:
                self._policy_events.append(
                    {"kind": "scale_up", "tick": tick,
                     "skipped": f"at max_shards={pol.max_shards}"})
                return False
            moved = self._scale_up()
            self._peak_shards = max(self._peak_shards, self.shards)
            self._policy_events.append(
                {"kind": "scale_up", "tick": tick,
                 "from": self.shards - 1, "to": self.shards,
                 "tenants": len(moved), "moved": moved})
            pol.note_executed("up", tick, migrated=len(moved),
                              shards=self.shards)
            return True
        if act == "down":
            if self.shards <= pol.min_shards:
                self._policy_events.append(
                    {"kind": "scale_down", "tick": tick,
                     "skipped": f"at min_shards={pol.min_shards}"})
                return False
            moved = self._scale_down()
            self._policy_events.append(
                {"kind": "scale_down", "tick": tick,
                 "from": self.shards + 1, "to": self.shards,
                 "tenants": len(moved), "moved": moved})
            pol.note_executed("down", tick, migrated=len(moved),
                              shards=self.shards)
            return True
        if act == "rebalance":
            from anomod.serve.policy import plan_rebalance
            dead = (self._supervisor.dead_shards
                    if self._supervisor is not None else ())
            moves = plan_rebalance(self.shard_of, self.shards,
                                   self.specs, pol.rate_ewma,
                                   self.capacity_spans_per_s,
                                   int(d.get("k", 1)), dead=dead)
            if not moves:
                pol.note_noop(tick)
                self._policy_events.append(
                    {"kind": "rebalance", "tick": tick,
                     "skipped": "already balanced"})
                return False
            imb_before = pol.imbalance()
            for tid, dst in moves:
                self._move_tenant(tid, dst)
            self._policy_events.append(
                {"kind": "rebalance", "tick": tick,
                 "tenants": len(moves), "moved": [t for t, _ in moves],
                 "imbalance_ewma": round(imb_before, 4)})
            pol.note_executed("rebalance", tick, migrated=len(moves))
            return True
        # brownout: degrade (or restore) the auxiliary planes — RCA
        # budget at level >= 1 (applied at the _rca_tick call site),
        # flight digest cadence at level >= 2 (applied here)
        level = max(0, min(int(d.get("level", 1)),
                           self._policy_max_brownout()))
        prev = pol.brownout_level
        if level == prev:
            # a redundant scripted step is journaled like any other
            # clamped decision — an auditor must be able to tell
            # "evaluated, already there" from "never executed"
            self._policy_events.append(
                {"kind": "brownout", "tick": tick,
                 "skipped": f"already at level {prev}"})
            return False
        self._apply_brownout(level)
        self._policy_events.append(
            {"kind": "brownout", "tick": tick, "from": prev,
             "to": level})
        pol.note_executed("brownout", tick, level=level)
        return False

    def _policy_max_brownout(self) -> int:
        from anomod.serve.policy import MAX_BROWNOUT_LEVEL
        return MAX_BROWNOUT_LEVEL

    def _apply_brownout(self, level: int) -> None:
        fr = self.flight_recorder
        if fr is not None:
            fr.digest_every = (self._flight_digest_base * 4
                               if level >= 2
                               else self._flight_digest_base)

    def _scale_up(self) -> List[int]:
        """Grow the shard set by one worker and migrate the rendezvous
        DELTA — only tenants the new candidate wins under the grown
        set move (minimal disruption: everything else keeps its owner,
        so the migration bill is ~1/(n+1) of the fleet, not a full
        reshuffle).  Returns the moved tenant ids."""
        from functools import partial

        from anomod.serve.shard import rendezvous_shard
        s = self.shards
        moved = [tid for tid in sorted(self.shard_of)
                 if rendezvous_shard(tid, s + 1) == s]
        if self.worker_mode == "process":
            # the new shard's runner lives in its child; the
            # coordinator grows a mirror cloned from shard 0's
            # resolved static facts (RCA keeps its one
            # coordinator-resident plane)
            from anomod.serve.procshard import RunnerMirror
            m0 = self._runners[0]
            self._runners.append(RunnerMirror(
                self.cfg, m0.buckets, lane_buckets=m0.lane_buckets,
                native_stage=m0.native_stage, state=m0.state_mode))
            self._fold_state.append(dict())
            self.shards = s + 1
            if self._workers is not None:
                w = self._make_worker(s)
                self._workers.append(w)
                # warm the new child's compile grid inside the measured
                # tick wall (scaling is real work), off the coordinator
                # thread
                rep = w.call({"op": "warm"})
                self._apply_shard_reply(s, rep)
            for tid in moved:
                self._move_tenant(tid, s)
            return moved
        reg = obs.Registry(enabled=self._proc_registry.enabled)
        runner = BucketRunner(self.cfg, self._buckets_arg, registry=reg,
                              pool_slots=max(len(moved), 1),
                              **self._runner_kw)
        self._shard_regs.append(reg)
        self._runners.append(runner)
        self._fold_state.append(dict())
        if self.rca:
            from anomod.serve.rca import OnlineRCA, RcaRunner
            self._rca_planes.append(OnlineRCA(
                self.services, self.cfg.window_us, self.t0_us,
                RcaRunner(self._rca_kw["buckets"], registry=reg),
                topk=self._rca_kw["topk"],
                windows=self._rca_kw["windows"]))
        self.shards = s + 1
        if self._workers is not None:
            self._workers.append(self._make_worker(s))
            # warm the new runner's compile grid on its own worker —
            # inside the measured tick wall (scaling is real work), off
            # the serving threads
            self._workers[s].submit(partial(self._warm_shard, s))
            self._workers[s].join()
        else:
            self._warm_shard(s)
        for tid in moved:
            self._move_tenant(tid, s)
        return moved

    def _scale_down(self) -> List[int]:
        """Drain the highest shard through the live-migration seam and
        retire its worker.  The victim is ALWAYS the tail id, so the
        candidate set stays ``range(shards)`` and the rendezvous key
        stays the one placement definition; its tenants re-place by
        rendezvous over the shrunk set — exactly the tenants whose
        owner changed, nobody else moves.  The victim's cumulative
        book/walls are retained so the report still covers the whole
        run, and its registry takes a final drain fold.  Returns the
        moved tenant ids."""
        from anomod.serve.shard import rendezvous_shard
        s = self.shards - 1
        dead = (self._supervisor.dead_shards
                if self._supervisor is not None else set())
        candidates = [x for x in range(s) if x not in dead]
        moved = sorted(tid for tid, sh in self.shard_of.items()
                       if sh == s)
        for tid in moved:
            self._move_tenant(
                tid, rendezvous_shard(tid, s, candidates=candidates))
        errs = []
        if self.worker_mode == "process" and self._workers is not None:
            # drain the dying child's registry BEFORE retiring it —
            # after close there is no pipe left to ask
            w = self._workers[s]
            if w.alive:
                try:
                    rep = w.call({"op": "reg_delta",
                                  "fold": self.fold_mode, "final": True})
                    if rep.get("delta") is not None:
                        self._fold_shard_registries(
                            deltas=[(s, rep["delta"])], final=True)
                except RuntimeError:
                    pass                      # crashed mid-drain: close
        if self._workers is not None:
            try:
                self._workers.pop().close()
            except BaseException as e:        # noqa: BLE001 — re-raised
                errs.append(e)
        if self.worker_mode != "process":
            self._proc_registry.fold_from(self._shard_regs[s],
                                          self._fold_state[s],
                                          shard=str(s), final=True)
        self._retired_runners.append(_runner_stats(self._runners[s]))
        self._runners.pop()
        if self._shard_regs:                  # empty in process mode
            self._shard_regs.pop()
        self._fold_state.pop()
        if self.rca and len(self._rca_planes) > s:
            self._rca_planes.pop()
        if self._supervisor is not None:
            self._supervisor.dead_shards.discard(s)
        self.shards = s
        if errs:
            raise errs[0]
        return moved

    def _move_tenant(self, tid: int, dst: int) -> None:
        """Live-migrate one tenant between shards through the official
        state seams: gather (always-copy) via ``snapshot_replay``,
        reinstall on the new owner via ``restore_replay``, repoint the
        detector's replay plane, and carry the RCA evidence buffers.
        Tenant bits are placement-invariant (the PR-5/8 pins), so the
        move cannot shift a single scored byte."""
        src = self.shard_of.get(tid, 0)
        if src == dst:
            return
        if self.worker_mode == "process":
            # gather/reinstall over the pipes, through the SAME
            # snapshot seams (supervise.snapshot_replay/restore_replay
            # run inside the children): take from the src child, put
            # into the dst child.  The coordinator's resident stubs
            # and alert mirrors carry over unchanged — alerts already
            # mirrored, and the dst child re-anchors its ship base at
            # install time.
            self.shard_of[tid] = dst
            if self._workers is not None:
                self._ensure_workers()
                taken = self._workers[src].call(
                    {"op": "take_tenant", "tid": tid})
                snap = taken.get("snap")
                if snap is not None:
                    rep_snap, det_snap = snap
                    self.policy_migrated_spans += int(rep_snap["n_spans"])
                    put = self._workers[dst].call(
                        {"op": "put_tenant", "tid": tid,
                         "replay": rep_snap, "det": det_snap})
                    self._apply_shard_reply(dst, put)
            return
        rep = self._tenant_replay.pop(tid, None)
        self.shard_of[tid] = dst
        if rep is not None:
            from anomod.serve.supervise import (restore_replay,
                                                snapshot_replay)
            snap = snapshot_replay(rep)
            self.policy_migrated_spans += int(snap["n_spans"])
            if hasattr(rep, "release"):
                rep.release()            # hand the pool slot back
            new_rep = self._replay_for(tid)
            restore_replay(new_rep, snap)
            det = self._tenant_det.get(tid)
            if det is not None:
                det.replay = new_rep
        if self.rca and len(self._rca_planes) > max(src, dst):
            self._rca_planes[src].move_tenant_evidence(
                self._rca_planes[dst], tid)

    # -- the online alert→culprit pass (anomod.serve.rca) -----------------

    def _rca_enqueue(self, now: float) -> None:
        """Queue one RCA item per (tenant, batch of new alerts) — the
        ``_rca_seen`` high-water mark makes repeated calls within a
        tick no-ops, so the tick path may enqueue early (ahead of
        evidence-buffer pruning) without double-queuing."""
        for tid in sorted(self._tenant_det):
            det = self._tenant_det[tid]
            n = len(det.alerts)
            seen = self._rca_seen.get(tid, 0)
            if n > seen:
                w = max(a.window for a in det.alerts[seen:])
                self._rca_queue.append((self._rca_seq, tid, w, now))
                self._rca_seq += 1
                self._obs_rca_queued.inc()
                self._rca_seen[tid] = n

    def _rca_tick(self, now: float, budget: Optional[int] = None) -> None:
        """Enqueue one item per (tenant, tick with new alerts), keyed by
        the NEWEST new alert window — the verdict's evidence lookback
        reaches BACK from its anchor, so anchoring at the newest window
        covers every alert of the batch (a min anchor would exclude a
        same-batch later-window alert from the evidence, and a pre-onset
        noise alert sharing the batch with the first real fault alert
        would mis-anchor the verdict before the onset).  Then drain up
        to ``budget`` items (default: the per-tick ``rca_budget``) —
        inline on the 1-shard engine, on the owning shard workers
        otherwise, verdicts folding at the barrier in enqueue order
        either way.  A tenant that keeps alerting while earlier items
        still queue gets a NEW item per tick-batch of alerts (never
        absorbed into a stale one), so the item set — and therefore the
        verdict stream — is identical at any budget; the budget moves
        only ``scored_s``."""
        self._rca_enqueue(now)
        if not self._rca_queue:
            return
        burst = min(budget if budget is not None else self.rca_budget,
                    len(self._rca_queue))
        items = [self._rca_queue.popleft() for _ in range(burst)]
        with self._span("serve.rca"):
            if self._use_workers and self.worker_mode == "process":
                # process mode keeps ONE coordinator-resident plane
                # (evidence is buffered coordinator-side, rca.py's
                # shard-count-invariant contract) — the mirrors'
                # alert lists feed it exactly like thread detectors
                folded = []
                self._rca_run_items(self._rca_planes[0], items, folded,
                                    now)
            elif self._use_workers:
                from anomod.serve.shard import fold_verdicts, join_all
                parts: List[list] = [[] for _ in range(self.shards)]
                for it in items:
                    parts[self.shard_of[it[1]]].append(it)
                self._ensure_workers()
                from functools import partial
                results: List[list] = [[] for _ in range(self.shards)]
                submitted = []
                for s, worker in enumerate(self._workers):
                    if parts[s]:
                        worker.submit(partial(self._rca_shard, s, parts[s],
                                              results[s], now))
                        submitted.append(worker)
                join_all(submitted)
                folded = fold_verdicts(results)
            else:
                folded = []
                self._rca_run_items(self._rca_planes[0], items, folded,
                                    now)
        for _, verdict, wall in folded:
            self.rca_verdicts.append(verdict)
            self._rca_slo.record(wall)
            self.rca_wall_s += wall

    def _rca_run_items(self, plane, items: list, out: list,
                       now: float) -> None:
        for seq, tid, w, enq in items:
            det = self._tenant_det.get(tid)
            alerts = det.alerts if det is not None else []
            verdict, wall = plane.run(tid, w, alerts, enqueued_s=enq,
                                      scored_s=now)
            out.append((seq, verdict, wall))

    def _rca_shard(self, shard_id: int, items: list, out: list,
                   now: float) -> None:
        self._rca_run_items(self._rca_planes[shard_id], items, out, now)

    def run(self, traffic, duration_s: float, warm: bool = True,
            served_log: Optional[list] = None) -> "ServeReport":
        """Drive the engine from a traffic source for ``duration_s``
        virtual seconds, then close every tenant's last window.  A
        ``served_log`` list receives each tick's served batches in tick
        order — what a sequential re-scoring of the same run is fed
        (``chip_smoke.fused_vs_sequential``)."""
        if warm and self.mesh is None:
            if self._use_workers and self.worker_mode == "process":
                # the thread discipline, over the pipe: shard 0 warms
                # first and alone (it populates the persistent compile
                # cache for the siblings),
                # then the rest overlap — all sends complete before
                # any recv.  Replies carry each child's compile walls
                # into the coordinator mirrors.
                from anomod.serve.procshard import rebuild_exc
                self._ensure_workers()
                reps: List[Optional[dict]] = [None] * self.shards
                self._workers[0].send({"op": "warm"})
                reps[0] = self._workers[0].recv()
                for s in range(1, self.shards):
                    self._workers[s].send({"op": "warm"})
                for s in range(1, self.shards):
                    reps[s] = self._workers[s].recv()
                for s, rep in enumerate(reps):
                    self._apply_shard_reply(s, rep)
                for rep in reps:
                    if rep.get("error") is not None:
                        raise rebuild_exc(rep["error"])
                if self.rca:
                    # the single coordinator-resident plane (process
                    # mode keeps RCA evidence out of the children)
                    self._rca_planes[0].runner.warm()
            elif self._use_workers:
                # warm shard 0 FIRST, alone: it populates the
                # persistent compile cache, so the remaining
                # shards' identical-HLO grids (warmed in parallel on
                # their own workers next) are cache reads instead of N
                # concurrent compilers thrashing the host — compiles
                # stay outside the measured wall either way
                from functools import partial

                from anomod.serve.shard import join_all
                self._ensure_workers()
                self._workers[0].submit(partial(self._warm_shard, 0))
                self._workers[0].join()
                for s in range(1, self.shards):
                    self._workers[s].submit(partial(self._warm_shard, s))
                join_all(self._workers[1:])
            else:
                self.runner.warm()               # compiles outside the wall
                if self._fused:
                    self.runner.warm_lanes()
                if self.rca:
                    self._rca_planes[0].runner.warm()
            if self._seq is not None:
                self._seq.warm()
        n_ticks = max(int(round(duration_s / self.clock.tick_s)), 1)
        mod_src = getattr(traffic, "modality_arrivals", None) \
            if self.multimodal else None
        with self._span("serve.run"):
            for _ in range(n_ticks):
                lo = self.clock.now_s
                hi = lo + self.clock.tick_s
                served = self.tick(
                    traffic.arrivals(lo, hi),
                    mod_src(lo, hi) if mod_src is not None else ())
                if served_log is not None:
                    served_log.append(served)
        if self._deferred is not None:
            # the run-end barrier: the last tick's deferred commit must
            # land before finish() reads any tenant state (its wall
            # joins the serve wall — the seam hides waits, never drops
            # them)
            t0 = time.perf_counter()
            self._commit_deferred()
            self.serve_wall_s += time.perf_counter() - t0
        t_wall = time.perf_counter()
        if self._tier is not None:
            # run-end tier settlement: batches whose one-tick cold
            # deferral crossed the run end still score (through the
            # NORMAL per-tick scoring paths, in park order), and every
            # tiered tenant promotes back to residency — finish() must
            # close the whole fleet's last windows, the report counts
            # the whole fleet's alerts, and the settlement record's
            # forced digest anchors FULL state.  Sorted promotion order
            # keeps the event stream deterministic; the events land in
            # the settlement record's `tiering` key below.
            if self._tier_parked:
                parked, self._tier_parked = self._tier_parked, {}
                leftovers: List[QueuedBatch] = []
                for tid, batches in parked.items():
                    if tid in self._tier:
                        self._tier_promote(tid, deferred=True)
                    leftovers.extend(batches)
                if leftovers:
                    sup = self._supervisor
                    if sup is not None:
                        sup.begin_tick(leftovers)
                    if self._use_workers:
                        self._score_sharded(leftovers)
                    elif self._fused:
                        self._score_fused(leftovers)
                    else:
                        self._score_shard(0, leftovers)
                    if sup is not None:
                        sup.end_tick()
            for tid in sorted(self._tier.tids()):
                self._tier_promote(tid, deferred=False)
        if self.score:
            if self._use_workers and self.worker_mode == "process":
                # the detectors live in the children: fan the finish
                # out over the pipes; replies carry the closing
                # windows' alerts (and registry deltas) back
                self._finish_proc()
            else:
                for det in self._tenant_det.values():
                    det.finish()
        if self.rca:
            # end-of-run settlement: alerts raised by finish() (the last
            # window closing) still get culprits, and anything the
            # per-tick budget deferred drains now — every alert of the
            # run is answered before the report
            self._rca_tick(self.clock.now_s, budget=len(self._tenant_det)
                           + len(self._rca_queue) + 1)
            while self._rca_queue:
                self._rca_tick(self.clock.now_s,
                               budget=len(self._rca_queue))
        self.serve_wall_s += time.perf_counter() - t_wall
        if self.census and self._census_tracker is not None:
            # run-end settlement census (the forced-digest idiom):
            # every census-on run ends on a full resident-bytes +
            # hot-set anchor regardless of the cadence, feeding the
            # report fields and the settlement record's census key
            t0 = time.perf_counter()
            self._census_tick_doc = self._census_drain()
            self.census_wall_s += time.perf_counter() - t0
        if self.flight_recorder is not None:
            # run-end settlement record: finish() alerts + drained RCA
            # verdicts land here, and the forced state digest gives every
            # journal a full end-state parity anchor regardless of the
            # per-tick digest cadence
            self._flight_tick(self.clock.now_s, [],
                              time.perf_counter() - t_wall, final=True)
        if self._use_workers:
            # run-end registry fold: shard histograms (lane counts
            # etc.) DRAIN through the Histogram.merge_digest seam — the
            # same way the per-tenant SLO digests already join; drain
            # semantics make a re-run() engine fold its new data only
            if self.worker_mode == "process":
                self._final_fold_proc()
            else:
                self._fold_shard_registries(final=True)
            self.close()
        return self.report(traffic=traffic)

    def _finish_proc(self) -> None:
        """Fan ``Detector.finish()`` out to the shard children.

        A dead (crashed, unsupervised) child is skipped: its
        detectors died with it, exactly like a thread-mode engine
        whose state was lost would have nothing to finish — the
        documented unsupervised-crash degradation.
        """
        if self._workers is None:
            return
        sent = []
        for s, w in enumerate(self._workers):
            if not w.alive:
                continue
            try:
                w.send({"op": "finish", "fold": self.fold_mode})
                sent.append((s, w))
            except RuntimeError:
                continue
        from anomod.serve.procshard import rebuild_exc
        deltas, first_err = [], None
        for s, w in sent:
            try:
                rep = w.recv()
            except RuntimeError:
                continue
            self._apply_shard_reply(s, rep)
            if rep.get("reg_delta") is not None:
                deltas.append((s, rep["reg_delta"]))
            if rep.get("error") is not None and first_err is None:
                first_err = rebuild_exc(rep["error"])
        self._fold_shard_registries(deltas=deltas)
        if first_err is not None:
            raise first_err

    def _final_fold_proc(self) -> None:
        """Run-end registry drain over the pipes (final=True folds)."""
        if self._workers is None:
            return
        deltas = []
        for s, w in enumerate(self._workers):
            if not w.alive:
                continue
            try:
                rep = w.call({"op": "reg_delta", "fold": self.fold_mode,
                              "final": True})
            except RuntimeError:
                continue
            if rep.get("delta") is not None:
                deltas.append((s, rep["delta"]))
        self._fold_shard_registries(deltas=deltas, final=True)

    def _warm_shard(self, shard_id: int) -> None:
        runner = self._runners[shard_id]
        runner.warm()
        if self._fused:
            runner.warm_lanes()
        if self.rca:
            self._rca_planes[shard_id].runner.warm()

    # -- reporting --------------------------------------------------------

    @property
    def seq_scores(self):
        """The sequence-model plane's closed windows, the newest last:
        ``(tenant, window, spans, mean surprisal, max surprisal)``; None
        where the engine has no such plane."""
        return None if self._seq is None else self._seq.scores

    @property
    def seq_counters(self):
        """The sequence-model plane's counters
        (``anomod.serve.seqplane.COUNTERS``), or None."""
        return None if self._seq is None else self._seq.counters

    def alerts_for(self, tenant_id: int,
                   onset_window: Optional[int] = None):
        """A tenant's alert stream; ``onset_window`` filters it through
        the ONE pre-onset-noise eligibility rule (:func:`onset_eligible`
        — shared with the golden fault-detection metrics and the RCA hit
        accounting, so report consumers cannot apply a different rule)."""
        det = self._tenant_det.get(tenant_id)
        alerts = list(det.alerts) if det is not None else []
        if onset_window is not None:
            alerts = onset_eligible_alerts(alerts, onset_window)
        return alerts

    def _fault_detection(self, traffic) -> Optional[dict]:
        faults = getattr(traffic, "faults", None)
        if not faults:
            return None
        win_s = self.cfg.window_us / 1e6
        lat = []
        hits = 0
        for tid, fault in sorted(faults.items()):
            det = self._tenant_det.get(tid)
            onset_w = int(fault.onset_s // win_s)
            fw = None
            if det is not None:
                # only alerts AT or AFTER the onset can be the fault
                # (onset_eligible — the shared pre-onset-noise rule): a
                # pre-onset noise alert on the culprit service must not
                # count as (negative-latency) detection
                ws = [a.window
                      for a in onset_eligible_alerts(det.alerts, onset_w)
                      if a.service_name == self.services[fault.service]]
                fw = min(ws) if ws else None
            if fw is not None:
                hits += 1
                lat.append(fw - onset_w)
        return {
            "n_fault_tenants": len(faults),
            "n_detected": hits,
            "median_alert_latency_windows":
                (float(np.median(lat)) if lat else None),
        }

    def _rca_hits(self, traffic) -> Tuple[Dict[int, int], int]:
        """Top-k hit counts against the traffic script's injected-fault
        ground truth: per fault tenant, its FIRST onset-eligible verdict
        (triggering alert at/after the onset window — the same
        :func:`onset_eligible` rule the golden fault-detection metrics
        apply) is checked for the culprit in its top-1/3/5.  With
        ``serve_rca_topk`` (or the service table) below 5 the ranking is
        shorter than k and hit@k degrades to hit@len — a conservative
        UNDERSTATEMENT, never an overstatement."""
        faults = getattr(traffic, "faults", None) \
            if traffic is not None else None
        hits = {1: 0, 3: 0, 5: 0}
        eligible = 0
        if not (self.rca and faults):
            return hits, eligible
        win_s = self.cfg.window_us / 1e6
        by_tenant: Dict[int, list] = {}
        for v in self.rca_verdicts:
            by_tenant.setdefault(v.tenant_id, []).append(v)
        for tid, fault in sorted(faults.items()):
            onset_w = int(fault.onset_s // win_s)
            vs = [v for v in by_tenant.get(tid, ())
                  if onset_eligible(v.alert_window, onset_w)]
            if not vs:
                continue
            eligible += 1
            first = min(vs, key=lambda v: (v.alert_window, v.scored_s))
            culprit = self.services[fault.service]
            for k in hits:
                if culprit in first.services[:k]:
                    hits[k] += 1
        return hits, eligible

    def report(self, traffic=None) -> ServeReport:
        tot = self.admission.totals()
        shed_fraction = (tot.shed_spans / tot.offered_spans
                         if tot.offered_spans else 0.0)
        per_pri = {}
        # walk the SLO rows that exist (the lazy map holds only
        # ever-served tenants), never the registered fleet — a
        # spec-driven walk would materialize O(registered) digest rows
        # right here
        pri_slos: Dict[int, List[_TenantSLO]] = {}
        for tid, slo in self._slo.items():
            pri_slos.setdefault(
                self.admission.specs.priority_of(tid), []).append(slo)
        for pri, c in sorted(self.admission.per_priority().items()):
            per_pri[pri] = {
                "offered_spans": c.offered_spans,
                "served_spans": c.served_spans,
                "shed_spans": c.shed_spans,
                "shed_fraction": (c.shed_spans / c.offered_spans
                                  if c.offered_spans else 0.0),
                **_merged_quantiles(pri_slos.get(pri, ())),
            }
        n_alerts = sum(len(d.alerts) for d in self._tenant_det.values())
        n_alerted = sum(1 for d in self._tenant_det.values() if d.alerts)
        # runner stats aggregate across the shard runners (the 1-shard
        # list is just [self.runner]); counts are identical to the
        # 1-shard engine's except lane GROUPING stats (fused_dispatches,
        # lanes_by_bucket, pad waste), which legitimately depend on how
        # many tenants share a shard's stack
        disp_by_width: Dict[int, int] = {}
        lanes_by_bucket: Dict[int, int] = {}
        staged_lanes = live_lanes = fused_dispatches = 0
        compile_s = lane_compile_s = 0.0
        native_staged = 0
        stage_wall = dispatch_wall = fold_wall = score_wall = 0.0
        # live runners + the books/walls of runners an elastic
        # scale-down retired: the canonical dispatch counts (and the
        # wall legs) must cover the WHOLE run, not just the final
        # topology
        stats = [_runner_stats(r) for r in self._runners] \
            + self._retired_runners
        for st in stats:
            book = st["book"]
            for w, n in book["dispatches_by_width"].items():
                disp_by_width[w] = disp_by_width.get(w, 0) + n
            for b, n in book["lanes_by_bucket"].items():
                lanes_by_bucket[b] = lanes_by_bucket.get(b, 0) + n
            staged_lanes += book["staged_lanes"]
            live_lanes += book["live_lanes"]
            fused_dispatches += book["fused_dispatches"]
            native_staged += book["native_staged"]
            compile_s += st["compile_s"]
            lane_compile_s += st["lane_compile_s"]
            stage_wall += st["stage_wall_s"]
            dispatch_wall += st["dispatch_wall_s"]
            fold_wall += st["fold_wall_s"]
            score_wall += st["score_wall_s"]
        shard_tenants: Dict[int, int] = {s: 0 for s in range(self.shards)}
        shard_spans: Dict[int, int] = {s: 0 for s in range(self.shards)}
        # the inline engine's placement map is empty (everyone defaults
        # to shard 0): count the unplaced arithmetically, walk only the
        # placed — never the registered fleet
        shard_tenants[0] += len(self.specs) - len(self.shard_of)
        for tid, sh in self.shard_of.items():
            shard_tenants[sh] += 1
        for tid, c in self.admission.counters.items():
            # only ever-offered tenants hold a counter row (the lazy
            # map): a [] walk over specs would materialize O(registered)
            shard_spans[self.shard_of.get(tid, 0)] += c.served_spans
        total_shard_spans = sum(shard_spans.values())
        shard_imbalance = (max(shard_spans.values())
                           / (total_shard_spans / self.shards)
                           if total_shard_spans else 1.0)
        rca_hits, rca_eligible = self._rca_hits(traffic)
        delays = [v.scored_s - v.enqueued_s for v in self.rca_verdicts]
        rca_delay = {
            q: (round(float(np.quantile(delays, p)), 6) if delays
                else None)
            for q, p in (("p50_s", 0.5), ("p99_s", 0.99))}
        rca_lat = {}
        for q, p in (("p50_s", 0.5), ("p99_s", 0.99)):
            got = self._rca_slo.quantile(p) \
                if self._rca_slo is not None else None
            rca_lat[q] = round(got, 6) if got is not None else None
        return ServeReport(
            n_tenants=len(self.specs),
            duration_s=round(self.clock.now_s, 6),
            ticks=self.clock.ticks,
            capacity_spans_per_s=self.capacity_spans_per_s,
            offered_spans=tot.offered_spans,
            admitted_spans=tot.admitted_spans,
            served_spans=tot.served_spans,
            shed_spans=tot.shed_spans,
            shed_fraction=round(shed_fraction, 6),
            served_batches=tot.served_batches,
            peak_backlog_spans=self.admission.peak_backlog_spans,
            max_backlog=self.admission.max_backlog,
            buckets=self.runner.buckets,
            dispatches_by_width=disp_by_width,
            fused=self._fused,
            fused_dispatches=fused_dispatches,
            lane_buckets=self.runner.lane_buckets,
            lanes_by_bucket=lanes_by_bucket,
            lane_pad_waste=round(1.0 - live_lanes / staged_lanes
                                 if staged_lanes else 0.0, 6),
            compile_s=round(compile_s, 4),
            lane_compile_s=round(lane_compile_s, 4),
            native_staging=any(r.native_stage for r in self._runners),
            native_staged_dispatches=native_staged,
            serve_state=self.serve_state,
            stage_wall_s=round(stage_wall, 4),
            dispatch_wall_s=round(dispatch_wall, 4),
            fold_wall_s=round(fold_wall, 4),
            score_wall_s=round(score_wall, 4),
            shards=self.shards,
            pipeline=self.pipeline,
            shard_tenants=shard_tenants,
            shard_spans=shard_spans,
            shard_imbalance=round(shard_imbalance, 6),
            latency=_merged_quantiles(list(self._slo.values())),
            per_priority=per_pri,
            modality_events=dict(self.modality_events),
            n_alerts=n_alerts,
            n_tenants_alerted=n_alerted,
            fault_detection=self._fault_detection(traffic),
            rca_enabled=self.rca,
            n_rca_runs=len(self.rca_verdicts),
            rca_topk_hits=rca_hits,
            rca_eligible=rca_eligible,
            rca_latency=rca_lat,
            rca_alert_to_culprit_s=rca_delay,
            rca_wall_s=round(self.rca_wall_s, 4),
            supervised=self._supervisor is not None,
            ckpt_every=self.ckpt_every,
            n_checkpoints=(self._supervisor.n_checkpoints
                           if self._supervisor is not None else 0),
            ckpt_wall_s=round(self._supervisor.ckpt_wall_s
                              if self._supervisor is not None else 0.0,
                              4),
            n_shard_crashes=(self._supervisor.n_crashes
                             if self._supervisor is not None else 0),
            n_respawns=(self._supervisor.n_respawns
                        if self._supervisor is not None else 0),
            n_restored_ticks=(self._supervisor.n_restored_ticks
                              if self._supervisor is not None else 0),
            n_quarantined=(self._supervisor.n_quarantined
                           if self._supervisor is not None else 0),
            n_migrated_tenants=(self._supervisor.n_migrated
                                if self._supervisor is not None else 0),
            recovery_wall_s=round(self._supervisor.recovery_wall_s
                                  if self._supervisor is not None
                                  else 0.0, 4),
            policy=(self.policy.mode if self.policy is not None
                    else "off"),
            n_scale_ups=(self.policy.n_scale_ups
                         if self.policy is not None else 0),
            n_scale_downs=(self.policy.n_scale_downs
                           if self.policy is not None else 0),
            n_rebalances=(self.policy.n_rebalances
                          if self.policy is not None else 0),
            n_policy_migrations=(self.policy.n_migrated
                                 if self.policy is not None else 0),
            brownout_ticks=(self.policy.brownout_ticks
                            if self.policy is not None else 0),
            peak_shards=max(self._peak_shards, self.shards),
            policy_wall_s=round(self.policy_wall_s, 4),
            flight_enabled=self.flight,
            flight_recorded_ticks=(self.flight_recorder.n_recorded
                                   if self.flight_recorder is not None
                                   else 0),
            flight_dropped_ticks=(self.flight_recorder.n_dropped
                                  if self.flight_recorder is not None
                                  else 0),
            census_enabled=self.census,
            census_ticks=self.census_ticks,
            census_hot_set=dict(self.census_hot_set),
            census_resident_bytes=dict(self.census_resident),
            census_wall_s=round(self.census_wall_s, 4),
            tier_hot=self.tier_hot,
            n_tier_demotions_warm=(self._tier.demotions_warm
                                   if self._tier is not None else 0),
            n_tier_demotions_cold=(self._tier.demotions_cold
                                   if self._tier is not None else 0),
            n_tier_promotions=(self._tier.promotions
                               if self._tier is not None else 0),
            n_tier_misses=(self._tier.misses
                           if self._tier is not None else 0),
            tier_prefetch_hidden=(self._tier.prefetch_hits
                                  if self._tier is not None else 0),
            tier_wall_s=round(self.tier_wall_s, 4),
            async_commit=self.async_commit,
            async_ticks=self.async_ticks,
            commit_defer_wall_s=round(self.commit_defer_wall_s, 6),
            worker=self.worker_mode,
            fold=self.fold_mode,
            fold_payload_bytes=self.fold_payload_bytes,
            serve_wall_s=round(self.serve_wall_s, 4),
            sustained_spans_per_sec=round(
                self.n_spans_served / max(self.serve_wall_s, 1e-9), 1),
        )
