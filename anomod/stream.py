"""Online (streaming) detection over the replay plane.

The reference is strictly post-hoc: collectors archive an experiment after
it ran, and any detection happens offline on the archive
(`/root/reference/SN_collection-scripts/collect_all_data.sh:379`,
`T-Dataset/collect_all_modalities.sh:196-254`).  An operator of those
testbeds wants the obvious next step — alerts while the fault is live.
This module provides it on top of the existing replay machinery:

- :class:`StreamReplay` feeds span micro-batches (arrival order) through
  the SAME jitted chunk step the batch replay scans with
  (`anomod.replay.make_chunk_step`) — the incremental state is
  bit-identical to a one-shot replay of the same spans (parity-tested),
  so everything downstream of the aggregate plane (percentiles, HLL
  distinct-trace counts, detectors) works unchanged on a live stream.
- :class:`OnlineDetector` scores each *closed* 60 s window per service
  with four plane-derived z statistics (SE-of-mean log-latency, smoothed
  binomial error rate, per-window drop, recovery-resetting CUSUM) and
  raises :class:`Alert` rows with hysteresis; culprit ranking sums alert
  scores under dependency-chain attribution over the observed call
  graph.  Detection latency — windows from fault onset to first alert on
  the culprit — is the streaming-mode quality metric the offline sweep
  cannot measure.
- :class:`MultimodalDetector` fuses the log / metric / API planes — the
  streaming counterpart of the offline detector's five-modality
  features — which closes the span statistics' sparse-service floor.

TPU notes: the hot path is the shared chunk step (one bf16 MXU matmul per
micro-batch chunk); window scoring reads the tiny [S*W, F] plane back to
host, which is the natural cadence point (once per closed window, not per
span).  The plane itself shards over a device mesh
(anomod.parallel.stream.ShardedStreamReplay, injectable via
``OnlineDetector(replay=...)``).

Operating envelope: the SPAN z statistics need traffic density — around
≥10 spans per (service, window) the taxonomy localizes with 0-4 window
latency and the normal baselines stay quiet; below that, span evidence
loses power honestly (a sub-1-span/window service killed mid-run may
never alert from spans alone — CUSUM z ≈ 1.6 at best).  The multimodal
planes close exactly that gap (request-rate collapse and error-rate
series localize the quiet kills: both testbeds reach top-1 = 1.0).
Edge-locus faults (the callee side of the culprit's outgoing calls
degrades while its node-scoped evidence stays healthy) are covered by
the OUT-EDGE plane (``edge_attribution``, default on): every span is
pushed twice through the same jitted chunk scan — once keyed by its
service, once by caller-resolved edge slot — and a hot out-edge slot
with cool callee self-edges alerts the CALLER with evidence="edge"
(11/12 at live density/severity).  This plane is the framework's ONLY
working edge-locus detector: the offline models consume per-service
aggregates, so link faults are architecturally outside their evidence
(every node-feature model ≤ 0.06 once the generator's coverage/API
target-identity leak was gated — see docs/QUALITY.md, "Generator-leak
retraction").  The residual gap is the de-saturated sparse regime, where
pooled out-edge windows against an 8-window baseline cap the z below
threshold at ~1 span/window.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import List, Optional, Sequence

import numpy as np

from anomod.replay import (F_COUNT, F_ERR, F_LOGLAT, N_FEATS, ReplayConfig,
                           ReplayState, make_chunk_step, named_jit,
                           stage_columns)
from anomod.schemas import LOG_ERROR, SpanBatch, take_spans


@dataclasses.dataclass(frozen=True)
class Alert:
    window: int            # closed window index that scored anomalous
    service: int           # service id (index into the batch's table)
    service_name: str
    score: float           # RANKING score: max of the latency/error z and
    #                        the drop z's weighted by their deficit
    #                        FRACTION — may be far below the raw z fields
    #                        (alerting thresholds the raw max; ranking
    #                        needs specificity, see _score_through)
    z_latency: float       # standard-error z on the window's log-latency mean
    z_error: float         # binomial z on the window's error rate
    z_drop: float          # per-window z on missing throughput
    z_drop_cum: float = 0.0  # CUSUM z: accumulated missing throughput over
    #                          the current deficit run (resets when the
    #                          service returns to its baseline rate) — the
    #                          signal that catches a SPARSE service going
    #                          dark (per-window evidence for a 3-spans/min
    #                          service never clears any sane threshold;
    #                          8 windows of total silence does)
    evidence: str = ""       # which signal won the ranking score for this
    #                          alert: latency/error/drop/cusum, or a
    #                          modality plane (log/metric/api) in the
    #                          multimodal detector


def roll_ring_state(state: ReplayState, cfg: ReplayConfig,
                    k: int) -> ReplayState:
    """Evict the oldest ``k`` windows from a ring-shaped ReplayState:
    shift plane columns left, zero the tail (anchor bookkeeping is the
    caller's).  ONE definition of the ring-eviction math, shared by the
    single-chip and mesh-sharded streaming planes.  HLL registers are
    per-service (not per-window) and pass through untouched."""
    import jax.numpy as jnp
    shift = min(k, cfg.n_windows)

    def roll2(x, width):
        x = np.asarray(x).reshape(cfg.n_services, cfg.n_windows, width)
        out = np.zeros_like(x)
        if shift < cfg.n_windows:
            out[:, :cfg.n_windows - shift] = x[:, shift:]
        return jnp.asarray(out.reshape(cfg.sw, width))

    return state._replace(agg=roll2(state.agg, N_FEATS),
                          hist=roll2(state.hist, cfg.n_hist_buckets))


def plane_view(state: ReplayState, cfg: ReplayConfig) -> np.ndarray:
    """Host copy of the aggregate plane as [S, W, F]."""
    return np.asarray(state.agg).reshape(
        cfg.n_services, cfg.n_windows, N_FEATS)


def edge_combined_cfg(cfg: ReplayConfig, n_services: int) -> ReplayConfig:
    """The COMBINED-id-space config an edge-attributing detector runs its
    replay on: node ids ⊕ self-edge slots ⊕ out-edge slots = 3S rows.
    Use this to construct an injectable plane (e.g.
    ``ShardedStreamReplay(edge_combined_cfg(cfg, S), t0, mesh)``) for
    ``OnlineDetector(..., replay=..., edge_attribution=True)``."""
    return dataclasses.replace(cfg, n_services=3 * n_services)


def _binom_tail_z(x: int, n: int, p: float) -> float:
    """z-equivalent of the upper binomial tail P(X >= x | n, p).

    Exact summation at the small counts the sparse-edge error channel
    lives in (2 errors in 6 spans is not Gaussian; a normal z there is
    either fabricated or blind); normal approximation once n*p is large
    enough for it to be honest.  The tail converts to a z through the
    standard-normal survival function so one threshold governs every
    evidence channel."""
    import math
    if x <= 0 or n <= 0:
        return 0.0
    if n > 60 and n * p > 10.0:
        return float((x - n * p) / math.sqrt(max(n * p * (1.0 - p), 1e-9)))
    tail = 0.0
    for k in range(int(x), int(n) + 1):
        tail += math.comb(int(n), k) * p ** k * (1.0 - p) ** (int(n) - k)
    if tail >= 0.5:
        return 0.0
    lo, hi = 0.0, 40.0
    for _ in range(60):                      # bisection on the survival fn
        mid = 0.5 * (lo + hi)
        if 0.5 * math.erfc(mid / math.sqrt(2.0)) > tail:
            lo = mid
        else:
            hi = mid
    return lo


def resolve_parent_services(batch: SpanBatch) -> np.ndarray:
    """Per-span PARENT-service id (-1 for roots).

    ``SpanBatch.parent`` holds batch-global row indices, so this must run
    on the FULL corpus BEFORE any row slicing (``take_spans`` does not
    remap parents).  A live collector does the same join at ingest from
    the wire format's parentSpanId (Jaeger/SkyWalking both carry it) —
    this helper is that join for the offline stand-in corpora."""
    psvc = np.full(batch.n_spans, -1, np.int32)
    has = batch.parent >= 0
    psvc[has] = batch.service[batch.parent[has]]
    return psvc


def window_span_z(col_plane: np.ndarray, b: dict, cusum, cusum_k,
                  min_count, drop_memory) -> dict:
    """THE per-closed-window span-plane z math, in one place.

    ``col_plane`` is the window's aggregate column ``[..., K, F]``,
    ``b`` the frozen calibration snapshot with ``[..., K]`` fields,
    ``cusum``/``cusum_k`` the CUSUM carry state, ``min_count`` /
    ``drop_memory`` the detector thresholds (scalars, or ``[..., 1]``
    arrays when batching).  Everything is elementwise/broadcast numpy,
    so a leading batch axis prepends freely: the sequential scorer
    (:meth:`OnlineDetector._score_through`, no batch axis) and the
    serving plane's batched scorer (:func:`score_closed_windows_batched`,
    tenants stacked on axis 0) run the IDENTICAL per-element arithmetic
    — which is what makes batched serving scoring byte-identical to
    per-tenant scoring, pinned in tests/test_serve_state.py.

    The three signals read straight off the aggregate plane's moments,
    each normalized by the statistically right denominator for sparse
    windows (see the scoring notes on :class:`OnlineDetector`):
    latency = standard-error z on the window's log-latency mean, error
    rate = binomial z vs the pooled baseline, throughput = Poisson z on
    MISSING spans plus a recovery-resetting CUSUM (the signal that
    catches a SPARSE service going dark — per-window evidence for a
    3-spans/min service never clears any sane threshold; 8 windows of
    total silence does).  The ``frac_*`` weights price detection vs
    localization: a high-fan-in carrier's statistically huge z on a 30%
    dip must not outrank certainty about a service 100% dark, so the
    ranking score weights the drop signals by their deficit FRACTION.

    Returns ``dict(zl, ze, zd, zdc, frac_w, frac_t, cusum, cusum_k)``
    with the CUSUM state advanced (the caller installs it).
    """
    n_w = col_plane[..., F_COUNT]
    safe = np.maximum(n_w, 1.0)
    ok = (n_w >= min_count) & b["calibrated"]
    zl = np.where(ok, (col_plane[..., F_LOGLAT] / safe - b["mu_l"])
                  / np.sqrt(b["var_span"] / safe + b["var_bl"]), 0.0)
    ze = np.where(ok, (col_plane[..., F_ERR] / safe - b["p_err"])
                  / np.sqrt(b["err_var"] / safe + b["var_be"]), 0.0)
    zd = np.where(b["active"], (b["rate0"] - n_w) / b["sd_cnt"], 0.0)
    # CUSUM on missing throughput: the slack term keeps healthy jitter
    # from accumulating; a window back at (or above) the baseline rate
    # RESETS the run — no lingering "still down" alerts after recovery.
    # Run length is capped at drop_memory for the normalization.
    healthy = n_w >= b["rate0"]
    slack = 0.25 * b["sd_cnt"]
    cusum = np.where(healthy, 0.0,
                     np.maximum(0.0, cusum + b["rate0"] - n_w - slack))
    cusum_k = np.where(cusum > 0,
                       np.minimum(cusum_k + 1, drop_memory),
                       0).astype(np.int32)
    k_run = np.maximum(cusum_k, 1)
    zdc = np.where(b["cum_active"],
                   cusum / (b["sd_cnt"] * np.sqrt(k_run)), 0.0)
    frac_t = np.clip(cusum / np.maximum(k_run * b["rate0"], 1e-9),
                     0.0, 1.0)
    frac_w = np.clip(1.0 - n_w / np.maximum(b["rate0"], 1e-9), 0.0, 1.0)
    return dict(zl=zl, ze=ze, zd=zd, zdc=zdc, frac_w=frac_w,
                frac_t=frac_t, cusum=cusum, cusum_k=cusum_k)


#: ranking-evidence channel order of the base span planes — the ONE
#: ordering shared by the sequential scorer's part dicts and the batched
#: scorer's stacks (argmax indices must mean the same channel in both)
SPAN_EV_NAMES = ("latency", "error", "drop", "cusum")


class StreamReplay:
    """Incremental replay state over arrival-ordered span micro-batches.

    ``t0_us`` anchors the window grid at stream start.  The grid ROLLS: a
    push whose spans start past the last column evicts the oldest windows
    (host-side roll of the tiny [S*W, *] state) and advances the anchor,
    so a live stream of any duration keeps scoring — ``window_offset``
    is the absolute index of plane column 0 and only grows.  Late
    stragglers older than the rolled anchor clamp into column 0 (the
    bounded misbinning of any ring buffer).  Chunk size should be sized
    to the expected micro-batch (default 4096 vs the batch path's 32768).
    """

    def __init__(self, cfg: ReplayConfig, t0_us: int,
                 with_hll: bool = False):
        import jax.numpy as jnp

        self.cfg = cfg
        self.t0_us = int(t0_us)
        self.window_offset = 0     # absolute window index of plane column 0
        self.n_spans = 0
        step = make_chunk_step(cfg, with_hll=with_hll)
        self._step = named_jit("anomod_chunk_step",
                               lambda st, ch: step(st, ch)[0])
        self.state = ReplayState(
            agg=jnp.zeros((cfg.sw, N_FEATS), jnp.float32),
            hist=jnp.zeros((cfg.sw, cfg.n_hist_buckets), jnp.float32),
            hll=(jnp.zeros((cfg.n_services, cfg.hll_m), jnp.int32)
                 if with_hll else None))
        #: one-time jit compile wall, measured at the first push (lazy —
        #: a detector constructed but never fed must not pay the compile)
        self.compile_s = 0.0
        self._warmed = False

    def _warm(self) -> None:
        """Compile the chunk step on an all-dead dummy chunk (sid = dead
        lane, valid = 0 → numerically a no-op on the state) so push()
        walls measure the steady pipeline, not one-time compilation."""
        from anomod.replay import dead_chunk

        from anomod import obs
        t0 = time.perf_counter()
        self.state = self._step(self.state, dead_chunk(self.cfg))
        np.asarray(self.state.agg)                # compile + execute barrier
        self.compile_s = time.perf_counter() - t0
        obs.counter("anomod_stream_compile_total").inc()
        obs.counter("anomod_stream_compile_seconds_total").inc(
            self.compile_s)
        self._warmed = True

    def _roll(self, k: int) -> None:
        """Evict the oldest ``k`` windows (roll_ring_state) and advance
        the anchor.  The anchor advances by the FULL ``k`` even when that
        clears the whole plane (a feed gap wider than the grid) — only
        the column shift clamps, so later spans always bin into their
        true absolute window."""
        self.state = roll_ring_state(self.state, self.cfg, k)
        self.t0_us += k * self.cfg.window_us
        self.window_offset += k

    def push(self, batch: SpanBatch) -> int:
        """Fold a micro-batch into the plane.

        Returns the newest ABSOLUTE window the batch's spans were binned
        into (-1 for an empty batch) — the one true span→window mapping,
        so consumers never re-derive it from raw timestamps."""
        if batch.n_spans == 0:
            return -1
        if not self._warmed:
            self._warm()
        from anomod import obs
        t_push = time.perf_counter()
        w_need = int((int(batch.start_us.max()) - self.t0_us)
                     // self.cfg.window_us)
        if w_need > self.cfg.n_windows - 1:
            self._roll(w_need - (self.cfg.n_windows - 1))
            w_need = self.cfg.n_windows - 1
        chunks, n = stage_columns(batch, self.cfg, t0_us=self.t0_us)
        # double-buffered host→device staging (anomod.io.prefetch): chunk
        # i+1 transfers while the jitted step on chunk i is in flight
        from anomod.io.prefetch import iter_chunk_dicts, prefetch_to_device
        pipe = prefetch_to_device(iter_chunk_dicts(chunks))
        try:
            for staged in pipe:
                self.state = self._step(self.state, staged)
        finally:
            # a consumer-side error must not leave the worker parked on
            # the bounded queue holding staged device buffers
            pipe.close()
        self.n_spans += n
        obs.histogram("anomod_stream_push_seconds").observe(
            time.perf_counter() - t_push)
        return self.window_offset + max(w_need, 0)

    def agg_plane(self) -> np.ndarray:
        """Host copy of the aggregate plane as [S, W, F] (column w holds
        absolute window ``window_offset + w``)."""
        return plane_view(self.state, self.cfg)

    # -- the lane-stack gather/scatter seam (anomod.serve.batcher) --------
    #
    # Fused serving gathers many tenants' states, folds each tenant's
    # staged chunk through ONE lane-stacked dispatch, and hands each
    # lane's result back.  The seam is deliberately dumb — the state
    # pytree round-trips verbatim — but it is the OFFICIAL boundary:
    # consumers go through it instead of poking ``.state``, so a future
    # replay that keeps extra device-side residency can hook the
    # round-trip in one place.

    def get_state(self) -> ReplayState:
        """The replay plane's current state pytree (gather seam)."""
        return self.state

    def set_state(self, state: ReplayState) -> None:
        """Install an externally-advanced state pytree (scatter seam).
        The caller owns the parity contract: the installed state must be
        what this plane's own dispatch would have produced."""
        self.state = state


class OnlineDetector:
    """Window-closed z-score alerting over a :class:`StreamReplay`.

    The first ``baseline_windows`` closed windows per service calibrate
    mu/sigma for log-latency mean and error rate (the reference's
    pre-fault normal phase — faults start at 600 s = window 10 on the
    default grid, so the default 8 stays inside it).  A window is closed
    once a pushed span starts in a LATER window (in-order arrival is the
    stream contract).  ``consecutive`` windows above ``z_threshold`` are
    required before alerting (hysteresis against single-window noise).
    """

    def __init__(self, batch_services: Sequence[str], cfg: ReplayConfig,
                 t0_us: int, baseline_windows: int = 8,
                 z_threshold: float = 4.0, min_count: float = 5.0,
                 consecutive: int = 1, drop_memory: int = 8,
                 call_edges: Optional[set] = None,
                 replay=None, with_hll: bool = False,
                 edge_attribution: Optional[bool] = None,
                 edge_pool: int = 12, edge_mass: float = 8.0, mesh=None):
        if baseline_windows < 2:
            raise ValueError("need >= 2 baseline windows for a sigma")
        if baseline_windows >= cfg.n_windows:
            raise ValueError("baseline must fit inside the window ring "
                             f"({baseline_windows} >= {cfg.n_windows})")
        if consecutive < 1:
            raise ValueError("consecutive must be >= 1 (0 would alert "
                             "every service in every window)")
        if replay is not None and with_hll:
            raise ValueError("with_hll configures the detector's OWN "
                             "plane; an injected replay manages its own "
                             "HLL state")
        if mesh is not None and replay is not None:
            raise ValueError("give a mesh OR a pre-built replay, not both")
        if mesh is not None and with_hll:
            raise ValueError("the mesh streaming plane carries no HLL "
                             "state (psum-merged agg/hist only)")
        self.services = tuple(batch_services)
        S = len(self.services)
        self._n_svc = S
        #: EDGE-LOCUS coverage (default on when the detector owns its
        #: replay): the replay id space widens from S node ids to a
        #: STATIC 3S — S node ids ⊕ S self-edge slots ⊕ S out-edge
        #: slots — every span pushed twice (node id + edge slot) through
        #: the SAME jitted chunk scan.  A span whose parent belongs to a
        #: DIFFERENT service keys its edge copy to the CALLER's out-edge
        #: slot (2S + caller); own-parented and root spans key to their
        #: service's self-edge slot (S + svc).  A link fault
        #: (anomod.synth fault_locus="edge") degrades only the
        #: callee-side spans of the culprit's outgoing calls — node
        #: statistics then blame the callees, but the edge plane shows
        #: the signature directly: the culprit's OUT-edge slot goes hot
        #: while every callee's SELF-edge slot stays cool, so the
        #: detector alerts on the CALLER with evidence="edge" and
        #: ranking marks the callees edge-explained.  (Per-caller
        #: aggregation, not per-(caller, callee): out-edge traffic is a
        #: fraction of node traffic, and splitting it S-ways again would
        #: starve the z statistics at realistic densities; which callee
        #: is degraded is not needed to name the culprit.)
        # ``mesh`` builds the detector's own mesh-sharded plane (the
        # combined-cfg bookkeeping stays in one place); edge attribution
        # auto-enables for any detector-owned plane, mesh or single-chip
        self.edge_attribution = (replay is None) if edge_attribution is None \
            else bool(edge_attribution)
        if edge_pool < 1:
            raise ValueError("edge_pool must be >= 1 window")
        if edge_mass < 1:
            raise ValueError("edge_mass must be >= 1 span")
        self.edge_pool = edge_pool      # max window REACH of the edge pool
        self.edge_mass = edge_mass      # span-mass target the pool walks to
        if self.edge_attribution:
            K = 3 * S
            cfg = edge_combined_cfg(cfg, S)
            self._edge_hot: dict = {}       # caller id -> summed hot score
            self._self_hot = np.zeros(S, bool)
            # Per-(caller, callee) PAIR accumulators — the ranking's
            # concentration discriminator.  The pooled out-edge ROW can
            # say "caller p's outgoing traffic degraded" but not whether
            # the heat is spread across p's callees (link fault in p) or
            # concentrated on one (blast pointing at a node culprit).
            # O(observed pairs) streaming state: [n, sum_log1p_dur,
            # n_err] keyed caller*S+callee, split baseline/anomalous
            # phase at the calibration boundary.
            self._pair_base: dict = {}
            self._pair_anom: dict = {}
        else:
            K = S
        self._K = K
        # ``replay`` injects an alternative plane with the same contract —
        # e.g. anomod.parallel.stream.ShardedStreamReplay runs this whole
        # alerting stack over a device mesh unchanged.  With edge
        # attribution (pass edge_attribution=True explicitly; the default
        # only auto-enables for the detector's own plane) the injected
        # replay must be built on the COMBINED id space:
        # ``detector cfg with n_services = 3 * len(services)``.
        if replay is not None and (replay.cfg != cfg
                                   or replay.t0_us != int(t0_us)):
            raise ValueError(
                "injected replay's cfg/t0 disagree with the detector's"
                + (" (edge attribution widens the id space: build the "
                   f"replay with n_services = 3*S = {K})"
                   if self.edge_attribution else ""))
        if replay is None and mesh is not None:
            from anomod.parallel.stream import ShardedStreamReplay
            replay = ShardedStreamReplay(cfg, t0_us, mesh)
        self.replay = replay if replay is not None else \
            StreamReplay(cfg, t0_us, with_hll=with_hll)
        #: spans fed by the caller (the combined-id replay counts each
        #: span twice internally; pipeline metrics use THIS number)
        self.n_spans_in = 0
        self.baseline_windows = baseline_windows
        self.z_threshold = z_threshold
        self.min_count = min_count
        self.consecutive = consecutive
        self.drop_memory = drop_memory
        #: observed caller→callee service-id pairs (self-loops ignored);
        #: enables dependency-aware culprit ranking in ranked_services
        self.call_edges = {(a, b) for a, b in (call_edges or set())
                           if a != b}
        self.alerts: List[Alert] = []
        #: accumulated wall time inside push()/push_* (staging + jitted
        #: chunk steps + window scoring) — the live pipeline's cost;
        #: spans/sec = n_spans_in / push_wall_s (NOT replay.n_spans: the
        #: combined-id replay counts each span twice in edge mode)
        self.push_wall_s = 0.0
        self._scored_through = -1          # last closed ABSOLUTE window scored
        self._max_seen = -1                # newest absolute window with data
        # frozen grid anchor for the pair accumulators' phase split (the
        # replay's own t0 ROLLS with the ring)
        self._t0_us = int(t0_us)
        self._window_us = int(cfg.window_us)
        self._callees_cache: dict = {}
        self._streak = np.zeros(self._K, np.int32)
        self._baseline = None              # frozen calibration snapshot
        # CUSUM state for the cumulative drop signal: accumulated span
        # deficit + length of the current deficit run, per row (the drop
        # signals are consumed for node rows only)
        self._cusum = np.zeros(self._K, np.float64)
        self._cusum_k = np.zeros(self._K, np.int32)

    def _callees_of(self, p: int) -> frozenset:
        """Observed callees of service ``p`` (from ``call_edges``)."""
        got = self._callees_cache.get(p)
        if got is None:
            got = frozenset(c for a, c in self.call_edges if a == p)
            self._callees_cache[p] = got
        return got

    def _edge_ids(self, svc: np.ndarray,
                  psvc: Optional[np.ndarray]) -> np.ndarray:
        """Edge slot per span: the CALLER's out-edge slot 2S+p for spans
        whose parent belongs to a different service, else the service's
        self-edge slot S+c (roots, own-parented spans, and every span
        when the pusher has no parent info — node-degraded, honest)."""
        S = self._n_svc
        out = (S + svc).astype(np.int32)
        if psvc is None:
            return out
        cross = (psvc >= 0) & (psvc != svc)
        if cross.any():
            out[cross] = (2 * S + psvc[cross]).astype(np.int32)
        return out

    _DUP_FIELDS = ("trace", "parent", "endpoint", "start_us",
                   "duration_us", "is_error", "status", "kind")

    def _accumulate_pairs(self, batch: SpanBatch, svc: np.ndarray,
                          psvc: np.ndarray) -> None:
        """Fold a micro-batch's cross edges into the per-pair phase
        accumulators (vectorized per unique pair; O(pairs) dict work)."""
        cross = (psvc >= 0) & (psvc != svc)
        if not cross.any():
            return
        wi = (batch.start_us[cross] - self._t0_us) // self._window_us
        keys = psvc[cross].astype(np.int64) * self._n_svc + svc[cross]
        dur = np.log1p(batch.duration_us[cross].astype(np.float64))
        err = batch.is_error[cross].astype(np.float64)
        in_base = wi < self.baseline_windows
        for phase, m in ((self._pair_base, in_base),
                         (self._pair_anom, ~in_base)):
            if not m.any():
                continue
            uk, inv = np.unique(keys[m], return_inverse=True)
            ns = np.bincount(inv).astype(np.float64)
            ds = np.bincount(inv, weights=dur[m])
            es = np.bincount(inv, weights=err[m])
            for k_, n_, d_, e_ in zip(uk.tolist(), ns, ds, es):
                acc = phase.setdefault(k_, [0.0, 0.0, 0.0])
                acc[0] += n_
                acc[1] += d_
                acc[2] += e_

    def _pair_verdict(self, p: int) -> Optional[tuple]:
        """Concentration verdict for caller ``p``'s per-pair heat:
        ``("concentrated", callee)`` when one callee carries >= 60% of
        the degradation mass, ``("spread", -1)`` when it is spread, and
        ``None`` when there is not enough pair data to tell.

        Spread-vs-concentrated is THE link-vs-node discriminator: an
        edge-locus fault degrades ALL of the culprit's outgoing pairs,
        while a node culprit heats exactly the one pair pointing at it
        from each caller."""
        S = self._n_svc
        deltas: List[tuple] = []
        n_obs = 0
        for k, (n_a, d_a, e_a) in self._pair_anom.items():
            if k // S != p or n_a < 3:
                continue
            base = self._pair_base.get(k)
            if not base or base[0] < 3:
                continue
            n_obs += 1
            d = max(d_a / n_a - base[1] / base[0], 0.0) \
                + 5.0 * max(e_a / n_a - base[2] / base[0], 0.0)
            if d > 0:
                deltas.append((d, int(k % S)))
        if n_obs < 2 or not deltas:
            return None          # one observed pair: spread undefined
        tot = sum(d for d, _ in deltas)
        d0, c0 = max(deltas)
        return ("concentrated", c0) if d0 >= 0.6 * tot else ("spread", -1)

    def push(self, batch: SpanBatch,
             parent_service: Optional[np.ndarray] = None) -> List[Alert]:
        """Feed a micro-batch; returns alerts for newly closed windows.

        Window indices in alerts are ABSOLUTE (they keep growing after the
        replay ring rolls past its grid width).  The newest window comes
        from the replay itself — the detector never re-derives binning
        from raw timestamps.

        ``parent_service`` (optional, len n_spans, -1 = root) feeds the
        edge plane; resolve it on the FULL corpus with
        :func:`resolve_parent_services` BEFORE slicing (a live collector
        resolves it at ingest from parentSpanId).  Without it, spans land
        on their self-edge slot and edge attribution degrades to node
        evidence."""
        if batch.n_spans and not self.replay._warmed:
            self.replay._warm()          # compile outside the timed wall
        t0 = time.perf_counter()
        try:
            w_max = self.replay.push(
                self.replay_batch(batch, parent_service))
            return self.note_pushed(batch.n_spans, w_max)
        finally:
            self.push_wall_s += time.perf_counter() - t0

    def replay_batch(self, batch: SpanBatch,
                     parent_service: Optional[np.ndarray] = None
                     ) -> SpanBatch:
        """Host-side pre-replay half of :meth:`push`: the EXACT batch
        push() hands the replay plane (edge-id duplication + per-pair
        phase accumulation applied; the identity when edge attribution is
        off).  The fused serving plane (anomod.serve.engine) calls this,
        folds the result through a lane-stacked dispatch, then finishes
        with :meth:`note_pushed` — one definition of both halves, so the
        fused and sequential scoring paths cannot drift."""
        if not (self.edge_attribution and batch.n_spans):
            return batch
        svc = batch.service.astype(np.int32)
        psvc = None if parent_service is None else \
            np.asarray(parent_service, np.int32)
        if psvc is not None:
            self._accumulate_pairs(batch, svc, psvc)
        eids = self._edge_ids(svc, psvc)
        return batch._replace(
            service=np.concatenate([svc, eids]),
            **{f: np.concatenate([getattr(batch, f)] * 2)
               for f in self._DUP_FIELDS})

    def note_pushed(self, n_spans: int, w_max: int) -> List[Alert]:
        """Post-replay half of :meth:`push`: bookkeeping plus scoring of
        the newly closed windows.  ``n_spans`` is the ORIGINAL batch's
        span count (pre edge duplication); ``w_max`` is the replay
        plane's returned newest absolute window."""
        through = self.note_bookkeep(n_spans, w_max)
        if through is None:
            return []
        return self._score_through(through)

    def note_bookkeep(self, n_spans: int, w_max: int) -> Optional[int]:
        """The bookkeeping half of :meth:`note_pushed` (span count +
        window high-water mark); returns the ``through`` bound scoring
        would scan, or None for an empty push.  The serving plane's
        batched COMMIT phase calls this per tenant and then scores every
        batch-scorable tenant in one vectorized pass
        (:func:`score_closed_windows_batched`) — one definition of the
        bookkeeping for the sequential and batched paths."""
        if w_max < 0:
            return None
        self.n_spans_in += n_spans
        self._max_seen = max(self._max_seen, w_max)
        return self._max_seen - 1

    def scoring_window_range(self, through: int):
        """The closed-window range ``(start, through)`` that
        :meth:`_score_through` would score, or None after recording the
        no-op advance — ONE definition of the early return, shared by
        the sequential scorer and the batched serve scorer (so the two
        advance ``_scored_through`` identically)."""
        start = max(self._scored_through + 1, self.baseline_windows)
        if through < start:
            self._scored_through = max(self._scored_through, through)
            return None
        return start, through

    def ensure_baseline(self, plane: np.ndarray) -> dict:
        """The frozen calibration snapshot, computed from ``plane`` on
        first need.  Calibration reads only columns ``[0, B)``, so the
        batched serve scorer may pass a gathered ``[K, B, F]`` block —
        same values, same frozen statistics."""
        if self._baseline is None:
            self._baseline = self._calibrate(plane)
        return self._baseline

    @property
    def batch_scorable(self) -> bool:
        """True when scoring is exactly the base span-plane math — no
        edge rows, no modality planes — i.e. the serve engine's batched
        scorer (:func:`score_closed_windows_batched`) can score this
        detector in the vectorized pass with byte-identical results.
        Subclasses (the multimodal detector: per-tenant modality dicts)
        and edge-attributing detectors keep the sequential path."""
        return type(self) is OnlineDetector and not self.edge_attribution

    def finish(self) -> List[Alert]:
        """End of stream: the newest window with data counts as closed.

        Windows past the last span are never scored — an ended stream is
        not a fleet-wide outage, and scoring empty windows would fire the
        drop signal for every active service (the busiest loudest)."""
        return self._score_through(self._max_seen)

    # -- scoring ----------------------------------------------------------
    #
    # The three signals read straight off the aggregate plane's moments,
    # each normalized by the statistically right denominator for sparse
    # windows (a handful of spans per (service, window) is the realistic
    # regime — per-window-mean sigmas explode there):
    #   latency:    z = (mean_w - mu0) / sqrt(var_span0 / n_w)
    #               (standard error of the window mean; var_span0 pooled
    #                from the baseline spans via the E[x^2] plane)
    #   error rate: binomial z vs the pooled baseline rate
    #   throughput: Poisson z on MISSING spans — a killed service stops
    #               emitting, which latency/error z-scores cannot see
    #               (the reference's Lv_S kill faults fail exactly this way)

    def _calibrate(self, plane: np.ndarray) -> dict:
        """Freeze baseline statistics from plane columns [0, B).

        Called once, the first time scoring reaches the end of the
        calibration phase — before the ring can roll (B << n_windows), so
        the columns still hold absolute windows 0..B-1.  Frozen stats keep
        every later window scored against the SAME healthy reference even
        after the ring evicts those columns."""
        from anomod.replay import F_LOGLAT2
        B = self.baseline_windows
        if self.replay.window_offset > 0:
            raise RuntimeError(
                "stream jumped past the calibration phase before "
                f"{B} baseline windows closed (ring already rolled)")
        cnt = plane[..., F_COUNT]
        # pooled baseline per service (count-weighted, all B windows)
        C0 = np.maximum(cnt[:, :B].sum(axis=1), 1.0)
        mu_l = plane[:, :B, F_LOGLAT].sum(axis=1) / C0
        var_span = np.maximum(
            plane[:, :B, F_LOGLAT2].sum(axis=1) / C0 - mu_l ** 2, 1e-4)
        # Laplace-smoothed error rate: an all-clean baseline must not make
        # the first stray background error an infinite-z event — the +1/+2
        # prior keeps the binomial variance honest at small counts (one
        # error in a 6-span window on a 24-span clean baseline: z ~ 1.6,
        # vs ~13 with a raw rate and a hard variance floor)
        p_err = (plane[:, :B, F_ERR].sum(axis=1) + 1.0) / (C0 + 2.0)
        err_var = np.maximum(p_err * (1.0 - p_err), 1e-6)
        rate0 = cnt[:, :B].mean(axis=1)          # spans per baseline window
        # between-window baseline variance: endpoint-mix drift and traffic
        # burstiness are real window-to-window variation that the pure
        # within-window denominators (SE-of-mean, binomial, Poisson) do not
        # carry — without these terms a bursty-but-healthy service alerts
        # on every naturally quiet window
        bsafe = np.maximum(cnt[:, :B], 1.0)
        bvalid = cnt[:, :B] >= self.min_count
        nb = np.maximum(bvalid.sum(axis=1), 1)

        def _between_var(per_window):
            m = (per_window * bvalid).sum(axis=1) / nb
            return ((per_window - m[:, None]) ** 2 * bvalid).sum(axis=1) / nb

        # Sparse-row drift variance for the POOLED edge z: var_bl/var_be
        # above average only windows with >= min_count spans, so a row
        # whose every baseline window is thinner (the ~1 span/window edge
        # regime the pooled z exists for) gets 0 — no between-window
        # protection at all.  For those rows estimate drift from ALL
        # non-empty windows and subtract the sampling noise a window mean
        # of n̄ spans carries (E[observed between-var] = drift +
        # var_within/n̄), clamping at 0: a pure-Poisson sparse row prices
        # ~0 drift (keeping sensitivity), a genuinely bursty one keeps
        # its real drift term.
        bvalid1 = cnt[:, :B] >= 1.0
        nb1 = np.maximum(bvalid1.sum(axis=1), 1)
        nbar1 = np.maximum((cnt[:, :B] * bvalid1).sum(axis=1) / nb1, 1.0)

        def _between_var_any(per_window):
            m = (per_window * bvalid1).sum(axis=1) / nb1
            return ((per_window - m[:, None]) ** 2
                    * bvalid1).sum(axis=1) / nb1

        drift_l = np.maximum(
            _between_var_any(plane[:, :B, F_LOGLAT] / bsafe)
            - var_span / nbar1, 0.0)
        drift_e = np.maximum(
            _between_var_any(plane[:, :B, F_ERR] / bsafe)
            - err_var / nbar1, 0.0)
        var_bl = _between_var(plane[:, :B, F_LOGLAT] / bsafe)
        var_be = _between_var(plane[:, :B, F_ERR] / bsafe)

        out = dict(
            mu_l=mu_l, var_span=var_span, p_err=p_err, err_var=err_var,
            rate0=rate0, C0=C0,
            var_bl_pool=np.where(var_bl > 0, var_bl, drift_l),
            var_be_pool=np.where(var_be > 0, var_be, drift_e),
            active=rate0 >= self.min_count,   # per-window drop needs traffic
            # the cumulative drop accumulates evidence across windows, so
            # even ~1 span/window suffices — but a service with a near-zero
            # baseline rate has nothing measurable to lose
            cum_active=rate0 >= 1.0,
            # latency/error z need a calibrated baseline: a service unseen
            # (or barely seen) during calibration has a fabricated mu/var
            # and its first busy window would be a guaranteed false alert
            calibrated=C0 >= 2.0 * self.min_count,
            var_bl=var_bl, var_be=var_be,
            sd_cnt=np.sqrt(np.maximum(cnt[:, :B].var(axis=1),
                                      np.maximum(rate0, 1.0))))
        if self.edge_attribution:
            out.update(self._calibrate_edges(plane))
        return out

    def _calibrate_edges(self, plane: np.ndarray) -> dict:
        """Shrunk baselines for the SPARSE edge rows [S, 3S).

        Edge traffic is a fraction of node traffic, so at realistic
        densities an edge row's own baseline holds a handful of spans —
        a raw mean/variance from 1-5 spans is noise, and the old hard
        ``C0 >= min_count`` gate simply zeroed those rows (the
        sparse-density edge-locus collapse, docs/QUALITY.md).  Instead
        every edge row gets an empirical-Bayes baseline: its own stats
        shrunk toward a borrowed population with prior mass
        ``tau = 1.2*min_count`` —
          - SELF-edge rows borrow the same service's NODE row (their
            spans are a subset of it);
          - OUT-edge rows borrow the count-weighted pooled baseline of
            ALL out-edge rows, with the between-row spread of out-edge
            means priced into the variance (caller populations differ).
        The error channel gets a fleet null instead of the node plane's
        +1/+2 Laplace prior (which at C0=3 fabricates a 20% baseline
        error rate and swallows any real excess): posterior mean under a
        fleet-rate prior, doubled and floored at 0.5% as a drift-safety
        margin — scored by exact binomial tail (:func:`_binom_tail_z`),
        not a normal z, because 2 errors in 6 spans is not Gaussian."""
        from anomod.replay import F_LOGLAT2
        B = self.baseline_windows
        S = self._n_svc
        tau = 1.2 * self.min_count
        cnt = plane[..., F_COUNT]
        c = cnt[S:3 * S, :B].sum(axis=1)             # raw, unclamped
        s1 = plane[S:3 * S, :B, F_LOGLAT].sum(axis=1)
        s2 = plane[S:3 * S, :B, F_LOGLAT2].sum(axis=1)
        csafe = np.maximum(c, 1.0)
        own_mu = s1 / csafe
        own_var = np.maximum(s2 / csafe - own_mu ** 2, 1e-4)
        # borrowed population per row
        node_mu = np.tile(plane[:S, :B, F_LOGLAT].sum(axis=1)
                          / np.maximum(cnt[:S, :B].sum(axis=1), 1.0), 2)
        node_c = np.maximum(cnt[:S, :B].sum(axis=1), 1.0)
        node_var = np.tile(np.maximum(
            plane[:S, :B, F_LOGLAT2].sum(axis=1) / node_c
            - (node_mu[:S]) ** 2, 1e-4), 2)
        oc = c[S:]                                   # out-edge rows
        o_tot = max(float(oc.sum()), 1.0)
        mu_pop_out = float(s1[S:].sum()) / o_tot
        var_pop_out = max(float(s2[S:].sum()) / o_tot - mu_pop_out ** 2,
                          1e-4)
        good = oc >= 4
        if int(good.sum()) >= 3:
            between = float(np.average(
                (own_mu[S:][good] - mu_pop_out) ** 2, weights=oc[good]))
        else:
            between = 0.25 * var_pop_out
        pop_mu = node_mu.copy()
        pop_var = node_var.copy()
        pop_mu[S:] = mu_pop_out
        pop_var[S:] = var_pop_out + between
        w = c / (c + tau)
        mu_sh = w * own_mu + (1 - w) * pop_mu
        var_sh = np.where(c > 1, w * own_var + (1 - w) * pop_var, pop_var)
        # the borrowed prior is worth tau pseudo-spans of baseline mass in
        # the two-sample term — bounded confidence from borrowed data
        c_eff = c + tau
        # fleet error null (node plane pools every span once)
        p_fleet = float(plane[:S, :B, F_ERR].sum()
                        / max(float(cnt[:S, :B].sum()), 1.0))
        own_e = plane[S:3 * S, :B, F_ERR].sum(axis=1)
        p_null = np.clip((own_e + 2 * tau * p_fleet) / (c + 2 * tau)
                         * 2.0 + 0.005, 0.005, 0.5)
        return dict(edge_mu=mu_sh, edge_var=var_sh, edge_c_eff=c_eff,
                    edge_p_null=p_null)

    def _score_through(self, through: int) -> List[Alert]:
        """Score closed ABSOLUTE windows (scored_through, through]."""
        rng = self.scoring_window_range(through)
        if rng is None:
            return []
        start, through = rng
        plane = self.replay.agg_plane()
        b = self.ensure_baseline(plane)
        S, K = self._n_svc, self._K
        cnt = plane[..., F_COUNT]
        off = self.replay.window_offset
        # fleet-activity per column: a window where nobody reported is
        # feed silence, skipped below (never evidence for any service).
        # Node rows [0, S) see every span exactly once, so they alone
        # define fleet activity (edge rows are the same spans re-keyed).
        fleet = cnt[:S].sum(axis=0) > 0
        out: List[Alert] = []
        for w in range(start, through + 1):
            col = w - off
            if col < 0:          # evicted before it could be scored
                self._streak[:] = 0      # a gap breaks any consecutive run
                self._cusum[:] = 0.0
                self._cusum_k[:] = 0
                continue
            if not fleet[col]:
                # nobody at all reported in this window: that is feed
                # silence (collector outage / gap), not per-service
                # evidence — firing z_drop for EVERY active service would
                # be an alert storm carrying no localization signal.  The
                # silence also breaks hysteresis and the CUSUM run:
                # windows on either side of a gap are not consecutive
                self._streak[:] = 0
                self._cusum[:] = 0.0
                self._cusum_k[:] = 0
                continue
            # the per-window z math lives in window_span_z — ONE
            # definition with the batched serve scorer.  CUSUM evidence:
            # per-window Poisson z for a 2-3 spans/window service never
            # clears the threshold, but several windows of silence
            # accumulate to certainty.  Detection vs localization: alerts
            # fire on the raw z (sensitivity); the recorded ranking score
            # weights the drop signals by their deficit FRACTION
            # (specificity) — subclass modality planes (log/metric/api
            # z's) join both sides at full weight, they are per-service
            # direct evidence, not blast-radius carriers.
            z = window_span_z(plane[:, col], b, self._cusum,
                              self._cusum_k, self.min_count,
                              self.drop_memory)
            self._cusum = z["cusum"]
            self._cusum_k = z["cusum_k"]
            zl, ze, zd, zdc = z["zl"], z["ze"], z["zd"], z["zdc"]
            frac_w, frac_t = z["frac_w"], z["frac_t"]
            extras = self._modality_z(w)
            if K > S:
                # modality planes are node-scoped by construction; edge
                # rows carry span evidence only
                extras = {k: np.concatenate([v, np.zeros(K - S)])
                          for k, v in extras.items()}
            det_parts = dict(latency=zl, error=ze, drop=zd, cusum=zdc,
                             **extras)
            rank_parts = dict(latency=zl, error=ze, drop=zd * frac_w,
                              cusum=zdc * frac_t, **extras)
            detect_z = np.stack(list(det_parts.values())).max(axis=0)
            rank_stack = np.stack(list(rank_parts.values()))
            score = rank_stack.max(axis=0)
            ev_names = list(rank_parts)
            ev_idx = rank_stack.argmax(axis=0)
            hot = detect_z >= self.z_threshold
            if K > S:
                # Edge rows alert on span latency/error only: a per-edge
                # drop just mirrors node evidence (caller died / callee
                # died) at lower counts, and the drop z's blast-radius
                # caveats would apply per edge with no extra signal.
                # Edge traffic is a fraction of node traffic (each span
                # keys to ONE edge), so per-window edge counts sit below
                # min_count at realistic densities — the edge z pools a
                # VARIABLE-width window: walk back from the current
                # window until ``edge_mass`` spans accumulate, capped at
                # ``edge_pool`` windows of reach.  Mass-based pooling is
                # what fixes the sparse-density collapse the fixed
                # 8-window pool had: a thin edge reaches further back for
                # the same evidence mass, a dense one pools narrowly and
                # is not diluted by healthy windows.
                P = self.edge_pool
                plo = max(col - P + 1, 0)
                seg = plane[S:, plo:col + 1]
                rev_cnt = seg[..., F_COUNT][:, ::-1]
                cumc = rev_cnt.cumsum(axis=1)
                reach = cumc.shape[1]
                # Two-scale mass pooling, max over scales: the NARROW pool
                # walks back to ``edge_mass`` spans (a concentrated error
                # burst or latency spike scores undiluted); the WIDE pool
                # walks to one baseline-block's worth (C0 ~ B windows of
                # this row's traffic — the smoothing dense rows need, and
                # past n_p ~ C0 the baseline term dominates the variance
                # anyway so wider pooling only dilutes).  A thin row's two
                # scales coincide at the edge_mass floor.
                cuml = seg[..., F_LOGLAT][:, ::-1].cumsum(axis=1)
                cume = seg[..., F_ERR][:, ::-1].cumsum(axis=1)
                zl_p = np.zeros(2 * S)
                ze_p = np.zeros(2 * S)
                scales = (np.full(2 * S, self.edge_mass),
                          np.maximum(b["C0"][S:], self.edge_mass))
                n_p_wide = np.zeros(2 * S)  # wide-scale pooled counts,
                # captured explicitly for the self_ok gate below (must not
                # depend on which scale the loop happens to end on)
                for mass in scales:
                    m = mass[:, None]
                    has = cumc[:, -1:] >= m
                    kidx = np.where(
                        has, np.argmax(cumc >= m, axis=1, keepdims=True),
                        reach - 1)
                    n_p = np.take_along_axis(cumc, kidx, axis=1)[:, 0]
                    suml = np.take_along_axis(cuml, kidx, axis=1)[:, 0]
                    sume = np.take_along_axis(cume, kidx, axis=1)[:, 0]
                    safe_p = np.maximum(n_p, 1.0)
                    # the shrunk empirical-Bayes baselines
                    # (_calibrate_edges) replace the old hard
                    # C0 >= min_count gate: a thin-baseline row scores
                    # against its borrowed baseline, with the borrow
                    # priced as tau pseudo-spans in the two-sample term —
                    # only a minimal evidence mass is still required
                    ok_p = n_p >= min(3.0, self.edge_mass)
                    zl_p = np.maximum(zl_p, np.where(
                        ok_p,
                        (suml / safe_p - b["edge_mu"])
                        / np.sqrt(b["edge_var"] / safe_p
                                  + b["edge_var"] / b["edge_c_eff"]
                                  + b["var_bl_pool"][S:]),
                        0.0))
                    # error channel: exact binomial tail against the
                    # fleet null — only rows with >= 2 pooled errors can
                    # score (one stray background error must never be
                    # 4-sigma evidence)
                    for ei in np.nonzero(ok_p & (sume >= 2.0))[0]:
                        ze_p[ei] = max(ze_p[ei], _binom_tail_z(
                            int(sume[ei]), int(n_p[ei]),
                            float(b["edge_p_null"][ei])))
                    if mass is scales[1]:
                        n_p_wide = n_p
                # The SELF-edge channel is the node-vs-link locus
                # discriminator: a self-edge falsely hot on borrowed-
                # baseline noise reads as "node-borne in the callee" and
                # suppresses the caller's true out-edge attribution.  So
                # self rows keep the conservative gates (own baseline AND
                # evidence mass >= min_count) — the borrowed-baseline
                # liberalization is for OUT-edge attribution only.
                self_ok = (b["C0"][S:2 * S] >= self.min_count) & \
                    (n_p_wide[:S] >= self.min_count)
                zl_p[:S] = np.where(self_ok, zl_p[:S], 0.0)
                ze_p[:S] = np.where(self_ok, ze_p[:S], 0.0)
                span_z = np.concatenate(
                    [np.maximum(zl, ze)[:S], np.maximum(zl_p, ze_p)])
                # Out-edge alerting is two-tier: the pooled scan runs FAR
                # fewer effective tests than the node plane (one
                # correlated statistic per row vs S x W independent
                # windows), which earns a halved-sigma threshold; below
                # that, a row that UNIQUELY dominates the out-edge plane
                # by a wide margin is attribution-grade evidence even
                # sub-threshold (a scan where exactly one of S rows
                # stands out is a stronger event than one row crossing a
                # line).  Self-edge heat (the node-vs-link locus
                # discriminator) stays at the full node threshold —
                # mis-declaring "node-borne" flips rankings.
                hot[S:] = span_z[S:] >= self.z_threshold
                out_z = span_z[2 * S:]
                if os.environ.get("ANOMOD_EDGE_DEBUG"):
                    _t = int(out_z.argmax())
                    print(f"[edge] w{w} top={self.services[_t]} "
                          f"z={out_z[_t]:.2f} "
                          f"2nd={float(np.partition(out_z, -2)[-2]):.2f}")
                hot_hi = out_z >= self.z_threshold - 0.5
                if out_z.size >= 2:
                    top = int(out_z.argmax())
                    second = float(np.partition(out_z, -2)[-2])
                    # the dominance tier exists for rows whose baseline is
                    # STRUCTURALLY too thin to support the hi threshold; a
                    # well-calibrated dense row (C0 >= 4*min_count) that
                    # cannot reach hi is not signal-limited — letting it
                    # through would alert normal baselines on weak flukes
                    if (out_z[top] >= self.z_threshold - 1.5
                            and out_z[top] >= 1.2 * max(second, 1e-9)
                            and b["C0"][2 * S + top]
                            < 4.0 * self.min_count):
                        hot_hi[top] = True
                hot[2 * S:] |= hot_hi
            self._streak = np.where(hot, self._streak + 1, 0)
            for s in np.nonzero(self._streak[:S] >= self.consecutive)[0]:
                out.append(Alert(window=w, service=int(s),
                                 service_name=self.services[s],
                                 score=float(score[s]),
                                 z_latency=float(zl[s]),
                                 z_error=float(ze[s]),
                                 z_drop=float(zd[s]),
                                 z_drop_cum=float(zdc[s]),
                                 evidence=ev_names[int(ev_idx[s])]))
            if K > S:
                # self-edge heat is the node-vs-edge locus discriminator:
                # a NODE fault inflates the culprit's own-parented/root
                # spans (self-edge hot); a LINK fault leaves every self
                # -edge cool and only the culprit's out-edge slot hot
                self._self_hot |= span_z[S:2 * S] >= self.z_threshold
                for pi in np.nonzero(
                        self._streak[2 * S:] >= self.consecutive)[0]:
                    p = int(pi)
                    # if any callee of p shows a hot SELF-edge, the
                    # degradation is node-borne in that callee and the
                    # out-edge heat is its reflection — the node path
                    # owns the blame
                    callees = self._callees_of(p)
                    if callees and bool(
                            (span_z[S + np.fromiter(callees, np.int64)]
                             >= self.z_threshold).any()):
                        continue
                    slot = 2 * S + p
                    sc = float(span_z[slot])
                    self._edge_hot[p] = self._edge_hot.get(p, 0.0) + sc
                    out.append(Alert(window=w, service=p,
                                     service_name=self.services[p],
                                     score=sc,
                                     z_latency=float(zl_p[slot - S]),
                                     z_error=float(ze_p[slot - S]),
                                     z_drop=0.0, z_drop_cum=0.0,
                                     evidence="edge"))
        self._scored_through = through
        self._after_score(through)
        self.alerts.extend(out)
        return out

    def _after_score(self, through: int) -> None:
        """Hook after scoring advances (multimodal subclass prunes its
        per-window host state here)."""

    def _modality_z(self, w: int) -> dict:
        """Hook for extra per-window z planes (multimodal subclass)."""
        return {}

    # -- stream-mode quality metrics --------------------------------------

    def ranked_services(self) -> List[str]:
        """Culprit ranking: deepest anomalous dependency first.

        SUMMED alert scores per service (persistence is signal — a
        culprit sustains, a blast victim flickers), but a service with an
        anomalous service TRANSITIVELY downstream of it (reachable over
        the call graph) ranks after services with none — a gateway/caller whose
        error spike is (at least partly) explained by a misbehaving
        dependency must not outrank that dependency, no matter how
        statistically loud the blast radius is at the aggregation point,
        and a healthy-but-silent middle hop must not shield the caller.
        Reachability runs on the condensation (strongly-connected
        components collapse to one node), so mutual call edges between
        two anomalous services leave BOTH unexplained — peak order
        decides — instead of degenerating the whole ranking.  Needs
        ``call_edges``; without it, pure peak-score order."""
        peak: dict = {}
        total: dict = {}
        windows: dict = {}
        for a in self.alerts:
            peak[a.service] = max(peak.get(a.service, 0.0), a.score)
            total[a.service] = total.get(a.service, 0.0) + a.score
            windows.setdefault(a.service, set()).add(a.window)
        # edge-explained callees: a service whose anomaly is edge-borne —
        # hot incoming cross edge(s), self-edge never hot, and no direct
        # node-scoped modality evidence (a NODE fault degrades the
        # service's own logs/metrics; a link fault cannot) — is a blast
        # victim of the edge's CALLER, which already carries the edge
        # alerts.  It must neither outrank the caller nor "explain" the
        # caller away in the downstream walk.
        edge_explained: set = set()
        edge_dom: set = set()
        direct_node_ev: set = set()
        if self.edge_attribution and self._edge_hot:
            # node-borne modality evidence must SUSTAIN (>= 2 distinct
            # windows): a single 4-sigma log/metric window across S
            # services x W windows is expected multiple-testing noise,
            # and letting it certify a service as node-borne would both
            # shield blast victims from edge-explanation and explain
            # away a genuine edge culprit upstream of the noise
            mod_windows: dict = {}
            plane_groups: dict = {}   # evidence classification, shared
            # with the corroboration tier below (single source for the
            # log/metric/api-vs-span split)
            for a in self.alerts:
                g = a.evidence if a.evidence in ("log", "metric", "api") \
                    else "span"
                plane_groups.setdefault(a.service, set()).add(g)
                if g != "span":
                    mod_windows.setdefault(a.service, set()).add(a.window)
            direct_node_ev = {s for s, ws in mod_windows.items()
                              if len(ws) >= 2}
            # NOTE a known, irreducible single-modality corner: a leaf
            # callee with no own-parented spans (entry-only service)
            # shows IDENTICAL span evidence under "node fault in me" and
            # "link fault from my caller" — its self-edge has no traffic
            # to stay cool or go hot.  The ranking prefers the CALLER
            # (link) reading, which wins every edge-locus benchmark and
            # costs exactly one spans-only cell on SN (the multimodal
            # planes disambiguate it: node faults degrade the callee's
            # logs/metrics, link faults cannot — SN multimodal stays
            # 9/9).  Fan-out-parsimony and self-traffic gating were both
            # tried and measured WORSE on the edge benchmarks (they
            # surrender the caller attribution exactly where the link
            # signal is spread across thin callees).
            hot_children = {c for p in self._edge_hot
                            for c in self._callees_of(p)}
            for c in hot_children:
                if c in peak and not self._self_hot[c] \
                        and c not in direct_node_ev:
                    edge_explained.add(c)
            #: callers whose evidence is mostly edge-borne — their
            #: anomaly is ABOUT their outgoing links, so it must not be
            #: explained away by the blast those same links cause
            #: downstream (stalled traces thin downstream throughput,
            #: firing drop/cusum on the callees' subtrees)
            edge_dom = {p for p, eh in self._edge_hot.items()
                        if p in total and eh >= 0.5 * total[p]}
            if edge_dom:
                # upstream blast: callers of a link-faulted service stall
                # (their traces wait on the slow edge), firing drop/cusum
                # with peaks that can dwarf the culprit's edge z — the
                # walk's magnitude guard then refuses to explain them.
                # A service whose evidence is neither node-borne nor
                # edge-dominant, and from which an edge-dominant caller
                # is reachable, is that caller's blast radius.
                direct = {}
                for a, c in self.call_edges:
                    direct.setdefault(a, set()).add(c)

                def _reaches_edge_dom(q):
                    seen, frontier = {q}, [q]
                    while frontier:
                        nxt = direct.get(frontier.pop(), ())
                        for r in nxt:
                            if r in edge_dom:
                                return True
                            if r not in seen:
                                seen.add(r)
                                frontier.append(r)
                    return False

                for q in set(peak) - edge_dom - edge_explained:
                    if not self._self_hot[q] and q not in direct_node_ev \
                            and _reaches_edge_dom(q):
                        edge_explained.add(q)
        anomalous = set(peak) - edge_explained
        explained = _explained_by_downstream(self.call_edges, anomalous,
                                             peaks=peak, windows=windows)
        if edge_dom:
            # an edge-dominant caller yields only to NODE-borne anomalies
            # downstream (hot self-edge or direct modality evidence — a
            # real culprit living deeper), not to its own blast radius.
            # (A direct-callee-only variant was measured in round 4: it
            # keeps sparse edge culprits from being explained away by
            # unrelated downstream decoys, but costs the same number of
            # in-dist cells where a blast-heated caller must yield to a
            # node culprit whose self-edge is underpowered — net zero on
            # top1, so the general walk stays.)
            # Concentration refutation (round 5): sustained modality
            # evidence alone cannot certify a callee as node-borne when
            # the per-pair data says its edge-dominant caller's heat is
            # SPREAD across callees — under a link fault, planted decoys
            # downstream of the culprit carry exactly that signature and
            # were forcing the culprit to yield to them.  A callee the
            # caller's heat CONCENTRATES on keeps (indeed earns) its
            # node-borne status; with no pair data the old reading
            # stands.
            verdicts = {p: self._pair_verdict(p) for p in edge_dom}

            def _node_borne(s):
                if self._self_hot[s]:
                    return True
                if s not in direct_node_ev:
                    return False
                calling = [verdicts[p] for p in edge_dom
                           if verdicts[p] is not None
                           and s in self._callees_of(p)]
                # concentration wins over a spread refutation from some
                # other caller (one caller's heat pointing squarely at s
                # IS the node-culprit signature, and this must agree
                # with conc_exempt's any-caller semantics — never with
                # set iteration order)
                if any(v == ("concentrated", s) for v in calling):
                    return True
                return not any(v == ("spread", -1) for v in calling)
            node_borne = {s for s in anomalous if _node_borne(s)}
            strict = _explained_by_downstream(
                self.call_edges, node_borne | edge_dom,
                peaks=peak, windows=windows)
            explained = (explained - edge_dom) | (strict & edge_dom)

        # Plane-corroboration tier, active only when (a) an edge-dominant
        # candidate exists and (b) the run is genuinely multimodal (>= 2
        # evidence plane groups fired somewhere).  An out-edge alert is
        # precision-calibrated structural evidence — it survived a
        # dominance scan over the whole out-edge plane — while its z is
        # arithmetically small next to a raw 6-sigma log/metric window on
        # some unrelated service (S x W cells of multiple testing plus
        # planted confounders produce those routinely at sparse density).
        # The reorder is PAIRWISE, not a global tier: each edge-dominant
        # candidate lifts above the single-plane services ranked ahead of
        # it, and every pair NOT involving an edge-dominant candidate
        # keeps its magnitude order — a global tier was measured to cost
        # two in-dist cells by letting arbitrary services pass a
        # single-plane node culprit it had demoted.
        uncorroborated: set = set()
        if edge_dom and os.environ.get("ANOMOD_RANK_TIER", "1") != "0":
            groups = plane_groups
            if len(set().union(*groups.values())) >= 2:
                # span-plane evidence is exempt even alone: latency/error
                # /drop z is anchored to the service's own traffic (a node
                # culprit can legitimately be spans-only at sparse
                # density), while a lone log/metric/api plane with healthy
                # spans is exactly the planted-confounder shape
                # concentration exemption: when an edge-dominant
                # candidate's per-pair heat is CONCENTRATED on one
                # callee, that callee is the node-culprit reading of the
                # same picture (the caller's "edge evidence" is blast
                # pointing at it) — the bubble must not let the blast
                # outrank it.  Spread heat (the edge-locus signature)
                # exempts nobody, which is what lets sustained
                # single-plane decoys be demoted where the earlier
                # sustained-evidence exemption had to protect them.
                conc_exempt = {v[1] for v in verdicts.values()
                               if v is not None and v[0] == "concentrated"}
                # a SUSTAINED-modality service is demotable only under a
                # positive spread refutation (it is a callee of an
                # edge-dominant caller whose pair heat is spread); with
                # no pair data the node-culprit reading stands — absence
                # of evidence must not demote a real culprit
                spread_callees: set = set()
                for p, v in verdicts.items():
                    if v == ("spread", -1):
                        spread_callees |= self._callees_of(p)
                uncorroborated = {
                    s for s in total
                    if s not in edge_dom and not self._self_hot[s]
                    and s not in conc_exempt
                    and (s not in direct_node_ev or s in spread_callees)
                    and len(groups.get(s, ())) < 2
                    and "span" not in groups.get(s, ())}

        # ranking key: SUM of alert scores, not the single peak — a
        # culprit sustains its anomaly across the fault (many windows,
        # several evidence channels) while a blast-radius victim flickers;
        # persistence is signal the peak throws away.  Guards above still
        # compare peaks (comparable instantaneous strength).
        def key(s):
            return (s in explained or s in edge_explained, -total[s])

        order = sorted(total, key=key)
        if uncorroborated:
            # bubble each edge-dominant candidate above adjacent
            # uncorroborated services within the same explained tier:
            # exactly the pairs the corroboration argument covers move
            changed = True
            while changed:
                changed = False
                for i in range(len(order) - 1):
                    a, b = order[i], order[i + 1]
                    if a in uncorroborated and b in edge_dom \
                            and key(a)[0] == key(b)[0]:
                        order[i], order[i + 1] = b, a
                        changed = True
        return [self.services[s] for s in order]

    def first_alert_window(self, service_name: Optional[str] = None):
        ws = [a.window for a in self.alerts
              if service_name is None or a.service_name == service_name]
        return min(ws) if ws else None


def score_closed_windows_batched(work, gather_cols) -> int:
    """Score many detectors' newly closed windows in ONE vectorized pass.

    ``work`` is a list of ``(det, start, through)`` — ``batch_scorable``
    detectors (base span-plane math only) whose
    :meth:`OnlineDetector.scoring_window_range` returned ``(start,
    through)``.  ``gather_cols(items)`` materializes plane columns:
    ``items`` is a list of ``(work_index, col)`` pairs and the return is
    float32 ``[len(items), K, F]`` — the serve engine backs it with one
    fused device-pool gather per window (only the scored columns leave
    the device), host-state replays contribute plane views.

    This is the serving plane's batched COMMIT scorer: the per-window z
    math is :func:`window_span_z` (the sequential scorer's own core)
    applied with a leading tenant axis, and the threshold compare /
    hysteresis streak / CUSUM carry / alert construction run the same
    elementwise ops the per-tenant loop runs — so alerts, streaks, CUSUM
    state and ``_scored_through`` advance BYTE-identically to calling
    ``det._score_through(through)`` per tenant (pinned in
    tests/test_serve_state.py), while the per-tenant Python loop over
    plane readbacks and small-array z pipelines collapses into one
    stacked pass per closed window.  Calibration (a once-per-tenant
    event) gathers each tenant's baseline block through its own
    ``agg_plane()`` exactly as the sequential path would.

    Returns the number of alerts raised.
    """
    if not work:
        return 0
    dets = [d for d, _, _ in work]
    K = dets[0]._K
    assert all(d._K == K for d in dets), \
        "batched scoring needs a uniform service table"
    # calibrate first (the sequential path calibrates at the same
    # moment: the first _score_through that passes the early return)
    for det in dets:
        if det._baseline is None:
            det.ensure_baseline(det.replay.agg_plane())
    # stacked frozen baselines + mutable scoring state (written back at
    # the end; rows are per-tenant, so views never alias across tenants)
    bkeys = ("mu_l", "var_span", "var_bl", "p_err", "err_var", "var_be",
             "active", "cum_active", "calibrated", "rate0", "sd_cnt")
    b_all = {k: np.stack([d._baseline[k] for d in dets]) for k in bkeys}
    streak = np.stack([d._streak for d in dets])
    cusum = np.stack([d._cusum for d in dets])
    cusum_k = np.stack([d._cusum_k for d in dets])
    min_count = np.asarray([d.min_count for d in dets])[:, None]
    drop_memory = np.asarray([d.drop_memory for d in dets])[:, None]
    consecutive = np.asarray([d.consecutive for d in dets])[:, None]
    thr = np.asarray([d.z_threshold for d in dets])[:, None]
    offs = np.asarray([d.replay.window_offset for d in dets])
    new_alerts: dict = {t: [] for t in range(len(dets))}
    lo = min(s for _, s, _ in work)
    hi = max(t for _, _, t in work)
    for w in range(lo, hi + 1):
        act = np.asarray([s <= w <= t for _, s, t in work], bool)
        if not act.any():
            continue
        idx = np.nonzero(act)[0]
        cols = w - offs[idx]
        gathered = gather_cols(
            [(int(i), int(max(c, 0))) for i, c in zip(idx, cols)])
        # fleet activity per tenant (node rows see every span once);
        # a window nobody reported in is feed silence, and — exactly as
        # a column evicted before it could score — it breaks hysteresis
        # and the CUSUM run instead of becoming per-service evidence
        fleet = gathered[..., F_COUNT].sum(axis=1) > 0
        skip = (cols < 0) | ~fleet
        if skip.any():
            reset = idx[skip]
            streak[reset] = 0
            cusum[reset] = 0.0
            cusum_k[reset] = 0
        live = idx[~skip]
        if live.size == 0:
            continue
        z = window_span_z(gathered[~skip],
                          {k: v[live] for k, v in b_all.items()},
                          cusum[live], cusum_k[live],
                          min_count[live], drop_memory[live])
        cusum[live] = z["cusum"]
        cusum_k[live] = z["cusum_k"]
        # channel order = SPAN_EV_NAMES, the sequential part-dict order
        det_stack = np.stack([z["zl"], z["ze"], z["zd"], z["zdc"]])
        rank_stack = np.stack([z["zl"], z["ze"], z["zd"] * z["frac_w"],
                               z["zdc"] * z["frac_t"]])
        detect_z = det_stack.max(axis=0)
        score = rank_stack.max(axis=0)
        ev_idx = rank_stack.argmax(axis=0)
        hot = detect_z >= thr[live]
        streak[live] = np.where(hot, streak[live] + 1, 0)
        firing = streak[live] >= consecutive[live]
        for j, s in np.argwhere(firing):
            t = int(live[j])
            det = dets[t]
            new_alerts[t].append(Alert(
                window=w, service=int(s),
                service_name=det.services[s],
                score=float(score[j, s]),
                z_latency=float(z["zl"][j, s]),
                z_error=float(z["ze"][j, s]),
                z_drop=float(z["zd"][j, s]),
                z_drop_cum=float(z["zdc"][j, s]),
                evidence=SPAN_EV_NAMES[int(ev_idx[j, s])]))
    n_alerts = 0
    for t, (det, _, through) in enumerate(work):
        det._streak = streak[t].copy()
        det._cusum = cusum[t].copy()
        det._cusum_k = cusum_k[t].copy()
        det._scored_through = through
        det._after_score(through)
        det.alerts.extend(new_alerts[t])
        n_alerts += len(new_alerts[t])
    return n_alerts


class MultimodalDetector(OnlineDetector):
    """Online detector fusing all the time-resolved modalities.

    The offline detector scores five modalities at experiment granularity
    (anomod.detect.extract_features); this is its streaming counterpart:
    logs, metrics, and API responses accumulate into per-(service,
    absolute-window) host planes (kB/s volumes — the MXU plane is for
    spans) and contribute three per-service z signals to every closed
    window, fused with the span statistics in the base class:

    - ``log``: Laplace-smoothed binomial z on the window's log-error rate
      (collect_log.sh's error counting, made into a statistic);
    - ``metric``: per-SERIES |z| of the window mean vs its own frozen
      baseline (counters detected by monotone baseline means and
      rate-ified by window diffs, Prometheus-style), max over the
      service's series — this is the plane that localizes a killed
      sparse service (request-rate collapse, error-rate series,
      kube_pod restarts) when its span stream is too thin to matter;
    - ``api``: binomial z on per-owner-service probe error rates
      (endpoint→owner via the gateway route tables, as offline).

    Coverage is not time-resolved (end-of-run artifact) and stays
    offline-only.  Modalities must be pushed before the span push that
    closes their windows (stream_experiment_multimodal slices all four
    on one clock).
    """

    #: minimum lines/records in a window for its rate to be scored
    MIN_EVENTS = 3.0

    def __init__(self, batch_services: Sequence[str], cfg: ReplayConfig,
                 t0_us: int, testbed: Optional[str] = None, **kw):
        super().__init__(batch_services, cfg, t0_us, **kw)
        self.testbed = testbed
        self._t0_s = t0_us / 1e6
        self._win_s = cfg.window_us / 1e6
        self._svc_index = {s: i for i, s in enumerate(batch_services)}
        S = len(batch_services)
        self._S = S
        self._log_tot: dict = {}     # abs window -> [S] float
        self._log_err: dict = {}
        self._api_tot: dict = {}
        self._api_err: dict = {}
        # metric series: canonical key -> {"svc": id, "win": {w: [sum, n]}}
        self._met: dict = {}
        self._mm_base: Optional[dict] = None
        self._owner_cache: dict = {}

    def _windows_of(self, t_s: np.ndarray) -> np.ndarray:
        return ((t_s - self._t0_s) // self._win_s).astype(np.int64)

    def push_logs(self, lb) -> None:
        if lb is None or lb.n_lines == 0:
            return
        t0 = time.perf_counter()
        smap = np.array([self._svc_index.get(n, -1) for n in lb.services],
                        np.int32)
        svc = smap[lb.service]
        w = self._windows_of(lb.t_s)
        keep = (svc >= 0) & (w >= 0)
        err = keep & (lb.level == LOG_ERROR)
        for wv in np.unique(w[keep]):
            m = keep & (w == wv)
            tot = self._log_tot.setdefault(int(wv), np.zeros(self._S))
            np.add.at(tot, svc[m], 1.0)
            ev = self._log_err.setdefault(int(wv), np.zeros(self._S))
            me = err & (w == wv)
            np.add.at(ev, svc[me], 1.0)
        self.push_wall_s += time.perf_counter() - t0

    def push_metrics(self, mb) -> None:
        if mb is None or mb.n_samples == 0:
            return
        t0 = time.perf_counter()
        smap = np.array([self._svc_index.get(n, -1) for n in mb.services],
                        np.int32)
        w = self._windows_of(mb.t_s)
        finite = np.isfinite(mb.value)
        # one accumulator per (metric, label-set) PAIR: the schema allows
        # a producer to reuse one series id (label-set id) across metrics,
        # and pooling different metrics' values would poison the baseline
        nm = len(mb.metric_names)
        combo = mb.series.astype(np.int64) * nm + mb.metric
        ok = finite & (w >= 0)
        for cv in np.unique(combo[ok]):
            si, mi = int(cv) // nm, int(cv) % nm
            sv = mb.series_service[si]
            svc = int(smap[sv]) if sv >= 0 else -1
            if svc < 0:
                continue
            sel = ok & (combo == cv)
            key = f"{mb.metric_names[mi]}|{mb.series_keys[si]}"
            rec = self._met.setdefault(key, {"svc": svc, "win": {}})
            for wv, val in zip(w[sel], mb.value[sel]):
                acc = rec["win"].setdefault(int(wv), [0.0, 0])
                acc[0] += float(val)
                acc[1] += 1
        self.push_wall_s += time.perf_counter() - t0

    def push_api(self, ab) -> None:
        if ab is None or ab.n_records == 0:
            return
        t0 = time.perf_counter()
        from anomod.suite import endpoint_owner
        owner = np.empty(len(ab.endpoints), np.int32)
        for i, e in enumerate(ab.endpoints):
            if e not in self._owner_cache:
                self._owner_cache[e] = self._svc_index.get(
                    endpoint_owner(e, self.testbed or "TT"), -1)
            owner[i] = self._owner_cache[e]
        svc = owner[ab.endpoint]
        w = self._windows_of(ab.t_s)
        keep = (svc >= 0) & (w >= 0)
        err = keep & (ab.status >= 500)
        for wv in np.unique(w[keep]):
            m = keep & (w == wv)
            tot = self._api_tot.setdefault(int(wv), np.zeros(self._S))
            np.add.at(tot, svc[m], 1.0)
            ev = self._api_err.setdefault(int(wv), np.zeros(self._S))
            me = err & (w == wv)
            np.add.at(ev, svc[me], 1.0)
        self.push_wall_s += time.perf_counter() - t0

    # -- modality baselines + per-window z --------------------------------

    def _rate_baseline(self, tot: dict, err: dict) -> dict:
        B = self.baseline_windows
        T0 = np.zeros(self._S)
        E0 = np.zeros(self._S)
        rates = []
        for wv in range(B):
            t = tot.get(wv)
            if t is None:
                continue
            e = err.get(wv, np.zeros(self._S))
            T0 += t
            E0 += e
            with np.errstate(invalid="ignore", divide="ignore"):
                rates.append(np.where(t >= self.MIN_EVENTS, e / np.maximum(
                    t, 1.0), np.nan))
        p = (E0 + 1.0) / (T0 + 2.0)
        var = np.maximum(p * (1.0 - p), 1e-6)
        if rates:
            stack = np.stack(rates)           # [B_present, S], NaN = too few
            mask = np.isfinite(stack)
            n = np.maximum(mask.sum(axis=0), 1)
            mean = np.where(mask, stack, 0.0).sum(axis=0) / n
            var_b = np.where(mask, (stack - mean) ** 2, 0.0).sum(axis=0) / n
        else:
            var_b = np.zeros(self._S)
        return dict(p=p, var=var, var_b=var_b)

    def _metric_baseline(self) -> dict:
        B = self.baseline_windows
        out = {}
        for key, rec in self._met.items():
            means = {wv: s / n for wv, (s, n) in rec["win"].items() if n}
            base = [means[wv] for wv in range(B) if wv in means]
            if len(base) < 3:
                continue
            arr = np.asarray(base)
            counter = bool(np.all(np.diff(arr) >= -1e-12) and arr[-1] > arr[0])
            if counter:
                arr = np.diff(arr)
            mu = float(arr.mean())
            # relative sd floor: B windows underestimate a series' true
            # spread often enough that a tighter floor turns ordinary
            # gauge jitter into fake certainty (multiple testing over
            # every series x window)
            sd = float(max(arr.std(), 0.1 * (abs(mu) + 1.0)))
            out[key] = dict(svc=rec["svc"], mu=mu, sd=sd, counter=counter)
        return out

    def _series_z(self, key: str, b: dict, w: int) -> float:
        rec = self._met.get(key)
        if rec is None:
            return 0.0
        acc = rec["win"].get(w)
        if not acc or not acc[1]:
            return 0.0
        v = acc[0] / acc[1]
        if b["counter"]:
            prev = rec["win"].get(w - 1)
            if not prev or not prev[1]:
                return 0.0
            v = v - prev[0] / prev[1]
        return abs(v - b["mu"]) / b["sd"]

    def _mm_calibrate(self) -> None:
        self._mm_base = dict(
            log=self._rate_baseline(self._log_tot, self._log_err),
            api=self._rate_baseline(self._api_tot, self._api_err),
            met=self._metric_baseline())

    def _rate_z(self, w: int, tot: dict, err: dict, base: dict) -> np.ndarray:
        t = tot.get(w)
        if t is None:
            return np.zeros(self._S)
        e = err.get(w, np.zeros(self._S))
        ok = t >= self.MIN_EVENTS
        safe = np.maximum(t, 1.0)
        return np.where(ok, (e / safe - base["p"])
                        / np.sqrt(base["var"] / safe + base["var_b"]), 0.0)

    def _metric_z(self, w: int) -> np.ndarray:
        """Per-service metric z: max over the service's series of the
        SUSTAINED two-window z (min of this window's and the previous
        window's) — metric sampling noise is window-uncorrelated, fault
        effects persist, so the min clips single-window spikes that the
        per-series multiple testing would otherwise surface."""
        z = np.zeros(self._S)
        for key, b in self._mm_base["met"].items():
            zi = min(self._series_z(key, b, w),
                     self._series_z(key, b, w - 1))
            s = b["svc"]
            if zi > z[s]:
                z[s] = zi
        return z

    def _modality_z(self, w: int) -> dict:
        if self._mm_base is None:
            self._mm_calibrate()
        out = {}
        if self._log_tot:
            out["log"] = self._rate_z(w, self._log_tot, self._log_err,
                                      self._mm_base["log"])
        if self._api_tot:
            out["api"] = self._rate_z(w, self._api_tot, self._api_err,
                                      self._mm_base["api"])
        if self._mm_base["met"]:
            out["metric"] = self._metric_z(w)
        return out

    def _after_score(self, through: int) -> None:
        """Bound the per-window host planes: once calibrated, windows
        older than ``through - 1`` are never read again (counter diffs
        need one lookback), so evict them — the modality state stays
        O(ring), matching the span plane's bounded footprint on an
        unbounded live stream."""
        if self._mm_base is None:
            return
        cut = through - 1
        for d in (self._log_tot, self._log_err, self._api_tot,
                  self._api_err):
            for wv in [k for k in d if k < cut]:
                del d[wv]
        for rec in self._met.values():
            win = rec["win"]
            for wv in [k for k in win if k < cut]:
                del win[wv]


#: per-batch-type row fields (explicit — a shape heuristic would corrupt
#: a side table whose length coincidentally equals the sample count,
#: e.g. MetricBatch.series_service when n_series == n_samples)
_ROW_FIELDS = {
    "LogBatch": ("service", "t_s", "level"),
    "MetricBatch": ("metric", "series", "t_s", "value"),
    "ApiBatch": ("endpoint", "t_s", "status", "latency_ms",
                 "content_length"),
}


def _take_nt(nt, mask):
    """Row-subset of a NamedTuple batch: sample-axis fields masked, side
    tables kept whole."""
    fields = _ROW_FIELDS[type(nt).__name__]
    return nt._replace(**{f: getattr(nt, f)[mask] for f in fields})


def stream_experiment_multimodal(exp, cfg: Optional[ReplayConfig] = None,
                                 slice_s: float = 60.0, **detector_kw):
    """Replay a full experiment bundle — spans, logs, metrics, API — in
    arrival order through the multimodal online detector.  One clock
    slices all four modalities; within each slice the low-volume
    modalities are pushed first so their windows are populated before the
    span push closes them.  Returns the finished detector."""
    batch = exp.spans
    cfg = cfg or ReplayConfig(n_services=batch.n_services, chunk_size=4096)
    edges = set()
    if batch.n_spans:
        has_parent = batch.parent >= 0
        edges = set(zip(batch.service[batch.parent[has_parent]].tolist(),
                        batch.service[has_parent].tolist()))
    psvc = resolve_parent_services(batch)
    order = np.argsort(batch.start_us, kind="stable")
    batch = take_spans(batch, order)
    psvc = psvc[order]
    t0 = int(batch.start_us.min()) if batch.n_spans else 0
    det = MultimodalDetector(batch.services, cfg, t0, testbed=exp.testbed,
                             call_edges=edges, **detector_kw)
    if not batch.n_spans:
        det.finish()
        return det
    t0_s = t0 / 1e6
    end_s = float(batch.start_us.max()) / 1e6
    lo_s = t0_s
    while lo_s <= end_s:
        hi_s = lo_s + slice_s
        if exp.logs is not None and exp.logs.n_lines:
            det.push_logs(_take_nt(exp.logs, (exp.logs.t_s >= lo_s)
                                   & (exp.logs.t_s < hi_s)))
        if exp.metrics is not None and exp.metrics.n_samples:
            det.push_metrics(_take_nt(exp.metrics, (exp.metrics.t_s >= lo_s)
                                      & (exp.metrics.t_s < hi_s)))
        if exp.api is not None and exp.api.n_records:
            det.push_api(_take_nt(exp.api, (exp.api.t_s >= lo_s)
                                  & (exp.api.t_s < hi_s)))
        m = (batch.start_us >= lo_s * 1e6) & (batch.start_us < hi_s * 1e6)
        if m.any():
            det.push(take_spans(batch, m), parent_service=psvc[m])
        lo_s = hi_s
    det.finish()
    return det


def _explained_by_downstream(call_edges: set, anomalous: set,
                             peaks: Optional[dict] = None,
                             windows: Optional[dict] = None,
                             rho: float = 0.5) -> set:
    """Anomalous nodes explained by an anomalous node strictly downstream.

    Condense the call graph into strongly-connected components (iterative
    Tarjan), then mark an anomalous node "explained" iff some OTHER SCC
    reachable from its own contains an anomalous node that passes two
    guards (when the data is provided):

    - **magnitude** (``peaks``): the downstream anomaly's peak ranking
      score must be ≥ ``rho`` × the caller's — blame flows downstream
      only onto an anomaly of comparable strength; a marginal noise
      alert deep in the graph must not demote a loud true culprit above
      it;
    - **onset** (``windows``): the explanation must start WITH the
      symptom — the explainer's first alert may lag the caller's by at
      most 2 windows (sparse-culprit detection lag) but never more: a
      downstream victim that only turns anomalous 8 windows into the
      caller's sustained anomaly is a consequence, not a cause (the
      code-fault-in-the-caller case);
    - **concentration** (``windows``): the explainer's activity must
      either mostly fall inside the caller's anomalous interval (±1) or
      cover at least half of that interval — an "explainer" that mostly
      fires outside the symptom's period (scattered noise blips) explains
      nothing, while a sustained culprit that OUTLASTS a briefly-detected
      symptom still does.

    Nodes locked in a cycle with their only anomalous dependency stay
    unexplained — the edge direction carries no blame signal inside an
    SCC."""
    nodes = {n for e in call_edges for n in e} | set(anomalous)
    adj = {n: [] for n in nodes}
    for a, b in call_edges:
        adj[a].append(b)
    # iterative Tarjan SCC
    index = {}
    low = {}
    comp = {}
    stack, on_stack = [], set()
    counter = [0]
    n_comp = [0]
    for root in nodes:
        if root in index:
            continue
        work = [(root, iter(adj[root]))]
        index[root] = low[root] = counter[0]
        counter[0] += 1
        stack.append(root)
        on_stack.add(root)
        while work:
            v, it = work[-1]
            advanced = False
            for u in it:
                if u not in index:
                    index[u] = low[u] = counter[0]
                    counter[0] += 1
                    stack.append(u)
                    on_stack.add(u)
                    work.append((u, iter(adj[u])))
                    advanced = True
                    break
                if u in on_stack:
                    low[v] = min(low[v], index[u])
            if advanced:
                continue
            work.pop()
            if work:
                pv = work[-1][0]
                low[pv] = min(low[pv], low[v])
            if low[v] == index[v]:
                while True:
                    u = stack.pop()
                    on_stack.discard(u)
                    comp[u] = n_comp[0]
                    if u == v:
                        break
                n_comp[0] += 1
    # condensation adjacency + anomalous members per SCC
    canom = {}
    for n in anomalous:
        canom.setdefault(comp[n], set()).add(n)
    cadj = {}
    for a, b in call_edges:
        if comp[a] != comp[b]:
            cadj.setdefault(comp[a], set()).add(comp[b])
    # anomalous nodes in strictly-downstream SCCs.  Tarjan emits SCCs in
    # REVERSE topological order (every successor SCC is completed — gets a
    # smaller id — before its predecessors), so one pass over component
    # ids in emission order visits children before parents: no recursion,
    # no stack-depth limit (the reason Tarjan above is iterative too).
    memo = {}
    for c in range(n_comp[0]):
        acc = set()
        for d in cadj.get(c, ()):
            acc |= canom.get(d, set())
            acc |= memo.get(d, set())
        memo[c] = acc

    def downstream_anom(c):
        return memo[c]

    def guards_pass(n, b):
        if peaks is not None and peaks.get(b, 0.0) < rho * peaks.get(n, 0.0):
            return False
        if windows is not None:
            wn, wb = windows.get(n, set()), windows.get(b, set())
            if not wn or not wb:
                return False
            first_n, last_n = min(wn), max(wn)
            if min(wb) > first_n + 2:          # consequence, not cause
                return False
            inside = sum(1 for y in wb
                         if first_n - 1 <= y <= last_n + 1)
            span_n = last_n - first_n + 1
            if inside < 0.5 * len(wb) and inside < 0.5 * span_n:
                return False                   # scattered blips
        return True

    return {n for n in anomalous
            if any(guards_pass(n, b) for b in downstream_anom(comp[n]))}


def stream_quality(testbed: str = "TT", n_traces: int = 400, seed: int = 0,
                   experiments: Optional[Sequence[str]] = None,
                   multimodal: bool = False, severity: float = 1.0,
                   noise: float = 0.0, n_confounders: int = 0,
                   shift: str = "in-dist", **detector_kw) -> List[dict]:
    """Streaming-mode quality over the full fault taxonomy: one row per
    experiment with localization (top1/top3 among alerted services) and
    signed detection latency in windows (fault onset = window 10).  The
    streaming analog of detect.evaluate_corpus — measures what the
    offline sweep cannot: how FAST the fault surfaces.  ``experiments``
    filters to a subset by name (tests); ``multimodal`` fuses the
    log/metric/api planes (stream_experiment_multimodal); ``severity`` /
    ``noise`` / ``n_confounders`` de-saturate the generator via the SAME
    corpus builder as the offline quality sweep (rca.experiment_stream) —
    a streaming-vs-offline comparison at matching knobs scores identical
    difficulty; ``shift`` evaluates under the offline sweep's shifted
    generators (quality.SHIFTS: effect shape / fault timing / locus) —
    the detector is training-free, so this measures raw statistic
    robustness, e.g. whether bursty on/off faults defeat the CUSUM's
    recovery reset."""
    from anomod import synth
    from anomod.quality import SHIFTS
    from anomod.rca import experiment_stream
    # fault onset in WINDOWS follows the window width actually in use
    # (synth faults start at 600 s; a custom cfg rescales the grid)
    cfg = detector_kw.get("cfg")
    win_us = cfg.window_us if cfg is not None else 60_000_000
    onset_w = int(600_000_000 // win_us)
    hard = synth.HardMode(severity=severity, noise=noise, **SHIFTS[shift])
    rows = []
    for label, exp in experiment_stream(testbed, seed, n_traces=n_traces,
                                        hard=hard,
                                        n_confounders=n_confounders,
                                        experiments=experiments):
        det = (stream_experiment_multimodal(exp, **detector_kw) if multimodal
               else stream_experiment(exp.spans, **detector_kw))
        ranked = det.ranked_services()
        row = dict(experiment=label.experiment, testbed=testbed,
                   target_service=label.target_service,
                   n_alerts=len(det.alerts), ranked_top3=ranked[:3])
        if label.is_anomaly and label.target_service:
            fw = det.first_alert_window(label.target_service)
            row.update(
                top1_hit=bool(ranked) and ranked[0] == label.target_service,
                top3_hit=label.target_service in ranked[:3],
                first_culprit_alert_window=fw,
                detection_latency_windows=(None if fw is None
                                           else fw - onset_w))
        rows.append(row)
    return rows


def stream_experiment(batch: SpanBatch, cfg: Optional[ReplayConfig] = None,
                      slice_s: float = 60.0, **detector_kw):
    """Replay a corpus in arrival order through the online detector.

    Sorts spans by start time, slices the timeline into ``slice_s``-second
    micro-batches, and pushes each — the offline corpus standing in for a
    live feed.  Returns the finished :class:`OnlineDetector`.
    """
    cfg = cfg or ReplayConfig(n_services=batch.n_services, chunk_size=4096)
    # observed call graph from span parents — computed on the FULL batch
    # (time slices cut parent/child pairs across micro-batches, so the
    # caller of each span must be resolved before slicing)
    edges = set()
    if batch.n_spans and "call_edges" not in detector_kw:
        has_parent = batch.parent >= 0
        callers = batch.service[batch.parent[has_parent]]
        callees = batch.service[has_parent]
        edges = set(zip(callers.tolist(), callees.tolist()))
        detector_kw = dict(detector_kw, call_edges=edges)
    # parent services resolve on the FULL batch (same reason as edges:
    # slicing breaks the parent row indices), then ride the sort order
    psvc = resolve_parent_services(batch)
    order = np.argsort(batch.start_us, kind="stable")
    batch = take_spans(batch, order)
    psvc = psvc[order]
    t0 = int(batch.start_us.min()) if batch.n_spans else 0
    det = OnlineDetector(batch.services, cfg, t0, **detector_kw)
    if batch.n_spans:
        rel_s = (batch.start_us - t0) / 1e6
        bounds = np.searchsorted(
            rel_s, np.arange(slice_s, float(rel_s[-1]) + slice_s, slice_s))
        for lo, hi in zip(np.concatenate([[0], bounds]),
                          np.concatenate([bounds, [batch.n_spans]])):
            if hi > lo:
                sl = slice(int(lo), int(hi))
                det.push(take_spans(batch, sl), parent_service=psvc[sl])
    det.finish()
    return det
