"""Online root-cause inference inside the serve tick: alert → culprit.

The offline harness (anomod.rca) trains and evaluates RCA models
post-hoc; the serving plane (anomod.serve.engine) stopped at per-tenant
alerts.  This module is the bridge the paper's product implies: when a
tenant's ``OnlineDetector`` fires during a tick, run incremental GNN
culprit inference over that tenant's LIVE service graph — within the
serve SLO — and emit a ranked culprit list (:class:`RCAVerdict`).

Shape discipline is the serving plane's (the batcher's): inference runs
in a FIXED grid of padded ``(nodes, neighbors)`` bucket shapes
(``ANOMOD_SERVE_RCA_BUCKETS``), AOT-compiled once per bucket through the
same ``lower().compile()`` seam as the fused lane grid — so a sustained
run pays exactly one XLA compile per bucket (pinned via the registry
compile counters), never a mid-tick compile wall.  Neighbor lists use
SAMPLED aggregation (the VersaGNN / GNN-sampling-accelerator playbook,
PAPERS.md arXiv 2105.01280, 2209.02916): each node keeps at most K
seeded-uniformly-sampled callees, padded to the bucket's K — sample +
aggregate stays cheap and shape-stable at any live-graph degree.

Determinism contract (tests/test_serve_rca.py):

- the neighbor sampler is seeded by ``(RCA_SEED, tenant_id,
  alert_window)`` alone, and a verdict's evidence window is anchored to
  its TRIGGERING alert window (not the tick it ran in), so reruns of the
  same seed, N-shard vs 1-shard runs, and budget-delayed runs all
  produce byte-identical culprit rankings;
- RCA is a pure READ-side consumer of the alert stream and its own span
  buffers: detector states, alerts, admission, SLO and shed decisions
  are byte-identical with RCA on or off.

Node features come from the shared offline/online feature module
(anomod.rca_features — ONE definition with the training harness, parity
pinned in tests/test_rca_features.py) plus two alert-evidence channels;
the scorer itself is training-free blame propagation: per-node evidence
``e = x @ W`` (fixed documented weights), then ``ROUNDS`` rounds of
``h = e − β · mean(sampled callee h)`` — a caller whose degradation is
explained by a hot callee hands its blame downstream, so ranking
concentrates on the deepest anomalous node (the classic dependency-walk
RCA heuristic, here as a fixed-shape GNN message pass).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from anomod import obs
from anomod.config import validate_rca_buckets
from anomod.graph import build_service_graph
from anomod.rca_features import windowed_features
from anomod.replay import ReplayConfig
from anomod.schemas import SpanBatch, concat_span_batches, take_spans

#: feature width of the culprit scorer's node inputs: 4 per-window means
#: + 4 recent-vs-early trend deltas (anomod.rca_features) + 2 alert
#: evidence channels (max alert ranking score, max raw z)
N_RCA_FEATS = 10

#: the sampler seed root — a constant, so verdicts depend only on
#: (tenant stream, alert window), never on shard count or run order
RCA_SEED = 0x52CA

#: fixed evidence weights over the N_RCA_FEATS columns
#: [cnt_mean, err_mean, lat_mean, 5xx_mean,
#:  cnt_trend, err_trend, lat_trend, 5xx_trend, alert_score, alert_zmax]
#: — means carry no blame (a busy healthy service must not outrank a
#: quiet broken one); trends carry it (error/5xx jumps loudest, latency
#: next, a count DROP — negative trend — via the negative weight); the
#: detector's own alert evidence dominates (it already encodes the
#: calibrated per-service baselines the raw trends lack)
EVIDENCE_WEIGHTS = np.array(
    [0.0, 0.0, 0.0, 0.0, -0.5, 2.0, 1.0, 2.0, 1.0, 0.25], np.float32)

#: blame handed from a caller to its sampled callees per round
BLAME_SHIFT = 0.5
#: message-pass rounds (2 ≈ the call-depth of the testbed graphs)
RCA_ROUNDS = 2


@dataclasses.dataclass(frozen=True)
class RCAVerdict:
    """One alert→culprit inference result (JSON-able, byte-comparable:
    no wall-clock fields — run wall rides the engine's RCA SLO digest)."""
    tenant_id: int
    alert_window: int          # absolute window of the triggering alert
    alert_close_s: float       # virtual close time of that window
    enqueued_s: float          # virtual tick the alert entered the queue
    scored_s: float            # virtual tick the verdict was produced
    services: Tuple[str, ...]  # ranked culprits, best first (top-k)
    scores: Tuple[float, ...]  # their scores, same order
    n_spans: int               # evidence spans in the feature window
    n_edges: int               # live service-graph edges
    bucket: Tuple[int, int]    # (nodes, neighbors) shape it ran in

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["services"] = list(self.services)
        d["scores"] = list(self.scores)
        d["bucket"] = list(self.bucket)
        return d


def make_culprit_scorer():
    """The jittable fixed-shape scorer: evidence + sampled-neighbor
    blame propagation.  Inputs are one bucket's padded arrays
    (``x [N, F]``, ``neigh [N, K]`` int32, ``nmask [N, K]`` f32,
    ``node_mask [N]`` f32); dead pad rows score ``-inf`` so they can
    never enter a ranking."""
    import jax.numpy as jnp
    w = jnp.asarray(EVIDENCE_WEIGHTS)

    def score(x, neigh, nmask, node_mask):
        e = (x @ w) * node_mask
        h = e
        for _ in range(RCA_ROUNDS):
            msgs = h[neigh] * nmask                       # [N, K]
            agg = msgs.sum(-1) / jnp.maximum(nmask.sum(-1), 1.0)
            # only POSITIVE callee evidence de-blames the caller: a
            # healthy callee is no excuse, and a negative aggregate
            # must never amplify the caller's score
            h = e - BLAME_SHIFT * jnp.maximum(agg, 0.0)
        return jnp.where(node_mask > 0, h, -jnp.inf)

    return score


def sample_neighbors(g, k: int,
                     rng: np.random.Generator) -> Tuple[np.ndarray,
                                                        np.ndarray]:
    """``([S, k] callee ids, [S, k] f32 mask)`` — each node's observed
    callees sampled WITHOUT replacement down to ``k`` (seeded; kept in
    CSR order so a node at/below the cap is exact, not resampled).  The
    fixed-width sample is what keeps the aggregate shape-stable at any
    live-graph degree (the VersaGNN bucket discipline)."""
    S = g.n_services
    neigh = np.zeros((S, k), np.int32)
    mask = np.zeros((S, k), np.float32)
    for i in range(S):
        cal = g.neighbors[i][g.neighbor_mask[i]]
        if cal.shape[0] > k:
            sel = np.sort(rng.choice(cal.shape[0], size=k, replace=False))
            cal = cal[sel]
        m = cal.shape[0]
        neigh[i, :m] = cal
        mask[i, :m] = 1.0
    return neigh, mask


def online_node_features(batch: Optional[SpanBatch], services,
                         cfg: ReplayConfig) -> np.ndarray:
    """[S, 8] online node features: per-window means + recent-vs-early
    trend deltas of the SHARED windowed extractor
    (anomod.rca_features.windowed_features — the offline harness's exact
    feature code, so online and offline RCA can never drift)."""
    S = len(services)
    if batch is None or batch.n_spans == 0:
        return np.zeros((S, 8), np.float32)
    wf = windowed_features(batch, tuple(services), cfg)       # [S, W, 4]
    q = max(cfg.n_windows // 4, 1)
    mean = wf.mean(axis=1)
    trend = wf[:, -q:].mean(axis=1) - wf[:, :q].mean(axis=1)
    return np.concatenate([mean, trend], axis=-1).astype(np.float32)


class RcaRunner:
    """The compile-once-per-bucket culprit-scorer dispatcher (the RCA
    twin of :class:`anomod.serve.batcher.BucketRunner`): one jit of the
    scorer, AOT ``lower().compile()``d per (nodes, neighbors) bucket,
    compile wall + counts recorded in the runner AND the registry
    (``anomod_serve_rca_compile_total`` — the exactly-one-compile-per-
    bucket pin reads these)."""

    def __init__(self, buckets: Optional[tuple] = None, registry=None):
        import jax
        from anomod.config import get_config
        if buckets is None:
            buckets = get_config().serve_rca_buckets
        self.buckets = validate_rca_buckets(buckets)
        self._reg = registry if registry is not None else obs.get_registry()
        self._fn = jax.jit(make_culprit_scorer())
        self._exec: Dict[Tuple[int, int], object] = {}
        self.compile_s_by_bucket: Dict[Tuple[int, int], float] = {}
        self.runs_by_bucket: Dict[Tuple[int, int], int] = {}
        self._obs_runs = self._reg.counter("anomod_serve_rca_runs_total")

    def bucket_for(self, n_services: int) -> Tuple[int, int]:
        """The smallest bucket whose node count holds ``n_services``."""
        for n, k in self.buckets:
            if n >= n_services:
                return (n, k)
        raise ValueError(
            f"no RCA bucket holds {n_services} services (grid "
            f"{self.buckets}; raise ANOMOD_SERVE_RCA_BUCKETS)")

    def _dead_args(self, n: int, k: int) -> tuple:
        return (np.zeros((n, N_RCA_FEATS), np.float32),
                np.zeros((n, k), np.int32),
                np.zeros((n, k), np.float32),
                np.zeros(n, np.float32))

    def _exec_for(self, key: Tuple[int, int], args: tuple):
        exe = self._exec.get(key)
        if exe is None:
            t0 = time.perf_counter()
            exe = self._fn.lower(*args).compile()
            self._exec[key] = exe
            wall = time.perf_counter() - t0
            self.compile_s_by_bucket[key] = wall
            self._reg.counter("anomod_serve_rca_compile_total").inc()
            self._reg.counter(
                "anomod_serve_rca_compile_seconds_total").inc(wall)
        return exe

    def warm(self) -> float:
        """Compile the whole bucket grid on dead inputs (outside any
        measured wall); returns the total compile wall; idempotent."""
        total = 0.0
        for n, k in self.buckets:
            if (n, k) in self.compile_s_by_bucket:
                continue
            args = self._dead_args(n, k)
            exe = self._exec_for((n, k), args)
            np.asarray(exe(*args))              # compile+execute barrier
            total += self.compile_s_by_bucket[(n, k)]
        return total

    @property
    def compile_s(self) -> float:
        return float(sum(self.compile_s_by_bucket.values()))

    @property
    def bucket_shapes(self) -> set:
        """Every (nodes, neighbors) bucket compiled so far."""
        return set(self.compile_s_by_bucket)

    def score(self, x: np.ndarray, neigh: np.ndarray, nmask: np.ndarray,
              node_mask: np.ndarray) -> np.ndarray:
        """Run one padded bucket through its compiled executable."""
        key = (int(x.shape[0]), int(neigh.shape[1]))
        exe = self._exec_for(key, (x, neigh, nmask, node_mask))
        out = np.asarray(exe(x, neigh, nmask, node_mask))
        self.runs_by_bucket[key] = self.runs_by_bucket.get(key, 0) + 1
        self._obs_runs.inc()
        return out


class OnlineRCA:
    """Per-shard online-RCA plane: bounded span buffers (the live
    service-graph source) + the bucketed culprit scorer.

    The engine buffers each tenant's SERVED spans here (coordinator
    side, so buffer content is shard-count-invariant), and — when that
    tenant's detector fires — calls :meth:`run` on the shard that owns
    the tenant.  A verdict's evidence is anchored to its triggering
    alert window: the feature extractor reads exactly the ``windows``
    windows ENDING at the alert window, so a budget-delayed run scores
    the same evidence a same-tick run would.
    """

    def __init__(self, services: Sequence[str], window_us: int, t0_us: int,
                 runner: RcaRunner, topk: int = 5, windows: int = 8,
                 seed: int = RCA_SEED):
        self.services = tuple(services)
        S = len(self.services)
        self._svc_index = {s: i for i, s in enumerate(self.services)}
        self.cfg = ReplayConfig(n_services=S, n_windows=int(windows),
                                window_us=int(window_us), chunk_size=4096)
        self.runner = runner
        runner.bucket_for(S)        # fail loud at construction, not mid-tick
        self.topk = min(int(topk), S)
        self.windows = int(windows)
        self.window_us = int(window_us)
        self.t0_us = int(t0_us)
        self.seed = int(seed)
        self._buf: Dict[int, List[SpanBatch]] = {}
        self._buf_hi: Dict[int, int] = {}

    def buffer(self, tenant_id: int, batch: SpanBatch,
               keep_window: Optional[int] = None) -> None:
        """Append a served micro-batch to the tenant's evidence buffer,
        pruning batches that fell entirely out of feature reach (one
        extra window of slack: a verdict's window range ends at its
        alert window, which trails the newest buffered span).

        ``keep_window`` floors the pruning at the oldest QUEUED alert
        window for this tenant: a budget-delayed run must still find
        its full ``[keep_window+1-windows, keep_window+1)`` evidence
        range in the buffer, no matter how far the live stream has run
        ahead of the queue (the delayed-run determinism clause)."""
        if batch.n_spans == 0:
            return
        buf = self._buf.setdefault(tenant_id, [])
        buf.append(batch)
        hi = max(self._buf_hi.get(tenant_id, 0), int(batch.start_us.max()))
        self._buf_hi[tenant_id] = hi
        cutoff = hi - (self.windows + 1) * self.window_us
        if keep_window is not None:
            cutoff = min(
                cutoff,
                self.t0_us + (keep_window + 1 - self.windows)
                * self.window_us)
        while buf and int(buf[0].start_us.max()) < cutoff:
            buf.pop(0)

    def move_tenant_evidence(self, other: "OnlineRCA",
                             tenant_id: int) -> None:
        """Hand one tenant's evidence buffer (and its high-water mark)
        to ``other`` — the migration seam for dead-shard recovery
        (anomod.serve.supervise) and elastic scaling
        (anomod.serve.engine), so neither reaches into the private
        buffer dicts.  A tenant with no buffered evidence is a no-op;
        batches move by reference (they are immutable)."""
        buf = self._buf.pop(tenant_id, None)
        hi = self._buf_hi.pop(tenant_id, None)
        if buf is not None:
            other._buf[tenant_id] = buf
        if hi is not None:
            other._buf_hi[tenant_id] = hi

    def _evidence_batch(self, tenant_id: int,
                        alert_window: int) -> Optional[SpanBatch]:
        lo = self.t0_us + (alert_window + 1 - self.windows) * self.window_us
        hi = self.t0_us + (alert_window + 1) * self.window_us
        parts = []
        for b in self._buf.get(tenant_id, ()):
            m = (b.start_us >= lo) & (b.start_us < hi)
            if m.any():
                parts.append(take_spans(b, m))
        if not parts:
            return None
        return parts[0] if len(parts) == 1 else concat_span_batches(parts)

    def run(self, tenant_id: int, alert_window: int, alerts,
            enqueued_s: float,
            scored_s: float) -> Tuple[RCAVerdict, float]:
        """One alert→culprit inference; returns ``(verdict, wall_s)``
        (wall kept out of the verdict so verdicts stay byte-comparable
        across reruns and shard counts)."""
        t0 = time.perf_counter()
        S = len(self.services)
        batch = self._evidence_batch(tenant_id, alert_window)
        feats = online_node_features(batch, self.services, self.cfg)
        ev = np.zeros((S, 2), np.float32)
        lo_w = alert_window - self.windows
        for a in alerts:
            if not (lo_w < a.window <= alert_window):
                continue
            i = self._svc_index.get(a.service_name)
            if i is None:
                continue
            ev[i, 0] = max(ev[i, 0], np.float32(a.score))
            ev[i, 1] = max(ev[i, 1], np.float32(
                max(a.z_latency, a.z_error, a.z_drop, a.z_drop_cum)))
        x = np.concatenate([feats, ev], axis=-1)
        n, k = self.runner.bucket_for(S)
        xp = np.zeros((n, N_RCA_FEATS), np.float32)
        xp[:S] = x
        node_mask = np.zeros(n, np.float32)
        node_mask[:S] = 1.0
        neigh = np.zeros((n, k), np.int32)
        nmask = np.zeros((n, k), np.float32)
        n_edges = 0
        if batch is not None:
            g = build_service_graph(batch, services=self.services)
            n_edges = g.n_edges
            rng = np.random.default_rng(
                (self.seed, tenant_id, alert_window))
            sn, sm = sample_neighbors(g, k, rng)
            neigh[:S] = sn
            nmask[:S] = sm
        scores = self.runner.score(xp, neigh, nmask, node_mask)[:S]
        # stable descending rank, ties to the lower service index
        order = np.lexsort((np.arange(S), -scores))[:self.topk]
        verdict = RCAVerdict(
            tenant_id=int(tenant_id),
            alert_window=int(alert_window),
            alert_close_s=round(
                (self.t0_us + (alert_window + 1) * self.window_us) / 1e6, 6),
            enqueued_s=round(float(enqueued_s), 6),
            scored_s=round(float(scored_s), 6),
            services=tuple(self.services[i] for i in order),
            scores=tuple(round(float(scores[i]), 6) for i in order),
            n_spans=int(batch.n_spans) if batch is not None else 0,
            n_edges=int(n_edges),
            bucket=(n, k))
        return verdict, time.perf_counter() - t0
