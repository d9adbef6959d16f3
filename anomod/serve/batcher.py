"""Dynamic micro-batching into fixed padded bucket shapes, with a FUSED
lane-stacked dispatch path for the multi-tenant tick loop.

The serving plane's hot path is the SAME jitted chunk step the batch
replay scans with (anomod.replay.make_chunk_step) — but tenant
micro-batches are small and ragged, and staging every 150-span batch
into a 32768-wide chunk wastes 99% of each dispatch.  The batcher pads
each admitted micro-batch to the smallest shape from a FIXED bucket set
(``ANOMOD_SERVE_BUCKETS``), so XLA compiles the step once per bucket
width and every later dispatch of that width reuses the executable.

On top of the width buckets, the FUSED path (``ANOMOD_SERVE_FUSE``,
default on) batches across TENANTS: per engine tick, same-width staged
chunks from many tenants stack into ``[lanes, width]`` arrays and run as
ONE dispatch of the lane-stacked chunk step
(anomod.replay.make_lane_delta), with lane counts padded up to a small
fixed bucket set (``ANOMOD_SERVE_LANE_BUCKETS``) so XLA compiles once
per (width, lane-bucket) shape.  Dead pad lanes carry all-pad rows and
their outputs are dropped — the corresponding tenants' states pass
through untouched.  This is the power-law-fleet shape: many small
irregular work items, one wide regular kernel (cf. the Sparse-Allreduce
and VersaGNN batched-aggregation framings in PAPERS.md).

Replay parity is exact by construction, at every level:

- WIDTH buckets: a batch is split at ``cfg.chunk_size`` boundaries (full
  chunks stage exactly as the sequential StreamReplay would) and only
  the TAIL remainder is padded to a bucket.  Padding rows target the
  dead lane (sid = cfg.sw, valid = 0), whose contribution to every live
  segment is exactly 0.0 — and the real rows occupy the same leading
  positions they would in the sequential staging — so the f32 state
  after a bucketed push is BIT-IDENTICAL to the sequential fixed-chunk
  push on CPU (tests/test_serve.py pins this, alert stream included).
- STEP engine: on XLA:CPU the runner dispatches the scatter
  (segment-sum) formulation of the chunk step, pinned bit-identical to
  the one-hot matmul formulation there (anomod.replay.make_chunk_step's
  engine contract) — ~10x faster on a host core, same bits.
- LANE stacking: each lane of the fused dispatch reduces its own rows in
  the same order the single-lane dispatch would, and the per-lane DELTA
  is folded into the tenant's state with the same elementwise f32 add
  the in-step update performs — so a fused tick's states (and therefore
  the alert stream) are BIT-IDENTICAL to dispatching every tenant's
  chunks one by one (tests/test_serve.py pins this too).  The fused
  surface follows the step engine on every backend unless
  ``ANOMOD_SERVE_LANE_ENGINE=pallas`` opts into the single Mosaic
  kernel, whose latency moments carry the bf16 hi/lo envelope instead
  of matching bit-for-bit (anomod.replay.default_lane_engine).

STAGING is interpreter-free end to end (``ANOMOD_NATIVE``): the pinned
``[lanes, width]`` scratch slots are 64-byte-aligned host buffers the
AOT executables may alias zero-copy on XLA:CPU, and the packing of
drained micro-batches into them (live rows + dead-chunk fills) runs
through the C++ ``stage_lanes`` entry (anomod.io.native) with the GIL
RELEASED — byte-identical to the interpreter fill (pinned), but staging
for scratch slot k+1 overlaps the in-flight dispatch on slot k, and
shard workers stage concurrently instead of convoying on the GIL.  The
per-dispatch stage/dispatch/fold walls are accounted separately
(``anomod_serve_{stage,dispatch,fold}_seconds_total``; the benchmark's
``dispatch_ms`` and ``fold_wait_ms`` read them), so the serving-overhead
decomposition is measured, not prose.

:class:`BucketedStreamReplay` duck-types :class:`anomod.stream.StreamReplay`
(it subclasses it and overrides only the dispatch), so
``OnlineDetector(..., replay=...)`` runs the full alerting stack over the
shared bucket runner unchanged — thousands of tenants share ONE compiled
step per (width, lane-bucket) shape instead of compiling per tenant.
"""

from __future__ import annotations

import collections
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from anomod import obs
from anomod.config import DEFAULT_SERVE_BUCKETS as DEFAULT_BUCKETS
from anomod.config import validate_lane_buckets
from anomod.config import validate_serve_buckets as validate_buckets
from anomod.io import native as native_io
from anomod.replay import (N_FEATS, STAGE_KEYS, ReplayConfig, ReplayState,
                           TenantStatePool, dead_chunk,
                           default_lane_engine, default_step_engine,
                           fold_delta, make_chunk_step, make_lane_delta,
                           named_jit, stage_columns_fused)
from anomod.schemas import SpanBatch
from anomod.stream import StreamReplay
from anomod.utils.tracing import span_of


def split_plan(n_spans: int, chunk_size: int,
               buckets: Tuple[int, ...]) -> List[Tuple[int, int, int]]:
    """(lo, hi, staged_width) slices for one micro-batch.

    Full ``chunk_size`` slices first (identical to sequential staging),
    then the tail remainder padded to the smallest bucket that holds it
    (``chunk_size`` itself when every bucket is narrower).  This is the
    ONE definition of the parity-preserving split, shared by the runner
    and its tests.
    """
    plan: List[Tuple[int, int, int]] = []
    lo = 0
    while n_spans - lo >= chunk_size:
        plan.append((lo, lo + chunk_size, chunk_size))
        lo += chunk_size
    rem = n_spans - lo
    if rem > 0:
        width = next((b for b in buckets if b >= rem and b <= chunk_size),
                     chunk_size)
        plan.append((lo, n_spans, width))
    return plan


class BucketRunner:
    """The shared compile-once-per-shape chunk-step dispatcher.

    One ``jax.jit`` of the shared chunk step serves every tenant; XLA
    compiles one executable per distinct chunk width (= per bucket, plus
    the full ``cfg.chunk_size``), tracked in ``compile_s_by_width`` /
    ``dispatches_by_width`` for the ServeReport.  The FUSED path adds
    one jit of the lane-stacked delta kernel, compiled once per
    (width, lane-bucket) shape (``lane_shapes`` / ``lane_compile_s``).
    """

    def __init__(self, cfg: ReplayConfig,
                 buckets: Optional[Tuple[int, ...]] = None,
                 lane_buckets: Optional[Tuple[int, ...]] = None,
                 engine: Optional[str] = None, registry=None,
                 pipeline: int = 1,
                 native_stage: Optional[bool] = None,
                 lane_engine: Optional[str] = None,
                 state: Optional[str] = None,
                 pool_slots: int = 32,
                 tracer=None):
        from anomod.config import get_config
        if buckets is None:
            buckets = get_config().serve_buckets
        if lane_buckets is None:
            lane_buckets = get_config().serve_lane_buckets
        if pipeline < 1:
            raise ValueError("pipeline depth must be >= 1")
        self.cfg = cfg
        #: tenant-state residency (the validated ANOMOD_SERVE_STATE knob
        #: unless the caller overrides): "device" owns a per-runner
        #: TenantStatePool — tenants map to slots at first service, the
        #: retire fold is an on-device scatter-add in dispatch order,
        #: pinned BIT-identical to the host seam — "host" is the
        #: per-tenant numpy pytree seam.  "auto" resolves to device on
        #: every backend: the pool performs the exact same f32 adds, so
        #: there is no tolerance trade to gate on.
        _state = state if state is not None else get_config().serve_state
        if _state not in ("auto", "host", "device"):
            raise ValueError(f"unknown serve state mode {_state!r} "
                             "(auto|host|device)")
        self.state_mode = "device" if _state == "auto" else _state
        _lane_eng = lane_engine if lane_engine is not None else \
            (engine if engine is not None else default_lane_engine())
        #: the shard's device-resident state pool (None on the host
        #: seam).  ANOMOD_SERVE_LANE_ENGINE=pallas routes the pool's
        #: batched-scoring gather to the fused Mosaic kernel too (the
        #: same TPU opt-in; bit-identical — a pure copy either way).
        self.pool = (TenantStatePool(
            cfg, capacity=max(int(pool_slots), 1),
            gather_engine="pallas" if _lane_eng == "pallas" else "xla")
            if self.state_mode == "device" else None)
        #: GIL-free native scratch packing (anomod.io.native.stage_lanes):
        #: resolved from the validated ANOMOD_NATIVE knob (auto/on/off)
        #: unless the caller overrides (a python-staging reference run
        #: passes False); byte-identical either way
        self.native_stage = native_io.staging_enabled(native_stage)
        #: metric sink: the sharded engine hands each shard's runner its
        #: OWN registry (thread-isolated hot path; merged into the
        #: process registry at the tick barrier) — default is the
        #: process registry, exactly as before
        self._reg = registry if registry is not None else obs.get_registry()
        #: the engine's tracer (``span(name, **tags)``), or None: one
        #: span per lane fill, lane dispatch and fold retire — per
        #: dispatch, never per batch or per tenant
        self.tracer = tracer
        #: max in-flight fused dispatches is ``pipeline - 1`` (depth 1 =
        #: fully synchronous, the pre-pipelining behavior); the submit/
        #: drain path keeps ``pipeline`` pinned scratch slots per
        #: (width, lane-bucket) shape so staging slot s+1 never touches
        #: buffers an in-flight dispatch still reads
        self.pipeline = int(pipeline)
        self.buckets = validate_buckets(buckets)
        self.lane_buckets = validate_lane_buckets(lane_buckets)
        #: chunk-step engine: scatter on XLA:CPU (bit-identical, ~10x),
        #: the one-hot bf16 matmul on accelerators (the MXU shape)
        self.engine = engine if engine is not None else \
            default_step_engine()
        #: fused lane-dispatch engine: an explicit ``engine=`` pins both
        #: surfaces to one formulation (the parity tests rely on that);
        #: otherwise default_lane_engine — the ANOMOD_SERVE_LANE_ENGINE
        #: knob when set (``pallas`` = the single fused Mosaic kernel,
        #: a deliberate TPU opt-in whose latency moments carry the bf16
        #: hi/lo envelope), else the step engine itself so fused and
        #: single-chunk dispatch stay BIT-identical on every backend
        self.lane_engine = _lane_eng
        step = make_chunk_step(cfg, with_hll=False, engine=self.engine)
        self._step = named_jit("anomod_chunk_step",
                               lambda st, ch: step(st, ch)[0])
        self._lane_fn = named_jit(
            "anomod_lane_delta",
            make_lane_delta(cfg, engine=self.lane_engine))
        #: AOT-compiled lane executables, one per (width, lane-bucket)
        #: shape: calling the compiled object skips the pjit python
        #: dispatch path (~5-10 ms per call on this class of host for
        #: the 7-column chunk dict — a third of the whole dispatch wall)
        #: and is bit-identical to calling the jit (same HLO, same
        #: executable)
        self._lane_exec: Dict[Tuple[int, int], object] = {}
        self.compile_s_by_width: Dict[int, float] = {}
        #: one compile wall per fused (width, lane-bucket) shape — the
        #: compile-count pin asserts this never grows past the warm grid
        self._lane_compile_s: Dict[Tuple[int, int], float] = {}
        self.dispatches_by_width: Dict[int, int] = {}
        self.n_dispatches = 0
        self.fused_dispatches = 0
        #: the serve tick's wall decomposition: host packing (stage_plan
        #: + scratch fill), dispatch issue (the executable call — an ENQUEUE wall
        #: on async backends), and fold (output materialization — the
        #: execute barrier — plus the per-lane state adds).  What the
        #: serve wall spends OUTSIDE these three is admission/detector/
        #: bookkeeping time.
        self.stage_wall_s = 0.0
        self.dispatch_wall_s = 0.0
        self.fold_wall_s = 0.0
        #: window-scoring wall (the engine's COMMIT phase adds here, so
        #: the decomposition splits the old ``other`` leg into score vs
        #: true bookkeeping)
        self.score_wall_s = 0.0
        #: fused dispatches whose scratch was packed natively (GIL-free)
        self.native_staged = 0
        #: fused dispatches per lane-bucket (the lanes histogram's
        #: deterministic report twin)
        self.lanes_by_bucket: Dict[int, int] = {}
        self.staged_lanes = 0
        self.live_lanes = 0
        # pinned host scratch, reused across ticks: ``pipeline``
        # [lanes, width] buffer sets (SLOTS) per fused shape, so
        # steady-state staging stops reallocating (and re-faulting)
        # megabytes per tick — staged columns arrive UNPADDED
        # (stage_columns_raw) and pad here.  Reuse is safe ONLY because
        # a slot refills strictly after the dispatch that last read it
        # materialized its outputs (run_lanes materializes immediately;
        # the pipelined submit/drain path retires a slot's dispatch
        # before cycling back to it); the single-lane dispatch pads into
        # fresh buffers instead (see dispatch()).
        self._lane_scratch: Dict[Tuple[int, int, int],
                                 Dict[str, np.ndarray]] = {}
        #: per-slot native marshalling plans (anomod.io.native.StagePlan):
        #: the pinned slots outlive every dispatch, so dst pointers /
        #: fill patterns / ctypes arrays marshal once per slot, not per
        #: call — None caches a slot the runtime refused
        self._stage_plans: Dict[Tuple[int, int, int], object] = {}
        self._slot_next: Dict[Tuple[int, int], int] = {}
        #: FIFO of in-flight fused dispatches: (replays, dagg, dhist,
        #: slot key).  Retiring materializes the deltas (the execute
        #: barrier) and folds them through the get_state/set_state seam
        #: in dispatch order — so any pipeline depth is bit-identical.
        self._inflight: "collections.deque" = collections.deque()
        self._dead_cols: Dict[int, dict] = {}
        # registry mirrors (anomod.obs): staged-vs-live row counters make
        # the bucket-pad waste fraction derivable from any scrape
        # (waste = 1 - live/staged); handles cached — staging and the
        # fused dispatch are the serving hot path.  The lane twins
        # (staged/live LANES + lanes-per-dispatch histogram) price the
        # fused path's dead-lane padding the same way.
        reg = self._reg
        self._obs_dispatches = reg.counter("anomod_serve_dispatches_total")
        self._obs_staged = reg.counter("anomod_serve_staged_rows_total")
        self._obs_live = reg.counter("anomod_serve_live_rows_total")
        self._obs_waste = reg.gauge("anomod_serve_pad_waste_fraction")
        self._obs_fused = reg.counter(
            "anomod_serve_fused_dispatches_total")
        self._obs_lanes = reg.histogram("anomod_serve_fused_lanes")
        self._obs_staged_lanes = reg.counter(
            "anomod_serve_staged_lanes_total")
        self._obs_live_lanes = reg.counter(
            "anomod_serve_live_lanes_total")
        self._obs_lane_waste = reg.gauge(
            "anomod_serve_lane_pad_waste_fraction")
        # tick-wall decomposition mirrors: seconds counters per phase so
        # any scrape can attribute the serve wall (stage vs dispatch vs
        # fold) instead of guessing, + the native-staging counters
        self._obs_stage_s = reg.counter("anomod_serve_stage_seconds_total")
        self._obs_dispatch_s = reg.counter(
            "anomod_serve_dispatch_seconds_total")
        self._obs_fold_s = reg.counter("anomod_serve_fold_seconds_total")
        self._obs_score_s = reg.counter("anomod_serve_score_seconds_total")
        self._obs_native = reg.counter("anomod_serve_native_staged_total")
        reg.gauge("anomod_serve_native_staging").set(
            1.0 if self.native_stage else 0.0)

    @property
    def widths(self) -> Tuple[int, ...]:
        """Every chunk width this runner may dispatch."""
        per_bucket = tuple(b for b in self.buckets
                           if b <= self.cfg.chunk_size)
        return tuple(sorted(set(per_bucket) | {self.cfg.chunk_size}))

    @property
    def lane_shapes(self) -> set:
        """Every (width, lane-bucket) fused shape compiled so far."""
        return set(self._lane_compile_s)

    def zero_state(self) -> ReplayState:
        # host-side zeros: the fused scatter-back keeps tenant states as
        # host arrays (jit transfers them per dispatch either way on the
        # shapes involved, and host residency makes the per-lane
        # delta-add allocation-cheap)
        cfg = self.cfg
        return ReplayState(
            agg=np.zeros((cfg.sw, N_FEATS), np.float32),
            hist=np.zeros((cfg.sw, cfg.n_hist_buckets), np.float32))

    def warm(self) -> float:
        """Compile every bucket width on an all-dead chunk (numerically a
        no-op on any state) so serving never pays a compile wall mid-
        stream.  Returns the total compile wall; idempotent."""
        total = 0.0
        state = self.zero_state()
        for width in self.widths:
            if width in self.compile_s_by_width:
                continue
            t0 = time.perf_counter()
            state = self._step(state, dead_chunk(self.cfg, width))
            np.asarray(state.agg)               # compile + execute barrier
            self.compile_s_by_width[width] = time.perf_counter() - t0
            total += self.compile_s_by_width[width]
            self._reg.counter("anomod_serve_compile_total").inc()
            self._reg.counter("anomod_serve_compile_seconds_total").inc(
                self.compile_s_by_width[width])
        return total

    def warm_lanes(self) -> float:
        """Compile the full (width x lane-bucket) fused-dispatch grid on
        all-dead lane stacks, so a fused serve never pays a compile wall
        mid-stream.  Returns the total compile wall; idempotent."""
        total = 0.0
        for width in self.widths:
            dead = self._dead_cols_for(width)
            for lanes in self.lane_buckets:
                key = (width, lanes)
                if key in self._lane_compile_s:
                    continue
                stacked = {k: np.broadcast_to(
                    v, (lanes, width)) for k, v in dead.items()}
                exe = self._lane_exec_for(key, stacked)
                dagg, _ = exe(stacked)
                np.asarray(dagg)                # execute barrier
                total += self._lane_compile_s[key]
        if self.pool is not None:
            # device-state mode: the pool's scatter/gather/roll shapes
            # compile here too, so the first serving tick never pays a
            # pool-op compile inside the measured wall
            total += self.pool.warm(self.lane_buckets)
        return total

    def _lane_exec_for(self, key: Tuple[int, int], args: dict):
        """The AOT lane executable for one (width, lane-bucket) shape,
        lowered+compiled on first need (``args`` supplies the concrete
        shapes) — exactly one compile per shape per runner, recorded in
        ``_lane_compile_s`` / the registry compile counters like every
        other compile in this file."""
        exe = self._lane_exec.get(key)
        if exe is None:
            t0 = time.perf_counter()
            exe = self._lane_fn.lower(args).compile()
            self._lane_exec[key] = exe
            self._record_lane_compile(key, time.perf_counter() - t0)
        return exe

    def _record_lane_compile(self, key: Tuple[int, int],
                             wall_s: float) -> None:
        self._lane_compile_s[key] = wall_s
        self._reg.counter("anomod_serve_fused_compile_total").inc()
        self._reg.counter(
            "anomod_serve_fused_compile_seconds_total").inc(wall_s)

    @property
    def compile_s(self) -> float:
        return float(sum(self.compile_s_by_width.values()))

    @property
    def lane_compile_s(self) -> float:
        return float(sum(self._lane_compile_s.values()))

    def _dead_cols_for(self, width: int) -> dict:
        got = self._dead_cols.get(width)
        if got is None:
            got = dead_chunk(self.cfg, width, xp=np)
            self._dead_cols[width] = got
        return got

    # -- staging (shared by the sequential and fused paths) ---------------

    def stage_plan(self, batch: SpanBatch,
                   t0_us: int) -> List[Tuple[int, dict]]:
        """Host-side staging of one micro-batch into its bucket plan:
        the ordered ``(width, columns)`` chunks a push dispatches, with
        UNPADDED columns (each entry holds its slice's live rows; the
        pad to ``width`` happens at scratch-fill time with the
        dead-chunk fill values — same bits, no per-batch allocation).

        ``t0_us`` is the caller's (rolled) window anchor — binning is the
        caller's contract, exactly as in StreamReplay.push.  This is the
        ONE staging definition: the sequential path dispatches the
        returned chunks one by one, the fused path stacks the identical
        chunks across tenants — so the two paths cannot stage apart.
        Logical-dispatch and pad-waste accounting live here for the same
        reason (``dispatches_by_width`` counts staged chunks, identical
        under either execution strategy).
        """
        cfg = self.cfg
        t0 = time.perf_counter()
        mat, raw = stage_columns_fused(batch, cfg, t0_us)
        # the staged matrix's pointer, extracted ONCE per batch: every
        # chunk below carries its slice as ptr/stride/m ints, so the
        # native packer marshals a lane without touching ndarray
        # internals on the per-dispatch path (anomod.io.native.StagedChunk)
        mat_ptr = mat.ctypes.data
        stride = mat.shape[1]
        out: List[Tuple[int, dict]] = []
        staged_rows = 0
        for lo, hi, width in split_plan(batch.n_spans, cfg.chunk_size,
                                        self.buckets):
            cols = native_io.StagedChunk(
                (k, v[lo:hi]) for k, v in raw.items())
            cols.mat = mat
            cols.ptr = mat_ptr + 4 * lo
            cols.stride = stride
            cols.m = hi - lo
            out.append((width, cols))
            self.n_dispatches += 1
            self.dispatches_by_width[width] = \
                self.dispatches_by_width.get(width, 0) + 1
            staged_rows += width
        dt = time.perf_counter() - t0
        self.stage_wall_s += dt
        self._obs_stage_s.inc(dt)
        if out:
            self._obs_dispatches.inc(len(out))
            self._obs_staged.inc(staged_rows)
            self._obs_live.inc(batch.n_spans)
            staged = self._obs_staged.value
            if staged:
                self._obs_waste.set(1.0 - self._obs_live.value / staged)
        return out

    def _pad_fill(self, key: str):
        """The per-column dead-row fill value (= the dead_chunk fill)."""
        return self.cfg.sw if key == "sid" else 0

    def dispatch(self, state: ReplayState, cols: dict,
                 width: int) -> ReplayState:
        """Fold ONE staged chunk into ``state`` (single-lane path),
        padding the live rows to ``width`` exactly as ``stage_columns``
        would.

        The pad buffers are FRESH per call, never reused: jax's CPU
        backend may zero-copy an aligned host array into the dispatch
        under an immutability promise, and this path hands the state
        back WITHOUT materializing it — mutating a shared scratch here
        while the async step still reads it corrupts the fold (the fused
        ``run_lanes`` path is the one that may reuse pinned scratch,
        because it materializes its outputs — completing the dispatch's
        reads — before every refill).
        """
        n = cols["sid"].shape[0]
        if n != width:
            t0 = time.perf_counter()
            padded = {}
            for k, c in cols.items():
                buf = np.empty(width, c.dtype)
                buf[:n] = c
                buf[n:] = self._pad_fill(k)
                padded[k] = buf
            dt = time.perf_counter() - t0
            self.stage_wall_s += dt
            self._obs_stage_s.inc(dt)
            cols = padded
        elif type(cols) is not dict:
            # StagedChunk is a dict subclass jax's pytree registry won't
            # flatten — hand the jitted step a plain dict view
            cols = dict(cols)
        t0 = time.perf_counter()
        out = self._step(state, cols)
        dt = time.perf_counter() - t0
        self.dispatch_wall_s += dt
        self._obs_dispatch_s.inc(dt)
        return out

    # -- the fused (lane-stacked) path ------------------------------------

    def lane_plan(self, n: int) -> List[Tuple[int, int]]:
        """``(n_live, lane_bucket)`` dispatch groups covering ``n``
        lanes: the largest bucket repeatedly, then the smallest bucket
        covering the remainder (dead-padded)."""
        out: List[Tuple[int, int]] = []
        big = self.lane_buckets[-1]
        while n > big:
            out.append((big, big))
            n -= big
        if n > 0:
            out.append((n, next(b for b in self.lane_buckets if b >= n)))
        return out

    def _fill_slot(self, width: int, lanes: int,
                   group_cols: List[dict]) -> Tuple[dict, Tuple[int, int,
                                                                int]]:
        """Stage ``group_cols`` (one unpadded chunk per live lane) into
        the next free pinned scratch slot for the (width, lanes) shape,
        dead-padding the row tails and any dead lanes.  Cycles through
        ``self.pipeline`` slots per shape; before reusing a slot, any
        in-flight dispatch still reading it is retired (materialized) —
        the PR-4 aliasing hazard (mutating host arrays under an async
        dispatch) is structurally impossible here.

        With ``native_stage`` the packing runs through the C++
        ``stage_lanes`` entry (anomod.io.native): byte-identical to the
        interpreter fill below (pinned in tests/test_native.py /
        test_serve.py), but GIL-FREE — staging slot k+1 makes progress
        under the in-flight dispatch on slot k, and shard workers stage
        concurrently.  Slots are 64-byte-aligned (aligned_empty) so
        XLA:CPU's zero-copy host aliasing applies to the very buffers
        the packer writes — the scratch ring is end-to-end zero-copy."""
        shape = (width, lanes)
        slot = self._slot_next.get(shape, 0)
        self._slot_next[shape] = (slot + 1) % self.pipeline
        key = (width, lanes, slot)
        while any(e[3] == key for e in self._inflight):
            self._retire_one()
        with span_of(self.tracer, "serve.lane_fill", width=width,
                     lanes=lanes, live=len(group_cols)):
            t0 = time.perf_counter()
            scratch = self._lane_scratch.get(key)
            if scratch is None:
                scratch = {k: native_io.aligned_empty((lanes, width),
                                                      v.dtype)
                           for k, v in self._dead_cols_for(width).items()}
                self._lane_scratch[key] = scratch
                if self.native_stage:
                    self._stage_plans[key] = native_io.make_stage_plan(
                        scratch, self._pad_fill, mat_keys=STAGE_KEYS)
            plan = self._stage_plans.get(key)
            if plan is not None and plan.stage(group_cols):
                self.native_staged += 1
                self._obs_native.inc()
            else:
                self._fill_slot_py(scratch, group_cols, width, lanes)
            dt = time.perf_counter() - t0
            self.stage_wall_s += dt
            self._obs_stage_s.inc(dt)
        return scratch, key

    def _fill_slot_py(self, scratch: dict, group_cols: List[dict],
                      width: int, lanes: int) -> None:
        """The interpreter fill — the behavioral oracle the native packer
        is pinned byte-identical to, and the fallback when the .so is
        unavailable (or a column breaks its 4-byte contract)."""
        n_live = len(group_cols)
        for k, buf in scratch.items():
            fill = self._pad_fill(k)
            for i, cols in enumerate(group_cols):
                c = cols[k]
                m = c.shape[0]
                buf[i, :m] = c
                if m < width:
                    buf[i, m:] = fill
            if n_live < lanes:
                buf[n_live:] = fill

    def _account_group(self, n_live: int, lanes: int) -> None:
        self.fused_dispatches += 1
        self.lanes_by_bucket[lanes] = \
            self.lanes_by_bucket.get(lanes, 0) + 1
        self.staged_lanes += lanes
        self.live_lanes += n_live
        self._obs_fused.inc()
        self._obs_lanes.observe(n_live)
        self._obs_staged_lanes.inc(lanes)
        self._obs_live_lanes.inc(n_live)
        self._obs_lane_waste.set(1.0 - self.live_lanes / self.staged_lanes)

    def run_lanes(self, width: int,
                  work: List[Tuple[ReplayState, dict]]) -> List[ReplayState]:
        """Fold ``work[i]``'s staged chunk into ``work[i]``'s state via
        lane-bucketed fused dispatches; returns the updated states in
        order (synchronous: each dispatch materializes before the next
        stages — the pipelined twin is :meth:`submit_lanes`).

        Per-lane results are BIT-identical to :meth:`dispatch` per lane:
        each lane reduces its own rows in the same order, dead pad lanes
        contribute nothing and are dropped (their tenants' states pass
        through untouched), and the per-lane delta folds into the state
        with the same elementwise f32 add the in-step update performs.
        Staging rides pinned scratch buffers reused across ticks.
        """
        self.drain_lanes()      # never interleave with pipelined folds
        out: List[ReplayState] = []
        pos = 0
        for n_live, lanes in self.lane_plan(len(work)):
            group = work[pos:pos + n_live]
            pos += n_live
            scratch, _ = self._fill_slot(width, lanes,
                                         [cols for _, cols in group])
            exe = self._lane_exec_for((width, lanes), scratch)
            with span_of(self.tracer, "serve.lane_dispatch", width=width,
                         lanes=lanes):
                t0 = time.perf_counter()
                dagg, dhist = exe(scratch)
                t1 = time.perf_counter()
            with span_of(self.tracer, "serve.fold_retire", lanes=lanes,
                         device=False):
                # materialize before the scratch is reused: the host
                # copy is the execute barrier, and the scatter-back
                # below reads it
                dagg = np.asarray(dagg)
                dhist = np.asarray(dhist)
                for i, (st, _) in enumerate(group):
                    out.append(fold_delta(st, dagg[i], dhist[i]))
                t2 = time.perf_counter()
            self.dispatch_wall_s += t1 - t0
            self._obs_dispatch_s.inc(t1 - t0)
            self.fold_wall_s += t2 - t1
            self._obs_fold_s.inc(t2 - t1)
            self._account_group(n_live, lanes)
        return out

    # -- the pipelined (async double-buffered) path -----------------------

    def submit_lanes(self, width: int, work: List[Tuple[object, dict]],
                     ) -> None:
        """Pipelined twin of :meth:`run_lanes`: ``work`` pairs each
        REPLAY PLANE (anything with the ``get_state``/``set_state`` seam)
        with its staged unpadded chunk.  Dispatches are issued
        immediately; readback + state fold are DEFERRED until the
        dispatch retires — at most ``pipeline - 1`` dispatches stay in
        flight, so with depth d the shard stages dispatch t+1 while
        dispatch t's XLA work is still running.  Folds always apply in
        dispatch order through ``set_state`` (bit-identical to the
        synchronous path at any depth); callers MUST :meth:`drain_lanes`
        before reading the planes (the sharded engine drains at tick
        end, before window scoring).
        """
        pos = 0
        for n_live, lanes in self.lane_plan(len(work)):
            group = work[pos:pos + n_live]
            pos += n_live
            scratch, key = self._fill_slot(width, lanes,
                                           [cols for _, cols in group])
            exe = self._lane_exec_for((width, lanes), scratch)
            with span_of(self.tracer, "serve.lane_dispatch", width=width,
                         lanes=lanes):
                t0 = time.perf_counter()
                dagg, dhist = exe(scratch)
                dt = time.perf_counter() - t0
            self.dispatch_wall_s += dt
            self._obs_dispatch_s.inc(dt)
            self._inflight.append(
                ([replay for replay, _ in group], dagg, dhist, key))
            self._account_group(n_live, lanes)
            while len(self._inflight) > self.pipeline - 1:
                self._retire_one()

    def _retire_one(self) -> None:
        """Retire the OLDEST in-flight dispatch and fold its per-lane
        deltas into the paired replay planes.

        DEVICE path (every paired replay lives in this runner's state
        pool): the fold is ONE on-device scatter-add
        (``TenantStatePool.scatter_fold``) — no host materialization of
        the [lanes, SW, F+H] deltas, no per-lane numpy adds — pinned
        bit-identical to the host seam because the scatter performs the
        same f32 ``state + delta`` per slot in the same dispatch order.
        The scratch-reuse barrier is ``block_until_ready`` on the delta:
        the lane dispatch's outputs being ready means it can no longer
        read its host scratch slot (no host copy needed).

        HOST path (any replay without a slot on this pool — the
        host-seam mode, or generic callers pairing plain replays): the
        host copy is the execute barrier, then :func:`fold_delta` per
        lane through the get_state/set_state seam — the same
        elementwise f32 add the in-step update performs."""
        replays, dagg, dhist, key = self._inflight.popleft()
        t0 = time.perf_counter()
        pool = self.pool
        on_device = bool(pool is not None and replays and all(
            getattr(r, "_slot", None) is not None
            and getattr(r, "_runner", None) is self
            for r in replays))
        with span_of(self.tracer, "serve.fold_retire", lanes=key[1],
                     device=on_device):
            if on_device:
                pool.scatter_fold([r._slot for r in replays], dagg, dhist)
                dagg.block_until_ready()       # scratch-reuse barrier
            else:
                dagg = np.asarray(dagg)
                dhist = np.asarray(dhist)
                for i, replay in enumerate(replays):
                    replay.set_state(fold_delta(replay.get_state(),
                                                dagg[i], dhist[i]))
        dt = time.perf_counter() - t0
        self.fold_wall_s += dt
        self._obs_fold_s.inc(dt)

    def drain_lanes(self) -> None:
        """Retire every in-flight dispatch (tick-end barrier)."""
        while self._inflight:
            self._retire_one()

    def abort_lanes(self) -> None:
        """Failed-tick cleanup: discard every in-flight dispatch WITHOUT
        folding.  Outputs are still materialized — the execute barrier;
        a scratch slot must never be refilled under a dispatch that can
        still read it — but the deltas are dropped, so the paired replay
        planes keep their last-folded states instead of silently
        absorbing an aborted tick's work on some later drain."""
        while self._inflight:
            _, dagg, dhist, _ = self._inflight.popleft()
            np.asarray(dagg)
            np.asarray(dhist)

    @property
    def inflight_dispatches(self) -> int:
        return len(self._inflight)

    def leg_walls(self) -> dict:
        """Cumulative flight-leg snapshot of this runner's wall/dispatch
        book — what the flight recorder (anomod.obs.flight) deltas per
        tick.  ``by_width`` (staged chunks per width) is the canonical
        dispatch-plane content: ``stage_plan`` is the ONE staging
        definition, so the counts are identical under every execution
        strategy (fused/unfused, any shard count, any pipeline depth).
        The walls and lane-grouping counts are journal-variant (wall
        clock / topology).  Read at the tick barrier only — the dicts
        mutate on this runner's worker thread mid-tick."""
        return {"stage_s": self.stage_wall_s,
                "dispatch_s": self.dispatch_wall_s,
                "fold_s": self.fold_wall_s,
                "score_s": self.score_wall_s,
                "chunks": self.n_dispatches,
                "fused": self.fused_dispatches,
                "native_staged": self.native_staged,
                "by_width": dict(self.dispatches_by_width)}

    def book_snapshot(self) -> dict:
        """The runner's cumulative dispatch-COUNT book — what the shard
        supervisor (anomod.serve.supervise) checkpoints and restores
        around a recovery re-execution, so re-executed slices cannot
        double-count the flight journal's canonical dispatch plane
        (``chunks``/``by_width`` deltas) or the ServeReport counters.
        Walls and compile bookkeeping deliberately stay OUT: recovery
        wall is real work, reported in its own report leg, and compiles
        happened regardless of what the counters say."""
        return {"n_dispatches": self.n_dispatches,
                "dispatches_by_width": dict(self.dispatches_by_width),
                "fused_dispatches": self.fused_dispatches,
                "native_staged": self.native_staged,
                "staged_lanes": self.staged_lanes,
                "live_lanes": self.live_lanes,
                "lanes_by_bucket": dict(self.lanes_by_bucket)}

    def book_restore(self, book: dict) -> None:
        """Install a :meth:`book_snapshot` (checkpoint restore)."""
        self.n_dispatches = book["n_dispatches"]
        self.dispatches_by_width = dict(book["dispatches_by_width"])
        self.fused_dispatches = book["fused_dispatches"]
        self.native_staged = book["native_staged"]
        self.staged_lanes = book["staged_lanes"]
        self.live_lanes = book["live_lanes"]
        self.lanes_by_bucket = dict(book["lanes_by_bucket"])

    @property
    def lane_pad_waste(self) -> float:
        """Dead-lane fraction of every fused dispatch so far (the lane
        twin of the row pad-waste gauge)."""
        return (1.0 - self.live_lanes / self.staged_lanes
                if self.staged_lanes else 0.0)


class BucketedStreamReplay(StreamReplay):
    """StreamReplay whose dispatch rides a shared :class:`BucketRunner`.

    Same ring/anchor bookkeeping as the parent (``_roll`` is inherited —
    ONE definition of the eviction math); only ``push`` and ``_warm``
    differ: chunks stage through the runner's bucket plan and the
    compiled executables are shared across every tenant on the runner.
    ``plan_push`` additionally exposes the staging half alone, for the
    fused engine's lane-stacked dispatch.
    """

    def __init__(self, cfg: ReplayConfig, t0_us: int, runner: BucketRunner):
        if runner.cfg != cfg:
            raise ValueError("runner cfg disagrees with the replay cfg")
        # deliberately NOT super().__init__: the parent builds a
        # per-instance jitted step and zero planes this subclass never
        # uses (the runner owns the ONE jit for the whole fleet), and a
        # live-looking unused self._step would dispatch outside the
        # runner's accounting if anything ever called it
        self.cfg = cfg
        self.t0_us = int(t0_us)
        self.window_offset = 0
        self.n_spans = 0
        self._step = None                 # dispatch goes through the runner
        self.compile_s = 0.0
        self._warmed = False
        self._runner = runner
        self.state = runner.zero_state()

    def _warm(self) -> None:
        self._runner.warm()
        self.compile_s = self._runner.compile_s
        self._warmed = True

    def plan_push(self, batch: SpanBatch):
        """The staging half of :meth:`push`: roll the ring, account the
        spans, stage the bucket plan — WITHOUT dispatching.  Returns
        ``(newest absolute window, ordered (width, columns) chunks)``;
        applying the chunks to ``state`` in order (``runner.dispatch``,
        or lanes of them stacked across tenants via ``runner.run_lanes``)
        reproduces ``push()`` bit-exactly.  This is the fused engine's
        gather seam."""
        if batch.n_spans == 0:
            return -1, []
        if not self._warmed:
            self._warm()
        w_need = int((int(batch.start_us.max()) - self.t0_us)
                     // self.cfg.window_us)
        if w_need > self.cfg.n_windows - 1:
            self._roll(w_need - (self.cfg.n_windows - 1))
            w_need = self.cfg.n_windows - 1
        plan = self._runner.stage_plan(batch, self.t0_us)
        self.n_spans += batch.n_spans
        return self.window_offset + max(w_need, 0), plan

    def push(self, batch: SpanBatch) -> int:
        w_ret, plan = self.plan_push(batch)
        for width, cols in plan:
            self.state = self._runner.dispatch(self.state, cols, width)
        return w_ret


class PooledStreamReplay(BucketedStreamReplay):
    """BucketedStreamReplay whose state lives in the runner's
    DEVICE-RESIDENT tenant pool (``ANOMOD_SERVE_STATE=device``/``auto``).

    The tenant maps to a pool slot at construction (= first service).
    ``state`` stays the official surface — reads GATHER the slot to host,
    writes SCATTER it back, so every ``get_state``/``set_state`` consumer
    (parity tests, checkpoints, the host-seam fold fallback, future
    migration) behaves exactly as before and round-trips byte-identically
    — but the hot paths never touch it: the lane fold is the runner's
    on-device scatter-add (:meth:`BucketRunner._retire_one`), the ring
    roll runs on the pool row (bit-identical to the host roll), and the
    batched serve scorer gathers only the scored window columns."""

    def __init__(self, cfg: ReplayConfig, t0_us: int, runner: BucketRunner):
        if runner.pool is None:
            raise ValueError(
                "runner keeps host-seam states (ANOMOD_SERVE_STATE=host); "
                "use BucketedStreamReplay or a device-state runner")
        self._slot = runner.pool.acquire()
        try:
            super().__init__(cfg, t0_us, runner)
        except BaseException:
            # a failed construction must hand its slot back, or every
            # retried admission leaks a pool row
            runner.pool.release(self._slot)
            self._slot = None
            raise

    def _live_slot(self) -> int:
        # a released replay must fail loud: pool.put(None, ...) would
        # broadcast over EVERY slot (None is np.newaxis on the numpy
        # engine) — silent fleet-wide state corruption
        if self._slot is None:
            raise ValueError("pool slot was released (tenant churn); "
                             "this PooledStreamReplay is dead")
        return self._slot

    @property
    def state(self) -> ReplayState:
        return self._runner.pool.gather(self._live_slot())

    @state.setter
    def state(self, st: ReplayState) -> None:
        self._runner.pool.put(self._live_slot(), st)

    def _roll(self, k: int) -> None:
        self._runner.pool.roll(self._live_slot(), k)
        self.t0_us += k * self.cfg.window_us
        self.window_offset += k

    def release(self) -> None:
        """Return the slot to the pool, zeroed (tenant churn; the
        migration seam's teardown half).  Idempotent is NOT the
        contract — a double release would re-free a slot another
        tenant may already own."""
        self._runner.pool.release(self._live_slot())
        self._slot = None
