"""Sharded span-stream replay: the TPU feature-extraction hot path.

The reference's richest data path is trace ingestion — paginated fetch, then
per-span Python graph building (trace_collector.py:296-547).  The TPU-native
equivalent replays an experiment corpus *as data*: span columns staged into
HBM, then a jitted scan over fixed-size chunks computes windowed per-service
aggregates (count / errors / latency moments / log-latency histogram) on the
MXU.  Throughput (spans/sec/chip) is the headline benchmark
(BASELINE.json: ≥1M spans/sec/chip on TT_data replay).

Design notes (TPU-first):
  - static shapes: spans padded to chunk multiples; windows/services fixed.
  - the scatter-heavy aggregation is expressed as one-hot matmuls (MXU) for
    the [S*W] aggregate plane and a segment histogram over log-latency
    buckets — fused by XLA into a handful of kernels.
  - per-chip state is tiny (S*W*F + S*W*H floats), so the multi-chip replay
    shards the span stream and psum-merges state (anomod.parallel).
"""

from __future__ import annotations

import dataclasses
import os
import time
from functools import lru_cache
from typing import NamedTuple, Optional, Tuple

import numpy as np

from anomod.schemas import SpanBatch

# Feature plane order: the three exact 0/1 columns first (bf16-exact matmul),
# the three latency moments last (HIGHEST-precision matmul).
F_COUNT, F_ERR, F_STATUS5XX, F_LAT, F_LOGLAT, F_LOGLAT2 = range(6)
N_FEATS = 6


class ReplayState(NamedTuple):
    agg: "object"          # [S*W, F] float32
    hist: "object"         # [S*W, H] float32 — log-latency histogram
    hll: "object" = None   # [S, 2^p] int32 — distinct-trace registers (opt.)


@dataclasses.dataclass(frozen=True)
class ReplayConfig:
    n_services: int
    n_windows: int = 32
    n_hist_buckets: int = 16
    chunk_size: int = 1 << 15
    window_us: int = 60_000_000  # 60 s windows
    hll_p: int = 8               # per-service distinct-trace HLL precision

    @property
    def sw(self) -> int:
        return self.n_services * self.n_windows

    @property
    def hll_m(self) -> int:
        return 1 << self.hll_p


def segment_ids(batch: SpanBatch, cfg: ReplayConfig,
                t0_us: Optional[int] = None) -> np.ndarray:
    """[n] int32 (service, window) segment id per span — the ONE definition
    of the replay's segment binning, shared by :func:`stage_columns` and
    lightweight consumers that need segment occupancy without paying the
    full staging pass."""
    n = batch.n_spans
    t0 = int(batch.start_us.min()) if t0_us is None and n else (t0_us or 0)
    window = np.minimum((batch.start_us - t0) // cfg.window_us,
                        cfg.n_windows - 1).astype(np.int32)
    window = np.maximum(window, 0)
    return batch.service.astype(np.int32) * cfg.n_windows + window


#: the chunk column schema's row order in the staged matrix — the ONE
#: ordering shared by :func:`stage_columns_fused`, :func:`dead_chunk` and
#: the native packer's matrix fast path (anomod.io.native.StagePlan): a
#: reorder here without a matching ``mat_keys`` change would break the
#: byte-parity pin in tests/test_native.py, never silently stage garbage.
STAGE_KEYS = ("sid", "dur", "dur_raw", "err", "s5", "valid", "tid")


def stage_columns_fused(batch: SpanBatch, cfg: ReplayConfig,
                        t0_us: Optional[int] = None):
    """UNPADDED per-span chunk columns staged as ONE C-contiguous
    ``[7, n]`` float32 matrix (every chunk column is a 4-byte dtype;
    ``sid``/``tid`` live as int32 row views) — ``(mat, columns)`` where
    ``columns`` maps the :data:`STAGE_KEYS` schema to row views of
    ``mat``.  The serving batcher stages through this and pads at
    scratch-fill time into pinned reused buffers (pad value per column =
    the :func:`dead_chunk` fill), so the hot tick loop stops allocating —
    and the single backing matrix is what lets the native GIL-free packer
    (anomod.io.native.stage_lanes) describe a whole lane with ONE base
    pointer + row stride instead of seven per-column pointer
    extractions (each of which costs as much as a small numpy copy)."""
    n = batch.n_spans
    mat = np.empty((len(STAGE_KEYS), n), np.float32)
    sid = mat[0].view(np.int32)
    sid[:] = segment_ids(batch, cfg, t0_us)
    dur_raw = mat[2]
    np.copyto(dur_raw, batch.duration_us, casting="unsafe")
    np.log1p(dur_raw, out=mat[1])
    np.copyto(mat[3], batch.is_error, casting="unsafe")
    np.copyto(mat[4], batch.status >= 500, casting="unsafe")
    mat[5].fill(1.0)
    tid = mat[6].view(np.int32)                 # for distinct-trace HLL
    np.copyto(tid, batch.trace, casting="unsafe")
    return mat, dict(sid=sid, dur=mat[1], dur_raw=dur_raw, err=mat[3],
                     s5=mat[4], valid=mat[5], tid=tid)


def stage_columns_raw(batch: SpanBatch, cfg: ReplayConfig,
                      t0_us: Optional[int] = None) -> dict:
    """UNPADDED per-span chunk columns — the :func:`stage_columns`
    transforms without the pad (:func:`stage_columns_fused`'s column
    dict; the values are row views of one staged matrix, byte-identical
    to independently computed columns)."""
    return stage_columns_fused(batch, cfg, t0_us)[1]


def stage_columns(batch: SpanBatch, cfg: ReplayConfig, t0_us: Optional[int] = None):
    """Host-side packing: SpanBatch -> padded int32/float32 chunk arrays."""
    n = batch.n_spans
    pad = (-n) % cfg.chunk_size
    raw = stage_columns_raw(batch, cfg, t0_us)
    def p(a, fill=0):
        return np.pad(a, (0, pad), constant_values=fill)
    cols = {k: p(v, fill=cfg.sw if k == "sid" else 0)
            for k, v in raw.items()}   # padding rows target a dead segment
    n_chunks = (n + pad) // cfg.chunk_size
    return {k: v.reshape(n_chunks, cfg.chunk_size) for k, v in cols.items()}, n


def dead_chunk(cfg: ReplayConfig, width: Optional[int] = None, xp=None):
    """An all-dead staged chunk (sid = the dead pad lane, valid = 0) —
    numerically a no-op on any replay state.  The ONE definition of the
    chunk column schema's dummy instance, shared by every warm/compile
    path (StreamReplay._warm, the sharded stream's group padding, the
    serve BucketRunner) so a chunk-schema change cannot silently desync
    a warm path from :func:`stage_columns`."""
    if xp is None:
        import jax.numpy as xp
    w = int(width or cfg.chunk_size)
    return {
        "sid": xp.full((w,), cfg.sw, np.int32),
        "dur": xp.zeros((w,), np.float32),
        "dur_raw": xp.zeros((w,), np.float32),
        "err": xp.zeros((w,), np.float32),
        "s5": xp.zeros((w,), np.float32),
        "valid": xp.zeros((w,), np.float32),
        "tid": xp.zeros((w,), np.int32),
    }


def hll_scatter_update(regs, sid, tid, cfg: ReplayConfig):
    """Scatter-max trace-id ranks into per-service HLL registers — the ONE
    definition of the distinct-trace plane, shared by the single-chip chunk
    step and the pod-sharded whole-shard build.  Routes through
    anomod.ops.hll.hll_add (one hash pipeline in the repo); rows with
    sid >= cfg.sw are padding and go to an extra dead lane, dropped."""
    import jax.numpy as jnp

    from anomod.ops.hll import hll_add

    svc = jnp.clip(sid // cfg.n_windows, 0, cfg.n_services - 1)
    lane = jnp.where(sid < cfg.sw, svc, cfg.n_services)
    regs_ext = jnp.concatenate(
        [regs, jnp.zeros((1, cfg.hll_m), regs.dtype)], axis=0)
    return hll_add(regs_ext, tid, p=cfg.hll_p, lane=lane, xp=jnp)[:-1]


def _split_hi_lo(x):
    """Two-way bf16 split ``x ≈ hi + lo`` (~16 mantissa bits) of an f32
    array, both halves bf16.

    ``hi`` is rounded with ``lax.reduce_precision``, NOT with an
    f32→bf16→f32 convert pair: XLA's TPU pipeline elides that pair
    (excess precision is allowed by default), which makes ``lo``
    identically zero and silently leaves the moments at bf16 precision —
    measured on a v5e in PR 21: 3.3e-3 max relative error on the latency
    moments against 5.7e-6 with the lo term alive.  Both round to nearest
    even, so the values are bit-identical wherever the pair survived
    (XLA:CPU, Mosaic)."""
    import jax
    import jax.numpy as jnp
    hi32 = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return hi32.astype(jnp.bfloat16), (x - hi32).astype(jnp.bfloat16)


def _scatter_rhs(chunk, cfg: ReplayConfig):
    """The [rows, 3+3+3+H] per-row feature payload of the SCATTER-engine
    step: bf16-rounded exact/hi/lo planes + masked bucket one-hot,
    widened back to f32.  Each row's value equals its matmul-path product
    against a one-hot 1.0 EXACTLY (the bf16 rounding happens before
    either reduction), which is what makes the scatter engine's f32
    accumulation bit-compatible with the matmul engine's on XLA:CPU —
    both reduce a segment's rows in row order, and the matmul's extra
    terms from other rows are exact ``+0.0``s.  ONE definition, shared by
    the single-lane scatter step and the fused lane-delta kernel."""
    import jax
    import jax.numpy as jnp
    H = cfg.n_hist_buckets
    exact = jnp.stack([chunk["valid"], chunk["err"], chunk["s5"]],
                      axis=1).astype(jnp.bfloat16)
    bucket = jnp.clip(chunk["dur"].astype(jnp.int32), 0, H - 1)
    bucket_oh = (jax.nn.one_hot(bucket, H, dtype=jnp.bfloat16)
                 * chunk["valid"][:, None].astype(jnp.bfloat16))
    durs = jnp.stack([chunk["dur_raw"], chunk["dur"],
                      chunk["dur"] * chunk["dur"]], axis=1)
    hi, lo = _split_hi_lo(durs)
    return jnp.concatenate([exact, hi, lo, bucket_oh],
                           axis=1).astype(jnp.float32)


def _split_acc(acc, state: ReplayState):
    """Fold a [SW, 3+3+3+H] per-segment accumulation into the state:
    recombine the hi/lo latency moments and apply the SAME elementwise
    f32 adds the matmul step performs."""
    import jax.numpy as jnp
    a_dur = acc[:, 3:6] + acc[:, 6:9]
    agg = state.agg + jnp.concatenate([acc[:, :3], a_dur], axis=1)
    hist = state.hist + acc[:, 9:]
    return agg, hist


def named_jit(name: str, fn, **jit_kw):
    """``jax.jit(fn)`` under a name the device trace keeps: the XLA module
    reads ``jit_<name>`` and every op's metadata carries the
    ``jax.named_scope`` of the same name, so a trace reduction finds the
    callable after a refactor.  Names and metadata only: the traced
    arithmetic, shapes, donation and layouts are ``fn``'s own."""
    import jax

    def named(*args):
        with jax.named_scope(name):
            return fn(*args)

    named.__name__ = named.__qualname__ = name
    return jax.jit(named, **jit_kw)


def default_step_engine() -> str:
    """The chunk-step engine for the current backend: "scatter" on
    XLA:CPU (a segment-sum over the staged rows — ~10x the one-hot
    matmul there, and pinned BIT-identical to it in tests/test_serve.py,
    so every downstream parity guarantee carries over), "matmul" on
    accelerators (the one-hot bf16 MXU formulation — scatter is the slow
    path on TPU)."""
    import jax
    return "scatter" if jax.default_backend() == "cpu" else "matmul"


def make_chunk_step(cfg: ReplayConfig, with_hll: bool = False,
                    engine: str = "matmul"):
    """The per-chunk aggregation step shared by the single-chip scan and the
    pod-sharded replay (one definition so the split-precision scheme can't
    diverge between them).  Returns ``step(state, chunk) -> (state, None)``
    for ``lax.scan``.

    ``engine="matmul"`` (default) is the one-hot bf16 MXU formulation
    below; ``engine="scatter"`` computes the same per-segment sums with a
    ``jax.ops.segment_sum`` over the identical bf16-rounded row payload —
    on XLA:CPU the two accumulate each segment's rows in the same order,
    so their f32 states are BIT-identical (pinned in tests/test_serve.py;
    the serving plane's BucketRunner picks per backend via
    :func:`default_step_engine`).
    """
    import jax
    import jax.numpy as jnp

    SW = cfg.sw
    H = cfg.n_hist_buckets
    if engine not in ("matmul", "scatter"):
        raise ValueError(f"unknown chunk-step engine {engine!r} "
                         "(matmul|scatter)")

    def hll_update(regs, chunk):
        return hll_scatter_update(regs, chunk["sid"], chunk["tid"], cfg)

    if engine == "scatter":
        def scatter_step(state: ReplayState, chunk):
            # padding rows carry sid = SW (the dead lane): segment-sum
            # them into an extra segment and drop it, exactly as the
            # matmul drops its pad column
            acc = jax.ops.segment_sum(_scatter_rhs(chunk, cfg),
                                      chunk["sid"],
                                      num_segments=SW + 1)[:SW]
            agg, hist = _split_acc(acc, state)
            hll = hll_update(state.hll, chunk) if with_hll else None
            return ReplayState(agg=agg, hist=hist, hll=hll), None

        return scatter_step

    def chunk_step(state: ReplayState, chunk):
        sid = chunk["sid"]                    # [C] int32, SW = padding
        # one-hot [C, SW+1] — pad lane absorbs padding rows, dropped after.
        # ONE bf16 MXU matmul per chunk aggregates every feature plane:
        #   - the 0/1 planes (count, error, 5xx, histogram buckets) are
        #     EXACT in bf16 with the MXU's f32 accumulation;
        #   - the latency moments ride a two-way hi/lo bf16 split
        #     (_split_hi_lo: x = hi + lo, ~16 mantissa bits): the
        #     one-hot operand is exact, products accumulate in f32, so the
        #     result carries ~1.5e-5 relative error at 1/3 the passes of a
        #     HIGHEST-precision f32 matmul.  Accepted error bound for
        #     consumers: reconstructing variance as E[x²]−E[x]² amplifies
        #     that to ~1.5e-5·E[x²]/Var(x) relative — fine for the synth
        #     corpus (log-latency σ≈0.4 ⇒ <1e-3) and any σ≳0.1, unreliable
        #     when Var(x)/E[x²] < ~1e-4 (then use the histogram plane
        #     instead; test_replay_variance_reconstruction_low_variance
        #     pins this bound).
        onehot16 = jax.nn.one_hot(sid, SW + 1, dtype=jnp.bfloat16)
        exact = jnp.stack([chunk["valid"], chunk["err"], chunk["s5"]],
                          axis=1).astype(jnp.bfloat16)
        bucket = jnp.clip(chunk["dur"].astype(jnp.int32), 0, H - 1)
        bucket_oh = (jax.nn.one_hot(bucket, H, dtype=jnp.bfloat16)
                     * chunk["valid"][:, None].astype(jnp.bfloat16))
        durs = jnp.stack([chunk["dur_raw"], chunk["dur"],
                          chunk["dur"] * chunk["dur"]], axis=1)
        hi, lo = _split_hi_lo(durs)
        rhs = jnp.concatenate([exact, hi, lo, bucket_oh], axis=1)
        acc = jnp.matmul(onehot16.T, rhs,
                         preferred_element_type=jnp.float32)[:SW]
        a_dur = acc[:, 3:6] + acc[:, 6:9]
        agg = state.agg + jnp.concatenate([acc[:, :3], a_dur], axis=1)
        # log-latency histogram: hist[s, h] += Σ_c 1[sid=c]·1[bucket=h],
        # the same matmul's trailing lanes instead of a scatter
        hist = state.hist + acc[:, 9:]
        hll = hll_update(state.hll, chunk) if with_hll else None
        return ReplayState(agg=agg, hist=hist, hll=hll), None

    return chunk_step


def default_lane_engine() -> str:
    """The FUSED lane-dispatch engine: the validated
    ``ANOMOD_SERVE_LANE_ENGINE`` knob when set, else
    :func:`default_step_engine`'s choice ("scatter" on XLA:CPU, the
    one-hot matmul on accelerators).

    The hands-off default deliberately FOLLOWS the single-chunk step
    engine on every backend — including TPU — so the fused lane path
    stays BIT-identical to sequential per-chunk dispatch and every
    serving parity guarantee (fused==sequential, N-shard==1-shard,
    pipeline depth-invariant) is backend-stable.  The single Mosaic
    kernel ("pallas", anomod.ops.pallas_replay.make_pallas_lane_delta_fn
    — the whole per-lane score chain as one kernel launch per fused
    shape instead of a vmap of one-hot matmuls) is a deployment OPT-IN
    via ``ANOMOD_SERVE_LANE_ENGINE=pallas``: its alert/histogram planes
    are exact vs the other engines but its latency moments carry the
    bf16 hi/lo envelope of the compiled-replay tolerance contract, so
    defaulting it on would silently soften the serve bit-parity pins."""
    from anomod.config import get_config
    knob = get_config().serve_lane_engine
    return default_step_engine() if knob == "auto" else knob


def make_lane_delta(cfg: ReplayConfig, engine: str = "scatter"):
    """The FUSED (lane-stacked) dispatch surface of the chunk step.

    Returns ``delta(chunks) -> (dagg, dhist)`` where every column in
    ``chunks`` is ``[lanes, width]`` (one staged micro-batch chunk per
    lane, dead-padded lanes carry all-pad rows) and the outputs are
    ``[lanes, SW, F]`` / ``[lanes, SW, H]`` per-lane aggregation DELTAS.
    The caller folds lane ``i`` into its tenant's state with the same
    elementwise f32 add the in-step update performs
    (``state.agg + dagg[i]``) — bit-identical to dispatching that lane's
    chunk through ``make_chunk_step`` alone, because the step's state
    update is exactly ``state + delta`` and a zero-state delta IS the
    per-segment sum.  One jit of this compiles once per
    ``(lane-bucket, width)`` shape.

    ``engine="scatter"`` flattens the lanes into ONE segment-sum over
    ``lanes * (SW+1)`` segments (each lane's rows stay contiguous and in
    row order, so per-lane bits match the single-lane scatter step — the
    "many small irregular work items, one wide regular kernel" shape);
    ``engine="matmul"`` is ``jax.vmap`` of the one-hot step for
    accelerator backends; ``engine="pallas"`` is the single fused Mosaic
    kernel (interpret mode off-TPU, so the kernel logic stays testable in
    tier-1) — 0/1 and histogram planes exact vs the other engines,
    latency moments within the bf16 hi/lo envelope (the compiled-replay
    tolerance contract; see make_pallas_lane_delta_fn).
    """
    import jax
    import jax.numpy as jnp

    SW, H = cfg.sw, cfg.n_hist_buckets
    if engine not in ("matmul", "scatter", "pallas"):
        raise ValueError(f"unknown chunk-step engine {engine!r} "
                         "(matmul|scatter|pallas)")

    if engine == "pallas":
        from anomod.ops.pallas_replay import make_pallas_lane_delta_fn
        pfn = make_pallas_lane_delta_fn(
            SW, H, interpret=jax.default_backend() != "tpu")

        def pallas_lane_delta(chunks):
            dur = chunks["dur"]
            # lane-major [L, 6, W] plane stack in the kernel's PLANES
            # order (stage_pallas_planes' row order, per lane)
            planes = jnp.stack(
                [chunks["valid"], chunks["err"], chunks["s5"],
                 chunks["dur_raw"], dur, dur * dur], axis=1)
            out = pfn(chunks["sid"], planes)       # [L, SW, 6+H]
            return out[..., :N_FEATS], out[..., N_FEATS:]

        return pallas_lane_delta

    if engine == "matmul":
        step = make_chunk_step(cfg, with_hll=False, engine="matmul")

        def one_lane(chunk):
            zero = ReplayState(agg=jnp.zeros((SW, N_FEATS), jnp.float32),
                               hist=jnp.zeros((SW, H), jnp.float32))
            st, _ = step(zero, chunk)
            return st.agg, st.hist

        return jax.vmap(one_lane)

    def lane_delta(chunks):
        L, C = chunks["sid"].shape
        flat = {k: v.reshape(L * C) for k, v in chunks.items()}
        # offset each lane's segment ids into its own [SW+1] block (the
        # +1 block absorbs that lane's padding rows), fold ONE segment
        # sum over the whole stack, then peel the pad segments off
        lane = jnp.repeat(jnp.arange(L, dtype=jnp.int32), C)
        sid = lane * (SW + 1) + flat["sid"]
        acc = jax.ops.segment_sum(_scatter_rhs(flat, cfg), sid,
                                  num_segments=L * (SW + 1))
        acc = acc.reshape(L, SW + 1, acc.shape[-1])[:, :SW]
        a_dur = acc[..., 3:6] + acc[..., 6:9]
        return (jnp.concatenate([acc[..., :3], a_dur], axis=-1),
                acc[..., 9:])

    return lane_delta


def fold_delta(state: ReplayState, dagg, dhist) -> ReplayState:
    """THE host-seam fold: apply one lane's aggregation delta to a tenant
    state with the same elementwise f32 adds the in-step update performs
    (``state + delta``).  ONE definition shared by the synchronous
    (``BucketRunner.run_lanes``) and pipelined (``_retire_one``) fold
    paths — and the contract the device pool's scatter-add is pinned
    bit-identical to (an XLA f32 scatter with unique per-dispatch slots
    performs exactly this add per slot)."""
    return ReplayState(agg=np.asarray(state.agg) + dagg,
                       hist=np.asarray(state.hist) + dhist)


#: the TPU's lane tile: a pool plane holds its rows at a multiple of it
POOL_ROW_ALIGN = 128


def pool_row_width(n_floats: int) -> int:
    """Columns a :class:`TenantStatePool` plane holds for a flat state row
    of ``n_floats`` floats: the next multiple of the 128-lane tile (TT's
    agg row of 8,640 is held at 8,704, +0.74% of that plane and +0.2% of
    the pool; TT's hist row of 23,040 and both SN rows need none)."""
    return -(-int(n_floats) // POOL_ROW_ALIGN) * POOL_ROW_ALIGN


class TenantStatePool:
    """POOL-RESIDENT per-tenant replay states for the serving plane.

    One ``[slots, row(SW·F)]`` agg plane plus a matching
    ``[slots, row(SW·H)]`` hist plane per shard runner: a tenant's
    ``[SW, F]`` / ``[SW, H]`` state is ONE FLAT ROW of its plane, held
    at :func:`pool_row_width` columns (the row's floats, then zero
    padding up to a multiple of 128 that nothing reads).  Tenants map
    to slots at first service (:meth:`acquire`).  Row 0 is the DEAD
    slot: dead pad lanes (and the non-current occurrences of a
    duplicated slot, see :meth:`scatter_fold`) scatter their deltas
    there, and it is never read.  The hot-loop fold becomes one
    scatter-add per retired dispatch — the per-lane interpreter adds
    (and, on accelerator backends, the per-tick device→host
    materialization barrier) of the host seam disappear — while
    :meth:`gather`/:meth:`put` keep the ``get_state``/``set_state``
    round-trip bit-exact for parity checks, checkpoints and (future)
    migration.

    Why flat rows (one v5e, PERF.md section 6, PR 27): a TPU array
    lives in (8, 128) tiles over its two minor dimensions, and the
    device orders each shape's dimensions itself (for every shape read
    here, the order that pads least).  The ``[slots, SW, F]`` planes
    this class held before came out
    SLOT-MINOR (``f32[34501,1440,6]{0,1,2:T(8,128)}``: 128 tenants of
    one cell side by side in a tile), while a scatter over rows
    compiles against slot-MAJOR operands — so every fold transposed
    both whole planes and back (27.6 ms a dispatch at 34,501 rows for
    0.05 ms of adds), and a one-row put or gather touched every tile of
    the pool.  Donation kept the BUFFER in place, not the LAYOUT.  A
    2-D plane whose row is a multiple of the 128-lane tile comes out
    row-major (``{1,0:T(8,128)}``), which is the layout the scatter,
    the row updates and the row gathers all compile to: device time
    per op follows the rows touched, never the rows held
    (tpu_tests/test_pool_layout.py guards it).  The row is padded in
    the SHAPE because an unpadded width that is no multiple of 128
    (TT's 8,640 = 67.5 tiles) comes out slot-minor again.

    Two fold ENGINES behind one seam, picked by backend (``auto``),
    both holding the same flat planes:

    - ``jax`` (accelerator backends): the planes are device arrays, the
      ops are jitted with buffer DONATION (the output aliases the
      input, and in the flat layout XLA updates the touched rows in
      place), the scored-window gather is one fused dispatch
      materializing only the requested columns.
    - ``numpy`` (the CPU backend): "device" memory IS host RAM there,
      and XLA:CPU's fixed per-dispatch overhead (~0.2-0.5 ms/call)
      swamps these row shapes — so the planes are host arrays and every
      op is an in-place vectorized numpy update, with the lane deltas
      read through the CPU backend's zero-copy ``np.asarray`` view (no
      readback copy, no XLA dispatch).  Same pool architecture, same
      adds.

    Bit-parity contract (pinned in tests/test_serve_state.py, both
    engines): every pool operation performs the SAME IEEE f32
    arithmetic as the host seam — scatter-add = ``state + delta`` per
    slot in dispatch order (duplicate slots within one dispatch fold in
    lane order via wave splitting), :meth:`roll` =
    :func:`anomod.stream.roll_ring_state`'s shift+zero, gather/put are
    pure copies — so ``device`` vs ``host`` serving is byte-identical,
    not a tolerance trade.
    """

    #: rows one step of the jitted window gather holds at once.  On one
    #: v5e at 34,501 TT rows (PERF.md section 6, PR 27): a request of
    #: 4,096 takes 3.5 ms in chunks of 512 against 4.9 ms whole, one of
    #: 65,536 (the warm grid's largest) 51 ms and 0.27 GB of temporaries
    #: against 73 ms and 5.4 GB
    _GATHER_CHUNK = 512

    def __init__(self, cfg: ReplayConfig, capacity: int = 32,
                 engine: str = "auto", gather_engine: str = "xla"):
        import jax
        import jax.numpy as jnp
        self.cfg = cfg
        self._jnp = jnp
        if engine not in ("auto", "jax", "numpy"):
            raise ValueError(f"unknown pool engine {engine!r} "
                             "(auto|jax|numpy)")
        if engine == "auto":
            engine = "numpy" if jax.default_backend() == "cpu" else "jax"
        self.engine = engine
        if gather_engine not in ("xla", "pallas"):
            raise ValueError(f"unknown pool gather engine "
                             f"{gather_engine!r} (xla|pallas)")
        #: batched-scoring gather formulation: "xla" (take_along_axis /
        #: the numpy engine's fancy-index twin) or "pallas" (the fused
        #: Mosaic gather kernel, anomod.ops.pallas_replay.
        #: make_pallas_window_gather_fn, over the touched rows reshaped
        #: to its [T, SW, F] operand — the serve plane routes
        #: ANOMOD_SERVE_LANE_ENGINE=pallas here).  A pure copy either
        #: way: bit-identical outputs.  The scatter FOLD stays on the
        #: engine's scatter-add (one fused dispatch / one vectorized
        #: in-place add already; see the kernel's docstring for why a
        #: Mosaic scatter is the unverifiable half).
        self.gather_engine = gather_engine
        S, W, H = cfg.n_services, cfg.n_windows, cfg.n_hist_buckets
        #: floats of one tenant's agg / hist row (its plane holds them
        #: at pool_row_width columns: the tail is padding nothing reads)
        self._wa, self._wh = cfg.sw * N_FEATS, cfg.sw * H
        wa, wh = self._wa, self._wh
        self._pallas_gather = None
        if gather_engine == "pallas":
            from anomod.ops.pallas_replay import make_pallas_window_gather_fn
            kernel = make_pallas_window_gather_fn(
                cfg.n_services, cfg.n_windows, N_FEATS,
                interpret=jax.default_backend() != "tpu")

            def _pallas_gather(agg, slots, cols):
                T = slots.shape[0]
                rows = agg[slots][:, :wa]
                return kernel(rows.reshape(T, cfg.sw, N_FEATS),
                              jnp.arange(T, dtype=jnp.int32), cols)

            self._pallas_gather = jax.jit(_pallas_gather)
        cap = max(int(capacity), 1)
        # +1: row 0 is the dead slot
        shape_a = (cap + 1, pool_row_width(wa))
        shape_h = (cap + 1, pool_row_width(wh))
        if engine == "numpy":
            self.agg = np.zeros(shape_a, np.float32)
            self.hist = np.zeros(shape_h, np.float32)
        else:
            self.agg = jnp.zeros(shape_a, jnp.float32)
            self.hist = jnp.zeros(shape_h, jnp.float32)
        self._free: list = []
        self._next = 1
        if engine == "numpy":
            return

        # jitted pool ops (jax engine; jax.jit caches per concrete
        # shape, so pool growth or new lane-bucket widths just add
        # compile-cache entries — warm() precompiles the serve grid).
        # The mutating ops DONATE the planes: the pool is the sole
        # owner of its buffers (every read goes through gather /
        # gather_window) and the rebind below always installs the op's
        # output before anything can read again.  Donation only lets
        # the output alias the input buffer; what keeps the update IN
        # PLACE is the planes' row-major layout (class docstring): each
        # op below reads and writes whole flat rows of it and reshapes
        # only the rows it touches, so none compiles to an op over a
        # whole plane.  The [lanes, SW, *] deltas are flattened inside
        # the fold: a relayout of the lanes, not of the pool.
        def _flat(delta, width, plane):
            rows = delta.reshape(delta.shape[0], width)
            return jnp.pad(rows, ((0, 0), (0, plane.shape[1] - width)))

        def _scatter(agg, hist, slots, dagg, dhist):
            return (agg.at[slots].add(_flat(dagg, wa, agg)),
                    hist.at[slots].add(_flat(dhist, wh, hist)))

        def _put(agg, hist, slot, ragg, rhist):
            return (jax.lax.dynamic_update_slice(
                        agg, ragg.reshape(1, wa), (slot, 0)),
                    jax.lax.dynamic_update_slice(
                        hist, rhist.reshape(1, wh), (slot, 0)))

        def _roll(agg, hist, slot, shift):
            # device twin of anomod.stream.roll_ring_state on one row:
            # shift plane columns left, zero the tail.  Taken values
            # pass through verbatim and the tail is exact 0.0, so the
            # result is bit-identical to the host roll.
            idx = jnp.arange(W) + shift
            take = jnp.clip(idx, 0, W - 1)
            live = (idx < W)[None, :, None]

            def roll2(plane, width):
                x = jax.lax.dynamic_slice(plane, (slot, 0), (1, width))
                x = x.reshape(S, W, -1)
                out = jnp.where(live, jnp.take(x, take, axis=1), 0.0)
                return jax.lax.dynamic_update_slice(
                    plane, out.reshape(1, width), (slot, 0))

            return roll2(agg, wa), roll2(hist, wh)

        chunk = self._GATHER_CHUNK     # the closures below hold no self

        def _gather_chunk(agg, slots, cols):
            rows = agg[slots][:, :wa].reshape(slots.shape[0], S, W,
                                              N_FEATS)
            return jnp.take_along_axis(
                rows, cols[:, None, None, None], axis=2)[:, :, 0]

        def _gather_window(agg, slots, cols):
            # [T, S, F]: ONE dispatch materializing only the scored
            # window column of each requested tenant — the batched
            # scorer's gather.  The touched rows are gathered whole and
            # the column taken from them, _GATHER_CHUNK rows at a time
            # (requests are powers of two): the temporaries stay those
            # of one chunk whatever the request
            T = slots.shape[0]
            if T <= chunk:
                return _gather_chunk(agg, slots, cols)
            out = jax.lax.map(lambda sc: _gather_chunk(agg, *sc),
                              (slots.reshape(-1, chunk),
                               cols.reshape(-1, chunk)))
            return out.reshape(T, S, N_FEATS)

        self._scatter_fn = named_jit("anomod_pool_scatter", _scatter,
                                     donate_argnums=(0, 1))
        self._put_fn = named_jit("anomod_pool_put", _put,
                                 donate_argnums=(0, 1))
        self._roll_fn = named_jit("anomod_pool_roll", _roll,
                                  donate_argnums=(0, 1))
        self._gather_window_fn = named_jit("anomod_pool_gather_window",
                                           _gather_window)

    @property
    def capacity(self) -> int:
        return int(self.agg.shape[0]) - 1

    @property
    def live_slots(self) -> int:
        return self._next - 1 - len(self._free)

    def acquire(self) -> int:
        """Map a new tenant to a zeroed slot (>= 1), growing the pool by
        doubling on exhaustion (growth concatenates zero rows — existing
        states keep their bits)."""
        if self._free:
            return self._free.pop()
        if self._next > self.capacity:
            xp = np if self.engine == "numpy" else self._jnp
            grow = max(self.capacity, 1)
            self.agg = xp.concatenate(
                [self.agg, xp.zeros((grow,) + self.agg.shape[1:],
                                    xp.float32)])
            self.hist = xp.concatenate(
                [self.hist, xp.zeros((grow,) + self.hist.shape[1:],
                                     xp.float32)])
        slot = self._next
        self._next += 1
        return slot

    def release(self, slot: int) -> None:
        """Return a churned tenant's slot to the free list, zeroed (the
        next acquire must start from a fresh state)."""
        z = self.zero_state()
        self.put(slot, z)
        self._free.append(int(slot))

    def zero_state(self) -> ReplayState:
        cfg = self.cfg
        return ReplayState(
            agg=np.zeros((cfg.sw, N_FEATS), np.float32),
            hist=np.zeros((cfg.sw, cfg.n_hist_buckets), np.float32))

    def gather(self, slot: int) -> ReplayState:
        """On-demand readback of one tenant's state (the get_state seam:
        parity, checkpoint, calibration, migration).  Always a COPY —
        the returned pytree must not alias rows later folds mutate."""
        slot = int(slot)   # a None slot must raise, not np.newaxis
        sw = self.cfg.sw
        agg, hist = self.agg[slot, :self._wa], self.hist[slot, :self._wh]
        if self.engine == "numpy":
            agg, hist = agg.copy(), hist.copy()
        return ReplayState(agg=np.asarray(agg).reshape(sw, -1),
                           hist=np.asarray(hist).reshape(sw, -1))

    def put(self, slot: int, state: ReplayState) -> None:
        """Install an externally-built state into a slot (set_state
        seam); a put(gather()) round-trip is byte-identical."""
        slot = int(slot)   # a None slot must raise, not broadcast
        if self.engine == "numpy":
            self.agg[slot, :self._wa] = np.asarray(
                state.agg, np.float32).reshape(-1)
            self.hist[slot, :self._wh] = np.asarray(
                state.hist, np.float32).reshape(-1)
            return
        self.agg, self.hist = self._put_fn(
            self.agg, self.hist, np.int32(slot),
            np.asarray(state.agg, np.float32),
            np.asarray(state.hist, np.float32))

    def roll(self, slot: int, k: int) -> None:
        """Evict the oldest ``k`` ring windows of one tenant's row —
        bit-identical to the host roll_ring_state (values pass through
        verbatim, the tail is exact 0.0)."""
        slot = int(slot)
        shift = min(int(k), self.cfg.n_windows)
        if self.engine == "numpy":
            cfg = self.cfg
            S, W = cfg.n_services, cfg.n_windows
            for plane, width in ((self.agg, self._wa),
                                 (self.hist, self._wh)):
                # in-place view of the row's floats
                x = plane[slot, :width].reshape(S, W, -1)
                if shift < W:
                    x[:, :W - shift] = x[:, shift:].copy()
                    x[:, W - shift:] = 0.0
                else:
                    x[:] = 0.0
            return
        self.agg, self.hist = self._roll_fn(self.agg, self.hist,
                                            np.int32(slot),
                                            np.int32(shift))

    def scatter_fold(self, slots, dagg, dhist) -> None:
        """Fold one retired dispatch's per-lane deltas into the pool:
        ``pool[slot] += delta`` on device, in dispatch order.

        ``slots`` has one entry per LIVE lane (dead pad lanes are
        routed to the dead slot 0 here).  Within one dispatch each live
        slot normally appears once (the engine stacks at most one chunk
        per tenant per round) and the scatter performs exactly one f32
        add per slot — the host seam's :func:`fold_delta` bit-for-bit.
        A duplicated slot folds in lane order on both engines: the
        numpy engine's per-row in-place adds apply sequentially, and
        the jax engine splits the dispatch into WAVES (k-th occurrence
        in wave k, other lanes routed to the dead slot — XLA's
        duplicate-index add order is unspecified) — always
        ((state + d_i) + d_j), never a pre-combined d_i + d_j."""
        L = dagg.shape[0]
        if self.engine == "numpy":
            ls = [int(s) for s in slots]
            n = len(ls)
            if not n:
                return
            # the CPU backend's np.asarray of a jax array is a
            # zero-copy view (it blocks until the dispatch's outputs
            # are ready) — the fold reads the deltas in place, with no
            # readback copy and no fresh state allocations: one slice
            # += when the slots are a contiguous run, else per-row
            # in-place adds (a fancy-index += triggers numpy's
            # gather/add/scatter temporaries and loses to both)
            wa, wh = self._wa, self._wh
            da = np.asarray(dagg).reshape(L, wa)
            dh = np.asarray(dhist).reshape(L, wh)
            lo = ls[0]
            if ls == list(range(lo, lo + n)):
                self.agg[lo:lo + n, :wa] += da[:n]
                self.hist[lo:lo + n, :wh] += dh[:n]
            else:
                for i, s in enumerate(ls):
                    a = self.agg[s, :wa]
                    np.add(a, da[i], out=a)
                    h = self.hist[s, :wh]
                    np.add(h, dh[i], out=h)
            return
        live = np.asarray(slots, np.int32)
        n = len(live)
        waves = 1
        wave_of = None
        if n and len(np.unique(live)) != n:
            order = {}
            wave_of = np.zeros(n, np.int32)
            for i, s in enumerate(live.tolist()):
                wave_of[i] = order.get(s, 0)
                order[s] = wave_of[i] + 1
            waves = int(wave_of.max()) + 1
        lane_slots = np.zeros(L, np.int32)
        lane_slots[:n] = live
        for k in range(waves):
            ws = lane_slots.copy()
            if waves > 1:
                mask = np.zeros(L, bool)
                mask[:n] = wave_of == k
                ws[~mask] = 0
            self.agg, self.hist = self._scatter_fn(
                self.agg, self.hist, ws, dagg, dhist)

    def gather_window(self, slots, cols) -> np.ndarray:
        """[T, S, F] host copy of one plane column per tenant — the
        batched scorer's fused gather (one dispatch, only the scored
        columns materialize).  The request pads to the next power of
        two with dead-slot/column-0 entries (sliced off before return),
        so the jitted gather compiles O(log capacity) shapes instead of
        one per distinct tenant count."""
        slots = np.asarray(slots, np.int32)
        cols = np.asarray(cols, np.int32)
        T = slots.shape[0]
        if self._pallas_gather is None and self.engine == "numpy":
            cfg = self.cfg
            # float (s, cols[t], f) of a flat row sits at
            # (s * W + cols[t]) * F + f
            at = ((np.arange(cfg.n_services)[None, :, None] * cfg.n_windows
                   + cols[:, None, None]) * N_FEATS
                  + np.arange(N_FEATS)[None, None, :])
            return np.asarray(self.agg[slots[:, None, None], at])
        pad = 1
        while pad < T:
            pad *= 2
        if pad != T:
            slots = np.concatenate([slots, np.zeros(pad - T, np.int32)])
            cols = np.concatenate([cols, np.zeros(pad - T, np.int32)])
        fn = (self._pallas_gather if self._pallas_gather is not None
              else self._gather_window_fn)
        return np.asarray(fn(self.agg, slots, cols))[:T]

    def gather_rows(self, slots) -> np.ndarray:
        """[T, SW, F] host copy of whole agg rows (calibration-time
        bulk gather; scoring uses :meth:`gather_window`)."""
        slots = np.asarray(slots, np.int32)
        rows = np.asarray(self.agg[slots])[:, :self._wa]
        return rows.reshape(len(slots), self.cfg.sw, N_FEATS)

    def warm(self, lane_buckets: Tuple[int, ...] = ()) -> float:
        """Compile the pool's hot ops OUTSIDE the measured serve wall:
        one scatter shape per lane bucket (all-zero deltas into the dead
        slot — numerically a no-op on any state), the put/roll row ops,
        and the power-of-two gather grid up to capacity.  Idempotent
        per shape (jax.jit caches); a no-op on the numpy engine (nothing
        compiles there).  Returns the warm wall."""
        if self.engine == "numpy" and self._pallas_gather is None:
            return 0.0
        t0 = time.perf_counter()
        cfg = self.cfg
        if self.engine != "numpy":
            for lanes in lane_buckets:
                self.scatter_fold(
                    [0], np.zeros((lanes, cfg.sw, N_FEATS), np.float32),
                    np.zeros((lanes, cfg.sw, cfg.n_hist_buckets),
                             np.float32))
            self.put(0, self.zero_state())
            self.roll(0, 0)
        pad = 1
        while True:
            self.gather_window(np.zeros(pad, np.int32),
                               np.zeros(pad, np.int32))
            if pad >= self.capacity:
                break
            pad *= 2
        if self.engine != "numpy":
            self.agg.block_until_ready()
        return time.perf_counter() - t0


def make_replay_fn(cfg: ReplayConfig, with_hll: bool = False,
                   inner_repeats: int = 1):
    """Build the jitted replay: scan over chunks, one-hot matmul aggregation.

    ``with_hll=True`` additionally maintains per-service distinct-trace-count
    HLL registers ([S, 2^p] int32, merged exactly by max) — the streaming
    replacement for the reference's exact trace-ID sets
    (trace_collector.py:358-360).

    ``inner_repeats > 1`` replays the staged chunks that many times inside one
    dispatch (a fori_loop around the scan): device-side corpus replication for
    throughput measurement without tiling the host arrays — the HBM working
    set stays one copy while the counted span volume scales.
    """
    import jax
    import jax.numpy as jnp

    SW, H, M = cfg.sw, cfg.n_hist_buckets, cfg.hll_m
    chunk_step = make_chunk_step(cfg, with_hll=with_hll)

    def replay(chunks):
        state = ReplayState(
            agg=jnp.zeros((SW, N_FEATS), jnp.float32),
            hist=jnp.zeros((SW, H), jnp.float32),
            hll=(jnp.zeros((cfg.n_services, M), jnp.int32)
                 if with_hll else None))
        if inner_repeats > 1:
            state = jax.lax.fori_loop(
                0, inner_repeats,
                lambda _, st: jax.lax.scan(chunk_step, st, chunks)[0],
                state)
        else:
            state, _ = jax.lax.scan(chunk_step, state, chunks)
        return state

    return jax.jit(replay)


def replay_numpy(chunks, cfg: ReplayConfig) -> ReplayState:
    """CPU oracle for the replay aggregation."""
    SW, H = cfg.sw, cfg.n_hist_buckets
    agg = np.zeros((SW, N_FEATS), np.float32)
    hist = np.zeros((SW, H), np.float32)
    sid = chunks["sid"].reshape(-1)
    valid = chunks["valid"].reshape(-1) > 0
    sid = sid[valid]
    feats = np.stack([
        chunks["valid"].reshape(-1)[valid],
        chunks["err"].reshape(-1)[valid],
        chunks["s5"].reshape(-1)[valid],
        chunks["dur_raw"].reshape(-1)[valid],
        chunks["dur"].reshape(-1)[valid],
        (chunks["dur"] ** 2).reshape(-1)[valid],
    ], axis=1)
    np.add.at(agg, sid, feats.astype(np.float32))
    bucket = np.clip(chunks["dur"].reshape(-1)[valid].astype(np.int32), 0, H - 1)
    np.add.at(hist, (sid, bucket), 1.0)
    return ReplayState(agg=agg, hist=hist)


def percentile_from_hist(hist: np.ndarray, q: float,
                         as_us: bool = False) -> np.ndarray:
    """Per-row percentile from the log-latency histogram, linearly
    interpolated within the winning bucket (continuous log1p-µs value
    instead of a bare bucket index; ``as_us`` converts back to µs).

    Detection deltas only need bucket resolution, but a reported "p99"
    should not quantize to 16 levels.  For reporting-grade accuracy use
    :func:`replay_percentiles`, which runs the t-digest plane over the same
    segments."""
    cum = np.cumsum(hist, axis=-1)
    total = cum[..., -1:]
    target = q * np.maximum(total, 1e-30)
    idx = np.minimum((cum < target).sum(axis=-1), hist.shape[-1] - 1)
    in_bucket = np.take_along_axis(hist, idx[..., None], axis=-1)[..., 0]
    below = np.take_along_axis(np.concatenate(
        [np.zeros_like(cum[..., :1]), cum], axis=-1),
        idx[..., None], axis=-1)[..., 0]
    frac = np.where(in_bucket > 0,
                    (target[..., 0] - below) / np.maximum(in_bucket, 1e-30),
                    0.5)
    p = idx.astype(np.float32) + np.clip(frac, 0.0, 1.0).astype(np.float32)
    p = np.where(total[..., 0] > 0, p, 0.0).astype(np.float32)  # empty row = 0
    return np.expm1(p).astype(np.float32) if as_us else p


def _resolve_tdigest_engine(engine: str) -> str:
    """Normalize the digest-engine selector: "host" (numpy build), "xla"
    (jitted one-hot build over the same staged lanes), "pallas" (Mosaic
    MXU kernel; interpret mode off-TPU), or "auto" — env override
    ``ANOMOD_TDIGEST_ENGINE`` first, else "xla" iff the default JAX
    backend is a TPU, "host" elsewhere.

    The Mosaic kernel is OPT-IN only (``ANOMOD_TDIGEST_ENGINE=pallas``):
    it does not beat the XLA build at either production regime — 0.884x
    at the replay-plane shape (1M values / 2976 segments) and 0.925x at
    long skewed lanes (2M / 256 segments, L=8064) in PR 21's compiled
    suite on one v5e (PERF.md) — so auto must not route through it.  Auto initializes the backend to look at it; callers
    that must stay host-only in an unknown device environment pass
    engine="host"."""
    engine = (engine or "auto").strip().lower()
    if engine == "auto":
        engine = os.environ.get(
            "ANOMOD_TDIGEST_ENGINE", "").strip().lower() or "auto"
    if engine == "auto":
        import jax
        engine = "xla" if jax.default_backend() == "tpu" else "host"
    if engine not in ("host", "xla", "pallas"):
        raise ValueError(f"unknown t-digest engine {engine!r}")
    return engine


@lru_cache(maxsize=None)
def _xla_tdigest_build(k: int):
    """One jitted XLA digest build per centroid count (compile-cached)."""
    import jax
    import jax.numpy as jnp

    from anomod.ops.tdigest import tdigest_build
    return jax.jit(lambda p, w: tdigest_build(p, k=k, weights=w, xp=jnp))


def _tdigest_by_segment_xla(values, segment_ids, n_segments: int, k: int):
    """Per-segment digests through the jitted XLA one-hot build — the TPU
    auto default.  Host :func:`segment_pad` staging with the kernel path's
    exact lane layout (pad_to=128), so switching engines changes only the
    build, never the staged lanes."""
    from anomod.ops.tdigest import segment_pad
    padded, weights = segment_pad(np.asarray(values, np.float32),
                                  np.asarray(segment_ids), n_segments,
                                  pad_to=128)
    return _xla_tdigest_build(k)(padded, weights)


def _digests_from_staged(chunks, cfg: ReplayConfig, k: int, engine: str):
    """Per-segment t-digest plane from already-staged chunk columns — the
    one engine dispatch shared by every digest entry so a caller that
    already paid ``stage_columns`` (e.g. the combined per-edge reporting
    pass) never re-stages for the digest plane."""
    from anomod.ops.tdigest import TDigest
    sid = chunks["sid"].reshape(-1)
    dur = chunks["dur"].reshape(-1)       # log1p(duration_us), staged
    real = sid < cfg.sw
    engine = _resolve_tdigest_engine(engine)
    if engine == "pallas":
        from anomod.ops.pallas_tdigest import tdigest_by_segment_pallas
        digests = tdigest_by_segment_pallas(dur[real], sid[real], cfg.sw, k=k)
    elif engine == "xla":
        digests = _tdigest_by_segment_xla(dur[real], sid[real], cfg.sw, k=k)
    else:
        from anomod.ops.tdigest import tdigest_by_segment
        digests = tdigest_by_segment(dur[real], sid[real], cfg.sw, k=k)
    return TDigest(mean=np.asarray(digests.mean),
                   weight=np.asarray(digests.weight))


def replay_digests(batch: SpanBatch, cfg: Optional[ReplayConfig] = None,
                   k: int = 64, engine: str = "auto"):
    """The per-(service, window) t-digest plane over the exact segments the
    replay aggregates: [S*W, K] log1p-µs digests (TDigest NamedTuple,
    host-resident numpy arrays — one device transfer regardless of how many
    quantiles are queried afterwards).

    This is the featurization entry the BASELINE names: on a TPU backend
    (engine="auto") the build runs through the jitted XLA one-hot build;
    elsewhere the numpy build.  The Mosaic kernel
    (anomod.ops.pallas_tdigest) remains available as
    ``ANOMOD_TDIGEST_ENGINE=pallas`` but measured no faster than XLA at
    production shapes (see _resolve_tdigest_engine).
    Digests are built in log1p domain — service latencies are heavy-tailed
    and linear-domain centroids smear the p99 tail."""
    cfg = cfg or ReplayConfig(n_services=len(batch.services))
    chunks, _ = stage_columns(batch, cfg)
    return _digests_from_staged(chunks, cfg, k, engine)


def replay_percentiles(batch: SpanBatch, cfg: Optional[ReplayConfig] = None,
                       qs: Tuple[float, ...] = (0.5, 0.95, 0.99),
                       k: int = 64, engine: str = "auto") -> np.ndarray:
    """Reporting-grade per-(service, window) latency percentiles in µs from
    the :func:`replay_digests` plane.

    Returns [S*W, len(qs)] float32.  The streaming digests bound quantile
    error by centroid capacity instead of the histogram's 16-bucket
    quantization — this wires the t-digest plane into the replay path for
    every consumer that reports percentiles rather than detection deltas."""
    from anomod.ops.tdigest import tdigest_quantile
    digests = replay_digests(batch, cfg, k=k, engine=engine)
    out = np.stack([np.expm1(tdigest_quantile(digests, q)) for q in qs],
                   axis=-1)
    return out.astype(np.float32)


def edge_keyed_batch(batch: SpanBatch):
    """Re-key spans to observed call-graph edges: each span maps to the
    (parent-service, own-service) edge (roots and own-parented spans to
    the (svc, svc) self-edge).  Returns ``(batch', edge_table)`` where
    ``batch'.service`` holds dense edge ids and ``edge_table[i]`` is the
    (caller, callee) service-id pair of edge ``i``.

    Parent resolution uses the batch-global ``parent`` row indices, so
    this must run on a FULL corpus (anomod.stream.resolve_parent_services
    has the same contract for the streaming path)."""
    psvc = batch.service.copy()            # default: self-edge
    has = batch.parent >= 0
    psvc[has] = batch.service[batch.parent[has]]
    pairs = psvc.astype(np.int64) * len(batch.services) + batch.service
    uniq, inv = np.unique(pairs, return_inverse=True)
    table = tuple((int(p // len(batch.services)),
                   int(p % len(batch.services))) for p in uniq.tolist())
    return batch._replace(service=inv.astype(np.int32)), table


def _edge_staged(batch: SpanBatch, cfg: Optional[ReplayConfig]):
    """One edge re-key + staging pass shared by every per-edge plane."""
    eb, table = edge_keyed_batch(batch)
    base = cfg or ReplayConfig(n_services=len(batch.services))
    cfg_e = dataclasses.replace(base, n_services=len(table))
    chunks, _ = stage_columns(eb, cfg_e)
    return chunks, cfg_e, table


def _edge_distinct_from_staged(chunks, cfg_e: ReplayConfig):
    from anomod.ops.hll import hll_estimate
    state = make_replay_fn(cfg_e, with_hll=True)(chunks)
    return np.asarray(
        [hll_estimate(r) for r in np.asarray(state.hll)], np.float64)


def _edge_percentiles_from_staged(chunks, cfg_e: ReplayConfig,
                                  qs: Tuple[float, ...], k: int,
                                  engine: str) -> np.ndarray:
    from anomod.ops.tdigest import tdigest_quantile
    digests = _digests_from_staged(chunks, cfg_e, k, engine)
    out = np.stack([np.expm1(tdigest_quantile(digests, q)) for q in qs],
                   axis=-1)
    return out.astype(np.float32)


def replay_edge_distinct(batch: SpanBatch,
                         cfg: Optional[ReplayConfig] = None):
    """PER-EDGE distinct-trace counts via the HLL register plane: how many
    distinct traces cross each observed call-graph edge — the HLL half of
    the BASELINE's per-edge featurization (the t-digest half is
    :func:`replay_edge_percentiles`).  Runs the spans re-keyed to dense
    edge ids through the same jitted chunk step the per-service HLL
    uses; registers merge by max, so shards/streams combine exactly.

    Returns ``(counts, edge_table)``: float64 [E] HLL estimates plus the
    edge id → (caller, callee) service-id table."""
    chunks, cfg_e, table = _edge_staged(batch, cfg)
    return _edge_distinct_from_staged(chunks, cfg_e), table


def replay_edge_percentiles(batch: SpanBatch,
                            cfg: Optional[ReplayConfig] = None,
                            qs: Tuple[float, ...] = (0.5, 0.95, 0.99),
                            k: int = 64, engine: str = "auto"):
    """PER-EDGE latency percentiles: the t-digest plane built over
    (call-graph edge, window) segments instead of (service, window) —
    the per-edge featurization the BASELINE north star names, through
    the same engine dispatch (engine="auto": XLA build on TPU).

    Returns ``(percentiles, edge_table)``: [E*W, len(qs)] float32 µs plus
    the edge id → (caller, callee) service-id table.  Per-edge p99 is
    the reporting view that localizes a slow LINK (the callee side of
    one caller's calls) that per-service percentiles smear across the
    callee's whole traffic mix."""
    chunks, cfg_e, table = _edge_staged(batch, cfg)
    return _edge_percentiles_from_staged(chunks, cfg_e, qs, k, engine), table


def replay_edge_features(batch: SpanBatch,
                         cfg: Optional[ReplayConfig] = None,
                         qs: Tuple[float, ...] = (0.5, 0.95, 0.99),
                         k: int = 64, engine: str = "auto"):
    """Both per-edge planes — t-digest percentiles AND HLL distinct-trace
    counts — from ONE edge re-key + staging pass (the combined reporting
    view ``anomod replay --edge-percentiles`` serves; running the two
    single-plane entries back-to-back would re-key, re-stage and re-scan
    the full corpus twice for the same answer).

    Returns ``(percentiles, counts, edge_table)`` with the same shapes and
    semantics as :func:`replay_edge_percentiles` /
    :func:`replay_edge_distinct`."""
    chunks, cfg_e, table = _edge_staged(batch, cfg)
    pct = _edge_percentiles_from_staged(chunks, cfg_e, qs, k, engine)
    return pct, _edge_distinct_from_staged(chunks, cfg_e), table


def stage_pallas_planes(chunks, xp=np):
    """Flatten staged chunk columns into the fused pallas kernel's layout:
    sid [N] plus the feature-major [6, N] plane stack (anomod.ops.
    pallas_replay.PLANES order; dur² is materialized once so the kernel
    reads every plane in its natural layout).  The single definition of
    the row order — host staging (xp=np) and the sharded replay's
    on-device path (xp=jnp) both use it."""
    sid = chunks["sid"].reshape(-1)
    dur = chunks["dur"].reshape(-1)
    planes = xp.stack([
        chunks["valid"].reshape(-1),
        chunks["err"].reshape(-1),
        chunks["s5"].reshape(-1),
        chunks["dur_raw"].reshape(-1),
        dur,
        dur * dur,
    ])
    return sid, planes


def pallas_block(chunk_size: int) -> int:
    """Pallas kernel block size for a staged corpus: must divide the span
    count (a chunk_size multiple) — chunk_size's largest power-of-2 factor,
    capped at 4096.  For the unsorted kernel that cap keeps its
    ``[block, SW+1]`` bfloat16 one-hot inside VMEM.  For the sorted-window
    kernel it is the STAGING granularity and no longer the size of a grid
    step: ``stage_sorted_planes`` pads each window's span run to a
    multiple of it (a smaller block pads less, a larger one reads fewer
    window ids), and the kernel folds up to eight such blocks a step."""
    block = min(4096, chunk_size & -chunk_size)
    if block < 128:
        raise ValueError(
            "pallas replay kernel needs chunk_size with a power-of-2 "
            f"factor >= 128; got chunk_size={chunk_size}")
    return block


@dataclasses.dataclass
class ThroughputResult:
    n_spans: int
    wall_s: float
    spans_per_sec: float
    compile_s: float
    kernel: str = "xla"
    raw_wall_s: Tuple[float, ...] = ()  # per-repeat walls (median -> wall_s)
    #: the last repeat's [SW, 6+H] aggregate ‖ histogram (host copy), so
    #: a caller can check WHAT was computed, not only how fast
    state: Optional[np.ndarray] = None


def measure_throughput(batch: SpanBatch, cfg: Optional[ReplayConfig] = None,
                       repeats: int = 3, replicate: int = 1,
                       kernel: str = "xla") -> ThroughputResult:
    """Compile, warm up, then time the replay over the staged corpus.

    Timing reads the (tiny) aggregate state back to host each iteration:
    the read-back is the barrier, and the span-count assert below needs
    the values anyway.  ``replicate`` replays the staged chunks that many
    times *on device* (inner fori_loop / outer grid dimension) to amortize
    the fixed per-dispatch overhead into a steady-state number without
    inflating the host arrays or the HBM working set.  ``kernel`` selects the aggregation path: "xla" (scan +
    one-hot matmuls), "pallas" (the fused anomod.ops.pallas_replay
    kernel), "pallas-sorted" (its sorted-window variant — one-time host
    pre-sort into aligned 128-segment windows so the kernel's one-hot is
    128 lanes wide instead of SW+1), or "numpy" — the framework's
    cpu-backend engine
    (BASELINE.json's backend switch): direct scatter-add over the staged
    columns, which is the right shape for a host core (~13x the XLA scan
    on one CPU core, where one-hot matmuls are wasted work) and doubles as
    the parity oracle both device kernels are tested against.
    """
    if kernel not in ("xla", "pallas", "pallas-sorted", "numpy"):
        raise ValueError(f"unknown replay kernel {kernel!r} (expected "
                         "'xla', 'pallas', 'pallas-sorted' or 'numpy')")
    cfg = cfg or ReplayConfig(n_services=len(batch.services))
    chunks_np, n = stage_columns(batch, cfg)
    n *= replicate

    # Per-kernel run_once() -> the column blocks of the [SW, 6+H]
    # aggregate ‖ histogram, the FIRST read back to the host: that
    # read-back is the timed barrier (the aggregate alone on the XLA path,
    # as ever); the rest is fetched and joined after the clock stops.  One
    # shared timing/median/count-assert block below so tolerance and
    # median policy can't silently diverge between engines.
    if kernel == "numpy":
        def run_once():
            for _r in range(replicate):        # host analog of inner_repeats
                out = replay_numpy(chunks_np, cfg)
            return out.agg, out.hist
    elif kernel == "pallas":
        import jax
        from anomod.io.prefetch import device_put_columns
        from anomod.ops.pallas_replay import make_pallas_replay_fn
        sid_np, planes_np = stage_pallas_planes(chunks_np)
        staged = device_put_columns({"sid": sid_np, "planes": planes_np})
        sid, planes = staged["sid"], staged["planes"]
        # off-TPU backends can't execute Mosaic — run the kernel's
        # interpret path so this branch stays testable on the CPU mesh
        interpret = jax.devices()[0].platform != "tpu"
        pfn = make_pallas_replay_fn(cfg.sw, cfg.n_hist_buckets,
                                    inner_repeats=replicate,
                                    block=pallas_block(cfg.chunk_size),
                                    interpret=interpret)
        def run_once():
            return (np.asarray(pfn(sid, planes)),)
    elif kernel == "pallas-sorted":
        import jax
        from anomod.ops.pallas_replay import (make_pallas_replay_sorted_fn,
                                              stage_sorted_planes)
        sid_np, planes_np = stage_pallas_planes(chunks_np)
        block = pallas_block(cfg.chunk_size)
        # one-time host re-stage: sort spans into aligned 128-segment
        # windows so the kernel's one-hot is 128 lanes wide, not SW+1
        sid_l, planes_s, wids = stage_sorted_planes(
            sid_np, planes_np, cfg.sw, block=block)
        from anomod.io.prefetch import device_put_columns
        staged = device_put_columns(
            {"sid": sid_l, "planes": planes_s, "wids": wids})
        sid_d, planes_d, wids_d = (staged["sid"], staged["planes"],
                                   staged["wids"])
        interpret = jax.devices()[0].platform != "tpu"
        pfn = make_pallas_replay_sorted_fn(cfg.sw, cfg.n_hist_buckets,
                                           block=block,
                                           inner_repeats=replicate,
                                           interpret=interpret)
        def run_once():
            return (np.asarray(pfn(sid_d, planes_d, wids_d)),)
    else:
        import jax  # noqa: F401 — backend init before the staged puts
        # double-buffered staging (anomod.io.prefetch): the H2D copy of
        # column j overlaps the enqueue of column j+1
        from anomod.io.prefetch import device_put_columns
        chunks = device_put_columns(chunks_np)
        xfn = make_replay_fn(cfg, inner_repeats=replicate)
        def run_once():
            st = xfn(chunks)
            return np.asarray(st.agg), st.hist

    from anomod import obs
    t0 = time.perf_counter()
    run_once()                                  # compile / cache warm-up
    compile_s = 0.0 if kernel == "numpy" else time.perf_counter() - t0
    if compile_s:
        obs.counter("anomod_replay_compile_total", kernel=kernel).inc()
        obs.counter("anomod_replay_compile_seconds_total",
                    kernel=kernel).inc(compile_s)
    dispatch_s = obs.histogram("anomod_replay_dispatch_seconds",
                               kernel=kernel)
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        blocks = run_once()
        times.append(time.perf_counter() - t0)
        dispatch_s.observe(times[-1])
    state = np.concatenate([np.asarray(b) for b in blocks], axis=1)
    if kernel == "numpy":
        state = state * replicate      # the host loop recomputed one copy
    total = float(state[:, F_COUNT].astype(np.float64).sum())
    # Sanity check with f32 headroom: per-segment counts accumulate on device
    # in f32 and lose exactness past 2^24 spans per (service, window) segment,
    # so allow a small relative slack instead of demanding exact equality.
    assert abs(total - n) <= max(8.0, 1e-6 * n), \
        f"span count mismatch: {total} != {n}"
    wall = sorted(times)[len(times) // 2]
    return ThroughputResult(n_spans=n, wall_s=wall,
                            spans_per_sec=n / wall, compile_s=compile_s,
                            kernel=kernel, raw_wall_s=tuple(times),
                            state=state)
