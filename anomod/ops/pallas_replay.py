"""Pallas TPU kernel for the replay aggregation hot loop.

Fuses the whole per-chunk pipeline of anomod.replay.make_replay_fn — bf16
hi/lo moment split, one-hot construction, histogram bucketing, and the MXU
matmul — into one kernel whose [F+H, SW+1] accumulator stays VMEM-resident
across the entire grid (state never round-trips to HBM between blocks).

PERF.md carries what the chip has said about these kernels
(``measure_throughput(kernel=)`` selects one; the benchmark's replay
cell runs the sorted-window variant).

Three structural fixes over the round-1 kernel (which measured 6.0e7
spans/sec vs 1.1e8 for the XLA scan path):

1. **Transposed formulation.**  out[F+H, SW+1] = rhsᵀ[F+H, B] @ onehot
   [B, SW+1] puts the narrow 25-row feature axis on *sublanes* (25→32
   padding, 1.3x) instead of lanes (25→128, 5x), and every operand is
   built in its natural layout — the old kernel's in-kernel ``feats.T``
   relayout is gone.
2. **bf16 one-hot + hi/lo moments, single MXU pass.**  The old kernel ran
   one f32 ``Precision.HIGHEST`` matmul (~6 bf16 MXU passes).  This kernel
   uses the same split as the XLA path (replay.py chunk_step): 0/1 planes
   exact in bf16, latency moments as a two-way bf16 hi/lo split, all in ONE
   bf16 matmul with f32 accumulation.
3. **VMEM-sized tiles.**  The old [8192, SW+1] f32 one-hot tile was ~46 MB
   — ~3x core VMEM (~16 MB), so Mosaic spilled it to HBM.  The default
   block of 4096 keeps the bf16 tile under 12 MB.

``inner_repeats`` replays the staged corpus on-device via an outer grid
dimension (same measurement trick as the XLA path's fori_loop).

Falls back to interpret mode off-TPU (used by the CPU-mesh tests).
"""

from __future__ import annotations

import numpy as np

# staged-column order fed to the kernel (matches anomod.replay plane order:
# the three exact 0/1 planes, then the three latency-moment planes)
PLANES = ("valid", "err", "s5", "dur_raw", "dur", "dur2")
N_PLANES = len(PLANES)


def _build_rhs_t(planes, block, n_hist):
    """Shared kernel-body stage for both replay kernels: the [3+6+H, B]
    bf16 right-hand side — exact 0/1 planes, two-way hi/lo split of the
    latency moments, and the in-kernel histogram bucket one-hot.  Traced
    inside a pallas kernel (plain jnp ops only)."""
    import jax
    import jax.numpy as jnp

    exact = planes[0:3].astype(jnp.bfloat16)  # valid / err / 5xx
    moments = planes[3:6]                     # dur_raw / dur / dur^2
    # The same values as replay._split_hi_lo, written as the convert pair
    # that function must avoid: ``lax.reduce_precision`` has no Pallas TPU
    # lowering in JAX 0.9.0 (tests/test_pallas_lowering.py trips the day
    # it gets one — then call _split_hi_lo here), and Mosaic, unlike
    # XLA:TPU, does not elide the pair.  That it keeps the lo term is
    # pinned compiled, < 1e-4 against float64 for every kernel that calls
    # this (tpu_tests/test_mosaic_parity.py).
    hi = moments.astype(jnp.bfloat16)
    lo = (moments - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    valid = planes[0]
    bucket = jnp.clip(planes[4].astype(jnp.int32), 0, n_hist - 1)
    h_iota = jax.lax.broadcasted_iota(jnp.int32, (n_hist, block), 0)
    bucket_oh = jnp.where(h_iota == bucket[None, :], valid[None, :],
                          0.0).astype(jnp.bfloat16)       # [H, B]
    return jnp.concatenate([exact, hi, lo, bucket_oh], axis=0)


def _recombine_moments(acc, n_segments):
    """Shared epilogue: recombine hi+lo moment rows, drop the dead-pad
    segment, transpose the row axis back behind the segment axis —
    ``[ROWS, SW+1] -> [SW, F+H]``, or batched ``[L, ROWS, SW+1] ->
    [L, SW, F+H]`` for the lane-stacked kernel.  The bf16 hi/lo split
    layout (3 exact + 3 hi + 3 lo + H histogram rows) is encoded HERE
    and in the kernels' rhs staging only."""
    import jax.numpy as jnp

    agg_t = jnp.concatenate(
        [acc[..., 0:3, :], acc[..., 3:6, :] + acc[..., 6:9, :],
         acc[..., 9:, :]], axis=-2)
    return jnp.swapaxes(agg_t, -1, -2)[..., :n_segments, :]


def make_pallas_replay_fn(n_segments: int, n_hist: int = 16,
                          block: int = 4096, interpret: bool = False,
                          inner_repeats: int = 1):
    """Returns fn(sid[N], planes[6, N]) -> agg[SW, 6+H].

    ``sid`` may contain n_segments (== dead/padding lane, dropped).
    ``planes`` rows follow :data:`PLANES`; the histogram bucket is computed
    in-kernel from the log-latency row (``clip(int(dur), 0, H-1)``), and the
    histogram occupies the trailing H columns of the output.

    When invoked inside ``shard_map``, the enclosing shard_map must pass
    ``check_vma=False``: the kernel's internal constants don't carry mesh
    varying-axes metadata, and the static checker rejects the mix whether
    or not the output declares a vma (see make_sharded_replay_fn).
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    SW1 = n_segments + 1          # + dead lane
    ROWS = 3 + 6 + n_hist         # exact + (hi, lo) moments + histogram

    def kernel(sid_ref, planes_ref, out_ref):
        @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
        def _init():
            out_ref[:] = jnp.zeros_like(out_ref)

        sid = sid_ref[:]                          # [B] int32
        # [6, B] f32 natural layout -> shared bf16 rhs build
        rhs_t = _build_rhs_t(planes_ref[:], block, n_hist)
        seg_iota = jax.lax.broadcasted_iota(jnp.int32, (block, SW1), 1)
        onehot = (seg_iota == sid[:, None]).astype(jnp.bfloat16)
        out_ref[:] += jax.lax.dot_general(
            rhs_t, onehot, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @jax.jit
    def run(sid, planes):
        n = sid.shape[0]
        assert planes.shape == (N_PLANES, n), \
            "planes must be feature-major [6, N]"
        assert n % block == 0, f"span count {n} must be a multiple of {block}"
        if n == 0:
            # zero-block grid would skip the init step and return garbage
            return jnp.zeros((n_segments, N_PLANES + n_hist), jnp.float32)
        grid = (inner_repeats, n // block)
        acc = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block,), lambda r, i: (i,)),
                pl.BlockSpec((N_PLANES, block), lambda r, i: (0, i)),
            ],
            out_specs=pl.BlockSpec((ROWS, SW1), lambda r, i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((ROWS, SW1), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
        )(sid, planes)
        return _recombine_moments(acc, n_segments)

    return run


def make_pallas_lane_delta_fn(n_segments: int, n_hist: int = 16,
                              block: int = 0, interpret: bool = False):
    """The serving plane's fused LANE-STACKED score kernel:
    ``fn(sid[L, W] int32, planes[L, 6, W] f32) -> [L, SW, 6+H]`` per-lane
    aggregation deltas — anomod.replay.make_lane_delta's TPU formulation
    as ONE Mosaic kernel instead of a vmap of the one-hot chunk step.

    Each grid step processes one ``block``-wide slice of one lane through
    the same fused pipeline as :func:`make_pallas_replay_fn` (bf16 hi/lo
    moment split, in-kernel histogram bucketing, single bf16 MXU matmul
    with f32 accumulation), accumulating into that lane's VMEM-resident
    ``[ROWS, SW+1]`` block — the per-lane roll/split/edge/score chain the
    interpreter used to drive as separate dispatches runs as one kernel
    launch per fused (lanes, width) shape.  Dead pad lanes carry all-pad
    rows (sid = SW, valid = 0) and produce exact-zero deltas, exactly as
    the scatter twin's dead segments.  ``block=0`` picks ``min(W, 4096)``
    (the VMEM-tuned replay default); W must be a block multiple — serve
    widths are powers of two, so the default always divides.

    Parity contract: identical 0/1 and histogram planes to the scatter/
    matmul engines (exact bf16 values, f32 accumulation); latency moments
    within the bf16 hi/lo split's error envelope — the same tolerance
    the compiled replay-kernel pins use.  Interpret mode keeps the
    kernel exercised in tier-1 on CPU (tests/test_replay.py); the
    Mosaic-compiled pin lives in tpu_tests/test_mosaic_parity.py.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    SW1 = n_segments + 1          # + dead lane
    ROWS = 3 + 6 + n_hist         # exact + (hi, lo) moments + histogram

    def run(sid, planes):
        L, W = sid.shape
        assert planes.shape == (L, N_PLANES, W), \
            "planes must be lane-major [L, 6, W]"
        blk = block or min(W, 4096)
        assert W % blk == 0, f"width {W} must be a multiple of {blk}"

        def kernel(sid_ref, planes_ref, out_ref):
            @pl.when(pl.program_id(1) == 0)
            def _init():
                out_ref[:] = jnp.zeros_like(out_ref)

            s = sid_ref[0, 0]                     # [B] int32, this lane
            rhs_t = _build_rhs_t(planes_ref[0], blk, n_hist)
            seg_iota = jax.lax.broadcasted_iota(jnp.int32, (blk, SW1), 1)
            onehot = (seg_iota == s[:, None]).astype(jnp.bfloat16)
            out_ref[0] += jax.lax.dot_general(
                rhs_t, onehot, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        acc = pl.pallas_call(
            kernel,
            grid=(L, W // blk),
            in_specs=[
                # sid rides as [L, 1, W]: a (1, blk) block of an [L, W]
                # array breaks Mosaic's rule that the block's last two
                # dims are (8, 128)-divisible or the array's full dims
                pl.BlockSpec((1, 1, blk), lambda l, i: (l, 0, i)),
                pl.BlockSpec((1, N_PLANES, blk), lambda l, i: (l, 0, i)),
            ],
            out_specs=pl.BlockSpec((1, ROWS, SW1), lambda l, i: (l, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((L, ROWS, SW1), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
        )(sid[:, None, :], planes)
        return _recombine_moments(acc, n_segments)

    return run


def stage_sorted_planes(sid, planes, n_segments, k: int = 128,
                        block: int = 4096):
    """Host-side re-staging for the sorted-window kernel: sort spans by
    segment id, bucket them into aligned windows of ``k`` segments
    (window w owns segments [w*k, (w+1)*k)), and pad each window's span run
    to a ``block`` multiple so every kernel block touches exactly one
    window.

    Returns ``(sid_local[T], planes[6, T], wids[T // block])`` where
    ``sid_local = sid - wid*k`` ∈ [0, k) and padding rows carry
    ``sid_local = 0`` with all-zero planes (they contribute nothing to any
    output plane — including the count — because every aggregated value is
    a plane-weighted sum).  One-time cost, O(N log N) on the host: replay
    measurement loops never re-stage.
    """
    sid = np.asarray(sid, np.int32)
    planes = np.asarray(planes, np.float32)
    n = sid.shape[0]
    nw = (n_segments + 1 + k - 1) // k      # + dead lane
    order = np.argsort(sid, kind="stable")
    sid_s = sid[order]
    wid_s = sid_s // k
    counts = np.bincount(wid_s, minlength=nw)
    padded = -(-counts // block) * block    # per-window ceil to block
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    pad_starts = np.concatenate([[0], np.cumsum(padded)[:-1]])
    total = int(padded.sum())
    dst = (pad_starts[wid_s] + (np.arange(n) - starts[wid_s])).astype(np.int64)
    sid_local = np.zeros(total, np.int32)
    sid_local[dst] = sid_s - wid_s * k
    planes_out = np.zeros((planes.shape[0], total), np.float32)
    planes_out[:, dst] = planes[:, order]
    wids = np.repeat(np.arange(nw, dtype=np.int32), padded // block)
    return sid_local, planes_out, wids


def make_pallas_replay_sorted_fn(n_segments: int, n_hist: int = 16,
                                 k: int = 128, block: int = 4096,
                                 interpret: bool = False,
                                 inner_repeats: int = 1):
    """Sorted-window variant of :func:`make_pallas_replay_fn`:
    ``fn(sid_local[T], planes[6, T], wids[T // block]) -> agg[SW, 6+H]``
    over arrays staged by :func:`stage_sorted_planes`.

    Same fused pipeline (bf16 hi/lo split, in-kernel bucketing, resident
    VMEM accumulator), but the one-hot and the MXU matmul are ``k`` lanes
    wide instead of ``n_segments + 1``: each block's spans all live in one
    aligned k-segment window (host staging guarantees it), so the block's
    [ROWS, k] partial accumulates into a dynamic k-wide slice of the
    accumulator at the window's column offset (``wids`` rides scalar
    prefetch into the index-map/kernel).  For the TT bench corpus
    (SW+1 = 1441, k = 128) that is ~11x less one-hot construction and MXU
    work per span for ~5% padding — aligned windows keep global segment s
    at column s, so the epilogue is unchanged."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nw = (n_segments + 1 + k - 1) // k
    NWK = nw * k
    ROWS = 3 + 6 + n_hist         # exact + (hi, lo) moments + histogram

    def kernel(wids_ref, sid_ref, planes_ref, out_ref):
        @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
        def _init():
            out_ref[:] = jnp.zeros_like(out_ref)

        sid = sid_ref[:]                          # [B] int32, window-local
        # [6, B] f32 -> shared bf16 rhs build (same split as the unsorted
        # kernel, so the two paths cannot diverge numerically)
        rhs_t = _build_rhs_t(planes_ref[:], block, n_hist)
        seg_iota = jax.lax.broadcasted_iota(jnp.int32, (block, k), 1)
        onehot = (seg_iota == sid[:, None]).astype(jnp.bfloat16)  # [B, k]
        partial = jax.lax.dot_general(
            rhs_t, onehot, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [ROWS, k]
        col = wids_ref[pl.program_id(1)] * k
        out_ref[:, pl.ds(col, k)] += partial

    @jax.jit
    def run(sid_local, planes, wids):
        t = sid_local.shape[0]
        assert planes.shape == (N_PLANES, t), \
            "planes must be feature-major [6, T]"
        assert t % block == 0, f"span count {t} must be a multiple of {block}"
        assert wids.shape == (t // block,)
        if t == 0:
            # zero-block grid would skip the init step and return garbage
            return jnp.zeros((n_segments, N_PLANES + n_hist), jnp.float32)
        grid = (inner_repeats, t // block)
        acc = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=grid,
                in_specs=[
                    pl.BlockSpec((block,), lambda r, i, w: (i,)),
                    pl.BlockSpec((N_PLANES, block), lambda r, i, w: (0, i)),
                ],
                out_specs=pl.BlockSpec((ROWS, NWK), lambda r, i, w: (0, 0)),
            ),
            out_shape=jax.ShapeDtypeStruct((ROWS, NWK), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
        )(wids, sid_local, planes)
        return _recombine_moments(acc, n_segments)

    return run


def pallas_replay_numpy(sid, planes, n_segments, n_hist):
    """Oracle for the fused kernel (planes feature-major [6, N])."""
    out = np.zeros((n_segments + 1, N_PLANES + n_hist), np.float32)
    np.add.at(out[:, :N_PLANES], sid, planes.T)
    valid = planes[0]
    bucket = np.clip(planes[4].astype(np.int32), 0, n_hist - 1)
    np.add.at(out, (sid, N_PLANES + bucket), valid)
    return out[:n_segments]


def make_pallas_window_gather_fn(n_services: int, n_windows: int,
                                 n_feats: int, interpret: bool = False):
    """The device state pool's batched-scoring gather as ONE Mosaic
    kernel: ``fn(pool[P, S*W, F], slots[T], cols[T]) -> [T, S, F]`` —
    tenant ``t``'s scored window column ``pool[slots[t]].reshape(
    S, W, F)[:, cols[t]]``, one grid step per tenant, slot/column
    indices scalar-prefetched so the block index maps can address the
    pool rows directly (the same PrefetchScalarGridSpec pattern as the
    sorted-window replay kernel above).

    This is the SCORE half of the serve plane's pallas opt-in
    (``ANOMOD_SERVE_LANE_ENGINE=pallas`` routes the pool's gather here;
    anomod.replay.TenantStatePool).  A pure copy, so the gathered
    columns are bit-identical to the XLA take_along_axis gather on
    every backend — interpret mode keeps it exercised in tier-1 on CPU.

    The FOLD half deliberately stays on XLA's scatter-add: it already
    runs as one fused dispatch, and a Mosaic scatter must revisit
    aliased output blocks when lanes share a slot (dead pad lanes all
    target slot 0), a write-back ordering hazard interpret mode cannot
    pin — fused-gather + XLA-scatter is the whole win without the
    unverifiable half.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, W, F = n_services, n_windows, n_feats

    def kernel(slots_ref, cols_ref, pool_ref, out_ref):
        del slots_ref                  # consumed by the index map
        c = cols_ref[pl.program_id(0)]
        # service s's window column c is row s*W + c of the [S*W, F]
        # plane: S dynamic-offset row loads straight off the ref (Mosaic
        # lowers neither dynamic_slice on a value nor the in-kernel
        # reshape to [S, W, F])
        for s in range(S):
            out_ref[0, pl.ds(s, 1), :] = pool_ref[0, pl.ds(s * W + c, 1), :]

    @jax.jit
    def run(pool, slots, cols):
        T = slots.shape[0]
        assert pool.shape[1:] == (S * W, F), "pool must be [P, S*W, F]"
        assert cols.shape == (T,)
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(T,),
                in_specs=[
                    pl.BlockSpec((1, S * W, F),
                                 lambda t, s, c: (s[t], 0, 0)),
                ],
                out_specs=pl.BlockSpec((1, S, F), lambda t, s, c: (t, 0, 0)),
            ),
            out_shape=jax.ShapeDtypeStruct((T, S, F), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
        )(slots, cols, pool)

    return run
