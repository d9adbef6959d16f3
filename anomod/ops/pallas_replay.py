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

1. **Transposed formulation.**  out[ROWS, SW+1] = rhsᵀ[ROWS, B] @ onehot
   puts the narrow feature axis on *sublanes* (32 rows) instead of lanes
   (25→128, 5x), and the planes are read as they lie, ``[6, B]`` with
   spans on lanes.  The unsorted and the lane-stacked kernel still build
   their one-hot span-major (``sid[:, None]``: one lane→sublane relayout
   of the segment ids a block, XLU work that is most of their step); the
   sorted-window kernel builds it segment-major and keeps spans on lanes
   in every operand (:func:`make_pallas_replay_sorted_fn`).
2. **bf16 one-hot + hi/lo moments, single MXU pass.**  The old kernel ran
   one f32 ``Precision.HIGHEST`` matmul (~6 bf16 MXU passes).  This kernel
   uses the same split as the XLA path (replay.py chunk_step): 0/1 planes
   exact in bf16, latency moments as a two-way bf16 hi/lo split, all in ONE
   bf16 matmul with f32 accumulation.
3. **A one-hot that fits VMEM.**  The old [8192, SW+1] f32 one-hot tile was
   ~46 MB — ~3x core VMEM (~16 MB), so Mosaic spilled it to HBM; a bf16
   tile of 4096 spans stays under 12 MB.  The sorted-window kernel's
   [128, 4096] one-hot never exists as an array: Mosaic pushes the compare
   masks into the MXU as they are made.

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


#: rows of the moment group of the kernels' right-hand side: one packed
#: bfloat16 tile (16 rows a vreg), so the histogram rows start on the next
MOMENT_ROWS = 16


def _build_rhs_t(planes, block, n_hist):
    """Shared kernel-body stage for the replay kernels: the
    ``[16 + H, B]`` bf16 right-hand side.  Rows 0:6 are the planes rounded
    to bfloat16 (the three 0/1 planes exactly, the hi half of the three
    latency moments), rows 8:14 what the rounding left (exactly 0 for the
    0/1 planes, the lo half of the moments), rows 16: the in-kernel
    histogram bucket one-hot; rows 6:8 and 14:16 are zero.  Every piece
    starts on a float32 vreg (8 rows) and every group on a packed bfloat16
    vreg (16 rows): no sublane shuffle.  Traced inside a pallas kernel
    (plain jnp ops only)."""
    import jax
    import jax.numpy as jnp

    # The same values as replay._split_hi_lo, written as the convert pair
    # that function must avoid: ``lax.reduce_precision`` has no Pallas TPU
    # lowering in JAX 0.9.0 (tests/test_pallas_lowering.py trips the day
    # it gets one — then call _split_hi_lo here), and Mosaic, unlike
    # XLA:TPU, does not elide the pair.  That it keeps the lo term is
    # pinned compiled, < 1e-4 against float64 for every kernel that calls
    # this (tpu_tests/test_mosaic_parity.py).
    hi = planes.astype(jnp.bfloat16).astype(jnp.float32)      # [6, B]
    lo = planes - hi
    pad = jnp.zeros((MOMENT_ROWS // 2 - N_PLANES, block), jnp.float32)
    moments = jnp.concatenate([hi, pad, lo, pad], axis=0)     # [16, B]
    valid = planes[0:1]
    bucket = jnp.clip(planes[4:5].astype(jnp.int32), 0, n_hist - 1)
    h_iota = jax.lax.broadcasted_iota(jnp.int32, (n_hist, block), 0)
    bucket_oh = jnp.where(h_iota == bucket, valid, 0.0)       # [H, B]
    return jnp.concatenate([moments, bucket_oh],
                           axis=0).astype(jnp.bfloat16)


def _recombine_moments(acc, n_segments):
    """Shared epilogue: add the lo rows back onto the hi rows, drop the
    dead-pad segment, transpose the row axis back behind the segment axis —
    ``[ROWS, SW+1] -> [SW, F+H]``, or batched ``[L, ROWS, SW+1] ->
    [L, SW, F+H]`` for the lane-stacked kernel.  The row order of
    :func:`_build_rhs_t` is encoded THERE and HERE only."""
    import jax.numpy as jnp

    half = MOMENT_ROWS // 2
    agg_t = jnp.concatenate(
        [acc[..., 0:N_PLANES, :] + acc[..., half:half + N_PLANES, :],
         acc[..., MOMENT_ROWS:, :]], axis=-2)
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
    ROWS = MOMENT_ROWS + n_hist   # hi + lo moment rows, histogram

    def kernel(sid_ref, planes_ref, out_ref):
        @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
        def _init():
            out_ref[:] = jnp.zeros_like(out_ref)

        sid = sid_ref[:]                          # [B] int32
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
    (the replay kernels' default); W must be a block multiple — serve
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
    ROWS = MOMENT_ROWS + n_hist   # hi + lo moment rows, histogram

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
    VMEM accumulator), but the one-hot and the MXU matmul are ``k``
    segments wide instead of ``n_segments + 1``: each staged block's spans
    all live in one aligned k-segment window (host staging guarantees it),
    so the block's [ROWS, k] partial accumulates into a dynamic k-wide
    slice of the accumulator at the window's column offset (``wids`` rides
    scalar prefetch into SMEM).  Aligned windows keep global segment s at
    column s, so the epilogue is the unsorted kernel's.

    **Spans stay on lanes in every operand.**  The one-hot is built
    segment-major, ``onehot_t[k, B] = (iota_k[:, None] == sid[None, :])``:
    ``sid`` is read as it lies and broadcast along sublanes, and the
    product contracts the lane axis of both operands
    (``rhs_t[ROWS, B] · onehot_t[k, B]ᵀ``, the q·kᵀ form of an attention
    kernel).  Mosaic then never materialises the one-hot: it packs the
    compare masks and pushes them into the MXU as transposed weights, 8
    pushes a 128-span tile, 256 a block of 4,096, and those pushes are
    what bounds the kernel on a v5e.  A span-major one-hot
    (``sid[:, None]``) costs a lane→sublane relayout of ``sid`` for each
    of its 512 vregs, XLU work that was most of the step (PERF.md §5 has
    the times of both, stage by stage).

    **``S`` staged blocks a grid step**, ``S`` the largest of 8, 4, 2, 1
    that divides the call's block count (a static of the traced shapes):
    one DMA of ``S * block`` rows, then a loop over the blocks, four an
    iteration so that one block's right-hand side is built while
    another's masks are pushed (the scheduler does not overlap loop
    iterations, and Mosaic unrolls a loop once or fully; four keeps the
    program near 2,100 bundles).  Each block reads its own window id
    from SMEM: a step may straddle a window boundary."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nw = (n_segments + 1 + k - 1) // k
    NWK = nw * k
    ROWS = MOMENT_ROWS + n_hist   # hi + lo moment rows, histogram
    GROUP = 4                     # staged blocks a loop iteration

    def make_kernel(S):
        def kernel(wids_ref, sid_ref, planes_ref, out_ref):
            # read outside the loop: the interpreter resolves program_id
            # in the kernel's own jaxpr only
            step = pl.program_id(1)

            @pl.when((pl.program_id(0) == 0) & (step == 0))
            def _init():
                out_ref[:] = jnp.zeros_like(out_ref)

            seg_iota = jax.lax.broadcasted_iota(jnp.int32, (k, block), 0)

            def fold(j):
                """Staged block ``j`` of this step into its window."""
                rows = pl.ds(pl.multiple_of(j * block, block), block)
                sid = sid_ref[rows].reshape(1, block)   # window-local
                # [6, B] f32 -> shared bf16 rhs build (same split as the
                # unsorted kernel, so the two cannot diverge numerically)
                rhs_t = _build_rhs_t(planes_ref[:, rows], block, n_hist)
                onehot_t = (seg_iota == sid).astype(jnp.bfloat16)  # [k, B]
                partial = jax.lax.dot_general(
                    rhs_t, onehot_t, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)         # [ROWS, k]
                col = pl.multiple_of(wids_ref[step * S + j] * k, k)
                out_ref[:, pl.ds(col, k)] += partial

            def fold_group(i, carry):
                for u in range(GROUP):
                    fold(GROUP * i + u)
                return carry

            if S <= GROUP:
                for j in range(S):
                    fold(j)
            else:
                jax.lax.fori_loop(0, S // GROUP, fold_group, 0)

        return kernel

    @jax.jit
    def run(sid_local, planes, wids):
        t = sid_local.shape[0]
        assert planes.shape == (N_PLANES, t), \
            "planes must be feature-major [6, T]"
        assert t % block == 0, f"span count {t} must be a multiple of {block}"
        n_blocks = t // block
        assert wids.shape == (n_blocks,)
        if t == 0:
            # zero-block grid would skip the init step and return garbage
            return jnp.zeros((n_segments, N_PLANES + n_hist), jnp.float32)
        S = next(s for s in (8, 4, 2, 1) if n_blocks % s == 0)
        acc = pl.pallas_call(
            make_kernel(S),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(inner_repeats, n_blocks // S),
                in_specs=[
                    pl.BlockSpec((S * block,), lambda r, i, w: (i,)),
                    pl.BlockSpec((N_PLANES, S * block),
                                 lambda r, i, w: (0, i)),
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
