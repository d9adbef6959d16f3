"""Mosaic-compiled parity for every Pallas kernel (round-2 weak #2).

The CPU-mesh suite proves kernel *logic* via interpret mode; this module
proves the *compiled* kernels — Mosaic layouts, bf16 hi/lo numerics on the
real MXU, VMEM residency at the bench block size (4096), the revisited
output block across grid steps, and the shard_map ``check_vma=False``
composition — against the same numpy oracles, on a real synthetic corpus
at production shapes.
"""

import numpy as np
import pytest


@pytest.fixture(scope="module")
def tt_corpus():
    """A real multi-experiment TT corpus staged exactly like the replay cell
    (all 13 labels so the service vocabulary and sid range match the
    production replay), small enough to stage in seconds."""
    from anomod import labels, synth
    from anomod.replay import ReplayConfig, stage_columns
    from anomod.schemas import concat_span_batches

    batches = [synth.generate_spans(l, n_traces=60)
               for l in labels.labels_for_testbed("TT")]
    batch = concat_span_batches(batches)
    cfg = ReplayConfig(n_services=batch.n_services)
    chunks, n = stage_columns(batch, cfg)
    return batch, cfg, chunks, n


def test_replay_kernel_compiled_production_shape(tt_corpus):
    """Fused replay kernel, Mosaic-compiled at the bench configuration
    (block=4096, full TT service vocabulary) vs the numpy oracle."""
    from anomod.ops.pallas_replay import make_pallas_replay_fn
    from anomod.replay import pallas_block, replay_numpy, stage_pallas_planes

    _, cfg, chunks, _ = tt_corpus
    sid, planes = stage_pallas_planes(chunks)
    fn = make_pallas_replay_fn(cfg.sw, cfg.n_hist_buckets,
                               block=pallas_block(cfg.chunk_size))
    out = np.asarray(fn(sid, planes))
    ref = replay_numpy(chunks, cfg)
    # same tolerance contract as the interpret-mode test: 0/1 planes and
    # histogram exact, moments within the bf16 hi/lo split's error
    np.testing.assert_allclose(out[:, :3], ref.agg[:, :3], rtol=0, atol=0)
    np.testing.assert_allclose(out[:, 6:], ref.hist, rtol=0, atol=0)
    np.testing.assert_allclose(out[:, 3:6], ref.agg[:, 3:6], rtol=2e-3,
                               atol=1e-2)


def moment_error(agg, chunks, sw):
    """Max relative error of the latency-moment columns ``agg[:, 3:6]``
    against a float64 oracle over the staged ``chunks``.  The hi/lo split
    is good to ~6e-6; with the lo term lost it is 3e-3."""
    sid = np.asarray(chunks["sid"]).reshape(-1)
    valid = np.asarray(chunks["valid"]).reshape(-1) > 0
    dur = np.asarray(chunks["dur"]).reshape(-1).astype(np.float64)
    raw = np.asarray(chunks["dur_raw"]).reshape(-1).astype(np.float64)
    oracle = np.zeros((sw + 1, 3), np.float64)
    np.add.at(oracle, sid[valid],
              np.stack([raw, dur, dur * dur], axis=1)[valid])
    oracle = oracle[:sw]
    live = oracle[:, 1] > 0
    assert live.any()
    rel = np.abs(agg[live, 3:6] - oracle[live]) / np.abs(oracle[live])
    return rel.max()


def test_latency_moments_keep_the_lo_term(tt_corpus):
    """The bf16 hi/lo split must survive compilation on EVERY device
    path.  XLA's TPU pipeline elides an f32->bf16->f32 convert pair, which
    once zeroed the lo term of the XLA scan (and the serve plane's matmul
    lanes) and left the moments at bf16 precision — 3.3e-3 max relative
    error, inside the 2e-3-rtol parity pins below on most segments.  The
    Pallas kernels still write that pair (``reduce_precision`` has no
    Mosaic lowering), so each of them is held to the float64 oracle too;
    the lane kernels are in test_lane_delta_kernel_compiled."""
    from anomod.ops.pallas_replay import (make_pallas_replay_fn,
                                          make_pallas_replay_sorted_fn,
                                          stage_sorted_planes)
    from anomod.replay import (make_replay_fn, pallas_block,
                               stage_pallas_planes)

    _, cfg, chunks, _ = tt_corpus
    block = pallas_block(cfg.chunk_size)
    s, p = stage_pallas_planes(chunks)
    got = {
        "xla": make_replay_fn(cfg)(chunks).agg,
        "pallas": make_pallas_replay_fn(
            cfg.sw, cfg.n_hist_buckets, block=block)(s, p),
        "pallas-sorted": make_pallas_replay_sorted_fn(
            cfg.sw, cfg.n_hist_buckets, block=block)(
                *stage_sorted_planes(s, p, cfg.sw, block=block)),
    }
    for name, agg in got.items():
        err = moment_error(np.asarray(agg), chunks, cfg.sw)
        assert err < 1e-4, (name, err)


def test_replay_kernel_compiled_inner_repeats(tt_corpus):
    """The bench measurement trick — replaying the staged corpus via the
    outer grid dimension — must accumulate exactly r copies of the state
    when compiled (revisited-output-block semantics under Mosaic)."""
    from anomod.ops.pallas_replay import make_pallas_replay_fn
    from anomod.replay import pallas_block, replay_numpy, stage_pallas_planes

    _, cfg, chunks, _ = tt_corpus
    sid, planes = stage_pallas_planes(chunks)
    r = 3
    fn = make_pallas_replay_fn(cfg.sw, cfg.n_hist_buckets,
                               block=pallas_block(cfg.chunk_size),
                               inner_repeats=r)
    out = np.asarray(fn(sid, planes))
    ref = replay_numpy(chunks, cfg)
    np.testing.assert_allclose(out[:, :3], r * ref.agg[:, :3], rtol=0, atol=0)
    np.testing.assert_allclose(out[:, 6:], r * ref.hist, rtol=0, atol=0)
    np.testing.assert_allclose(out[:, 3:6], r * ref.agg[:, 3:6], rtol=2e-3,
                               atol=3e-2)


def test_replay_sorted_kernel_compiled(tt_corpus):
    """Sorted-window kernel, Mosaic-compiled at production shape: the
    128-lane local one-hot, the scalar-prefetched window ids, and the
    dynamic-slice accumulate into the resident block — vs the numpy
    oracle, including inner_repeats accumulation."""
    from anomod.ops.pallas_replay import (make_pallas_replay_sorted_fn,
                                          stage_sorted_planes)
    from anomod.replay import pallas_block, replay_numpy, stage_pallas_planes

    _, cfg, chunks, _ = tt_corpus
    sid, planes = stage_pallas_planes(chunks)
    block = pallas_block(cfg.chunk_size)
    sid_l, planes_s, wids = stage_sorted_planes(sid, planes, cfg.sw,
                                                block=block)
    r = 2
    fn = make_pallas_replay_sorted_fn(cfg.sw, cfg.n_hist_buckets,
                                      block=block, inner_repeats=r)
    out = np.asarray(fn(sid_l, planes_s, wids))
    ref = replay_numpy(chunks, cfg)
    np.testing.assert_allclose(out[:, :3], r * ref.agg[:, :3], rtol=0, atol=0)
    np.testing.assert_allclose(out[:, 6:], r * ref.hist, rtol=0, atol=0)
    np.testing.assert_allclose(out[:, 3:6], r * ref.agg[:, 3:6], rtol=2e-3,
                               atol=3e-2)


@pytest.mark.parametrize("n_blocks,per_step", [(16, 8), (12, 4), (7, 1)])
def test_replay_sorted_kernel_blocks_a_step_compiled(tt_corpus, n_blocks,
                                                     per_step):
    """The sorted-window kernel folds ``per_step`` staged blocks a grid
    step (the largest of 8, 4, 2, 1 that divides the block count) and a
    step straddles window boundaries: each block of the step reads its
    own window id from SMEM and adds into its own columns.  Compiled,
    against float64 over the very rows handed to it: 0/1 planes and
    histogram exact, latency moments < 1e-4 (the lo term kept through
    the segment-major one-hot's masked pushes)."""
    from anomod.ops.pallas_replay import (make_pallas_replay_sorted_fn,
                                          pallas_replay_numpy,
                                          stage_sorted_planes)
    from anomod.replay import pallas_block, stage_pallas_planes

    _, cfg, chunks, _ = tt_corpus
    block = pallas_block(cfg.chunk_size)
    sid_l, planes_s, wids = stage_sorted_planes(
        *stage_pallas_planes(chunks), cfg.sw, block=block)
    assert wids.shape[0] >= n_blocks
    # staged arrays are whole blocks: the first n_blocks are a corpus too
    sid_l, planes_s, wids = (sid_l[:n_blocks * block],
                             planes_s[:, :n_blocks * block], wids[:n_blocks])
    assert next(s for s in (8, 4, 2, 1) if n_blocks % s == 0) == per_step
    if per_step > 1:
        assert len(set(wids[:per_step].tolist())) > 1
    fn = make_pallas_replay_sorted_fn(cfg.sw, cfg.n_hist_buckets,
                                      block=block)
    out = np.asarray(fn(sid_l, planes_s, wids))
    seg = sid_l + np.repeat(wids, block) * 128
    want = pallas_replay_numpy(seg, planes_s, cfg.sw, cfg.n_hist_buckets)
    np.testing.assert_array_equal(out[:, :3], want[:, :3])
    np.testing.assert_array_equal(out[:, 6:], want[:, 6:])
    moments = np.zeros((cfg.sw + 1, 3), np.float64)
    np.add.at(moments, seg, planes_s[3:6].astype(np.float64).T)
    moments = moments[:cfg.sw]
    live = moments[:, 1] > 0
    assert live.any()
    rel = np.abs(out[live, 3:6] - moments[live]) / moments[live]
    assert rel.max() < 1e-4, rel.max()


@pytest.mark.parametrize("engine", ["pallas", "matmul"])
def test_lane_delta_kernel_compiled(engine):
    """The serving plane's lane-stacked score step at serve shapes —
    the fused Mosaic kernel (ISSUE-7) and the default vmap'd one-hot
    matmul: [lanes, width] stacked chunks → per-lane deltas in ONE
    launch, vs the per-lane numpy oracle, with the latency moments held
    to the float64 oracle (the lo term alive).  Dead pad lanes must come
    back exactly zero.  (The CPU-interpret twin runs in tier-1:
    tests/test_replay.py.)"""
    import jax

    from anomod.replay import (ReplayConfig, dead_chunk, make_lane_delta,
                               replay_numpy, stage_columns)
    from anomod import labels, synth

    cfg = ReplayConfig(n_services=12, n_windows=32,
                       window_us=5_000_000, chunk_size=4096)  # serve shape
    lanes = []
    for i, l in enumerate(labels.labels_for_testbed("TT")[:4]):
        b = synth.generate_spans(l, n_traces=40, seed=i)
        b = b._replace(service=b.service % cfg.n_services,
                       services=b.services[:cfg.n_services])
        staged, _ = stage_columns(b, cfg, t0_us=0)
        lanes.append({k: v[0] for k, v in staged.items()})
    lanes.append(dead_chunk(cfg, cfg.chunk_size, xp=np))
    stack = {k: np.stack([np.asarray(c[k]) for c in lanes])
             for k in lanes[0]}
    fn = jax.jit(make_lane_delta(cfg, engine=engine))
    dagg, dhist = fn(stack)
    dagg, dhist = np.asarray(dagg), np.asarray(dhist)
    for i, chunk in enumerate(lanes):
        ref = replay_numpy({k: np.asarray(v)[None] for k, v in
                            chunk.items()}, cfg)
        np.testing.assert_allclose(dagg[i, :, :3], ref.agg[:, :3],
                                   rtol=0, atol=0)
        np.testing.assert_allclose(dhist[i], ref.hist, rtol=0, atol=0)
        np.testing.assert_allclose(dagg[i, :, 3:6], ref.agg[:, 3:6],
                                   rtol=2e-3, atol=1e-2)
    for i, chunk in enumerate(lanes[:-1]):
        err = moment_error(dagg[i], chunk, cfg.sw)
        assert err < 1e-4, (engine, i, err)
    assert (dagg[-1] == 0).all() and (dhist[-1] == 0).all()


def test_window_gather_kernel_compiled():
    """The device pool's batched-scoring gather, Mosaic-compiled at the
    serve plane's shape (12 services x 32 windows, a 256-slot pool): a
    pure copy, so bit-equal to the XLA take_along_axis gather."""
    from anomod.replay import ReplayConfig, TenantStatePool

    cfg = ReplayConfig(n_services=12, n_windows=32,
                       window_us=5_000_000, chunk_size=4096)
    rng = np.random.default_rng(5)
    pools = {g: TenantStatePool(cfg, capacity=256, engine="jax",
                                gather_engine=g) for g in ("xla", "pallas")}
    for slot in (1, 7, 200, 256):
        st = pools["xla"].zero_state()
        st = st._replace(agg=rng.normal(size=st.agg.shape)
                         .astype(np.float32))
        for pool in pools.values():
            pool.put(slot, st)
    slots = np.array([1, 7, 200, 256, 7], np.int32)
    cols = np.array([0, 31, 5, 17, 30], np.int32)
    want = pools["xla"].gather_window(slots, cols)
    got = pools["pallas"].gather_window(slots, cols)
    assert want.shape == (5, 12, 6) and np.abs(want).sum() > 0
    np.testing.assert_array_equal(got, want)


def test_sharded_replay_pallas_compiled(tt_corpus):
    """make_sharded_replay_fn(kernel='pallas') on a real-device mesh: the
    compiled kernel inside shard_map with check_vma=False, psum merge."""
    import jax
    from jax.sharding import Mesh

    from anomod.parallel.replay import make_sharded_replay_fn, stage_sharded
    from anomod.replay import replay_numpy

    batch, cfg, chunks, _ = tt_corpus
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    dev_chunks, _ = stage_sharded(batch, mesh, cfg)
    fn = make_sharded_replay_fn(cfg, mesh, kernel="pallas")
    state = fn(dev_chunks)
    ref = replay_numpy(chunks, cfg)
    np.testing.assert_allclose(np.asarray(state.hist), ref.hist, rtol=0,
                               atol=0)
    np.testing.assert_allclose(np.asarray(state.agg)[:, :3], ref.agg[:, :3],
                               rtol=0, atol=0)
    np.testing.assert_allclose(np.asarray(state.agg)[:, 3:6], ref.agg[:, 3:6],
                               rtol=2e-3, atol=1e-2)


def test_tdigest_kernel_compiled():
    """t-digest build + merge through the Mosaic-compiled MXU reduction at
    production lane counts (a TT service plane's worth of digest lanes)."""
    from anomod.ops.pallas_tdigest import (tdigest_build_pallas,
                                           tdigest_merge_pallas)
    from anomod.ops.tdigest import tdigest_build, tdigest_merge

    rng = np.random.default_rng(3)
    a = rng.lognormal(3.0, 1.0, size=(96, 1024)).astype(np.float32)
    b = rng.lognormal(3.5, 0.8, size=(96, 1024)).astype(np.float32)
    ra = tdigest_build(a, k=64)
    pa = tdigest_build_pallas(a, k=64)
    np.testing.assert_allclose(np.asarray(pa.weight), ra.weight, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(pa.mean), ra.mean, rtol=1e-3,
                               atol=1e-3)
    ref = tdigest_merge(ra, tdigest_build(b, k=64))
    out = tdigest_merge_pallas(pa, tdigest_build_pallas(b, k=64))
    np.testing.assert_allclose(np.asarray(out.weight), ref.weight, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out.mean), ref.mean, rtol=1e-3,
                               atol=1e-3)


def test_hll_kernel_compiled():
    """HLL register kernel compiled: hashing, branchless clz, and the
    revisited max-accumulated output block must match the numpy oracle
    register-for-register."""
    from anomod.ops.hll import hll_add, hll_estimate, hll_init
    from anomod.ops.pallas_hll import make_pallas_hll_fn

    p = 10
    items = (np.arange(65536, dtype=np.int64) * 2654435761 % (2**31)
             ).astype(np.int32)
    ref = hll_add(hll_init(p), items, p=p)
    fn = make_pallas_hll_fn(p=p, block=2048)
    out = np.asarray(fn(items))
    np.testing.assert_array_equal(out, ref)
    est = hll_estimate(out)
    assert abs(est - 65536) / 65536 < 0.05
