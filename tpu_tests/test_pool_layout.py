"""The device pool's fold touches the rows it writes — on the chip.

PR 27's finding (PERF.md section 6): a ``[slots, SW, F]`` plane is
resident slot-minor on a TPU, a scatter over rows compiles against
slot-major operands, and the donated fold therefore transposed the
whole pool and back on every dispatch (27.6 ms at 34,501 rows).  The
planes are flat rows held at a multiple of the lane tile now.  This is
the guard that keeps the copies from coming back with a JAX upgrade:
the fold compiled at two pool sizes holds no op over a whole plane but
its in-place update, and takes the same time at both.
``tests/test_pool_layout_compile.py`` reads the same programs in tier-1,
compiled for a described chip; only this one has a clock.
"""

import re
import time

import numpy as np
import pytest

#: pool rows (capacity + the dead row), TT shape, one 32-lane delta
SMALL, LARGE, LANES = 2049, 16385, 32


@pytest.fixture(scope="module")
def folds():
    """rows -> (pool, compiled fold text, its memory analysis, ms a
    fold: the median of 5 timings of 40 dispatches each, its inputs)."""
    import jax
    from anomod.replay import N_FEATS, ReplayConfig, TenantStatePool

    cfg = ReplayConfig(n_services=45, n_windows=32,
                       window_us=5_000_000, chunk_size=4096)
    rng = np.random.default_rng(27)
    dagg = jax.device_put(
        rng.random((LANES, cfg.sw, N_FEATS)).astype(np.float32))
    dhist = jax.device_put(
        rng.random((LANES, cfg.sw, cfg.n_hist_buckets)).astype(np.float32))
    out = {}
    for rows in (SMALL, LARGE):
        pool = TenantStatePool(cfg, capacity=rows - 1, engine="jax")
        slots = rng.choice(np.arange(1, rows), LANES - 2, replace=False)
        compiled = pool._scatter_fn.lower(
            pool.agg, pool.hist, np.zeros(LANES, np.int32), dagg,
            dhist).compile()
        pool.scatter_fold(slots, dagg, dhist)           # warm
        pool.agg.block_until_ready()
        walls = []
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(40):
                pool.scatter_fold(slots, dagg, dhist)
            pool.hist.block_until_ready()
            walls.append((time.perf_counter() - t0) / 40 * 1e3)
        out[rows] = (pool, compiled.as_text(), compiled.memory_analysis(),
                     float(np.median(walls)), (slots, dagg, dhist))
    return out


@pytest.mark.parametrize("rows", [SMALL, LARGE])
def test_fold_program_holds_no_whole_plane_op(folds, rows):
    pool, text, mem, _, _ = folds[rows]
    # resident row-major, tiled (8, 128): a tenant's row is contiguous
    for plane in (pool.agg, pool.hist):
        assert tuple(plane.format.layout.major_to_minor) == (0, 1)
        assert f"f32[{rows},{plane.shape[1]}]{{1,0:T(8,128)}}" \
            in text.split("ENTRY")[0]
    # an op over a whole plane needs a plane to write: both outputs
    # alias their inputs and the temporaries are a few lanes' worth, so
    # the only plane-shaped results are the updates in place
    assert not re.findall(rf"= f32\[{rows},\d+\]\S* (?:copy|transpose)\(", text)
    assert mem.temp_size_in_bytes < 4 * pool.agg.shape[1] * 1024
    assert mem.alias_size_in_bytes >= 4 * rows * (
        pool.agg.shape[1] + pool.hist.shape[1])


def test_fold_time_follows_lanes_not_pool_rows(folds):
    small, large = folds[SMALL][3], folds[LARGE][3]
    print(f"fold ms: {SMALL} rows {small:.3f}, {LARGE} rows {large:.3f}")
    # 8x the rows; the slot-minor planes paid 8x the copies
    assert large <= 1.5 * small, (small, large)


def test_fold_on_the_chip_is_the_host_seams_add(folds):
    """The fixture folded the same deltas 201 times into zero rows: every
    live row reads 201 sequential f32 adds, every other row 0."""
    from anomod.replay import fold_delta

    pool, _, _, _, (slots, dagg, dhist) = folds[SMALL]
    want = pool.zero_state()
    lane = 3
    for _ in range(1 + 5 * 40):
        want = fold_delta(want, np.asarray(dagg[lane]),
                          np.asarray(dhist[lane]))
    got = pool.gather(int(slots[lane]))
    assert got.agg.tobytes() == want.agg.tobytes()
    assert got.hist.tobytes() == want.hist.tobytes()
    untouched = next(s for s in range(1, SMALL) if s not in set(slots))
    assert not pool.gather(untouched).agg.any()
