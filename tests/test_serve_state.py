"""Device-resident tenant state (ANOMOD_SERVE_STATE): the bit-parity
pins behind PR 8's on-device scatter fold + batched window scoring.

The contract under test: ``device`` serving performs the SAME IEEE f32
arithmetic as the ``host`` seam in the SAME order — the pool's
scatter-add is ``state + delta`` per slot in dispatch order, its roll is
roll_ring_state's shift+zero, gather/put are pure copies, and the
batched COMMIT scorer is the sequential ``_score_through``'s own z core
with a leading tenant axis — so states, alerts, SLO and shed are
byte-identical across residencies, seeds, shard counts and pipeline
depths.  Nothing here is a tolerance check: every comparison is
``tobytes()`` or ``==``.
"""

import dataclasses

import numpy as np
import pytest

from anomod.replay import (N_FEATS, ReplayConfig, ReplayState,
                           TenantStatePool, fold_delta, pool_row_width)
from anomod.schemas import SpanBatch
from anomod.stream import (OnlineDetector, StreamReplay,
                           roll_ring_state, score_closed_windows_batched)


def _cfg(S=4, W=8):
    return ReplayConfig(n_services=S, n_windows=W, window_us=5_000_000,
                        chunk_size=512)


def _rand_state(cfg, rng):
    return ReplayState(
        agg=rng.random((cfg.sw, N_FEATS)).astype(np.float32),
        hist=rng.random((cfg.sw, cfg.n_hist_buckets)).astype(np.float32))


def _assert_state_bytes(a: ReplayState, b: ReplayState):
    assert np.asarray(a.agg).tobytes() == np.asarray(b.agg).tobytes()
    assert np.asarray(a.hist).tobytes() == np.asarray(b.hist).tobytes()


# -- the pool itself ------------------------------------------------------
#
# Every structural pool test runs on BOTH engines: "numpy" (the CPU
# backend's in-place host-plane engine — what tier-1 serving uses) and
# "jax" (the donated-buffer device engine accelerators use; it works on
# CPU too, just with per-dispatch overhead).  One parity contract, two
# implementations, zero drift.

ENGINES = ("numpy", "jax")

#: the pool holds a tenant's state as one flat row padded to a multiple
#: of 128 floats (TenantStatePool docstring), so the pins run at row
#: widths that are and are not such a multiple: tiny 192 (held at 256)
#: and 512; the SN shape 2,304 and 6,144 (both are); the TT shape 8,640
#: (67.5 tiles: held at 8,704) and 23,040 (is)
SHAPES = {"tiny": (4, 8), "sn": (12, 32), "tt": (45, 32)}
BOTH = pytest.mark.parametrize("engine", ENGINES)
ALL_SHAPES = pytest.mark.parametrize("shape", list(SHAPES))


def test_pool_rows_are_flat_and_held_at_a_lane_multiple():
    assert [pool_row_width(n) for n in (1, 128, 192, 2304, 8640, 23040)] \
        == [128, 128, 256, 2304, 8704, 23040]
    for engine in ENGINES:
        pool = TenantStatePool(_cfg(*SHAPES["tt"]), capacity=3,
                               engine=engine)
        assert pool.agg.shape == (4, 8704) and pool.hist.shape == (4, 23040)
        assert pool.capacity == 3


@BOTH
@ALL_SHAPES
def test_pool_round_trip_bit_exact_under_interleavings(engine, shape):
    """get_state/set_state seam via the pool: arbitrary cross-tenant
    interleavings of put/gather/roll/scatter_fold stay byte-identical
    to a host-side mirror applying fold_delta/roll_ring_state."""
    cfg = _cfg(*SHAPES[shape])
    rng = np.random.default_rng(42)
    pool = TenantStatePool(cfg, capacity=4, engine=engine)
    slots = [pool.acquire() for _ in range(4)]
    mirror = {s: pool.zero_state() for s in slots}
    for op in rng.integers(0, 4, 60):
        s = slots[int(rng.integers(0, len(slots)))]
        if op == 0:                                    # put
            st = _rand_state(cfg, rng)
            pool.put(s, st)
            mirror[s] = st
        elif op == 1:                                  # gather
            _assert_state_bytes(pool.gather(s), mirror[s])
        elif op == 2:                                  # roll
            k = int(rng.integers(1, cfg.n_windows + 2))
            pool.roll(s, k)
            mirror[s] = roll_ring_state(mirror[s], cfg, k)
        else:                                          # scatter_fold
            dagg = rng.random((2, cfg.sw, N_FEATS)).astype(np.float32)
            dhist = rng.random(
                (2, cfg.sw, cfg.n_hist_buckets)).astype(np.float32)
            other = slots[int(rng.integers(0, len(slots)))]
            picks = [s, other] if other != s else [s]
            pool.scatter_fold(picks, dagg, dhist)
            for i, sl in enumerate(picks):
                mirror[sl] = fold_delta(mirror[sl], dagg[i], dhist[i])
    for s in slots:
        _assert_state_bytes(pool.gather(s), mirror[s])


@BOTH
@ALL_SHAPES
def test_pool_scatter_duplicate_slots_fold_in_lane_order(engine, shape):
    """A slot repeated within one dispatch folds in LANE order via wave
    splitting: ((state + d0) + d1), bit-for-bit — never a pre-combined
    d0 + d1 handed to one scatter (XLA's duplicate-index add order is
    unspecified, and a numpy fancy-index += drops duplicates; the waves
    make both deterministic).  The dead pad lane's delta goes to row 0
    and touches no live row."""
    cfg = _cfg(*SHAPES[shape])
    rng = np.random.default_rng(7)
    pool = TenantStatePool(cfg, capacity=2, engine=engine)
    s, other = pool.acquire(), pool.acquire()
    st, st_other = _rand_state(cfg, rng), _rand_state(cfg, rng)
    pool.put(s, st)
    pool.put(other, st_other)
    dagg = rng.random((4, cfg.sw, N_FEATS)).astype(np.float32)
    dhist = rng.random((4, cfg.sw, cfg.n_hist_buckets)).astype(np.float32)
    pool.scatter_fold([s, s, s], dagg, dhist)  # lane 3 = dead pad
    want = st
    for i in range(3):
        want = fold_delta(want, dagg[i], dhist[i])
    _assert_state_bytes(pool.gather(s), want)
    _assert_state_bytes(pool.gather(other), st_other)


@BOTH
@ALL_SHAPES
def test_pool_roll_bit_identical_to_host_roll(engine, shape):
    """The pool roll (shift plane columns, zero the tail) vs
    roll_ring_state on the same bits, every shift regime: partial,
    full-plane, and past-the-grid."""
    cfg = _cfg(*SHAPES[shape])
    rng = np.random.default_rng(3)
    for k in (1, 3, cfg.n_windows - 1, cfg.n_windows, 2 * cfg.n_windows):
        pool = TenantStatePool(cfg, capacity=2, engine=engine)
        s = pool.acquire()
        st = _rand_state(cfg, rng)
        pool.put(s, st)
        pool.roll(s, k)
        _assert_state_bytes(pool.gather(s), roll_ring_state(st, cfg, k))


@BOTH
@ALL_SHAPES
def test_pool_slot_exhaustion_growth_and_churn_reuse(engine, shape):
    """Exhaustion grows the pool by doubling WITHOUT disturbing live
    states; release() returns a zeroed slot that the next acquire
    reuses (tenant churn must never leak a predecessor's bits); a fold
    after the growth (a new plane shape) is still the host seam's."""
    cfg = _cfg(*SHAPES[shape])
    rng = np.random.default_rng(0)
    pool = TenantStatePool(cfg, capacity=2, engine=engine)
    s1, s2 = pool.acquire(), pool.acquire()
    st1, st2 = _rand_state(cfg, rng), _rand_state(cfg, rng)
    pool.put(s1, st1)
    pool.put(s2, st2)
    assert pool.capacity == 2 and pool.live_slots == 2
    s3 = pool.acquire()                        # exhaustion -> growth
    assert pool.capacity == 4
    _assert_state_bytes(pool.gather(s1), st1)  # growth kept the bits
    _assert_state_bytes(pool.gather(s2), st2)
    pool.put(s3, _rand_state(cfg, rng))
    pool.release(s2)
    assert pool.live_slots == 2
    s2b = pool.acquire()                       # churn reuses the slot...
    assert s2b == s2
    z = pool.gather(s2b)                       # ...zeroed
    assert not np.asarray(z.agg).any() and not np.asarray(z.hist).any()
    _assert_state_bytes(pool.gather(s1), st1)
    # a fold over the grown planes: an old row twice (waves), a row the
    # growth added, a dead pad lane
    st3 = pool.gather(s3)
    dagg = rng.random((4, cfg.sw, N_FEATS)).astype(np.float32)
    dhist = rng.random((4, cfg.sw, cfg.n_hist_buckets)).astype(np.float32)
    pool.scatter_fold([s1, s3, s1], dagg, dhist)
    _assert_state_bytes(pool.gather(s3), fold_delta(st3, dagg[1], dhist[1]))
    _assert_state_bytes(pool.gather(s1), fold_delta(
        fold_delta(st1, dagg[0], dhist[0]), dagg[2], dhist[2]))
    assert not any(np.asarray(x).any() for x in pool.gather(s2b))


@ALL_SHAPES
def test_pool_gather_window_matches_plane_column_and_pallas_twin(shape):
    """The batched scorer's fused gather: [T, S, F] columns byte-equal
    to slicing the gathered rows, under the pow2 request padding — and
    the pallas gather kernel (interpret mode on CPU) returns the same
    bytes as the XLA formulation.  gather_rows is the same rows' agg
    half, whole."""
    cfg = _cfg(*SHAPES[shape])
    pool = TenantStatePool(cfg, capacity=4, engine="numpy")
    jx = TenantStatePool(cfg, capacity=4, engine="jax")
    pal = TenantStatePool(cfg, capacity=4, gather_engine="pallas")
    for p in (pool, jx, pal):
        r = np.random.default_rng(5)
        for _ in range(3):
            p.put(p.acquire(), _rand_state(cfg, r))
    slots, cols = [2, 1, 3], [0, cfg.n_windows - 1, 3]
    got = pool.gather_window(slots, cols)
    assert got.shape == (3, cfg.n_services, N_FEATS)
    for j, (s, c) in enumerate(zip(slots, cols)):
        want = pool.gather(s).agg.reshape(
            cfg.n_services, cfg.n_windows, N_FEATS)[:, c]
        assert got[j].tobytes() == want.tobytes()
    assert jx.gather_window(slots, cols).tobytes() == got.tobytes()
    assert pal.gather_window(slots, cols).tobytes() == got.tobytes()
    for p in (pool, jx, pal):
        rows = p.gather_rows(slots)
        assert rows.shape == (3, cfg.sw, N_FEATS)
        for j, s in enumerate(slots):
            assert rows[j].tobytes() == pool.gather(s).agg.tobytes()
    with pytest.raises(ValueError):
        TenantStatePool(cfg, gather_engine="mosaic")
    with pytest.raises(ValueError):
        TenantStatePool(cfg, engine="cuda")


def test_pool_gather_window_above_one_chunk_is_the_same_bytes():
    """A request above the jitted gather's chunk (512 rows a step) is
    served in steps inside the one dispatch: same bytes as the numpy
    engine's fancy index, slot 0 and repeated slots included."""
    cfg = _cfg()
    rng = np.random.default_rng(11)
    pools = [TenantStatePool(cfg, capacity=40, engine=e) for e in ENGINES]
    for s in range(1, 41, 3):
        st = _rand_state(cfg, rng)
        for p in pools:
            p.put(s, st)
    n = 2 * TenantStatePool._GATHER_CHUNK + 77      # pads to 2,048
    slots = rng.integers(0, 41, n)
    cols = rng.integers(0, cfg.n_windows, n)
    got = [p.gather_window(slots, cols) for p in pools]
    assert got[0].shape == (n, cfg.n_services, N_FEATS) and got[0].any()
    assert got[0].tobytes() == got[1].tobytes()


# -- the runner's device fold ---------------------------------------------


def _staged_work(runner, replays, seed, n=120):
    """One staged (width, [(replay, cols)]) group per replay via the
    real plan_push path (spans all land in the first few windows)."""
    rng = np.random.default_rng(seed)
    work_by_width = {}
    for rep in replays:
        svc = rng.integers(0, runner.cfg.n_services, n).astype(np.int32)
        b = SpanBatch(
            trace=np.arange(n, dtype=np.int32) % 7,
            parent=np.full(n, -1, np.int32), service=svc,
            endpoint=np.zeros(n, np.int32),
            start_us=np.sort(rng.integers(0, 3 * runner.cfg.window_us,
                                          n)).astype(np.int64),
            duration_us=rng.integers(900, 1100, n).astype(np.int64),
            is_error=np.zeros(n, np.bool_),
            status=np.full(n, 200, np.int16),
            kind=np.zeros(n, np.int8),
            services=tuple(f"s{i}" for i in range(runner.cfg.n_services)),
            endpoints=("ep",), trace_ids=tuple(f"t{i}" for i in range(7)),
        ).validate()
        _, plan = rep.plan_push(b)
        for width, cols in plan:
            work_by_width.setdefault(width, []).append((rep, cols))
    return work_by_width


def test_abort_lanes_leaves_pool_states_at_last_commit():
    """abort_lanes with IN-FLIGHT scatter folds: the pool keeps the
    last-committed bytes — an aborted tick's deltas never land, on the
    device path exactly as on the host path."""
    from anomod.serve.batcher import BucketRunner, PooledStreamReplay
    cfg = _cfg()
    runner = BucketRunner(cfg, (128, 512), lane_buckets=(1, 2, 4),
                          pipeline=3, state="device", pool_slots=4)
    reps = [PooledStreamReplay(cfg, 0, runner) for _ in range(3)]
    for width, group in _staged_work(runner, reps, seed=1).items():
        runner.submit_lanes(width, group)
    runner.drain_lanes()                       # committed baseline
    committed = [r.get_state() for r in reps]
    for width, group in _staged_work(runner, reps, seed=2).items():
        runner.submit_lanes(width, group)
    assert runner.inflight_dispatches > 0      # folds genuinely in flight
    runner.abort_lanes()
    for r, want in zip(reps, committed):
        _assert_state_bytes(r.get_state(), want)
    # and a post-abort tick folds normally from the committed states
    for width, group in _staged_work(runner, reps, seed=2).items():
        runner.submit_lanes(width, group)
    runner.drain_lanes()
    for r, was in zip(reps, committed):
        assert np.asarray(r.get_state().agg).tobytes() \
            != np.asarray(was.agg).tobytes()


def test_pooled_replay_state_seam_round_trips_interleaved():
    """PooledStreamReplay keeps get_state/set_state as the official
    surface: cross-tenant interleaved writes and reads round-trip
    byte-identically (the checkpoint/migration seam contract)."""
    from anomod.serve.batcher import BucketRunner, PooledStreamReplay
    cfg = _cfg()
    runner = BucketRunner(cfg, (128, 512), state="device", pool_slots=3)
    reps = [PooledStreamReplay(cfg, 0, runner) for _ in range(3)]
    rng = np.random.default_rng(11)
    states = [_rand_state(cfg, rng) for _ in reps]
    for i in (2, 0, 1):
        reps[i].set_state(states[i])
    for i in (1, 2, 0):
        _assert_state_bytes(reps[i].get_state(), states[i])
    reps[1].release()
    assert runner.pool.live_slots == 2
    _assert_state_bytes(reps[0].get_state(), states[0])


def test_released_replay_fails_loud_and_failed_ctor_frees_slot():
    """Lifecycle guards: every surface of a RELEASED PooledStreamReplay
    raises instead of touching the pool (pool.put(None, ...) would
    broadcast over every slot — silent fleet-wide corruption), a double
    release raises too, and a ctor that fails AFTER acquiring hands its
    slot back instead of leaking a pool row per retried admission."""
    from anomod.serve.batcher import BucketRunner, PooledStreamReplay
    cfg = _cfg()
    runner = BucketRunner(cfg, (128, 512), state="device", pool_slots=2)
    rep = PooledStreamReplay(cfg, 0, runner)
    keep = PooledStreamReplay(cfg, 0, runner)
    rng = np.random.default_rng(3)
    kept = _rand_state(cfg, rng)
    keep.set_state(kept)
    rep.release()
    for poke in (lambda: rep.get_state(),
                 lambda: rep.set_state(_rand_state(cfg, rng)),
                 lambda: rep._roll(1),
                 lambda: rep.release()):
        with pytest.raises(ValueError, match="released"):
            poke()
    _assert_state_bytes(keep.get_state(), kept)   # pool untouched
    # the pool's own seam refuses a None slot outright (defense in
    # depth below the replay guard)
    for op in (lambda: runner.pool.gather(None),
               lambda: runner.pool.put(None, kept)):
        with pytest.raises(TypeError):
            op()
    # ctor failure after acquire: cfg mismatch raises in the parent
    # ctor; the acquired slot must come back to the free list
    live = runner.pool.live_slots
    with pytest.raises(ValueError, match="cfg"):
        PooledStreamReplay(_cfg(W=16), 0, runner)
    assert runner.pool.live_slots == live


def test_host_runner_keeps_seam_and_refuses_pooled_replay():
    from anomod.serve.batcher import (BucketedStreamReplay, BucketRunner,
                                      PooledStreamReplay)
    cfg = _cfg()
    runner = BucketRunner(cfg, (128, 512), state="host")
    assert runner.pool is None
    assert isinstance(BucketedStreamReplay(cfg, 0, runner).state.agg,
                      np.ndarray)
    with pytest.raises(ValueError):
        PooledStreamReplay(cfg, 0, runner)
    with pytest.raises(ValueError):
        BucketRunner(cfg, (128, 512), state="vram")


# -- batched window scoring ----------------------------------------------


def _det_batches(seed, S=3, n_windows=14, per_w=24):
    """A seeded multi-push span stream crossing the calibration-freeze
    boundary, with a latency step so alerts actually fire."""
    rng = np.random.default_rng(seed)
    w_us = 5_000_000
    out = []
    for w in range(n_windows):
        n = per_w + int(rng.integers(0, 8))
        dur = rng.integers(900, 1100, n).astype(np.int64)
        if w >= 8:
            dur = dur * 25                     # post-calibration fault
        out.append(SpanBatch(
            trace=np.arange(n, dtype=np.int32) % 5,
            parent=np.full(n, -1, np.int32),
            service=rng.integers(0, S, n).astype(np.int32),
            endpoint=np.zeros(n, np.int32),
            start_us=np.sort(w * w_us + rng.integers(0, w_us, n)
                             ).astype(np.int64),
            duration_us=dur,
            is_error=rng.random(n) < 0.02,
            status=np.full(n, 200, np.int16),
            kind=np.zeros(n, np.int8),
            services=tuple(f"s{i}" for i in range(S)),
            endpoints=("ep",), trace_ids=tuple(f"t{i}" for i in range(5)),
        ).validate())
    return out


def _host_gather(work):
    """The test-local twin of the engine's host gather closure."""
    planes = {}

    def gather(items):
        out = np.empty((len(items), work[0][0]._n_svc, N_FEATS),
                       np.float32)
        for j, (i, c) in enumerate(items):
            pl = planes.get(i)
            if pl is None:
                pl = planes[i] = np.asarray(
                    work[i][0].replay.agg_plane(), np.float32)
            out[j] = pl[:, c]
        return out

    return gather


@pytest.mark.parametrize(
    "seed", [0, pytest.param(3, marks=pytest.mark.slow),
             pytest.param(9, marks=pytest.mark.slow)])
def test_batched_scoring_byte_identical_to_sequential(seed):
    """THE batched-scorer pin: score_closed_windows_batched over several
    tenants == per-tenant _score_through, byte-identical — alert stream
    (every field), hysteresis streaks, CUSUM carry, _scored_through —
    across the calibration-freeze boundary and through finish()."""
    cfg = ReplayConfig(n_services=3, n_windows=16, window_us=5_000_000,
                       chunk_size=512)
    svcs = tuple(f"s{i}" for i in range(3))

    def mk():
        return [OnlineDetector(svcs, cfg, 0,
                               replay=StreamReplay(cfg, 0),
                               baseline_windows=4, z_threshold=4.0)
                for _ in range(3)]

    seq, bat = mk(), mk()
    assert all(d.batch_scorable for d in seq)
    streams = [_det_batches(seed + 10 * t) for t in range(3)]
    for step in range(len(streams[0])):
        work = []
        for t in range(3):
            b = streams[t][step]
            # sequential: the one-call push path
            seq[t].push(b)
            # batched: replay push + bookkeep, then ONE vectorized pass
            d = bat[t]
            w = d.replay.push(d.replay_batch(b))
            through = d.note_bookkeep(b.n_spans, w)
            rng_ = (d.scoring_window_range(through)
                    if through is not None else None)
            if rng_ is not None:
                work.append((d, rng_[0], rng_[1]))
        if work:
            score_closed_windows_batched(work, _host_gather(work))
    fin_seq = [d.finish() for d in seq]
    fin_bat = [d.finish() for d in bat]
    for t in range(3):
        assert [dataclasses.asdict(a) for a in seq[t].alerts] == \
            [dataclasses.asdict(a) for a in bat[t].alerts]
        assert [dataclasses.asdict(a) for a in fin_seq[t]] == \
            [dataclasses.asdict(a) for a in fin_bat[t]]
        assert seq[t].alerts, "stream must actually alert to pin anything"
        assert seq[t]._scored_through == bat[t]._scored_through
        assert seq[t]._streak.tobytes() == bat[t]._streak.tobytes()
        assert seq[t]._cusum.tobytes() == bat[t]._cusum.tobytes()
        assert seq[t]._cusum_k.tobytes() == bat[t]._cusum_k.tobytes()


# -- the serving engine end to end ----------------------------------------


def _small_serve_kw(seed=5, duration=25):
    return dict(n_tenants=6, n_services=4, capacity_spans_per_s=1000,
                overload=2.0, duration_s=duration, tick_s=1.0, seed=seed,
                window_s=2.0, baseline_windows=4, fault_tenants=1,
                buckets=(64, 256), lane_buckets=(1, 2, 4),
                max_backlog=1500, n_windows=16)


def _fingerprint(eng):
    return {
        tid: ([dataclasses.asdict(a) for a in eng.alerts_for(tid)],
              np.asarray(eng._tenant_replay[tid].state.agg).tobytes(),
              np.asarray(eng._tenant_replay[tid].state.hist).tobytes())
        for tid in sorted(set(eng._tenant_det) | set(eng._tenant_replay))}


@pytest.mark.parametrize(
    "seed", [5, pytest.param(11, marks=pytest.mark.slow)])
def test_engine_device_vs_host_byte_identical(seed):
    """THE residency pin: a seeded overloaded fused run with the device
    pool emits per-tenant alerts, replay states, SLO quantiles and shed
    decisions byte-identical to the host seam — and the report records
    which residency served."""
    from anomod.serve.engine import run_power_law
    eh, rh = run_power_law(state="host", **_small_serve_kw(seed))
    ed, rd = run_power_law(state="device", **_small_serve_kw(seed))
    assert rh.serve_state == "host" and rd.serve_state == "device"
    assert _fingerprint(eh) == _fingerprint(ed)
    assert rh.latency == rd.latency
    assert rh.shed_fraction == rd.shed_fraction
    assert rh.per_priority == rd.per_priority


def test_engine_device_parity_across_shards_and_depths():
    """Residency composes with every execution axis: device at 2 shards
    and at pipeline depths 1 and 3 reproduces the host 1-shard depth-2
    fingerprint bit-for-bit (folds land in dispatch order on every
    path)."""
    from anomod.serve.engine import run_power_law
    eh, _ = run_power_law(state="host", **_small_serve_kw(seed=7))
    want = _fingerprint(eh)
    for kw in ({"shards": 2}, {"pipeline": 1}, {"pipeline": 3}):
        ed, rd = run_power_law(state="device", **kw,
                               **_small_serve_kw(seed=7))
        assert _fingerprint(ed) == want, kw
        assert rd.serve_state == "device"


def test_engine_default_is_device_and_unfused_uses_pool_too():
    """auto resolves to device on the bucket-runner plane (the pool is
    exact, not a tolerance trade), and the UNFUSED path's per-chunk
    dispatch serves through the pool seam with the same bytes as the
    host seam."""
    from anomod.serve.engine import run_power_law
    kw = _small_serve_kw(seed=3, duration=15)
    _, rep = run_power_law(**kw)
    assert rep.serve_state == "device"
    eh, _ = run_power_law(state="host", fuse=False, **kw)
    ed, _ = run_power_law(state="device", fuse=False, **kw)
    assert _fingerprint(eh) == _fingerprint(ed)


def test_engine_refuses_device_with_mesh_and_validates_knob():
    from anomod.serve.engine import ServeEngine
    from anomod.serve.queues import TenantSpec
    specs = [TenantSpec(tenant_id=0, name="t0", rate_spans_per_s=10.0)]
    with pytest.raises(ValueError, match="mesh plane manages its own"):
        ServeEngine(specs, ("a", "b"),
                    _cfg(S=2), mesh=object(), state="device")
    eng = ServeEngine(specs, ("a", "b"), _cfg(S=2), mesh=object(),
                      state="auto")
    assert eng.serve_state == "host"           # auto degrades under mesh
    with pytest.raises(ValueError, match="unknown serve state"):
        ServeEngine(specs, ("a", "b"), _cfg(S=2), state="gpu")


def test_serve_state_env_knob_validated(monkeypatch):
    """ANOMOD_SERVE_STATE joins the validated Config env contract."""
    from anomod.config import Config
    for raw, want in (("auto", "auto"), ("host", "host"),
                      ("device", "device"), (" DEVICE ", "device")):
        monkeypatch.setenv("ANOMOD_SERVE_STATE", raw)
        assert Config().serve_state == want
    monkeypatch.setenv("ANOMOD_SERVE_STATE", "vram")
    with pytest.raises(ValueError, match="ANOMOD_SERVE_STATE"):
        Config()
