"""The serve tick's tracer seam: which spans one tick opens and how they
nest, that a tracer changes no decision, that the product tracer's spans
land on the profiler's clock, and that the serve path's jitted callables
carry names a device trace keeps."""

import contextlib
import sys
import threading

import numpy as np
import pytest

from anomod.obs import Registry, set_registry
from anomod.replay import (ReplayConfig, ReplayState, TenantStatePool,
                           N_FEATS, dead_chunk)
from anomod.serve import BucketRunner, PowerLawTraffic, ServeEngine
from anomod.serve.traffic import TenantFault
from anomod.stream import StreamReplay

PER_TICK = ("serve.tick", "serve.admit", "serve.drain", "serve.score_fused",
            "serve.score_shard", "serve.stage", "serve.commit",
            "serve.bookkeep", "serve.score_windows", "serve.slo",
            "serve.recorders", "serve.scrape")
PER_DISPATCH = ("serve.lane_fill", "serve.lane_dispatch",
                "serve.fold_retire")
TAGS = {"serve.tick": {"tick", "offers"}, "serve.admit": {"offers"},
        "serve.stage": {"batches"},
        "serve.lane_fill": {"width", "lanes", "live"},
        "serve.lane_dispatch": {"width", "lanes"},
        "serve.fold_retire": {"lanes", "device"},
        "serve.bookkeep": {"tenants"}, "serve.score_windows": {"tenants"},
        "serve.score_shard": {"shard", "pipeline"}}


class Recorder:
    """A tracer that keeps ``(name, tags, parent's name)`` in open order
    and yields nothing, as no tracer does."""

    def __init__(self):
        self.spans = []
        self._stack = []

    @contextlib.contextmanager
    def span(self, name, **tags):
        self.spans.append((name, tags,
                           self._stack[-1] if self._stack else None))
        self._stack.append(name)
        try:
            yield None
        finally:
            self._stack.pop()


@pytest.fixture
def obs_off():
    """Telemetry off, so that ``tracer=None`` is no tracer at all (with
    it on the engine builds its own ``Tracer``)."""
    prev = set_registry(Registry(enabled=False, max_samples=10))
    yield
    set_registry(prev)


def _engine(tracer, async_commit=False, state="device", pipeline=None,
            shards=1, **engine_kw):
    traffic = PowerLawTraffic(
        n_tenants=6, total_rate_spans_per_s=1800, alpha=0.6, seed=5,
        n_services=4, batch_cap=64,
        faults={0: TenantFault("latency", service=1, onset_s=30.0,
                               factor=12.0)})
    cfg = ReplayConfig(n_services=4, n_windows=16, window_us=5_000_000,
                       chunk_size=1024)
    eng = ServeEngine(traffic.specs, traffic.services, cfg,
                      capacity_spans_per_s=1200, tick_s=1.0,
                      buckets=(128, 512), lane_buckets=(1, 2, 4),
                      max_backlog=2400, baseline_windows=4, fuse=True,
                      shards=shards, pipeline=pipeline, tracer=tracer,
                      async_commit=async_commit, state=state, **engine_kw)
    return eng, traffic


def _run(eng, traffic, ticks):
    served = []
    for k in range(ticks):
        served.append([(qb.tenant_id, qb.seq) for qb in eng.tick(
            traffic.arrivals(k * 1.0, (k + 1) * 1.0))])
    if eng._deferred is not None:
        eng._commit_deferred()
    states = {t: (np.asarray(r.get_state().agg).tobytes(),
                  np.asarray(r.get_state().hist).tobytes())
              for t, r in eng._tenant_replay.items()}
    alerts = {t: [(a.window, a.service, a.z_latency, a.z_error)
                  for a in eng.alerts_for(t)] for t in states}
    return served, states, alerts


def test_one_tick_opens_exactly_the_named_spans(obs_off):
    rec = Recorder()
    eng, traffic = _engine(rec)
    assert eng.runner.tracer is rec
    _run(eng, traffic, 3)
    del rec.spans[:]
    before = eng.runner.fused_dispatches
    eng.tick(traffic.arrivals(3.0, 4.0))
    dispatches = eng.runner.fused_dispatches - before
    names = [name for name, _, _ in rec.spans]
    assert dispatches > 0
    assert len(names) == 12 + 3 * dispatches
    assert sorted(n for n in names if n not in PER_DISPATCH) \
        == sorted(PER_TICK)
    for n in PER_DISPATCH:
        assert names.count(n) == dispatches
    # every span is inside serve.tick, the phases where the table puts them
    parents = {name: parent for name, _, parent in rec.spans}
    assert names[0] == "serve.tick" and parents["serve.tick"] is None
    assert all(p is not None for n, p in parents.items()
               if n != "serve.tick")
    assert parents["serve.stage"] == "serve.score_shard"
    assert parents["serve.commit"] == "serve.score_shard"
    assert parents["serve.bookkeep"] == "serve.commit"
    assert parents["serve.score_windows"] == "serve.commit"
    for n in PER_DISPATCH:
        assert parents[n] == "serve.score_shard"
    for n in ("serve.admit", "serve.drain", "serve.slo", "serve.recorders",
              "serve.scrape", "serve.score_fused"):
        assert parents[n] == "serve.tick"
    # tags ride the call that opens the span
    for name, tags, _ in rec.spans:
        assert set(tags) == TAGS.get(name, set()), name
    tick_tags = rec.spans[0][1]
    assert tick_tags["tick"] == 3 and tick_tags["offers"] > 0


def test_the_host_fold_retires_under_the_same_span(obs_off):
    rec = Recorder()
    eng, traffic = _engine(rec, state="host")
    _run(eng, traffic, 2)
    retired = [tags for name, tags, _ in rec.spans
               if name == "serve.fold_retire"]
    assert retired and all(t["device"] is False for t in retired)


def test_the_synchronous_lane_run_opens_the_dispatch_spans(obs_off):
    rec = Recorder()
    cfg = ReplayConfig(n_services=4, n_windows=8, chunk_size=256)
    runner = BucketRunner(cfg, (64,), lane_buckets=(1, 2), state="host",
                          tracer=rec)
    cols = {k: np.asarray(v)[:10]
            for k, v in dead_chunk(cfg, 64, xp=np).items()}
    out = runner.run_lanes(64, [(runner.zero_state(), cols)] * 2)
    assert len(out) == 2
    assert [n for n, _, _ in rec.spans] == list(PER_DISPATCH)


@pytest.mark.parametrize("async_commit", [False, True],
                         ids=["sync", "async-tail"])
def test_a_tracer_changes_no_decision(obs_off, async_commit):
    rec = Recorder()
    traced = _run(*_engine(rec, async_commit), 45)
    eng, traffic = _engine(None, async_commit)
    assert eng.tracer is None and eng.runner.tracer is None
    plain = _run(eng, traffic, 45)
    assert traced[0] == plain[0]                 # served order
    assert traced[1] == plain[1]                 # tenant state bytes
    assert traced[2] == plain[2]                 # alerts
    assert any(plain[2].values())                # the fault alerted
    names = {n for n, _, _ in rec.spans}
    assert set(PER_TICK) - {"serve.score_fused", "serve.score_shard"} \
        <= names
    if async_commit:
        assert {"serve.issue_tick", "serve.dispatch_shard",
                "serve.commit_shard"} <= names


def test_no_tracer_opens_no_span_and_no_annotation(obs_off, monkeypatch):
    import jax
    opened = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kw):
            opened.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    eng, traffic = _engine(None)
    _run(eng, traffic, 3)
    assert opened == []


# -- one dispatch, three spans: order and containment, never a duration -----

class SeqRecorder:
    """A tracer that numbers every open and every close in ONE sequence
    across threads (a stack a thread for the parent), so order and
    containment read off integers: span ``i`` is inside span ``j`` when
    ``opened[j] < opened[i]`` and ``closed[i] < closed[j]``."""

    def __init__(self):
        self.spans = []          # {name, tags, thread, parent, open, close}
        self._lock = threading.Lock()
        self._seq = 0
        self._tls = threading.local()

    def _tick(self):
        with self._lock:
            self._seq += 1
            return self._seq

    @contextlib.contextmanager
    def span(self, name, **tags):
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        rec = {"name": name, "tags": tags,
               "thread": threading.get_ident(),
               "parent": stack[-1] if stack else None,
               "open": self._tick(), "close": None}
        with self._lock:
            self.spans.append(rec)
        stack.append(rec)
        try:
            yield None
        finally:
            stack.pop()
            rec["close"] = self._tick()

    def named(self, name):
        return [s for s in self.spans if s["name"] == name]

    def still_open(self):
        return [s["name"] for s in self.spans if s["close"] is None]


def _inside(inner, outer):
    return outer["open"] < inner["open"] and inner["close"] < outer["close"]


def _dispatches_by_tick(rec):
    """tick span -> its dispatches as ``(fill, dispatch, retire)`` triples,
    a thread at a time in open order.  On one thread the k-th fill, the
    k-th dispatch and the k-th retire are the same dispatch: a runner
    belongs to one thread and retires in issue order."""
    out = []
    for tick in rec.named("serve.tick"):
        triples = []
        inside = [s for s in rec.spans if s["name"] in PER_DISPATCH
                  and _inside(s, tick)]
        for thread in sorted({s["thread"] for s in inside}):
            mine = [s for s in inside if s["thread"] == thread]
            by_name = [[s for s in mine if s["name"] == n]
                       for n in PER_DISPATCH]
            assert len({len(b) for b in by_name}) == 1, \
                [len(b) for b in by_name]
            triples.append(list(zip(*by_name)))
        out.append((tick, triples))
    return out


@pytest.mark.parametrize("shards", [1, 2])
@pytest.mark.parametrize("pipeline", [1, 2])
def test_each_dispatch_opens_fill_dispatch_retire_in_order(obs_off,
                                                           pipeline, shards):
    rec = SeqRecorder()
    eng, traffic = _engine(rec, pipeline=pipeline, shards=shards)
    _run(eng, traffic, 6)
    assert rec.still_open() == []
    n = sum(r.fused_dispatches for r in eng._runners)
    assert n > 6                         # more than one a tick
    for name in PER_DISPATCH:
        assert len(rec.named(name)) == n
    seen = 0
    for tick, threads in _dispatches_by_tick(rec):
        for triples in threads:
            for fill, dispatch, retire in triples:
                seen += 1
                assert fill["close"] < dispatch["open"]
                assert dispatch["close"] < retire["open"]
                assert fill["tags"]["width"] == dispatch["tags"]["width"]
                assert fill["tags"]["lanes"] == dispatch["tags"]["lanes"] \
                    == retire["tags"]["lanes"]
                assert 0 < fill["tags"]["live"] <= fill["tags"]["lanes"]
    # every dispatch span was inside a tick, all three in the same one
    assert seen == n
    if shards == 2:
        # each shard's runner on its own worker thread, tagged with it
        per_shard = rec.named("serve.score_shard")
        assert {s["tags"]["shard"] for s in per_shard} == {0, 1}
        assert len({s["thread"] for s in per_shard}) == 2


@pytest.mark.parametrize("pipeline", [1, 2])
def test_a_pipelined_fill_starts_before_the_previous_retire(obs_off,
                                                            pipeline):
    """Depth 2 stages dispatch k+1 while dispatch k is in flight: its
    fill opens before k's retire.  Depth 1 never does."""
    rec = SeqRecorder()
    eng, traffic = _engine(rec, pipeline=pipeline)
    _run(eng, traffic, 6)
    pairs = 0
    for _, threads in _dispatches_by_tick(rec):
        for triples in threads:
            for (_, _, retire), (fill, _, _) in zip(triples, triples[1:]):
                pairs += 1
                if pipeline == 2:
                    assert fill["open"] < retire["open"]
                else:
                    assert retire["close"] < fill["open"]
    assert pairs > 0


def test_an_aborted_dispatch_closes_its_spans(obs_off):
    """A dispatch that raises with another in flight (no supervisor to
    re-execute the tick): the tick fails, every span it opened is
    closed, the dropped dispatch is never retired, and the next tick's
    dispatches are whole again."""
    rec = SeqRecorder()
    eng, traffic = _engine(rec, pipeline=2, ckpt_every=0)
    _run(eng, traffic, 3)
    runner = eng.runner
    real = runner._lane_exec_for
    calls = []

    def second_one_raises(shape, scratch):
        exe = real(shape, scratch)
        calls.append(shape)
        if len(calls) != 2:
            return exe

        def boom(_):
            assert runner.inflight_dispatches == 1
            raise RuntimeError("scripted dispatch fault")
        return boom

    before = len(rec.spans)
    runner._lane_exec_for = second_one_raises
    with pytest.raises(RuntimeError, match="scripted dispatch fault"):
        eng.tick(traffic.arrivals(3.0, 4.0))
    del runner._lane_exec_for               # the method again
    assert runner.inflight_dispatches == 0
    assert rec.still_open() == []
    failed = [s["name"] for s in rec.spans[before:]
              if s["name"] in PER_DISPATCH]
    assert failed == ["serve.lane_fill", "serve.lane_dispatch",
                      "serve.lane_fill", "serve.lane_dispatch"]
    before = len(rec.spans)
    done = runner.fused_dispatches
    eng.tick(traffic.arrivals(4.0, 5.0))
    after = [s["name"] for s in rec.spans[before:]
             if s["name"] in PER_DISPATCH]
    n = runner.fused_dispatches - done
    assert n > 0 and all(after.count(name) == n for name in PER_DISPATCH)
    assert rec.still_open() == []


def test_engine_spans_survive_the_chrome_round_trip(obs_off):
    """``Tracer.to_chrome`` -> ``spans_from_chrome`` keeps the name, the
    tags, the parent and the lane of every dispatch span."""
    from anomod.utils.tracing import Tracer, spans_from_chrome
    tracer = Tracer("anomod-serve")
    eng, traffic = _engine(tracer, pipeline=2, shards=2)
    _run(eng, traffic, 4)
    back = spans_from_chrome(tracer.to_chrome())
    assert len(back) == tracer.n_spans
    kept = 0
    for was, got in zip(tracer._spans, back):
        assert (got["name"], got["parent"], got["tid"]) \
            == (was["name"], was["parent"], was["tid"])
        assert got["tags"] == {k: str(v) for k, v in was["tags"].items()}
        kept += was["name"] in PER_DISPATCH
    assert kept == 3 * sum(r.fused_dispatches for r in eng._runners)
    # the two shards' dispatch spans on two lanes, neither the tick's
    lanes = {s["tid"] for s in back if s["name"] in PER_DISPATCH}
    tick_lane = {s["tid"] for s in back if s["name"] == "serve.tick"}
    assert len(lanes) == 2 and not lanes & tick_lane


# -- the product tracer's lanes (worker threads) ----------------------------

def test_tracer_worker_thread_lanes_and_tags():
    """Satellite pin: worker-thread spans export on their OWN chrome
    lane (tid) with shard tags in args, and spans_from_chrome carries
    the lane through the round trip."""
    from anomod.utils.tracing import Tracer, spans_from_chrome
    tr = Tracer("anomod-test")
    with tr.span("coordinator"):
        pass
    # both workers alive at once (a finished thread's ident is
    # reusable — the engine's ShardWorkers are persistent, which is
    # what the lane-per-thread contract rides on)
    barrier = threading.Barrier(2)

    def worker(shard):
        with tr.span("serve.score_shard", shard=shard, pipeline=2):
            barrier.wait(timeout=10)

    ts = [threading.Thread(target=worker, args=(s,)) for s in (0, 1)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    events = tr.to_chrome()
    shard_spans = [e for e in events
                   if e["name"] == "serve.score_shard"]
    assert {e["args"]["shard"] for e in shard_spans} == {"0", "1"}
    # worker lanes are distinct from the coordinator's lane 0
    assert all(e["tid"] != 0 for e in shard_spans)
    assert len({e["tid"] for e in shard_spans}) == 2
    spans = spans_from_chrome(events)
    got = [s for s in spans if s["name"] == "serve.score_shard"]
    assert {s["tags"]["shard"] for s in got} == {"0", "1"}
    assert all(s["tid"] != 0 for s in got)


def test_sharded_engine_trace_carries_shard_tags():
    """The engine's worker-thread score spans carry the shard tag into
    the chrome export — a 2-shard trace's lanes group by shard."""
    from anomod.serve.engine import run_power_law
    from anomod.utils.tracing import Tracer
    tracer = Tracer("anomod-serve")
    run_power_law(n_tenants=6, n_services=4, capacity_spans_per_s=1000,
                  overload=2.0, duration_s=20, tick_s=1.0, seed=5,
                  window_s=5.0, baseline_windows=4, fault_tenants=1,
                  buckets=(64, 256), lane_buckets=(1, 2, 4),
                  max_backlog=1500, n_windows=16, shards=2, pipeline=2,
                  tracer=tracer)
    events = tracer.to_chrome()
    shard_spans = [e for e in events
                   if e["name"] == "serve.score_shard"]
    assert {e["args"]["shard"] for e in shard_spans} == {"0", "1"}
    assert len({e["tid"] for e in shard_spans}) == 2


# -- the product tracer on the profiler's clock -----------------------------

def test_tracer_spans_land_on_the_profilers_host_plane(tmp_path):
    import glob

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from anomod.utils.tracing import Tracer
    tracer = Tracer("anomod-serve")
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracer.span("serve.outer", shard=0) as sp:
            sp.set_tag("k", 1)
            with tracer.span("serve.inner"):
                jnp.ones(8).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))[0]
    host = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("serve."):
                        host[ev.name] = (ev.start_ns,
                                         ev.start_ns + ev.duration_ns)
    assert set(host) == {"serve.outer", "serve.inner"}
    assert host["serve.outer"][0] <= host["serve.inner"][0]
    assert host["serve.inner"][1] <= host["serve.outer"][1]
    # the Jaeger shape is what it was: parents, tags
    spans = tracer.to_jaeger()["data"][0]["spans"]
    assert [s["operationName"] for s in spans] == ["serve.outer",
                                                   "serve.inner"]
    assert spans[1]["references"][0]["spanID"] == spans[0]["spanID"]
    assert {"key": "k", "value": "1"} in spans[0]["tags"]


def test_tracer_without_jax_annotates_nothing(monkeypatch):
    from anomod.utils import tracing
    monkeypatch.setitem(sys.modules, "jax", None)
    monkeypatch.setitem(sys.modules, "jax.profiler", None)
    tracer = tracing.Tracer("no-jax")
    assert tracer._annotate is None
    with tracer.span("a", x=1):
        with tracer.span("b"):
            pass
    assert [(s["name"], s["parent"]) for s in tracer._spans] \
        == [("a", None), ("b", 0)]
    assert tracer._spans[0]["dur"] >= tracer._spans[1]["dur"] >= 0.0


def test_span_of_without_a_tracer_is_one_shared_no_op():
    from anomod.utils.tracing import NO_SPAN, span_of
    assert span_of(None, "serve.x", a=1) is NO_SPAN
    with span_of(None, "serve.x") as got:
        assert got is None


# -- names the device trace keeps -------------------------------------------

@pytest.fixture(scope="module")
def jitted():
    """name -> (jitted callable of the serve path, arguments to lower)."""
    cfg = ReplayConfig(n_services=4, n_windows=8, chunk_size=256)
    pool = TenantStatePool(cfg, capacity=3, engine="jax")
    runner = BucketRunner(cfg, (64,), lane_buckets=(2,), state="host")
    H = cfg.n_hist_buckets
    agg, hist = np.asarray(pool.agg), np.asarray(pool.hist)
    dagg = np.zeros((2, cfg.sw, N_FEATS), np.float32)
    dhist = np.zeros((2, cfg.sw, H), np.float32)
    slots = np.asarray([1, 2], np.int32)
    chunk = dead_chunk(cfg, 64, xp=np)
    lanes = {k: np.broadcast_to(v, (2, 64)) for k, v in chunk.items()}
    zero = ReplayState(agg=dagg[0], hist=dhist[0])
    return {
        "anomod_pool_scatter": (pool._scatter_fn,
                                (agg, hist, slots, dagg, dhist)),
        "anomod_pool_put": (pool._put_fn,
                            (agg, hist, np.int32(1), dagg[0], dhist[0])),
        "anomod_pool_roll": (pool._roll_fn,
                             (agg, hist, np.int32(1), np.int32(2))),
        "anomod_pool_gather_window": (pool._gather_window_fn,
                                      (agg, slots, slots)),
        "anomod_lane_delta": (runner._lane_fn, (lanes,)),
        "anomod_chunk_step": (runner._step, (zero, chunk)),
        "anomod_chunk_step.stream": (StreamReplay(cfg, 0)._step,
                                     (zero, chunk)),
    }


@pytest.mark.parametrize("which", [
    "anomod_pool_scatter", "anomod_pool_put", "anomod_pool_roll",
    "anomod_pool_gather_window", "anomod_lane_delta", "anomod_chunk_step",
    "anomod_chunk_step.stream"])
def test_a_jitted_callable_lowers_under_its_name(jitted, which):
    fn, args = jitted[which]
    name = which.split(".")[0]
    text = fn.lower(*args).as_text(debug_info=True)
    assert f"module @jit_{name} " in text
    # op metadata carries the scope: "jit(<name>)/<name>/<primitive>"
    assert f"jit({name})/{name}/" in text
