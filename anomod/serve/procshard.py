"""Process shard workers: the GIL-escape half of the serving plane.

``ANOMOD_SERVE_WORKER=process`` replaces each shard's worker THREAD
(:class:`anomod.serve.shard.ShardWorker`) with a spawn-context worker
PROCESS that owns the shard's whole scoring plane end to end —
detectors, replay states, its :class:`~anomod.serve.batcher.BucketRunner`
(own jitted executables, own pinned scratch) and its own obs
:class:`~anomod.obs.registry.Registry` — so N shards score on N
interpreters instead of time-slicing one GIL.

The seam is DATA, not code: a process cannot share the engine's memory,
so the coordinator drives each child through a picklable per-tick
command protocol over a duplex pipe — the drained-batch fan-out goes
out (``{"op": "score", "served": [...], "origin_tick": t}``), the
canonical results come back (new alerts, the runner's cumulative
wall/dispatch book, sparse registry deltas, chaos fired-counts).  The
child executes the slice through the SAME ``ServeEngine._score_shard``
code path as the thread worker — it builds a real 1-shard sub-engine
over its owned tenants (flight/census/policy/supervision/tiering
off; those planes live on the coordinator) — so the score plane is
byte-identical to the thread engine BY CONSTRUCTION, not by a parallel
reimplementation.

Determinism inventory (what crosses the pipe and why it's safe):

- **Alerts** ship as ``(tenant_id, base, alerts[base:])`` suffixes
  against a per-tenant high-water; the coordinator's mirror truncates
  to ``base`` and extends, so a supervised recovery's checkpoint rewind
  self-heals to the child's exact list.
- **Registry deltas** are :meth:`anomod.obs.registry.Registry.
  delta_snapshot` payloads (the sparse/dense tick-barrier wire shape);
  the child owns its fold high-water state, so a respawned child's
  fresh registry folds from zero without double counting.
- **State digests** ship as per-tenant ``(crc, len)`` fragments
  (:func:`anomod.obs.flight.state_digest_parts`) and fold with
  ``crc32_combine`` — bit-equal to the coordinator walking the states
  itself, without shipping a single state pytree.
- **Chaos fired-counts** ride every reply: a scripted fault's
  ``repeat`` budget lives in the child, and a respawned child must
  resume the budget where the dead one left it or a one-shot crash
  fault would re-trip on recovery re-execution, forever.

Errors cross the pipe as a pickled summary (type name, message,
``kills_worker``, formatted traceback) and are reconstructed on the
coordinator — chaos exception types by name from
:mod:`anomod.serve.chaos`, anything else as ``RuntimeError`` — so the
supervisor's retry/quarantine/migrate ladder sees the same exception
surface the thread worker raises at join().  A ``kills_worker`` fault
sends its reply first, then the child exits: force-delete-and-respawn,
exactly the thread seam's contract.
"""

from __future__ import annotations

import multiprocessing as mp
import traceback
from typing import Dict, List, Optional, Set

#: exception modules the coordinator will re-import by name when
#: rebuilding a shipped child error; everything else degrades to
#: RuntimeError (the pipe is trusted — same user, same box — but the
#: reconstruction surface stays a closed set anyway)
_TRUSTED_EXC_MODULES = ("builtins", "anomod.serve.chaos")


def ship_exc(e: BaseException) -> dict:
    """One child-side exception as a picklable summary."""
    return {"type": type(e).__name__,
            "module": type(e).__module__,
            "msg": str(e),
            "kills_worker": bool(getattr(e, "kills_worker", False)),
            "traceback": traceback.format_exc()}


def rebuild_exc(doc: dict) -> BaseException:
    """Coordinator-side reconstruction of :func:`ship_exc`.

    Chaos types (``ChaosFault`` / ``ChaosWorkerCrash``) and builtins
    rebuild as themselves so the supervisor's ``kills_worker``
    duck-typing and the tests' ``pytest.raises`` surfaces match the
    thread engine; unknown types become RuntimeError with the child's
    traceback attached for forensics."""
    exc: Optional[BaseException] = None
    mod = doc.get("module", "")
    name = doc.get("type", "RuntimeError")
    if mod in _TRUSTED_EXC_MODULES:
        try:
            import importlib
            cls = getattr(importlib.import_module(mod), name, None)
            if isinstance(cls, type) and issubclass(cls, BaseException):
                exc = cls(doc.get("msg", ""))
        except Exception:       # noqa: BLE001 — fall through to generic
            exc = None
    if exc is None:
        exc = RuntimeError(
            f"shard worker {name}: {doc.get('msg', '')}")
    if doc.get("kills_worker") and not getattr(exc, "kills_worker",
                                               False):
        exc.kills_worker = True        # type: ignore[attr-defined]
    exc.remote_traceback = doc.get("traceback")  # type: ignore[attr-defined]
    return exc


class RunnerMirror:
    """The coordinator's stand-in for a child-owned BucketRunner.

    Every runner fact the coordinator-side planes read — flight-header
    buckets, per-tick ``leg_walls()`` deltas, the supervisor's
    ``book_snapshot``/``book_restore`` double-count guard, the policy's
    ``n_dispatches`` chunk signal, the report's ``_runner_stats`` shape
    — is served from the child's last barrier reply, so the planes
    themselves never branch on the worker kind.  Resolution of the
    static facts (buckets, lane buckets, native staging, state mode)
    reuses the EXACT BucketRunner validators: the flight header is
    written in the engine ctor, before any child exists."""

    def __init__(self, cfg, buckets=None, lane_buckets=None,
                 native_stage=None, state=None):
        from anomod.config import get_config, validate_lane_buckets
        from anomod.config import validate_serve_buckets
        from anomod.io import native as native_io
        if buckets is None:
            buckets = get_config().serve_buckets
        if lane_buckets is None:
            lane_buckets = get_config().serve_lane_buckets
        self.cfg = cfg
        self.buckets = validate_serve_buckets(buckets)
        self.lane_buckets = validate_lane_buckets(lane_buckets)
        self.native_stage = native_io.staging_enabled(native_stage)
        _state = state if state is not None else get_config().serve_state
        self.state_mode = "device" if _state == "auto" else _state
        self.pool = None               # the pool lives in the child
        # cumulative book (the book_snapshot/book_restore shape)
        self.n_dispatches = 0
        self.dispatches_by_width: Dict[int, int] = {}
        self.fused_dispatches = 0
        self.native_staged = 0
        self.staged_lanes = 0
        self.live_lanes = 0
        self.lanes_by_bucket: Dict[int, int] = {}
        # wall/compile legs (the _runner_stats shape)
        self.compile_s = 0.0
        self.lane_compile_s = 0.0
        self.stage_wall_s = 0.0
        self.dispatch_wall_s = 0.0
        self.fold_wall_s = 0.0
        self.score_wall_s = 0.0
        self.inflight_dispatches = 0

    def apply(self, doc: dict) -> None:
        """Install one barrier reply's cumulative runner book."""
        self.book_restore(doc["book"])
        self.compile_s = doc["compile_s"]
        self.lane_compile_s = doc["lane_compile_s"]
        walls = doc["walls"]
        self.stage_wall_s = walls["stage_s"]
        self.dispatch_wall_s = walls["dispatch_s"]
        self.fold_wall_s = walls["fold_s"]
        self.score_wall_s = walls["score_s"]

    def leg_walls(self) -> dict:
        return {"stage_s": self.stage_wall_s,
                "dispatch_s": self.dispatch_wall_s,
                "fold_s": self.fold_wall_s,
                "score_s": self.score_wall_s,
                "chunks": self.n_dispatches,
                "fused": self.fused_dispatches,
                "native_staged": self.native_staged,
                "by_width": dict(self.dispatches_by_width)}

    def book_snapshot(self) -> dict:
        return {"n_dispatches": self.n_dispatches,
                "dispatches_by_width": dict(self.dispatches_by_width),
                "fused_dispatches": self.fused_dispatches,
                "native_staged": self.native_staged,
                "staged_lanes": self.staged_lanes,
                "live_lanes": self.live_lanes,
                "lanes_by_bucket": dict(self.lanes_by_bucket)}

    def book_restore(self, book: dict) -> None:
        self.n_dispatches = book["n_dispatches"]
        self.dispatches_by_width = dict(book["dispatches_by_width"])
        self.fused_dispatches = book["fused_dispatches"]
        self.native_staged = book["native_staged"]
        self.staged_lanes = book["staged_lanes"]
        self.live_lanes = book["live_lanes"]
        self.lanes_by_bucket = dict(book["lanes_by_bucket"])

    @property
    def lane_pad_waste(self) -> float:
        return (1.0 - self.live_lanes / self.staged_lanes
                if self.staged_lanes else 0.0)

    def abort_lanes(self) -> None:
        """In-flight dispatches live in the child; nothing to drop
        here (the child aborts its own lanes on a failed slice and on
        the ``drop`` command)."""


class DetMirror:
    """The coordinator's stand-in for a child-owned OnlineDetector:
    just the alert list (the only detector surface the coordinator
    planes read — flight alert digests, RCA enqueue, report counts),
    kept in sync by the barrier replies' suffix protocol."""

    __slots__ = ("alerts",)

    def __init__(self):
        self.alerts: list = []


class ProcShardWorker:
    """One shard's worker PROCESS behind the ShardWorker seam.

    Presents the thread seam's four members (``submit`` / ``join`` /
    ``close`` / ``alive``) plus the data-protocol halves the engine's
    process branches use directly: ``send`` (fan-out, non-blocking),
    ``recv`` (barrier, returns the raw reply dict), ``call``
    (send+recv, raising the reconstructed child error).  ``submit``
    takes a picklable command dict instead of a closure — a process
    cannot share the engine's memory, so the engine hands it data, not
    code; ``join`` re-raises the shipped error exactly like the thread
    worker's barrier."""

    kind = "process"

    def __init__(self, shard_id: int, init: dict,
                 start_timeout_s: float = 120.0,
                 name: str = "anomod-procshard"):
        ctx = mp.get_context("spawn")
        self.shard_id = shard_id
        parent_conn, child_conn = ctx.Pipe()
        self._conn = parent_conn
        self._proc = ctx.Process(target=_shard_main, args=(child_conn,),
                                 name=f"{name}-{shard_id}", daemon=True)
        self._closed = False
        self._dying = False
        self.last_reply: Optional[dict] = None
        self._proc.start()
        child_conn.close()
        try:
            self._conn.send(dict(init))
            # the spawn handshake: the child imports jax and compiles
            # nothing yet, but a wedged interpreter (or an init error)
            # must surface HERE, bounded by the validated knob, not
            # hang the first tick barrier forever
            if not self._conn.poll(start_timeout_s):
                raise TimeoutError(
                    f"shard {shard_id} worker process did not finish "
                    f"startup within {start_timeout_s:.0f}s "
                    "(ANOMOD_SERVE_WORKER_START_TIMEOUT_S)")
            hello = self._conn.recv()
        except BaseException:
            self.close(force=True)
            raise
        if hello.get("error") is not None:
            err = rebuild_exc(hello["error"])
            self.close(force=True)
            raise err
        #: the child's resolved runner facts (buckets / native staging /
        #: state mode) — forensic cross-check against the RunnerMirror
        self.hello = hello

    # -- data protocol ----------------------------------------------------

    def send(self, msg: dict) -> None:
        """Fan-out half: enqueue one command without waiting."""
        try:
            self._conn.send(msg)
        except (BrokenPipeError, OSError) as e:
            self._dying = True
            raise RuntimeError(
                f"shard {self.shard_id} worker process is gone "
                f"(command {msg.get('op')!r} not delivered)") from e

    def recv(self) -> dict:
        """Barrier half: one raw reply dict.  A shipped error stays IN
        the reply (the engine folds the partial results first and
        reconstructs the exception itself); only a dead pipe raises
        here."""
        try:
            rep = self._conn.recv()
        except (EOFError, OSError) as e:
            self._dying = True
            raise RuntimeError(
                f"shard {self.shard_id} worker process died "
                "mid-command") from e
        err = rep.get("error")
        if err is not None and err.get("kills_worker"):
            # the child exits right after this reply; flip alive NOW so
            # a respawn check can never race the process teardown
            self._dying = True
        self.last_reply = rep
        return rep

    def call(self, msg: dict) -> dict:
        """send + recv, raising the reconstructed child error."""
        self.send(msg)
        rep = self.recv()
        if rep.get("error") is not None:
            raise rebuild_exc(rep["error"])
        return rep

    # -- the ShardWorker seam ---------------------------------------------

    def submit(self, msg: dict) -> None:
        self.send(msg)

    def join(self) -> dict:
        rep = self.recv()
        if rep.get("error") is not None:
            raise rebuild_exc(rep["error"])
        return rep

    def close(self, force: bool = False) -> None:
        if self._closed:
            return
        self._closed = True
        try:
            if not force and self._proc.is_alive():
                self._conn.send({"op": "close"})
        except (BrokenPipeError, OSError):
            pass
        self._proc.join(timeout=10.0)
        if self._proc.is_alive():
            self._proc.terminate()
            self._proc.join(timeout=5.0)
        try:
            self._conn.close()
        except OSError:
            pass

    @property
    def alive(self) -> bool:
        return (not self._closed and not self._dying
                and self._proc.is_alive())


# -- the child ------------------------------------------------------------

def _shard_main(conn) -> None:
    """Worker-process entry point: receive the init payload, build the
    shard plane, then serve commands until ``close``/EOF (or until a
    ``kills_worker`` fault ends the process after its error reply)."""
    try:
        init = conn.recv()
    except (EOFError, OSError):
        return
    try:
        plane = _ShardPlane(init)
        conn.send({"ok": True, **plane.static_facts()})
    except BaseException as e:          # noqa: BLE001 — shipped
        try:
            conn.send({"error": ship_exc(e)})
        except (BrokenPipeError, OSError):
            pass
        return
    while True:
        try:
            msg = conn.recv()
        except (EOFError, OSError):
            return
        if msg.get("op") == "close":
            return
        reply, die = plane.handle(msg)
        try:
            conn.send(reply)
        except (BrokenPipeError, OSError):
            return
        if die:
            return


class _ShardPlane:
    """The child's side of the protocol: a real 1-shard sub-ServeEngine
    over the shard's owned tenants, plus the bookkeeping that turns its
    state changes into barrier replies.

    The sub-engine runs with every coordinator plane OFF — flight,
    census, policy, supervision, tiering, RCA (evidence buffering
    is documented coordinator-side: rca.py keeps buffer content
    shard-count-invariant there) — and every knob passed EXPLICITLY
    from the parent's resolved values, so the child can never drift
    onto a different env-sourced configuration than the engine that
    spawned it."""

    def __init__(self, init: dict):
        from anomod import obs
        from anomod.serve.engine import ServeEngine
        reg = obs.get_registry()
        # the child's process-default registry IS the shard registry:
        # match the parent's enabled bit (the env normally agrees, but
        # a test that force-enabled the parent's registry must see the
        # child's metrics too)
        reg.enabled = bool(init["registry_enabled"])
        self.shard_id = int(init["shard_id"])
        self.chaos = None
        chaos_script = init.get("chaos_script")
        if chaos_script:
            from anomod.serve.chaos import ServeChaos
            self.chaos = ServeChaos(chaos_script)
            # keep only this shard's faults, remapped to the
            # sub-engine's shard 0 (surge is coordinator-side arrival
            # amplification and never fires here)
            self.chaos.faults = [f for f in self.chaos.faults
                                 if f.kind != "surge"
                                 and f.shard == self.shard_id]
            for f in self.chaos.faults:
                f.shard = 0
            self._restore_chaos_fired(init.get("chaos_fired"))
        det_kw = init["det_kw"]
        self.eng = ServeEngine(
            init["specs"], init["services"], cfg=init["cfg"],
            t0_us=init["t0_us"],
            capacity_spans_per_s=init["capacity_spans_per_s"],
            tick_s=init["tick_s"], buckets=init["buckets"],
            max_backlog=init["max_backlog"], score=init["score"],
            fuse=init["fuse"], lane_buckets=init["lane_buckets"],
            shards=1, pipeline=init["pipeline"], rca=False,
            native=init["native"], state=init["state"], flight=False,
            census=False,
            chaos=self.chaos if self.chaos is not None else "",
            ckpt_every=0, policy="off", async_commit=False, tier_hot=0,
            worker="thread", fold="sparse", **det_kw)
        self._fold_state: Dict[tuple, float] = {}
        self._reg = reg
        #: per-tenant alert high-water: how much of each detector's
        #: alert list the coordinator's mirror already holds
        self._sent: Dict[int, int] = {}
        self._shipped_replay: Set[int] = set()
        self._shipped_det: Set[int] = set()

    def static_facts(self) -> dict:
        r = self.eng.runner
        return {"buckets": tuple(r.buckets),
                "lane_buckets": tuple(r.lane_buckets),
                "native_stage": bool(r.native_stage),
                "state_mode": r.state_mode}

    def _restore_chaos_fired(self, fired: Optional[List[int]]) -> None:
        """Reinstall a dead predecessor's fault fired-counts: a
        ``repeat``-budgeted fault must not reset its budget just
        because the crash it injected respawned the process."""
        if not fired or self.chaos is None:
            return
        for f, n in zip(self.chaos.faults, fired):
            f.fired = int(n)

    # -- reply assembly ---------------------------------------------------

    def _mirror_doc(self) -> dict:
        r = self.eng.runner
        return {"book": r.book_snapshot(),
                "compile_s": float(r.compile_s),
                "lane_compile_s": float(r.lane_compile_s),
                "walls": {"stage_s": r.stage_wall_s,
                          "dispatch_s": r.dispatch_wall_s,
                          "fold_s": r.fold_wall_s,
                          "score_s": r.score_wall_s}}

    def _alert_updates(self) -> list:
        ups = []
        for tid in sorted(self.eng._tenant_det):
            alerts = self.eng._tenant_det[tid].alerts
            prev = self._sent.get(tid, 0)
            if len(alerts) != prev:
                base = min(prev, len(alerts))
                ups.append((tid, base, list(alerts[base:])))
                self._sent[tid] = len(alerts)
        return ups

    def _residency_updates(self) -> dict:
        new_rep = [t for t in self.eng._tenant_replay
                   if t not in self._shipped_replay]
        new_det = [t for t in self.eng._tenant_det
                   if t not in self._shipped_det]
        self._shipped_replay.update(new_rep)
        self._shipped_det.update(new_det)
        return {"resident_new": sorted(new_rep),
                "det_new": sorted(new_det)}

    def handle(self, msg: dict):
        op = msg["op"]
        reply: dict = {}
        die = False
        try:
            out = getattr(self, "_op_" + op, self._op_unknown)(msg)
            if out:
                reply.update(out)
        except BaseException as e:      # noqa: BLE001 — shipped
            reply["error"] = ship_exc(e)
            die = bool(getattr(e, "kills_worker", False))
        if op in ("score", "warm", "finish", "install_tenant",
                  "put_tenant"):
            try:
                reply.update(self._mirror_doc())
                reply["alerts"] = self._alert_updates()
                reply.update(self._residency_updates())
                if op in ("score", "finish"):
                    reply["reg_delta"] = self._reg.delta_snapshot(
                        self._fold_state, mode=msg.get("fold", "sparse"),
                        final=False)
            except BaseException as e:  # noqa: BLE001 — shipped
                reply.setdefault("error", ship_exc(e))
        if self.chaos is not None:
            reply["chaos_fired"] = [f.fired for f in self.chaos.faults]
        return reply, die

    def _op_unknown(self, msg: dict):
        raise ValueError(f"unknown procshard command {msg.get('op')!r}")

    # -- command handlers -------------------------------------------------

    def _op_score(self, msg: dict):
        self.eng._score_shard(0, msg["served"], msg["origin_tick"])

    def _op_warm(self, msg: dict):
        r = self.eng.runner
        r.warm()
        if self.eng._fused:
            r.warm_lanes()

    def _op_finish(self, msg: dict):
        for det in self.eng._tenant_det.values():
            det.finish()

    def _op_digest(self, msg: dict):
        from anomod.obs.flight import state_digest_parts
        return {"parts": state_digest_parts(self.eng._tenant_replay)}

    def _op_reg_delta(self, msg: dict):
        return {"delta": self._reg.delta_snapshot(
            self._fold_state, mode=msg.get("fold", "sparse"),
            final=bool(msg.get("final", False)))}

    def _op_snapshot(self, msg: dict):
        from anomod.serve.supervise import (snapshot_detector,
                                            snapshot_replay)
        tenants = {}
        for tid, rep in self.eng._tenant_replay.items():
            det = self.eng._tenant_det.get(tid)
            tenants[tid] = (snapshot_replay(rep),
                            snapshot_detector(det)
                            if det is not None else None)
        return {"tenants": tenants,
                "book": self.eng.runner.book_snapshot()}

    def _op_book_restore(self, msg: dict):
        self.eng.runner.book_restore(msg["book"])

    def _op_drop(self, msg: dict):
        eng = self.eng
        for tid in list(eng._tenant_replay):
            rep = eng._tenant_replay.pop(tid)
            release = getattr(rep, "release", None)
            if release is not None:
                release()
        eng._tenant_det.clear()
        eng.runner.abort_lanes()
        self._sent.clear()
        self._shipped_replay.clear()
        self._shipped_det.clear()

    def _op_install_tenant(self, msg: dict):
        from anomod.serve.supervise import restore_detector, restore_replay
        tid = msg["tid"]
        rep = self.eng._replay_for(tid)
        restore_replay(rep, msg["replay"])
        det_snap = msg.get("det")
        if det_snap is not None:
            det = self.eng._detector_for(tid)
            restore_detector(det, det_snap)
            # the coordinator installs the mirror's alert list from the
            # same snapshot — nothing to ship
            self._sent[tid] = len(det.alerts)

    def _op_put_tenant(self, msg: dict):
        self._op_install_tenant(msg)

    def _op_take_tenant(self, msg: dict):
        from anomod.serve.supervise import (snapshot_detector,
                                            snapshot_replay)
        tid = msg["tid"]
        eng = self.eng
        rep = eng._tenant_replay.pop(tid, None)
        if rep is None:
            return {"snap": None}
        rep_snap = snapshot_replay(rep)
        release = getattr(rep, "release", None)
        if release is not None:
            release()
        det = eng._tenant_det.pop(tid, None)
        det_snap = snapshot_detector(det) if det is not None else None
        self._sent.pop(tid, None)
        self._shipped_replay.discard(tid)
        self._shipped_det.discard(tid)
        return {"snap": (rep_snap, det_snap)}
