"""Black-box flight recorder: the serve plane's tick-level journal,
deterministic audit replay, and divergence bisection.

The paper's premise is synchronized telemetry that makes faults
diagnosable *after the fact* (SURVEY.md §0); metrics and traces (PR 3)
say how the serve plane *performed*, but nothing records what the engine
*decided* each tick.  This module is that record: an always-on,
bounded-overhead ring journal of every serve tick — admission decisions,
the dispatch plan, the five-leg wall decomposition, alerts, RCA verdicts
and a cheap periodic tenant-state digest — self-describing (seed,
resolved Config snapshot, versions in the header) and atomically
dumpable.  ``anomod audit`` turns it into a forensic tool: ``record``
runs traffic with the recorder on, ``replay`` re-executes from the
header's seed+config (optionally at a different shard count / pipeline
depth / state residency — the determinism contracts under test), and
``diff`` compares two journals tick-aligned and names the FIRST
divergent tick and which PLANE diverged.

Two tiers of content per tick record, mirroring the serving plane's
``SHARD_VARIANT_REPORT_FIELDS`` discipline:

- the **canonical planes** (:data:`PLANES` — admission, dispatch, fold,
  score, rca) hold only seed-determined decisions: admission counts and
  a crc32 digest of the served decision set, staged-chunk counts per
  width (identical under every execution strategy — the batcher's
  ``stage_plan`` is the one staging definition), the cadenced
  tenant-state digest (crc32 over the ``get_state``/pool-gather bytes —
  pinned byte-exact across residencies), the running alert-stream
  digest, and the running RCA-verdict digest.  Same seed ⇒ byte-identical
  canonical journals across reruns, shard counts, pipeline depths and
  host-vs-device state (tests/test_flight.py pins all four).
- the **variant keys** (:data:`FLIGHT_VARIANT_KEYS` — ``walls``,
  ``topology``) hold wall-clock measurements (the five-leg
  stage/dispatch/fold/score/other decomposition per tick) and lane/shard
  grouping topology (which lanes shared a fused stack, per-shard leg
  walls folded at the tick barrier in shard order — the
  ``fold_verdicts`` idiom).  They ride in the dump for forensics and are
  EXCLUDED from the canonical byte surface and from ``diff``.

Durability follows the repo's one publish idiom (tmp + ``os.replace``):
a killed run never leaves a truncated journal behind a valid path.  The
ring is bounded (``ANOMOD_FLIGHT_MAX_TICKS``) and every eviction is
counted (``anomod_flight_dropped_ticks_total`` + the per-recorder
``n_dropped``) — loss is visible, never silent.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import os
import zlib
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

from anomod import obs

#: journal format version (bumped on any canonical-shape change: a diff
#: across formats would bisect shape drift, not behavior)
FLIGHT_FORMAT = 1

#: the canonical decision planes, in CAUSAL order — when several planes
#: diverge in the same tick, ``diff_journals`` names the earliest: a
#: wrong admission decision makes every downstream plane diverge too,
#: and the culprit is the first wrong decision, not its echoes.
PLANES: Tuple[str, ...] = ("admission", "dispatch", "fold", "score", "rca")

#: per-tick keys excluded from the canonical byte surface and from
#: ``diff``: wall-clock measurements, shard/lane grouping topology, the
#: supervisor's recovery events (what crashed/respawned/migrated is
#: execution-strategy forensics — the no-score-gap contract pins the
#: DECISION planes of a recovered run equal to fault-free, so recovery
#: marks must never touch them), and the elastic policy's scaling
#: events (what scaled up/down/rebalanced is likewise execution
#: topology: an elastic run's canonical planes stay equal to a static
#: run's), and
#: the fleet census observatory's resident-bytes/hot-set records
#: (anomod.obs.census — deterministic and wall-free, but per-shard
#: pool/scratch bytes follow the execution TOPOLOGY, so the key is
#: variant like ``topology``; unlike ``walls`` the census
#: stream is byte-equal across same-seed reruns of one topology,
#: pinned in tests/test_census.py) —
#: the flight twin of the serving plane's
#: SHARD_VARIANT_REPORT_FIELDS (one definition, shared by
#: canonical_ticks and the parity tests).
#: ``tiering`` (anomod.serve.tiering) joins the variant tier for one
#: precise reason: demote/promote/miss events are wall-free functions
#: of seed+config (byte-equal across same-config reruns, pinned in
#: tests/test_serve_tiering.py), but a cold promotion's one-tick
#: deferral legitimately moves WHICH tick a tenant's fold/score deltas
#: land in vs a never-evicted run of the same seed — content conserved,
#: placement shifted — so the key cannot sit on the canonical surface.
#: ``seq`` (anomod.serve.seqplane): the sequence-model plane's tick
#: (tokens, session-policy counts, a digest of the surprisals).  A pure
#: consumer of the served batches, so the canonical planes are equal with
#: the plane on or off; the key is variant because the plane is.
FLIGHT_VARIANT_KEYS: Tuple[str, ...] = ("walls", "topology", "recovery",
                                        "scaling", "census",
                                        "tiering", "seq")


def crc_text(text: str, prev: int = 0) -> int:
    """Running crc32 over a text chunk (stable across processes and
    Python hash seeds — the shard-partition idiom)."""
    return zlib.crc32(text.encode(), prev) & 0xFFFFFFFF


def crc_bytes(data: bytes, prev: int = 0) -> int:
    return zlib.crc32(data, prev) & 0xFFFFFFFF


def state_digest(replays: Dict[int, object], prev: int = 0) -> int:
    """crc32 over every tenant replay state, in sorted-tenant order.

    Reads through the ``get_state`` seam (a pool-backed replay gathers
    its slot; the host seam hands its pytree) — pinned byte-exact across
    residencies, which is what makes one digest comparable between a
    host-seam and a device-pool run.  The ring anchor
    (``window_offset``) and span count prefix each tenant so two states
    that happen to share bytes at different anchors still differ."""
    crc = prev
    for tid in sorted(replays):
        rep = replays[tid]
        st = rep.get_state() if hasattr(rep, "get_state") else rep.state
        crc = crc_text(f"{tid}:{getattr(rep, 'window_offset', 0)}"
                       f":{getattr(rep, 'n_spans', 0)}:", crc)
        crc = crc_bytes(np.ascontiguousarray(st.agg).tobytes(), crc)
        crc = crc_bytes(np.ascontiguousarray(st.hist).tobytes(), crc)
    return crc


def _gf2_matrix_times(mat: List[int], vec: int) -> int:
    s = 0
    i = 0
    while vec:
        if vec & 1:
            s ^= mat[i]
        vec >>= 1
        i += 1
    return s


def _gf2_matrix_square(mat: List[int]) -> List[int]:
    return [_gf2_matrix_times(mat, mat[n]) for n in range(32)]


def crc32_combine(crc1: int, crc2: int, len2: int) -> int:
    """zlib's crc32_combine in pure Python: the crc of ``A + B`` from
    ``crc32(A)``, ``crc32(B)`` and ``len(B)`` alone (GF(2) matrix
    shift).  This is what lets a worker PROCESS hand the coordinator
    per-tenant digest fragments — ``(crc, length)`` pairs, a few bytes
    each — instead of shipping whole state pytrees across the pipe,
    while the folded digest stays bit-equal to :func:`state_digest`'s
    sequential walk (pinned in tests/test_serve_procshard.py)."""
    if len2 <= 0:
        return crc1 & 0xFFFFFFFF
    odd = [0xEDB88320]          # CRC-32 polynomial, reflected
    row = 1
    for _ in range(31):
        odd.append(row)
        row <<= 1
    even = _gf2_matrix_square(odd)
    odd = _gf2_matrix_square(even)
    while True:
        even = _gf2_matrix_square(odd)
        if len2 & 1:
            crc1 = _gf2_matrix_times(even, crc1)
        len2 >>= 1
        if len2 == 0:
            break
        odd = _gf2_matrix_square(even)
        if len2 & 1:
            crc1 = _gf2_matrix_times(odd, crc1)
        len2 >>= 1
        if len2 == 0:
            break
    return (crc1 ^ crc2) & 0xFFFFFFFF


def state_digest_parts(replays: Dict[int, object]) -> List[Tuple[int, int,
                                                                 int]]:
    """The worker-side half of :func:`state_digest`: per-tenant
    ``(tenant_id, chunk_crc, chunk_len)`` fragments over exactly the
    bytes the sequential walk would consume (prefix + agg + hist).
    Each fragment is computed where the state lives; the coordinator
    folds fragments from every shard in global sorted-tenant order with
    :func:`fold_digest_parts`."""
    parts = []
    for tid in sorted(replays):
        rep = replays[tid]
        st = rep.get_state() if hasattr(rep, "get_state") else rep.state
        chunk = (f"{tid}:{getattr(rep, 'window_offset', 0)}"
                 f":{getattr(rep, 'n_spans', 0)}:".encode()
                 + np.ascontiguousarray(st.agg).tobytes()
                 + np.ascontiguousarray(st.hist).tobytes())
        parts.append((int(tid), crc_bytes(chunk), len(chunk)))
    return parts


def fold_digest_parts(parts: List[Tuple[int, int, int]],
                      prev: int = 0) -> int:
    """Coordinator fold of :func:`state_digest_parts` fragments (from
    any number of shards) into the running digest — bit-equal to
    :func:`state_digest` over the union of the shards' replays."""
    crc = prev
    for _tid, chunk_crc, chunk_len in sorted(parts):
        crc = crc32_combine(crc, chunk_crc, chunk_len)
    return crc


def config_snapshot() -> dict:
    """The resolved Config as a JSON-able dict (Paths stringified) —
    the header's "what knobs was this run serving under" record."""
    from anomod.config import get_config
    cfg = get_config()
    out = {}
    for f in dataclasses.fields(cfg):
        v = getattr(cfg, f.name)
        if isinstance(v, Path):
            v = str(v)
        elif isinstance(v, tuple):
            v = [list(x) if isinstance(x, tuple) else x for x in v]
        out[f.name] = v
    return out


def versions() -> dict:
    import platform as _platform

    import jax
    out = {"python": _platform.python_version(), "jax": jax.__version__,
           "numpy": np.__version__}
    try:
        import jaxlib
        out["jaxlib"] = jaxlib.__version__
    except Exception:
        pass
    return out


def canonical_ticks(ticks: List[dict]) -> List[dict]:
    """The byte-parity view of a tick list: every record with the
    variant keys (:data:`FLIGHT_VARIANT_KEYS`) stripped."""
    return [{k: v for k, v in rec.items()
             if k not in FLIGHT_VARIANT_KEYS} for rec in ticks]


def _atomic_write_json(path, doc: dict) -> Path:
    """The one publish idiom (tmp + ``os.replace``, anomod.io.cache) for
    this module's two documents — a killed run never leaves a truncated
    journal or bundle behind a valid path."""
    path = Path(path)
    if path.parent and not path.parent.exists():
        path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_name(f"{path.name}.{os.getpid()}.tmp")
    try:
        tmp.write_text(json.dumps(doc, sort_keys=True))
        os.replace(tmp, path)
    finally:
        if tmp.exists():
            try:
                tmp.unlink()
            except OSError:
                pass
    return path


class FlightRecorder:
    """Bounded ring journal of serve-tick records.

    The ENGINE builds each record (it owns the decision state); the
    recorder owns bounding, counting, the canonical surface and
    publication.  ``header`` is the self-describing preamble — engine
    shape, resolved Config snapshot, versions, and (when driven through
    ``run_power_law``) the ``run`` kwargs ``anomod audit replay``
    re-executes from."""

    def __init__(self, header: dict, max_ticks: Optional[int] = None,
                 digest_every: Optional[int] = None):
        from anomod.config import get_config
        cfg = get_config()
        self.max_ticks = int(cfg.flight_max_ticks if max_ticks is None
                             else max_ticks)
        self.digest_every = int(cfg.flight_digest_every
                                if digest_every is None else digest_every)
        if self.max_ticks < 1:
            raise ValueError("flight ring needs >= 1 tick")
        if self.digest_every < 1:
            raise ValueError("digest cadence must be >= 1 tick")
        self.header = dict(header)
        self.header.setdefault("flight_format", FLIGHT_FORMAT)
        self.header["digest_every"] = self.digest_every
        self.header["max_ticks"] = self.max_ticks
        self._ring: "collections.deque[dict]" = collections.deque(
            maxlen=self.max_ticks)
        self.n_recorded = 0
        self.n_dropped = 0
        self.dump_error: Optional[str] = None
        # registry mirrors: recording is per-tick on the serve hot path,
        # handles cached; the drop counter is the no-silent-loss pin
        self._obs_ticks = obs.counter("anomod_flight_ticks_total")
        self._obs_dropped = obs.counter(
            "anomod_flight_dropped_ticks_total")
        self._obs_dumps = obs.counter("anomod_flight_dumps_total")
        self._obs_dump_errors = obs.counter(
            "anomod_flight_dump_errors_total")

    def digest_tick(self, tick_idx: int) -> bool:
        """Whether ``tick_idx`` (0-based) is a state-digest tick — the
        cadence contract shared with the engine and documented for
        ``diff`` (journals only compare digests at matching cadence)."""
        return (tick_idx + 1) % self.digest_every == 0

    def record(self, rec: dict) -> None:
        if len(self._ring) == self.max_ticks:
            self.n_dropped += 1
            self._obs_dropped.inc()
        self._ring.append(rec)
        self.n_recorded += 1
        self._obs_ticks.inc()

    def records(self) -> List[dict]:
        return list(self._ring)

    def canonical_bytes(self) -> bytes:
        """The journal's byte-parity surface: the canonical tick records
        (variant keys stripped), serialized deterministically.  Same
        seed ⇒ equal bytes across reruns, shard counts, pipeline depths
        and state residencies."""
        return json.dumps({"flight_format": FLIGHT_FORMAT,
                           "ticks": canonical_ticks(self.records())},
                          sort_keys=True,
                          separators=(",", ":")).encode()

    def journal(self) -> dict:
        """The full journal document (header + counters + every record,
        variant keys included) — what :meth:`dump` publishes and
        :func:`diff_journals` consumes."""
        return {"flight_format": FLIGHT_FORMAT, "header": dict(self.header),
                "n_recorded": self.n_recorded, "n_dropped": self.n_dropped,
                "ticks": self.records()}

    def dump(self, path) -> dict:
        """Atomic publish of :meth:`journal`; returns the dict it
        wrote."""
        doc = self.journal()
        _atomic_write_json(path, doc)
        return doc

    def forensic(self, path, registry=None, tracer=None,
                 reason: str = "") -> Optional[str]:
        """Alert/SLO-breach forensic dump: ring snapshot + registry
        scrape + tracer spans in ONE atomically-published bundle.

        An OSError (disk full, unwritable dir) must not kill the serve
        tick that triggered the dump — it is counted
        (``anomod_flight_dump_errors_total``), recorded on
        ``dump_error``, and the tick proceeds; any other failure is a
        bug and propagates."""
        try:
            out = forensic_bundle(path, self, registry=registry,
                                  tracer=tracer, reason=reason)
            self._obs_dumps.inc()
            return str(out)
        except OSError as e:
            self.dump_error = f"{type(e).__name__}: {e}"
            self._obs_dump_errors.inc()
            return None


def forensic_bundle(path, recorder: FlightRecorder, registry=None,
                    tracer=None, reason: str = "") -> Path:
    """One forensic document: the flight journal, the metric registry's
    point-in-time snapshot + scrape journal, and the tracer's Jaeger
    spans — atomically published, so the bundle behind a valid path is
    always complete."""
    doc = {"bundle": "anomod-flight-forensic", "reason": str(reason),
           "flight": recorder.journal()}
    if registry is not None and getattr(registry, "enabled", False):
        doc["registry"] = {"snapshot": registry.snapshot(),
                           "journal": [list(s) for s
                                       in registry.journal()]}
    if tracer is not None:
        doc["trace"] = tracer.to_jaeger()
    return _atomic_write_json(path, doc)


def load_journal(path) -> dict:
    """Load a dumped journal; fails loud on a non-flight document (a
    diff against some other JSON would report nonsense ticks)."""
    doc = json.loads(Path(path).read_text())
    if not isinstance(doc, dict) or "ticks" not in doc \
            or doc.get("flight_format") != FLIGHT_FORMAT:
        raise ValueError(f"not a flight journal (format "
                         f"{FLIGHT_FORMAT}): {path}")
    return doc


def diff_journals(a: dict, b: dict) -> Optional[dict]:
    """Tick-aligned comparison of two journals' canonical planes.

    Returns ``None`` when the canonical surfaces are identical,
    otherwise a dict naming the FIRST divergent tick and the earliest
    divergent PLANE in causal order (:data:`PLANES`; ``clock`` = the
    tick index/virtual-time spine itself, ``length`` = one journal ran
    more ticks) with both sides' plane records — the bisection verdict
    ``anomod audit diff`` prints and exits nonzero on.  Wall-clock and
    topology keys never participate (:data:`FLIGHT_VARIANT_KEYS`).
    """
    ta = canonical_ticks(a.get("ticks", ()))
    tb = canonical_ticks(b.get("ticks", ()))
    notes: List[str] = []
    ha, hb = a.get("header", {}), b.get("header", {})
    if ha.get("digest_every") != hb.get("digest_every"):
        notes.append(
            f"digest cadence differs (a={ha.get('digest_every')}, "
            f"b={hb.get('digest_every')}): fold digests land on "
            "different ticks and will read as fold divergence")
    if a.get("n_dropped") or b.get("n_dropped"):
        notes.append(f"ring drops (a={a.get('n_dropped', 0)}, "
                     f"b={b.get('n_dropped', 0)}): journals may start "
                     "at different ticks")

    def verdict(i, plane, va, vb):
        out = {"tick": (ta[i].get("tick", i) if i < len(ta)
                        else tb[i].get("tick", i)),
               "index": i, "plane": plane, "a": va, "b": vb}
        if notes:
            out["notes"] = notes
        return out

    for i in range(min(len(ta), len(tb))):
        ra, rb = ta[i], tb[i]
        spine_a = (ra.get("tick"), ra.get("now_s"), ra.get("final"))
        spine_b = (rb.get("tick"), rb.get("now_s"), rb.get("final"))
        if spine_a != spine_b:
            return verdict(i, "clock", list(spine_a), list(spine_b))
        for plane in PLANES:
            if ra.get(plane) != rb.get(plane):
                return verdict(i, plane, ra.get(plane), rb.get(plane))
    if len(ta) != len(tb):
        i = min(len(ta), len(tb))
        return verdict(i, "length", len(ta), len(tb))
    return None
