"""Admission control: per-tenant weighted-fair queues, bounded backlog,
priority-aware load shedding.

The shapes are the classic inference-serving ones (the ROADMAP's
"thousands of concurrent feeds" regime): every tenant owns a FIFO of
pending span micro-batches, service order across tenants is start-time
fair queuing (SFQ — each batch gets a virtual finish tag
``start + cost/weight``; the drain always serves the globally smallest
tag), and two backlog bounds provide backpressure:

- a per-tenant bound, so one runaway feed cannot monopolize the queue
  memory (its own overflow is shed, nobody else's), and
- a global bound (``ANOMOD_SERVE_MAX_BACKLOG``): when offered load
  exceeds capacity the controller sheds in PRIORITY order — an arriving
  batch may evict queued work of strictly lower priority (latest-served
  first, so the evicted work is what fair queuing would have reached
  last), and is itself shed when nothing lower-priority is queued.

Everything is host-side bookkeeping over integers and floats — no wall
clocks, no randomness — so a seeded overload replay is bit-reproducible
(the determinism contract tests/test_serve.py pins).

Registry costs scale with the ACTIVE tenant set, not the registered one
(the tiering PR's O(hot-set) contract): the per-tenant counters, backlog
depths and SFQ last-finish tags are created lazily on a tenant's first
offer, the registered fleet lives in one columnar spec table
(:class:`_SpecTable` — id/priority/weight arrays, ~26 exact bytes per
registered tenant instead of a spec-dict entry), and the admission
totals are maintained as a RUNNING sum at every mutation site, so
``totals()`` — called per tick by the flight recorder — is O(1) instead
of an O(registered) walk.  Same integers on every path (pinned).

Two drain/shed engines implement the same contract
(``ANOMOD_SERVE_NATIVE_DRAIN``): the original per-span Python heap pair
(``off`` — kept as the parity oracle) and the columnar engine
(:class:`_ColumnarSFQ`, the default) whose candidate scans run over
parallel NumPy arrays — in the native runtime (``anomod_sfq_drain`` /
``anomod_sfq_victim``) when it loads, pure ``lexsort`` otherwise.  All
three paths are pinned byte-identical: same served order, same shed and
eviction victims, same SFQ virtual-time floats.
"""

from __future__ import annotations

import ctypes
import dataclasses
import heapq
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from anomod import obs
from anomod.schemas import SpanBatch

#: default scheduler weight per priority class (0 = most important).
PRIORITY_WEIGHTS = {0: 4.0, 1: 2.0, 2: 1.0}


@dataclasses.dataclass(frozen=True)
class TenantSpec:
    """One tenant's static admission contract."""
    tenant_id: int
    name: str
    priority: int = 1          # 0 = gold, 1 = silver, 2 = bronze
    weight: float = 0.0        # 0 -> PRIORITY_WEIGHTS[priority]
    rate_spans_per_s: float = 0.0   # offered-load hint (traffic generator)

    def effective_weight(self) -> float:
        if self.weight > 0:
            return self.weight
        return PRIORITY_WEIGHTS.get(self.priority, 1.0)


@dataclasses.dataclass
class QueuedBatch:
    """One admitted micro-batch waiting for the batcher."""
    tenant_id: int
    seq: int                   # global admission sequence number
    spans: SpanBatch
    n_spans: int
    priority: int
    enqueued_s: float          # virtual admission time
    finish_tag: float          # SFQ virtual finish time


@dataclasses.dataclass
class TenantCounters:
    offered_spans: int = 0
    admitted_spans: int = 0
    served_spans: int = 0
    shed_spans: int = 0
    offered_batches: int = 0
    served_batches: int = 0
    shed_batches: int = 0
    # evictions are the subset of shed batches destroyed AFTER admission
    # (displaced by a higher-priority arrival) — counted separately so
    # the flight recorder's admission plane journals them per tick
    evicted_batches: int = 0


class _LazyCounters(dict):
    """Per-tenant counters created on first touch — the registered
    fleet never materializes a row (the O(hot-set) registry contract);
    external readers of a never-offered tenant see zeros, same as the
    eager dict before."""

    def __missing__(self, tid: int) -> TenantCounters:
        c = self[tid] = TenantCounters()
        return c


class _SpecTable:
    """The registered fleet as columns: tenant id, priority, resolved
    SFQ weight and the rate hint as parallel arrays, names as a tuple
    of references — ~26 exact bytes per registered tenant where the
    spec dict paid a dict entry + bookkeeping rows each.  Dense ids
    (0..n-1, every generated fleet) index straight into the arrays;
    anything else goes through a side index.  ``__getitem__``
    rematerializes a :class:`TenantSpec` for report/test callers —
    never on the offer/drain hot path, which reads
    :meth:`priority_of` / :meth:`weight_of`."""

    __slots__ = ("ids", "pri", "wt", "rate", "names", "_index")

    def __init__(self, tenants: Sequence[TenantSpec]):
        self.ids = np.asarray([t.tenant_id for t in tenants], np.int64)
        if len(np.unique(self.ids)) != len(self.ids):
            raise ValueError("duplicate tenant_id in tenant specs")
        self.pri = np.asarray([t.priority for t in tenants], np.int16)
        self.wt = np.asarray([t.effective_weight() for t in tenants],
                             np.float64)
        self.rate = np.asarray([t.rate_spans_per_s for t in tenants],
                               np.float64)
        self.names = tuple(t.name for t in tenants)
        n = len(self.ids)
        dense = n > 0 and self.ids[0] == 0 and self.ids[n - 1] == n - 1 \
            and bool((self.ids == np.arange(n, dtype=np.int64)).all())
        self._index: Optional[Dict[int, int]] = None if dense \
            else {int(t): i for i, t in enumerate(self.ids)}

    def _row(self, tid: int) -> int:
        if self._index is None:
            if 0 <= tid < len(self.ids):
                return tid
            raise KeyError(tid)
        return self._index[tid]

    def priority_of(self, tid: int) -> int:
        return int(self.pri[self._row(tid)])

    def weight_of(self, tid: int) -> float:
        return float(self.wt[self._row(tid)])

    def __len__(self) -> int:
        return len(self.ids)

    def __contains__(self, tid: int) -> bool:
        try:
            self._row(tid)
        except KeyError:
            return False
        return True

    def __iter__(self):
        return iter(int(t) for t in self.ids)

    def __getitem__(self, tid: int) -> TenantSpec:
        i = self._row(tid)
        return TenantSpec(tenant_id=int(self.ids[i]),
                          name=self.names[i],
                          priority=int(self.pri[i]),
                          weight=0.0 if self.wt[i]
                          == PRIORITY_WEIGHTS.get(int(self.pri[i]), 1.0)
                          else float(self.wt[i]),
                          rate_spans_per_s=float(self.rate[i]))

    def nbytes(self) -> int:
        """Exact column bytes + 8 nominal per name reference (the
        strings are owned by the caller's spec objects) + the sparse
        index entries where ids are not dense — the census admission
        plane's per-REGISTERED price."""
        b = int(self.ids.nbytes + self.pri.nbytes + self.wt.nbytes
                + self.rate.nbytes) + 8 * len(self.names)
        if self._index is not None:
            b += 64 * len(self._index)
        return b


class _ColumnarSFQ:
    """Struct-of-arrays mirror of the SFQ drain/evict heap pair.

    The heap engine pays per-batch Python on the serve hot path: one
    heappush onto BOTH heaps per admitted batch, lazy-deletion pops,
    and an amortized evict-heap compaction.  Here the pending-batch
    book is five parallel columns (finish tag, admission seq, span
    count, priority, alive mask) and the two hot scans become kernels
    over them:

    - drain selection: sort the alive slots by ``(finish_tag, seq)`` —
      exactly the drain heap's pop order (seqs are unique) — then walk
      the budget down with the SAME sequential float64 subtraction the
      heap loop performs (serve while ``remaining > 0``, one-batch
      overdraw included), and
    - shed victim: lexicographic argmax of ``(priority, finish_tag,
      seq)`` over the alive slots — exactly what the lazy evict heap's
      top names.

    Both kernels run in the native runtime (GIL released) when it
    loads, with a pure-NumPy fallback (``lexsort`` + the same walk)
    otherwise; the per-batch bookkeeping (counters, virtual-time floor,
    :class:`QueuedBatch` emission) stays in the controller unchanged,
    so all three engines are byte-identical (tests/test_serve.py pins
    heap == columnar-numpy == columnar-native).
    """

    __slots__ = ("fin", "seq", "nsp", "pri", "alive", "engine", "_lib",
                 "_slot_of", "_free", "_n", "_out",
                 "_p_fin", "_p_seq", "_p_nsp", "_p_pri", "_p_alive",
                 "_p_out")

    def __init__(self, cap: int = 256, require_native: bool = False):
        from anomod.io import native as io_native
        self._lib = io_native.sfq_kernels(require=require_native)
        self.engine = "native" if self._lib is not None else "numpy"
        cap = max(int(cap), 16)
        self.fin = np.zeros(cap, np.float64)
        self.seq = np.zeros(cap, np.int64)
        self.nsp = np.zeros(cap, np.int64)
        self.pri = np.zeros(cap, np.int64)
        self.alive = np.zeros(cap, np.uint8)
        self._slot_of: Dict[int, int] = {}
        self._free: List[int] = []
        self._n = 0                       # slot high-water mark
        self._out = np.empty(cap, np.int64)
        self._rebind()

    def _rebind(self) -> None:
        # marshal the column pointers ONCE per (re)allocation — the
        # StagePlan discipline: per-call ctypes extraction costs as much
        # as the scan it wraps on a small backlog
        self._p_fin = self.fin.ctypes.data_as(
            ctypes.POINTER(ctypes.c_double))
        self._p_seq = self.seq.ctypes.data_as(
            ctypes.POINTER(ctypes.c_int64))
        self._p_nsp = self.nsp.ctypes.data_as(
            ctypes.POINTER(ctypes.c_int64))
        self._p_pri = self.pri.ctypes.data_as(
            ctypes.POINTER(ctypes.c_int64))
        self._p_alive = self.alive.ctypes.data_as(
            ctypes.POINTER(ctypes.c_uint8))
        self._p_out = self._out.ctypes.data_as(
            ctypes.POINTER(ctypes.c_int64))

    def _grow(self) -> None:
        cap = len(self.fin) * 2
        for name in ("fin", "seq", "nsp", "pri", "alive"):
            old = getattr(self, name)
            new = np.zeros(cap, old.dtype)
            new[:len(old)] = old
            setattr(self, name, new)
        self._out = np.empty(cap, np.int64)
        self._rebind()

    def add(self, qb: "QueuedBatch") -> None:
        if self._free:
            slot = self._free.pop()
        else:
            if self._n >= len(self.fin):
                self._grow()
            slot = self._n
            self._n += 1
        self.fin[slot] = qb.finish_tag
        self.seq[slot] = qb.seq
        self.nsp[slot] = qb.n_spans
        self.pri[slot] = qb.priority
        self.alive[slot] = 1
        self._slot_of[qb.seq] = slot

    def remove(self, seq: int) -> None:
        slot = self._slot_of.pop(seq)
        self.alive[slot] = 0
        self._free.append(slot)

    def select(self, budget: float) -> List[int]:
        """Admission seqs served by ``budget`` spans, in drain order."""
        n = self._n
        if not self._slot_of:
            return []
        if self._lib is not None:
            count = self._lib.anomod_sfq_drain(
                self._p_fin, self._p_seq, self._p_nsp, self._p_alive,
                n, ctypes.c_double(budget), self._p_out)
            if count >= 0:
                return [int(self.seq[s]) for s in self._out[:count]]
        idx = np.flatnonzero(self.alive[:n])
        order = np.lexsort((self.seq[idx], self.fin[idx]))
        out: List[int] = []
        remaining = float(budget)
        for slot in idx[order]:
            if not remaining > 0:
                break
            remaining -= int(self.nsp[slot])
            out.append(int(self.seq[slot]))
        return out

    def victim(self) -> Optional[int]:
        """Admission seq of the batch the evict heap's top would name
        (lexicographic max of (priority, finish_tag, seq) over the
        alive slots); None when nothing is queued."""
        n = self._n
        if not self._slot_of:
            return None
        if self._lib is not None:
            got = self._lib.anomod_sfq_victim(
                self._p_fin, self._p_seq, self._p_pri, self._p_alive, n)
            if got >= 0:
                return int(self.seq[got])
        idx = np.flatnonzero(self.alive[:n])
        k = np.lexsort((self.seq[idx], self.fin[idx], self.pri[idx]))[-1]
        return int(self.seq[idx[k]])


class AdmissionController:
    """Weighted-fair admission over a bounded multi-tenant backlog."""

    def __init__(self, tenants: Sequence[TenantSpec],
                 max_backlog: int = 200_000,
                 max_tenant_backlog: Optional[int] = None,
                 drain_engine: Optional[str] = None):
        if max_backlog < 1:
            raise ValueError("max_backlog must be >= 1 span")
        if drain_engine is None:
            from anomod.config import get_config
            drain_engine = get_config().serve_native_drain
        if drain_engine not in ("auto", "on", "off"):
            raise ValueError(
                f"drain_engine must be auto, on or off, got "
                f"{drain_engine!r}")
        #: the resolved drain/shed engine: "heap" (the Python oracle),
        #: "numpy" or "native" (both columnar) — what the flight header
        #: and `anomod validate` surface
        self.drain_engine = "heap"
        self._col: Optional[_ColumnarSFQ] = None
        if drain_engine != "off":
            self._col = _ColumnarSFQ(require_native=(drain_engine == "on"))
            self.drain_engine = self._col.engine
        # registered fleet: one columnar table, not a dict of specs —
        # O(registered) exact bytes, O(1)-ish lookups; raises the same
        # duplicate-id ValueError the dict comprehension used to
        self.specs = _SpecTable(tenants)
        self.max_backlog = int(max_backlog)
        self.max_tenant_backlog = int(max_tenant_backlog
                                      if max_tenant_backlog is not None
                                      else max(max_backlog // 8, 1))
        # ACTIVE-tenant registries: rows materialize on first offer, so
        # a million-registered fleet with a thousand live feeds pays for
        # a thousand rows (the tiering PR's O(hot-set) contract)
        self.counters: Dict[int, TenantCounters] = _LazyCounters()
        # running totals, bumped at every counter mutation site below —
        # totals() is O(1), the flight recorder calls it every tick
        self._tot = TenantCounters()
        self.backlog_spans = 0
        self.peak_backlog_spans = 0
        self._tenant_backlog: Dict[int, int] = {}
        # per-priority backlog totals: the eviction feasibility check
        # must know how much strictly-lower-priority work is queued
        # BEFORE destroying any of it
        self._priority_backlog: Dict[int, int] = {}
        # SFQ state: system virtual time + per-tenant last finish tag
        # (lazy: a tenant that never offers never gets a tag)
        self._vtime = 0.0
        self._last_finish: Dict[int, float] = {}
        self._seq = 0
        self._alive: Dict[int, QueuedBatch] = {}      # seq -> batch
        # drain heap: smallest finish tag first (seq breaks ties
        # deterministically); evict heap: lowest priority (largest
        # number) first, then latest finish tag — the work fair queuing
        # would serve last.  Both use lazy deletion against _alive.
        self._drain_heap: List[Tuple[float, int]] = []
        self._evict_heap: List[Tuple[int, float, int]] = []
        self._evict_stale = 0
        # registry mirrors (anomod.obs): cached handles — offer/drain run
        # per micro-batch on the serving hot path
        self._obs_offered = obs.counter("anomod_serve_offered_spans_total")
        self._obs_admitted = obs.counter("anomod_serve_admitted_spans_total")
        self._obs_served = obs.counter("anomod_serve_served_spans_total")
        self._obs_shed = obs.counter("anomod_serve_shed_spans_total")
        self._obs_evicted = obs.counter("anomod_serve_evicted_batches_total")
        self._obs_backlog = obs.gauge("anomod_serve_backlog_spans")
        self._obs_tenant_backlog = obs.gauge(
            "anomod_serve_max_tenant_backlog_spans")

    def observe_depths(self) -> None:
        """Set the two depth gauges from the queue as it stands.  The
        engine calls this once a tick where the registry is scraped
        (``serve.scrape``): the deepest queue is a walk over every tenant
        seen, which an offer or a drain does not pay."""
        self._obs_backlog.set(self.backlog_spans)
        self._obs_tenant_backlog.set(
            max(self._tenant_backlog.values(), default=0))

    # -- admission --------------------------------------------------------

    def offer(self, tenant_id: int, spans: SpanBatch,
              now_s: float) -> bool:
        """Admit (enqueue) or shed one tenant micro-batch.

        Returns True iff admitted.  Shedding is deterministic:
        per-tenant overflow sheds the arrival; global overflow evicts
        strictly-lower-priority queued work first and sheds the arrival
        only when none exists.
        """
        priority = self.specs.priority_of(tenant_id)
        n = spans.n_spans
        c = self.counters[tenant_id]
        c.offered_spans += n
        c.offered_batches += 1
        self._tot.offered_spans += n
        self._tot.offered_batches += 1
        self._obs_offered.inc(n)
        if n == 0:
            return False
        # both bounds refuse a batch only when queued work already exists
        # (the admission mirror of drain()'s one-batch overdraw): a batch
        # wider than a bound must still admit against an empty queue, or
        # it would be starved forever at ANY load
        backlog = self._tenant_backlog.get(tenant_id, 0)
        if backlog and backlog + n > self.max_tenant_backlog:
            c.shed_spans += n
            c.shed_batches += 1
            self._tot.shed_spans += n
            self._tot.shed_batches += 1
            self._obs_shed.inc(n)
            return False
        if self.backlog_spans and self.backlog_spans + n > self.max_backlog:
            # transactional eviction: only destroy lower-priority work if
            # enough of it exists to actually admit the arrival —
            # otherwise evicting would lose BOTH the victims and the
            # arrival (shed the arrival alone instead).  Emptying the
            # whole queue also admits (the empty-queue overdraw above),
            # so the headroom requirement caps at the current backlog.
            needed = min(self.backlog_spans + n - self.max_backlog,
                         self.backlog_spans)
            evictable = sum(v for p, v in self._priority_backlog.items()
                            if p > priority)
            if evictable < needed:
                c.shed_spans += n
                c.shed_batches += 1
                self._tot.shed_spans += n
                self._tot.shed_batches += 1
                self._obs_shed.inc(n)
                return False
        while self.backlog_spans and self.backlog_spans + n > self.max_backlog:
            victim = self._pop_eviction_candidate(priority)
            if victim is None:           # unreachable given the check above
                c.shed_spans += n
                c.shed_batches += 1
                self._tot.shed_spans += n
                self._tot.shed_batches += 1
                self._obs_shed.inc(n)
                return False
            vc = self.counters[victim.tenant_id]
            vc.shed_spans += victim.n_spans
            vc.shed_batches += 1
            vc.evicted_batches += 1
            vc.admitted_spans -= victim.n_spans
            self._tot.shed_spans += victim.n_spans
            self._tot.shed_batches += 1
            self._tot.evicted_batches += 1
            self._tot.admitted_spans -= victim.n_spans
            self._obs_shed.inc(victim.n_spans)
            self._obs_evicted.inc()
            self._remove(victim)
        start = max(self._vtime, self._last_finish.get(tenant_id, 0.0))
        finish = start + n / self.specs.weight_of(tenant_id)
        self._last_finish[tenant_id] = finish
        qb = QueuedBatch(tenant_id=tenant_id, seq=self._seq, spans=spans,
                         n_spans=n, priority=priority,
                         enqueued_s=now_s, finish_tag=finish)
        self._seq += 1
        self._alive[qb.seq] = qb
        if self._col is not None:
            self._col.add(qb)
        else:
            heapq.heappush(self._drain_heap, (qb.finish_tag, qb.seq))
            heapq.heappush(self._evict_heap,
                           (-qb.priority, -qb.finish_tag, -qb.seq))
        self.backlog_spans += n
        self._tenant_backlog[tenant_id] = backlog + n
        self._priority_backlog[priority] = \
            self._priority_backlog.get(priority, 0) + n
        self.peak_backlog_spans = max(self.peak_backlog_spans,
                                      self.backlog_spans)
        c.admitted_spans += n
        self._tot.admitted_spans += n
        self._obs_admitted.inc(n)
        return True

    def _pop_eviction_candidate(self, incoming_priority: int):
        """The queued batch a higher-priority arrival may displace:
        strictly lower priority than the arrival, lowest class first,
        latest finish tag first.  None when nothing qualifies."""
        if self._col is not None:
            seq = self._col.victim()
            if seq is None:
                return None
            qb = self._alive[seq]
            # the columnar argmax is the GLOBAL max priority number —
            # if even it is not strictly lower than the arrival,
            # nothing queued is (the lazy heap's top-check, restated)
            return qb if qb.priority > incoming_priority else None
        while self._evict_heap:
            neg_pri, neg_fin, neg_seq = self._evict_heap[0]
            qb = self._alive.get(-neg_seq)
            if qb is None:                      # already drained/evicted
                heapq.heappop(self._evict_heap)
                continue
            if -neg_pri <= incoming_priority:
                return None                     # nothing strictly lower
            heapq.heappop(self._evict_heap)
            return qb
        return None

    def _remove(self, qb: QueuedBatch) -> None:
        del self._alive[qb.seq]
        self.backlog_spans -= qb.n_spans
        self._tenant_backlog[qb.tenant_id] -= qb.n_spans
        self._priority_backlog[qb.priority] -= qb.n_spans
        if self._col is not None:
            self._col.remove(qb.seq)
            return
        # the evict heap prunes lazily only when overflow consults its
        # top; a long never-overloaded run would otherwise accumulate one
        # stale entry per drained batch forever — compact when stale
        # entries dominate (amortized O(1) per removal)
        self._evict_stale += 1
        if self._evict_stale > max(64, len(self._alive)):
            self._evict_heap = [(-q.priority, -q.finish_tag, -q.seq)
                                for q in self._alive.values()]
            heapq.heapify(self._evict_heap)
            self._evict_stale = 0

    # -- drain ------------------------------------------------------------

    def drain(self, budget_spans: float) -> List[QueuedBatch]:
        """Serve up to ``budget_spans`` in weighted-fair order.

        The budget may overdraw by at most one batch (batches are never
        split — the batcher needs them whole for replay parity), so a
        batch wider than a whole tick's budget still drains instead of
        deadlocking the queue.
        """
        if self._col is not None:
            out = []
            for seq in self._col.select(float(budget_spans)):
                qb = self._alive[seq]
                self._remove(qb)
                self._vtime = max(
                    self._vtime, qb.finish_tag - qb.n_spans
                    / self.specs.weight_of(qb.tenant_id))
                c = self.counters[qb.tenant_id]
                c.served_spans += qb.n_spans
                c.served_batches += 1
                self._tot.served_spans += qb.n_spans
                self._tot.served_batches += 1
                self._obs_served.inc(qb.n_spans)
                out.append(qb)
            return out
        out: List[QueuedBatch] = []
        remaining = float(budget_spans)
        while remaining > 0 and self._drain_heap:
            fin, seq = self._drain_heap[0]
            qb = self._alive.get(seq)
            if qb is None:                      # evicted under overload
                heapq.heappop(self._drain_heap)
                continue
            heapq.heappop(self._drain_heap)
            self._remove(qb)
            self._vtime = max(self._vtime, fin - qb.n_spans
                              / self.specs.weight_of(qb.tenant_id))
            remaining -= qb.n_spans
            c = self.counters[qb.tenant_id]
            c.served_spans += qb.n_spans
            c.served_batches += 1
            self._tot.served_spans += qb.n_spans
            self._tot.served_batches += 1
            self._obs_served.inc(qb.n_spans)
            out.append(qb)
        return out

    # -- report helpers ---------------------------------------------------

    def totals(self) -> TenantCounters:
        # O(1): the running sum, not a walk over per-tenant rows — the
        # flight recorder calls this every tick against fleets where
        # registered ≫ active
        return dataclasses.replace(self._tot)

    def per_priority(self) -> Dict[int, TenantCounters]:
        out: Dict[int, TenantCounters] = {}
        for tid, c in self.counters.items():
            pri = self.specs.priority_of(tid)
            acc = out.setdefault(pri, TenantCounters())
            for f in dataclasses.fields(TenantCounters):
                setattr(acc, f.name,
                        getattr(acc, f.name) + getattr(c, f.name))
        return out

    def tenant_backlog(self, tenant_id: int) -> int:
        """Queued spans for one tenant (0 when it never offered) — the
        demotion plane's skip-if-queued check."""
        return self._tenant_backlog.get(tenant_id, 0)

    def spec_table_nbytes(self) -> int:
        """Exact resident bytes of the registered-fleet spec table —
        the census admission plane's per-REGISTERED price."""
        return self.specs.nbytes()
