"""Tracing / profiling: the framework's own observability.

The reference instruments its SUTs with Jaeger/SkyWalking (SURVEY.md §5);
the analog for a TPU framework is (a) wall-clock span timing of pipeline
stages emitted in a Jaeger-compatible JSON shape — so this framework's own
trace can be loaded back through anomod.io.sn_traces — and (b) XLA device
profiling via jax.profiler for kernel-level inspection.

Thread-safety contract: spans may open from any thread (the prefetch
Pipeline's staging worker, ingest pool callbacks) — each thread keeps its
OWN span stack (thread-local), so parent links never cross threads and a
worker's span can never corrupt the main thread's nesting; the span list
itself is lock-protected.  A span opened on a fresh thread is a root of
the same trace (no cross-thread parent inference — wrong more often than
right, and the Jaeger shape has no way to say "maybe").

Durability contract: :meth:`Tracer.dump` publishes atomically
(same-directory tmp + ``os.replace``, the anomod.io.cache idiom), so a
run killed mid-write never leaves a truncated JSON behind a valid path.

Clock contract: every :meth:`Tracer.span` is also a
``jax.profiler.TraceAnnotation`` of the same name for the span's life, so
inside a ``jax.profiler`` session the spans land on the profiler's host
plane beside the device ops (one timeline, one clock).  With no session
the annotation is a flag test.  The class is imported once, when the
tracer is built; a tracer built where JAX is absent annotates nothing.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from pathlib import Path
from typing import List, Optional


#: the one shared no-op a seam enters when it holds no tracer
NO_SPAN = contextlib.nullcontext()


def span_of(tracer, name: str, **tags):
    """``tracer.span(name, **tags)``, or the shared no-op where the seam
    holds no tracer.  Tags are passed when the span opens and never set on
    what it yields: a :class:`Tracer` yields a :class:`Span`, the
    benchmark's tracer a list, no tracer ``None``."""
    return tracer.span(name, **tags) if tracer is not None else NO_SPAN


def _profiler_annotation():
    """``jax.profiler.TraceAnnotation``, or None where JAX is absent."""
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:
        return None
    return TraceAnnotation


class Span:
    """Handle yielded by :meth:`Tracer.span` — tag/event mutation only."""

    __slots__ = ("_rec",)

    def __init__(self, rec: dict):
        self._rec = rec

    def set_tag(self, key: str, value) -> None:
        self._rec["tags"][str(key)] = value

    def event(self, message: str, **fields) -> None:
        """Append a timestamped span log (Jaeger ``logs`` entry)."""
        self._rec["events"].append(
            {"t": time.time(), "message": str(message), **fields})


class Tracer:
    """Lightweight span tracer; dumps Jaeger-API-shaped JSON."""

    def __init__(self, service: str = "anomod"):
        self.service = service
        self._spans: List[dict] = []
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._trace_id = f"anomod-{int(time.time() * 1e6):x}"
        # thread ident -> small stable lane id, in first-span order: the
        # chrome exporter's ``tid`` — worker-thread spans (shard workers,
        # the prefetch pipeline) land on their OWN Perfetto lane instead
        # of all collapsing onto lane 0, so a sharded run's concurrency
        # structure is visually inspectable
        self._tids: dict = {}
        self._annotate = _profiler_annotation()

    def _stack(self) -> List[int]:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    @property
    def n_spans(self) -> int:
        with self._lock:
            return len(self._spans)

    @contextlib.contextmanager
    def span(self, name: str, **tags):
        stack = self._stack()
        parent = stack[-1] if stack else None
        ident = threading.get_ident()
        start = time.time()
        rec = {"name": name, "start": start, "dur": 0.0, "parent": parent,
               "tid": 0,
               "tags": {str(k): v for k, v in tags.items()}, "events": []}
        with self._lock:
            tid = self._tids.get(ident)
            if tid is None:
                tid = self._tids[ident] = len(self._tids)
            rec["tid"] = tid
            idx = len(self._spans)
            self._spans.append(rec)
        stack.append(idx)
        try:
            if self._annotate is None:
                yield Span(rec)
            else:
                with self._annotate(name):
                    yield Span(rec)
        finally:
            stack.pop()
            rec["dur"] = time.time() - start

    def to_jaeger(self) -> dict:
        """Jaeger API JSON (loadable by anomod.io.sn_traces)."""
        with self._lock:
            # copy the mutable containers too: a worker thread may still
            # be set_tag()/event()-ing an open span while we serialize
            # (each event dict is write-once at append, so list() is
            # deep enough)
            recs = [{**s, "tags": dict(s["tags"]),
                     "events": list(s["events"])} for s in self._spans]
        spans = []
        for i, s in enumerate(recs):
            refs = ([{"refType": "CHILD_OF", "traceID": self._trace_id,
                      "spanID": f"s{s['parent']:08x}"}]
                    if s["parent"] is not None else [])
            tags = [{"key": "span.kind", "value": "internal"}]
            tags.extend({"key": k, "value": str(v)}
                        for k, v in sorted(s["tags"].items()))
            logs = [{"timestamp": int(e["t"] * 1e6),
                     "fields": [{"key": k, "value": str(v)}
                                for k, v in e.items() if k != "t"]}
                    for e in s["events"]]
            spans.append({
                "traceID": self._trace_id, "spanID": f"s{i:08x}",
                "processID": "p0", "operationName": s["name"],
                "startTime": int(s["start"] * 1e6),
                "duration": int(s["dur"] * 1e6),
                "references": refs,
                "tags": tags,
                "logs": logs,
            })
        return {"data": [{"traceID": self._trace_id,
                          "processes": {"p0": {"serviceName": self.service}},
                          "spans": spans}]}

    def to_chrome(self) -> List[dict]:
        """The span list as Chrome trace-event JSON (the array form
        ``chrome://tracing`` / Perfetto load directly): one complete
        event (``"ph": "X"``) per span on the microsecond clock domain.

        The trace-event format has no parent references — nesting is
        inferred from timestamp containment per ``(pid, tid)`` lane — so
        the EXPLICIT parent index and span id ride in ``args`` alongside
        the span's tags, which is what lets :func:`spans_from_chrome`
        round-trip the exact parent links instead of re-guessing them
        from timestamps (guessing breaks on zero-duration spans)."""
        with self._lock:
            recs = [{**s, "tags": dict(s["tags"])} for s in self._spans]
        events = []
        for i, s in enumerate(recs):
            events.append({
                "name": s["name"], "ph": "X", "cat": self.service,
                "ts": int(s["start"] * 1e6),
                "dur": int(s["dur"] * 1e6),
                # one lane per recording thread: Perfetto groups
                # worker-thread spans (shard workers) instead of
                # collapsing every span onto lane 0; the shard TAGS
                # ride in args (below) so lanes group by shard in the
                # UI and survive the round trip
                "pid": 0, "tid": s.get("tid", 0),
                "args": {**{str(k): str(v)
                            for k, v in sorted(s["tags"].items())},
                         "span_id": i,
                         "parent": -1 if s["parent"] is None
                         else s["parent"]},
            })
        return events

    def _dump_json(self, path: Path, doc) -> None:
        """The one atomic-publish body behind both dump shapes (tmp +
        ``os.replace``, the anomod.io.cache idiom)."""
        path = Path(path)
        if path.parent and not path.parent.exists():
            path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_name(f"{path.name}.{os.getpid()}.tmp")
        try:
            tmp.write_text(json.dumps(doc))
            os.replace(tmp, path)
        finally:
            if tmp.exists():
                try:
                    tmp.unlink()
                except OSError:
                    pass

    def dump_chrome(self, path: Path) -> None:
        """Atomic publish of :meth:`to_chrome` (same contract as
        :meth:`dump`)."""
        self._dump_json(path, self.to_chrome())

    def dump(self, path: Path) -> None:
        """Atomic publish (tmp + ``os.replace``): a killed run never
        leaves a truncated trace behind a valid path."""
        self._dump_json(path, self.to_jaeger())


def spans_from_chrome(events: List[dict]) -> List[dict]:
    """Parse a Chrome trace-event array back into span records
    (``{"name", "start", "dur", "parent", "tags"}`` — seconds, parent
    by span index, ``None`` for roots): the round-trip contract of
    :meth:`Tracer.to_chrome`, the chrome twin of
    ``anomod.io.sn_traces.spans_from_jaeger``.  Only complete events
    (``"ph": "X"``) are spans; anything else (metadata, counters some
    other producer appended) is skipped.  Events are keyed back into
    index order by the ``args.span_id`` the exporter planted, so a
    reordered (e.g. Perfetto-sorted) file still parses losslessly."""
    spans = [e for e in events if e.get("ph") == "X"]
    spans.sort(key=lambda e: e.get("args", {}).get("span_id", 0))
    out = []
    for e in spans:
        args = dict(e.get("args", {}))
        parent = args.pop("parent", -1)
        args.pop("span_id", None)
        out.append({"name": e.get("name", ""),
                    "start": e.get("ts", 0) / 1e6,
                    "dur": e.get("dur", 0) / 1e6,
                    "parent": None if parent in (-1, None) else int(parent),
                    "tid": int(e.get("tid", 0)),
                    "tags": args})
    return out


@contextlib.contextmanager
def profile_to(log_dir: Optional[str]):
    """XLA device profiling (TensorBoard trace) when a dir is given."""
    if not log_dir:
        yield
        return
    import jax
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
