"""The share (%) of the first device's idle seconds in the window that
lies under the host spans ``spans`` (the whole window where none are
named) and under none of the spans ``less``.

Idle is the complement, in the window, of the union of the device's op
intervals; a gap is split by OVERLAP with the spans' intervals, so one
gap that runs through three spans is charged to each for the part it
covers (``Trace.idle_gaps`` charges it whole to the span over its
middle).  Shares over disjoint sets of spans that cover the window sum
to 100.  A named list none of whose spans is in the window, or a device
never idle or never busy -> None; spans present and no overlap -> 0."""

from benchmark import intervals
from benchmark.trace_reduce import clip, union


def read(ctx, spans=None, less=()):
    trace = ctx["trace"]
    lo, hi = trace.window
    events = next((ev for ev in trace.devices.values() if ev), None)
    if not events:
        return None
    busy = union(clip([[s, e] for _, s, e in events], lo, hi))
    idle = intervals.subtract([[lo, hi]], busy)
    if not busy or not idle:
        return None
    cover = [[lo, hi]]
    if spans is not None:
        cover = intervals.named(trace.host, spans, lo, hi)
        if not cover:
            return None
    if less:
        inner = intervals.named(trace.host, less, lo, hi)
        if not inner:
            return None
        cover = intervals.subtract(cover, inner)
    return 100.0 * intervals.measure(intervals.intersect(idle, cover)) \
        / intervals.measure(idle)
