"""Mean milliseconds a tick spends inside the program span ``span`` and
under none of the spans ``less`` (its named leaves): the span's self time
by the union of the leaves, so nested or adjacent leaves count once.  From
the benchmark's tracer.  No ``span`` in the window -> None."""

from benchmark import intervals


def read(ctx, span, less):
    ticks = ctx.get("ticks", 0)
    events = [(name, s, e) for name, s, e in ctx["tracer"].spans
              if s >= ctx["window_t0"]]
    outer = intervals.named(events, [span])
    if not ticks or not outer:
        return None
    inner = intervals.named(events, less)
    return 1e3 * intervals.measure(intervals.subtract(outer, inner)) / ticks
