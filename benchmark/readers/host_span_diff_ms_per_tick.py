"""Mean milliseconds a tick spends inside the host span ``span`` and
outside the spans ``less``, on the profiler's clock, clipped to the
window: ``bench.tick`` less ``serve.tick`` is what the driver waits for
after ``tick`` has returned (the drain of the last scatter).  Either name
missing from the trace -> None."""

from benchmark import intervals


def read(ctx, span, less):
    ticks = ctx.get("ticks", 0)
    trace = ctx["trace"]
    outer = intervals.named(trace.host, [span], *trace.window)
    inner = intervals.named(trace.host, less, *trace.window)
    if not ticks or not outer or not inner:
        return None
    return intervals.measure(intervals.subtract(outer, inner)) / 1e6 / ticks
