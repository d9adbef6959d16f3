"""Mean milliseconds of a tick's wall that no layer's own timer covers:
the tick's wall less the named program spans and the named wall counters
(seconds).  What is left is the tick barrier: per-tenant lookups,
coalescing, latency records, the flight journal, the scrape."""


def read(ctx, spans, counters=()):
    ticks = ctx.get("ticks", 0)
    deltas = ctx.get("counters", {})
    if not ticks or not all(c in deltas for c in counters):
        return None
    covered = ctx["tracer"].seconds(tuple(spans), ctx["window_t0"])
    covered += sum(deltas[c] for c in counters)
    rest = ctx["tick_wall_s"] - covered
    return 1e3 * rest / ticks if rest > 0 else None
