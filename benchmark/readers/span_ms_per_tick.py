"""Mean milliseconds a tick spends in the named program spans, from the
benchmark's tracer."""


def read(ctx, spans):
    ticks = ctx.get("ticks", 0)
    total = ctx["tracer"].seconds(tuple(spans), ctx["window_t0"])
    if not ticks or total <= 0:
        return None
    return 1e3 * total / ticks
