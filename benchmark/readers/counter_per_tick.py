"""Mean per tick of the window's delta of program counters (summed),
times ``scale`` (1e3 turns seconds into ms)."""


def read(ctx, counters, scale=1.0):
    ticks = ctx.get("ticks", 0)
    deltas = ctx.get("counters", {})
    if not ticks or not all(c in deltas for c in counters):
        return None
    total = sum(deltas[c] for c in counters)
    return scale * total / ticks if total > 0 else None
