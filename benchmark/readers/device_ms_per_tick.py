"""Mean device milliseconds a tick spends in the ops whose trace names
match ``ops`` (a regular expression)."""


def read(ctx, ops):
    ticks = ctx.get("ticks", 0)
    total = sum(ctx["trace"].op_seconds(ops).values())
    if not ticks or total <= 0:
        return None
    return 1e3 * total / ticks
