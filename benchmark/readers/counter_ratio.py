"""``scale`` times the window's delta of the counters ``over`` (summed)
divided by that of the counters ``under``.  A counter missing, or nothing
counted under the line -> None."""


def read(ctx, over, under, scale=1.0):
    n = ctx.get("counters", {})
    if not all(c in n for c in list(over) + list(under)):
        return None
    below = sum(n[c] for c in under)
    return scale * sum(n[c] for c in over) / below if below > 0 else None
