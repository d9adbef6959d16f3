"""A kernel's share of its roofline: the least time the chip's peaks
allow for the ALGORITHM's work (``benchmark/work.py``) over the kernel's
device time in the trace.  Nothing to read (no op matches) -> None, never
0."""

from benchmark import work


def read(ctx, ops):
    kernel_s = sum(ctx["trace"].op_seconds(ops).values())
    if kernel_s <= 0 or not ctx.get("work"):
        return None
    calls_in_trace = ctx["work"]["calls"]
    least, _ = work.least_seconds(ctx["work"], ctx["peaks"])
    return 100.0 * least * calls_in_trace / kernel_s
