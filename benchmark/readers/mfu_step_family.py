"""The whole step's share of the chip's peak for any decoder family: the
model FLOPs of the window's steps (``step_flops`` of ``benchmark/
<work_module>.py``, from the plane's counters that its ``STEP_COUNTERS``
names) over the traced window's seconds times the peak.  Idle time counts
against it: it is the share of the whole step, not a kernel's.  The
metric's file names the work module and the configuration key that only
the family has (``family_key``).  A counter absent (a program without the
family's counters), nothing counted or no configuration of the family ->
None."""

import importlib


def read(ctx, work_module, family_key):
    n = ctx.get("counters", {})
    trace = ctx.get("trace")
    config = ctx.get("cell", {}).get("config", {})
    if trace is None or trace.window_s <= 0 or family_key not in config:
        return None
    work = importlib.import_module(f"benchmark.{work_module}")
    if any(not n.get(c) for c in work.STEP_COUNTERS):
        return None
    return 100.0 * work.step_flops(config, n) \
        / (trace.window_s * ctx["peaks"]["flops_per_s"])
