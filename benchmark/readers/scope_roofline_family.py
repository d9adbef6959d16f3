"""``scope_roofline`` for the kernel families of any decoder family: the
least time the chip's peaks allow for a family's algorithmic FLOPs and
bytes over the window (``KERNEL_WORK[work]`` of ``benchmark/<work_module>
.py``) over the device time of every device op whose metadata names
``scope`` (``scope_roofline.scoped_ops`` reads the ``.xplane.pb``).  The
metric's file names the work module and the configuration key that only
the family has (``family_key``), so a later family brings a work module
and metric files, and no reader.  A counter of the family absent or
nothing counted, no trace file, no op under the scope, no configuration
of the family -> None."""

import importlib

from benchmark import harness, trace_reduce, work as peaks_of


def read(ctx, work, scope, work_module, family_key):
    n = ctx.get("counters", {})
    config = ctx.get("cell", {}).get("config", {})
    if family_key not in config or not ctx.get("trace_dir") \
            or ctx.get("trace") is None:
        return None
    fn, needs = importlib.import_module(
        f"benchmark.{work_module}").KERNEL_WORK[work]
    if any(not n.get(c) for c in needs):
        return None
    scoped_ops = harness.module_for("readers", "scope_roofline").scoped_ops
    try:
        with open(trace_reduce.find_xplane(ctx["trace_dir"]), "rb") as f:
            names = scoped_ops(memoryview(f.read()), scope)
    except (FileNotFoundError, OSError):
        return None
    trace = ctx["trace"]
    lo, hi = trace.window
    kernel_s = sum(min(e, hi) - max(s, lo)
                   for events in trace.devices.values()
                   for name, s, e in events
                   if name in names and e > lo and s < hi) / 1e9
    if kernel_s <= 0:
        return None
    least, _ = peaks_of.least_seconds(fn(config, n), ctx["peaks"])
    return 100.0 * least / kernel_s if least > 0 else None
