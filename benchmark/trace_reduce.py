"""From a profiler trace (``.xplane.pb``) to the numbers the line carries.

``busy_s`` is the UNION of the device-op intervals on ONE line of each
device plane (``XLA Ops``), clipped to the traced window, averaged over
the devices that ran anything.  Summing the ops, modules and steps lines
counts every instant two or three times and reads over the window; a
trace stopped before the last dispatch drains reads 0, so the drivers
stop the trace only after the window's last result is on the host.

The window is the host annotation ``bench.window`` that the drivers open
around the measured loop; host annotations and device ops share the
profiler's clock.  On the CPU (the tests' rehearsal only) the "device" is
the PjRt CPU client's executor threads.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals: list) -> list:
    """Merged, sorted ``[start, end]`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals: list, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


@dataclass
class Trace:
    """A reduced trace.  Times are nanoseconds on the profiler's clock."""
    window: tuple                     # (start, end) of bench.window
    devices: dict                     # plane name -> [(name, start, end)]
    host: list = field(default_factory=list)   # (name, start, end) spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def _busy(self, events) -> list:
        return union(clip([[s, e] for _, s, e in events], *self.window))

    @property
    def busy_s(self) -> float:
        """Union of op intervals inside the window, mean over devices."""
        per = [sum(e - s for s, e in self._busy(ev)) / 1e9
               for ev in self.devices.values() if ev]
        return sum(per) / len(per) if per else 0.0

    def op_seconds(self, pattern: str = None) -> dict:
        """name -> seconds inside the window (all devices; ops matching
        ``pattern`` only, when given)."""
        rx = re.compile(pattern) if pattern else None
        lo, hi = self.window
        out = {}
        for events in self.devices.values():
            for name, s, e in events:
                if e <= lo or s >= hi or (rx and not rx.search(name)):
                    continue
                out[name] = out.get(name, 0.0) + (min(e, hi) - max(s, lo)) / 1e9
        return out

    def idle_gaps(self) -> dict:
        """host-span name -> idle seconds of the first device: every gap
        between busy intervals, charged to the innermost host span that
        covers its middle (``(none)`` where no span does)."""
        events = next((ev for ev in self.devices.values() if ev), [])
        busy = self._busy(events)
        lo, hi = self.window
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        out = {}
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            mid = (g0 + g1) / 2
            cover = [(e - s, name) for name, s, e in self.host
                     if s <= mid < e and name != WINDOW]
            name = min(cover)[1] if cover else "(none)"
            out[name] = out.get(name, 0.0) + (g1 - g0) / 1e9
        return out

    def breakdown(self, top: int = 10) -> dict:
        def rows(d):
            return [[short_name(k), v] for k, v in sorted(
                d.items(), key=lambda kv: -kv[1])[:top]]
        return {"device_ops": rows(self.op_seconds()),
                "idle_gaps": rows(self.idle_gaps())}


def short_name(op: str) -> str:
    """An XLA op's trace name is its whole HLO line; keep the result's
    name and say whether it is a kernel (a custom call)."""
    head = op.split(" = ")[0].lstrip("%")
    kind = " (custom-call)" if " custom-call(" in op else ""
    return (head + kind)[:80]


def reduce_xplane(path: str, host_prefixes=("bench.", "serve.")) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host, cpu_exec = {}, [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events if ev.duration_ns > 0]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                executor = line.name.startswith("tf_XLAPjRtCpuClient")
                for ev in line.events:
                    if executor and ev.duration_ns > 0:
                        cpu_exec.append((ev.name, ev.start_ns,
                                         ev.start_ns + ev.duration_ns))
                    elif ev.name.startswith(host_prefixes):
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
    if not devices and cpu_exec:
        devices["/host:CPU"] = cpu_exec
    windows = [(s, e) for name, s, e in host if name == WINDOW]
    if not windows:
        raise ValueError(f"the trace has no {WINDOW} annotation")
    return Trace(window=windows[-1], devices=devices, host=host)
