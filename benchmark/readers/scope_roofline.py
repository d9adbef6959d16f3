"""The share of its roofline of a family of kernels inside the sequence
model's step: the least time the chip's peaks allow for their algorithmic
FLOPs and bytes over the window (``benchmark/model_work.py``, ``work``)
over the device time of every device op whose metadata names ``scope``:
the name of the ``jax.named_call`` the program runs them under, or, for
an op the compiler expands into custom calls that keep no call's name
(``jax.lax.ragged_dot``), the name the expansion gives them.

That name is not in an op's trace name (that is its HLO text); it is in
the ``tf_op`` stat of the op's event metadata, which ``ProfileData`` does
not hand out.  So the metadata tables of the device planes are read here
from the ``.xplane.pb`` bytes directly (the protobuf wire format, the few
fields needed), and the times come from the reduced trace as ever.  Loops
(``while``) enclose the ops of their bodies on the ops line and are left
out, or every instant would count twice.  The driver has to hand over
``trace_dir``; no file, no op under the scope, or no counters -> None."""

from benchmark import model_work, trace_reduce, work as peaks_of

ENCLOSING = ("while", "conditional", "call")


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf):
    """``(field number, wire type, value)`` of one message: a varint's
    number, or the bytes of a length-delimited field."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        kind = tag & 7
        if kind == 0:
            val, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif kind == 1:
            val, i = buf[i:i + 8], i + 8
        elif kind == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {kind}")
        yield tag >> 3, kind, val


def _map_value(entry):
    return next((v for f, _, v in fields(entry) if f == 2), b"")


def scoped_ops(xplane: bytes, scope: str) -> set:
    """Trace names of the device ops (``/device:`` planes) whose event
    metadata carries ``scope`` in its ``tf_op`` stat and whose
    ``hlo_category`` is no enclosing one.  XSpace.planes = 1; XPlane.name
    = 2, .event_metadata = 4, .stat_metadata = 5; XEventMetadata.name = 2,
    .stats = 5; XStatMetadata.id = 1, .name = 2; XStat.metadata_id = 1,
    .str_value = 5, .ref_value = 7."""
    found = set()
    for f, _, plane in fields(xplane):
        if f != 1:
            continue
        parts = list(fields(plane))
        name = next((v for g, _, v in parts if g == 2), b"")
        if not bytes(name).startswith(b"/device:"):
            continue
        stat_name = {}
        for g, _, entry in parts:
            if g == 5:
                meta = dict((h, v) for h, _, v in fields(_map_value(entry)))
                stat_name[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        for g, _, entry in parts:
            if g != 4:
                continue
            op_name, stats = "", {}
            for h, _, v in fields(_map_value(entry)):
                if h == 2:
                    op_name = bytes(v).decode(errors="replace")
                elif h == 5:
                    st = dict((k, x) for k, _, x in fields(v))
                    text = st.get(5)
                    if text is None and 7 in st:
                        text = stat_name.get(st[7], "").encode()
                    stats[stat_name.get(st.get(1), "")] = \
                        bytes(text or b"").decode(errors="replace")
            if scope in stats.get("tf_op", "") \
                    and stats.get("hlo_category") not in ENCLOSING:
                found.add(op_name)
    return found


def read(ctx, work, scope):
    n = ctx.get("counters", {})
    if not n.get("seq_tokens") or not ctx.get("trace_dir"):
        return None
    try:
        with open(trace_reduce.find_xplane(ctx["trace_dir"]), "rb") as f:
            names = scoped_ops(memoryview(f.read()), scope)
    except FileNotFoundError:
        return None
    trace = ctx["trace"]
    lo, hi = trace.window
    kernel_s = sum(min(e, hi) - max(s, lo)
                   for events in trace.devices.values()
                   for name, s, e in events
                   if name in names and e > lo and s < hi) / 1e9
    if kernel_s <= 0:
        return None
    least, _ = peaks_of.least_seconds(
        model_work.KERNEL_WORK[work](ctx["cell"]["config"], n), ctx["peaks"])
    return 100.0 * least / kernel_s
