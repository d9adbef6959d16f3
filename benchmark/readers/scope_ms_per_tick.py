"""Mean device milliseconds a tick of every device op that runs under one
of the call names ``scopes``: the ``jax.named_call`` the program runs a
part of its step under, or the name the compiler's expansion gives an op
that keeps no call's (``ragged-dot-none``).  The names are in the
``tf_op`` stat of the ops' event metadata, which
``scope_roofline.scoped_ops`` reads from the ``.xplane.pb``; loops and
calls enclose the ops of their bodies on the ops line and are left out,
so an instant counts once.

With ``complement`` the reading is the share (%) of the time of ALL the
window's non-enclosing device ops that lies under NONE of ``scopes``: what
of a step no name accounts for.  An op under two of the scopes counts
once there.

The file is read once a run and its tables walked once a scope, whatever
the number of metrics (kept in ``ctx``).  No trace file, no device plane,
no tick, no op under any of the scopes (a program without these names,
the CPU, a cell without the model) -> None."""

from benchmark import harness, trace_reduce

ALL = ""                    # every string holds it: all non-enclosing ops


def _kept(ctx):
    """What a run's metrics share: the file's bytes, each scope's op
    names, and the seconds of every op in the window."""
    return ctx.setdefault("_scope_ms_per_tick", {"names": {}})


def _names(ctx, scope):
    """Trace names of the non-enclosing device ops under ``scope``."""
    kept = _kept(ctx)
    if "xplane" not in kept:
        try:
            with open(trace_reduce.find_xplane(ctx["trace_dir"]), "rb") as f:
                kept["xplane"] = memoryview(f.read())
        except (KeyError, TypeError, FileNotFoundError, OSError):
            kept["xplane"] = None
    if kept["xplane"] is None:
        return set()
    if scope not in kept["names"]:
        kept["names"][scope] = harness.module_for(
            "readers", "scope_roofline").scoped_ops(kept["xplane"], scope)
    return kept["names"][scope]


def _seconds(ctx, names):
    kept = _kept(ctx)
    if "seconds" not in kept:
        kept["seconds"] = ctx["trace"].op_seconds()
    return sum(kept["seconds"].get(name, 0.0) for name in names)


def read(ctx, scopes, complement=False):
    if not ctx.get("ticks") or ctx.get("trace") is None:
        return None
    scoped = set().union(*(_names(ctx, s) for s in scopes))
    scoped_s = _seconds(ctx, scoped) if scoped else 0.0
    if scoped_s <= 0:
        return None
    if not complement:
        return 1e3 * scoped_s / ctx["ticks"]
    all_s = _seconds(ctx, _names(ctx, ALL))
    return 100.0 * (all_s - scoped_s) / all_s
