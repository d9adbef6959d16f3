"""The whole step's share of the chip's peak for the hybrid decoder: the
model FLOPs of the window's steps (``benchmark/hybrid_work.py``, from the
plane's counted tokens, scan pairs, attention pairs and expert rows) over
the traced window's seconds times the peak.  Idle time counts against it:
it is the share of the whole step, not a kernel's.  A counter absent (a
program without the hybrid plane), no token counted or no configuration
of the family -> None."""

from benchmark import hybrid_work


def read(ctx):
    n = ctx.get("counters", {})
    trace = ctx.get("trace")
    config = ctx.get("cell", {}).get("config", {})
    if trace is None or trace.window_s <= 0 or not n.get("seq_tokens") \
            or any(c not in n for c in hybrid_work.STEP_COUNTERS) \
            or not n["ssm_recurrent_tokens"] + n["ssm_scan_tokens"] \
            or "hybrid_override_pattern" not in config:
        return None
    flops = hybrid_work.step_flops(config, n)
    return 100.0 * flops / (trace.window_s * ctx["peaks"]["flops_per_s"])
