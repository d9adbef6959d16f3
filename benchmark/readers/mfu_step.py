"""The whole step's share of the chip's peak: the model FLOPs of the
window's steps (``benchmark/model_work.py``, from the plane's counted
tokens, pairs and expert rows) over the traced window's seconds times the
peak.  Idle time counts against it: it is the share of the whole step, not
a kernel's.  No such counters (a program without the plane) -> None."""

from benchmark import model_work


def read(ctx):
    n = ctx.get("counters", {})
    window_s = ctx["trace"].window_s
    if not n.get("seq_tokens") or window_s <= 0:
        return None
    flops = model_work.step_flops(ctx["cell"]["config"], n)
    return 100.0 * flops / (window_s * ctx["peaks"]["flops_per_s"])
