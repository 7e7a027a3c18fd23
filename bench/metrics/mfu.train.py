"""Device step: GraphSAGE training FLOPs of the steps run inside the
traced window (``bench/counts.py`` on each batch's real level sizes),
over the window's length on the trace's clock and the chip's peak, in %."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not ctx["flops"] or tr["window_s"] <= 0:
        return None
    return 100.0 * ctx["flops"] / tr["window_s"] / ctx["peaks"]["flops"]
