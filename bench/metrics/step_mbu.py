"""Model step: bytes the algorithm needs in the window, counted by the
model family (`window_work`; for dense models the weights once per
decode step and per prefill dispatch, the live KV rows each decode token
reads, the KV rows written), at peak HBM bandwidth, over the time the
device was busy in the window (the window times the busy share of the
traced slice)."""


def read(ctx):
    if ctx.trace is None or ctx.trace.busy_s <= 0:
        return None
    w = ctx.family.window_work(ctx)
    busy = (ctx.w1 - ctx.w0) * ctx.trace.busy_s / ctx.trace.window_s
    return 100.0 * w["bytes"] / (busy * ctx.peak["hbm_bytes_s"])
