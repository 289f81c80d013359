"""Model step: operations of the prefill and decode tokens done in the
window, counted from shapes by the model family (`window_work`; for
dense models lib/work.py and lib/counts.py), each precision's share
divided by that precision's peak, summed, over the time the device was
busy in the window.  The busy time is the window times the busy share of
the traced slice (lib/trace.py), so a faster step reads higher even where
the work is fixed by the offered load."""


def read(ctx):
    if ctx.trace is None or ctx.trace.busy_s <= 0:
        return None
    w = ctx.family.window_work(ctx)
    p = ctx.peak
    least = w["int8_ops"] / p["int8_ops_s"] + w["bf16_ops"] / p["bf16_flops_s"]
    busy = (ctx.w1 - ctx.w0) * ctx.trace.busy_s / ctx.trace.window_s
    return 100.0 * least / busy
