"""Front end (launch/frontend.py): the share of the window in which the
host's serve-loop work ran hidden under an in-flight segment, from the
front end's own counter `stats["hidden_host_s"]`."""


def read(ctx):
    return 100.0 * ctx.delta["hidden_host_s"] / (ctx.w1 - ctx.w0)
