"""Engine (launch/engine.py, scheduler.py): the 95th percentile, over the
requests sent in the window, of the first token's arrival less the
request's scheduled send, on the clients' clock: queue wait, admission
and prefill.  It is the open-loop cell's time to first token, read per
layer: a pause of the host lifts the p95 of some 80 requests too far for
an end-to-end bound."""


def read(ctx):
    return ctx.summary["ttft_p95_ms"]
