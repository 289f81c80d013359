"""Engine (launch/engine.py, scheduler.py): the 95th percentile, over the
requests sent in the window, of each request's longest gap between
consecutive tokens, on the clients' clock: a segment and the prefills
the engine runs between segments.  It is the open-loop cell's worst
stutter, read per layer: a pause of the host lifts the p95 of some 80
requests too far for an end-to-end bound."""


def read(ctx):
    return ctx.summary["stall_p95_ms"]
