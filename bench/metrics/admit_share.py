"""Engine (launch/engine.py): the share of the window the serve loop
spent admitting -- the program's `engine.admit` spans (compaction,
prefill dispatches and their first tokens, the scatter into slots),
clipped to the window, over the window.  Admission runs between decode
segments, so its time is time the decode stream waits."""
from bench.lib import spans


def read(ctx):
    rec = spans.recorder(ctx.w0)
    if rec is None:
        return None
    admits = [s for s in rec.spans("engine.admit")
              if s.end > ctx.w0 and s.start < ctx.w1]
    if not admits:
        return None
    return 100.0 * spans.clipped_s(admits, ctx.w0, ctx.w1) / (
        ctx.w1 - ctx.w0)
