"""Engine (launch/engine.py): the 95th percentile of the program's
`request.prefill` spans -- from `pop_ready` to the request's first token
registered after its group's prefill and scatter -- over the requests
submitted in the window (those whose `request.queued` span began in
it)."""
from bench.lib import spans


def read(ctx):
    rec = spans.recorder(ctx.w0)
    if rec is None:
        return None
    rids = {rid for rid, start, _ in spans.request_spans(
        rec, "request.queued") if ctx.w0 <= start < ctx.w1}
    return spans.p95_ms([sec for rid, start, sec in spans.request_spans(
        rec, "request.prefill") if rid in rids and start >= ctx.w0])
