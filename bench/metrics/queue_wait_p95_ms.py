"""Engine (launch/engine.py, scheduler.py): the 95th percentile of the
program's `request.queued` spans -- from a request's submit to the
engine's `pop_ready` -- over the requests submitted in the window.  A
request still queued when read counts with its wait so far.  With
`prefill_wait_p95_ms` it splits `ttft_p95_ms.engine`."""
from bench.lib import spans


def read(ctx):
    rec = spans.recorder(ctx.w0)
    if rec is None:
        return None
    return spans.p95_ms([sec for _, start, sec in spans.request_spans(
        rec, "request.queued") if ctx.w0 <= start < ctx.w1])
