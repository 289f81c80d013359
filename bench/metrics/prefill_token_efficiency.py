"""Engine (launch/engine.py): prompt tokens over the token rows the
prefill dispatches computed (batch bucket x prompt bucket, or the chunks
run), summed over the program's `engine.prefill` spans that began in the
window.  What is left is padding: prompts rounded up to a power-of-two
bucket, groups padded to a power-of-two batch (ROADMAP A8)."""
from bench.lib import spans


def read(ctx):
    rec = spans.recorder(ctx.w0)
    if rec is None:
        return None
    pre = spans.started_in(rec.spans("engine.prefill"), ctx.w0, ctx.w1)
    rows = sum(s.counts["rows"] for s in pre)
    if not rows:
        return None
    return 100.0 * sum(s.counts["tokens"] for s in pre) / rows
