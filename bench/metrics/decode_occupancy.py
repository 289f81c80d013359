"""Engine (launch/engine.py, scheduler.py): decode tokens emitted over
the slot-steps dispatched, from the engine's counters over the window.
Decode tokens are all tokens generated less each admission's first token
(which prefill makes); slot-steps are segment dispatches x segment_len x
n_slots."""


def read(ctx):
    seg = ctx.delta["segments"]
    if seg <= 0:
        return None
    e = ctx.cfg["engine"]
    decode = ctx.delta["generated"] - ctx.delta["admits"]
    return 100.0 * decode / (seg * e["segment_len"] * e["n_slots"])
