"""The work a window did, from what the clients received and the engine's
dispatch counters, counted with `counts.py`.

A request's token j (0-based) at prompt length P: token 0 comes from the
prefill of P positions (every projection at each position, the head at
the last, causal attention over the prompt); token j >= 1 from a decode
step at position P + j - 1, which attends P + j keys.  A token counts in
the window where the client received it.  Bytes: the weights once per
decode step (segment dispatches x segment_len) and once per prefill
dispatch, the K and V rows each decode token reads (P + j of them) and
writes (one), and the prompt's rows each prefill writes.
"""
from __future__ import annotations

from bench.lib import catalog, counts


def window_work(ctx) -> dict:
    m = catalog.model_block(ctx.cfg)
    fmt = ctx.fmt
    per_tok = counts.token_ops(m, fmt)
    head = counts.gemms(m)["lm_head"]
    head_ops = 2.0 * head[0] * head[1]
    head_prec = "int8" if "lm_head" in fmt["quantized"] else "bf16"
    kvb = counts.kv_bytes_per_token(m)
    ops = {"int8": 0.0, "bf16": 0.0}
    kv_bytes = 0.0
    for r in ctx.records:
        if not r.token_times:
            continue
        plen = r.prompt_len
        for j, t in enumerate(r.token_times):
            if not ctx.w0 <= t < ctx.w1:
                continue
            if j == 0:
                for k in ops:
                    ops[k] += plen * per_tok[k]
                ops[head_prec] -= (plen - 1) * head_ops
                ops["bf16"] += counts.attention_ops(
                    m, counts.prefill_context(plen))
                kv_bytes += plen * kvb
            else:
                for k in ops:
                    ops[k] += per_tok[k]
                ops["bf16"] += counts.attention_ops(m, plen + j)
                kv_bytes += (plen + j + 1) * kvb
    e = ctx.cfg["engine"]
    steps = ctx.delta["segments"] * e["segment_len"] + ctx.delta["prefills"]
    wbytes = steps * counts.weight_bytes(m, fmt)
    return {"int8_ops": ops["int8"], "bf16_ops": ops["bf16"],
            "bytes": wbytes + kv_bytes}
