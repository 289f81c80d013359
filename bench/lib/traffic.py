"""Traffic from a mix file, and the arithmetic of a measured window.

A mix file (`bench/traffic/<name>.json`) holds parameters only, read by
the one generator here:

    {"loop": "open", "rate_per_s": 7.0, "order_seed": 0,
     "prompt": {"dist": "lognormal", "median": 256, "sigma": 0.8,
                "min": 16, "max": 1536},
     "output": {"dist": "lognormal", "median": 96, "sigma": 0.6,
                "min": 8, "max": 384},
     "drain_s": 60}

    {"loop": "closed", "clients_per_slot": 2, "order_seed": 0,
     "prompt": {"dist": "uniform", "min": 256, "max": 640},
     "output": {"dist": "uniform", "min": 192, "max": 320},
     "drain_s": 60}

and optionally

    "prefix": {"count": 16, "len": 1024, "zipf": 1.2}
        every prompt starts with one of `count` shared prefixes of `len`
        tokens, the k-th (from 1) chosen with weight 1 / k**zipf; `prompt`
        is then the length of the request's own tail;
    "bursts": {"period_s": 10.0, "on_s": 3.0}
        (open loop) arrivals fall only in the first `on_s` seconds of
        every period, at the same mean rate over the window.

Every seed gets the same work in the same order.  Lengths are the
distribution's quantiles at the midpoints of n equal strata, open-loop
gaps are the exponential's quantiles scaled to fill the window exactly
(Poisson in distribution, not in each run's draw), and the order of
prompts, outputs, gaps and prefix choices is drawn from the mix's
`order_seed`.  The run's seed draws the tokens only (prompts all
distinct, shared prefixes shared), so two seeds differ in what the model
computes and not in how much of it or when.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import List, Optional

import numpy as np

_NORMAL = statistics.NormalDist()


def lengths(spec: dict, n: int) -> np.ndarray:
    """n lengths at the midpoints of n equal-probability strata."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([_NORMAL.inv_cdf(x) for x in u])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        v = spec["min"] + u * (spec["max"] + 1 - spec["min"]) - 0.5
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)


@dataclasses.dataclass
class Request:
    index: int
    prompt: np.ndarray
    max_new_tokens: int
    at: Optional[float] = None      # scheduled send, s after window start


def build(mix: dict, seed: int, seconds: float, vocab: int,
          n_slots: int) -> List[Request]:
    """The run's requests, in send order.  Open loop: rate x seconds
    requests whose gaps sum to `seconds`.  Closed loop: enough requests
    for every client to send one per 2 s of window (clients take them in
    order; the rest are never sent)."""
    order = np.random.default_rng(mix["order_seed"])
    tok = np.random.default_rng(seed)
    if mix["loop"] == "open":
        n = max(1, int(round(mix["rate_per_s"] * seconds)))
    elif mix["loop"] == "closed":
        clients = mix["clients_per_slot"] * n_slots
        n = clients * (2 + int(math.ceil(seconds / 2.0)))
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    p = order.permutation(lengths(mix["prompt"], n))
    o = order.permutation(lengths(mix["output"], n))
    at = [None] * n
    if mix["loop"] == "open":
        at = arrivals(mix, n, seconds, order)
    heads = [np.zeros(0, np.int32)] * n
    if "prefix" in mix:
        pf = mix["prefix"]
        shared = tok.integers(0, vocab, (pf["count"], pf["len"]),
                              dtype=np.int32)
        w = 1.0 / np.arange(1, pf["count"] + 1) ** pf["zipf"]
        pick = order.choice(pf["count"], size=n, p=w / w.sum())
        heads = [shared[k] for k in pick]
    return [Request(i, np.concatenate(
        [heads[i], tok.integers(0, vocab, int(p[i]), dtype=np.int32)]),
        int(o[i]), at[i]) for i in range(n)]


def arrivals(mix: dict, n: int, seconds: float, order) -> List[float]:
    """Open-loop send times in [0, seconds): exponential gaps at the
    strata's midpoints, in the order's sequence, scaled to fill the time
    in which the mix sends (the whole window, or its bursts)."""
    u = (np.arange(n) + 0.5) / n
    gaps = order.permutation(-np.log1p(-u))
    b = mix.get("bursts")
    period, on = (b["period_s"], b["on_s"]) if b else (seconds, seconds)
    full, part = divmod(seconds, period)
    sending = full * on + min(on, part)
    gaps *= sending / gaps.sum()
    t = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    k, r = np.divmod(t, on)
    return (k * period + r).tolist()


def prompt_lengths(mix: dict) -> List[int]:
    """The smallest and largest prompt and every power of two between:
    one length in each prompt bucket the mix can fill."""
    head = mix["prefix"]["len"] if "prefix" in mix else 0
    lo, hi = head + mix["prompt"]["min"], head + mix["prompt"]["max"]
    out = {lo, hi}
    b = 1
    while b < hi:
        if b > lo:
            out.add(b)
        b *= 2
    return sorted(out)


# ---------------------------------------------------------------------------
# window arithmetic
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Record:
    """What a client saw of one request (host clock, seconds)."""
    scheduled: float                # when it was due to be sent
    sent: Optional[float] = None
    token_times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    want: int = 0
    prompt_len: int = 0
    error: Optional[str] = None

    @property
    def done(self) -> bool:
        return self.error is None and len(self.tokens) == self.want


def percentile(values, q: float) -> Optional[float]:
    """The q-th percentile (0-100) by linear interpolation; None if
    empty."""
    if not len(values):
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def ttft(r: Record) -> Optional[float]:
    return r.token_times[0] - r.scheduled if r.token_times else None


def tpot(r: Record) -> Optional[float]:
    n = len(r.token_times)
    if n < 2:
        return None
    return (r.token_times[-1] - r.token_times[0]) / (n - 1)


def stall(r: Record) -> Optional[float]:
    t = r.token_times
    return max(b - a for a, b in zip(t, t[1:])) if len(t) > 1 else None


def summarize(records: List[Record], w0: float, w1: float) -> dict:
    """End-to-end numbers over the requests sent in [w0, w1).  Tokens
    count where they were received inside the window; ttft, tpot and stall
    are over requests that completed, and one that did not by the end of
    the drain has failed."""
    sent = [r for r in records if r.sent is not None and w0 <= r.sent < w1]
    ok = [r for r in sent if r.done]
    toks = sum(sum(1 for t in r.token_times if w0 <= t < w1)
               for r in records)
    ms = lambda f: [1e3 * f(r) for r in ok if f(r) is not None]
    lat = [r.sent - r.scheduled for r in sent]
    return {
        "attempted": len(sent), "failed": len(sent) - len(ok),
        "tok_s": toks / (w1 - w0),
        "ttft_p95_ms": percentile(ms(ttft), 95),
        "ttft_p50_ms": percentile(ms(ttft), 50),
        "tpot_p95_ms": percentile(ms(tpot), 95),
        "tpot_p50_ms": percentile(ms(tpot), 50),
        "stall_p95_ms": percentile(ms(stall), 95),
        "stall_p50_ms": percentile(ms(stall), 50),
        "sender_late_p95_ms": percentile([1e3 * x for x in lat], 95),
        "sender_late_max_ms": 1e3 * max(lat) if lat else None,
        "window_tokens": toks,
    }
