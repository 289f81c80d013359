"""The program's own spans over the window.

The serving program keeps its spans in one recorder per process
(`repro.launch.telemetry.RECORDER`, stamped with `time.perf_counter()`,
the clock of the window [w0, w1)), and it outlives the engine, so a
metric reader finds it after the run.  A program without the recorder,
a ring that no longer holds the window's start, or a window with no
span of the name gives None: nothing measured, never 0.
"""
from __future__ import annotations

import time
from typing import List, Optional, Tuple

from bench.lib.traffic import percentile


def recorder(w0: float):
    """The program's recorder, if it has one and it still holds every
    span that ended after `w0`; else None."""
    try:
        from repro.launch import telemetry
    except ImportError:
        return None
    rec = telemetry.RECORDER
    kept = rec.spans()
    if rec.dropped and (not kept or kept[0].end > w0):
        return None
    return rec


def clipped_s(spans, w0: float, w1: float) -> float:
    """Seconds of the spans inside [w0, w1)."""
    return sum(max(0.0, min(s.end, w1) - max(s.start, w0)) for s in spans)


def started_in(spans, w0: float, w1: float) -> list:
    return [s for s in spans if w0 <= s.start < w1]


def request_spans(rec, name: str, now: Optional[float] = None
                  ) -> List[Tuple[int, float, float]]:
    """(rid, start, seconds) of each per-request span `name`; one still
    open counts with its wait so far."""
    now = time.perf_counter() if now is None else now
    return [(s.rid, s.start, s.end - s.start) for s in rec.spans(name)] + \
        [(rid, start, now - start) for rid, start in rec.open_requests(name)]


def p95_ms(seconds: List[float]) -> Optional[float]:
    return percentile([1e3 * x for x in seconds], 95)
