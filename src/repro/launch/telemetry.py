"""Spans and counts of the serve loop, kept where the work happens.

One recorder, two sinks.  Every serve-loop span (`span`) opens a
`jax.profiler.TraceAnnotation` of the same name -- next to nothing while
the profiler is off, and while it is on a host event in the `.xplane.pb`
on the device ops' clock, so a device idle gap can be named by the
serve-loop work around it -- and, when it ends, appends one `Span` to a
bounded in-memory ring stamped with `time.perf_counter()`: name, start,
end, the enclosing span on the same thread, and small integer counts
taken at the same boundary (tokens, group size, ...).

Per-request spans (`request_begin` / `request_end`) carry the request's
rid and go to the ring only: they begin on the client's side, many
overlap one another, and on the trace's host line they would name idle
gaps by a wait instead of by the work that ran in it.

The recorder is always on, and a serving process has one, `RECORDER`,
at module level, so that an operator or a metric reader in the same
process can read the spans after the engine is gone.  The ring keeps the
newest `CAPACITY` spans; `dropped` counts what it let go.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

from jax.profiler import TraceAnnotation

#: spans the ring keeps (the newest); also the bound on requests with
#: spans open
CAPACITY = 1 << 15

now = time.perf_counter


class Span(NamedTuple):
    """One finished span: `start`/`end` on `time.perf_counter()`,
    `parent` the id of the span it ran inside on the same thread,
    `rid` the request's id for per-request spans."""
    id: int
    name: str
    start: float
    end: float
    parent: Optional[int]
    rid: Optional[int]
    counts: Optional[dict]


class Open:
    """A serve-loop span begun and not yet ended.  A context manager that
    ends it; or end it with `close()` where it crosses a function
    boundary.  `end` is set once it has ended."""
    __slots__ = ("_rec", "_ann", "id", "name", "start", "end", "parent",
                 "counts", "keep")

    def __init__(self, rec: "Recorder", name: str, counts: dict):
        self._rec = rec
        self.id = next(rec._ids)
        self.name = name
        self.counts = counts or None
        self.keep = True
        self.end = None
        stack = rec._stack()
        self.parent = stack[-1] if stack else None
        stack.append(self.id)
        self._ann = TraceAnnotation(name)
        self._ann.__enter__()
        self.start = now()

    def count(self, **counts) -> None:
        """Counts known only at the end of the work."""
        self.counts = dict(self.counts or (), **counts)

    def drop(self) -> None:
        """Keep this span out of the ring (its trace event stays): for a
        call that turned out to do nothing."""
        self.keep = False

    def close(self) -> None:
        if self.end is not None:
            return
        self.end = now()
        self._ann.__exit__(None, None, None)
        stack = self._rec._stack()
        if stack and stack[-1] == self.id:
            stack.pop()
        elif self.id in stack:
            stack.remove(self.id)
        if self.keep:
            self._rec._append(Span(self.id, self.name, self.start,
                                   self.end, self.parent, None,
                                   self.counts))

    def __enter__(self) -> "Open":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class Recorder:
    """The bounded ring of finished spans, and the open request spans."""

    def __init__(self, capacity: int = CAPACITY):
        self.capacity = capacity
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        self._ids = itertools.count(1)
        self._local = threading.local()
        # id(request) -> {name: (rid, start)}: begun, not yet ended
        self._requests: Dict[int, Dict[str, Tuple[int, float]]] = {}
        self._appended = 0

    def _append(self, s: Span) -> None:
        self._ring.append(s)
        self._appended += 1

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def span(self, name: str, **counts) -> Open:
        """Begin a serve-loop span now (`with recorder.span(...)`)."""
        return Open(self, name, counts)

    def request_begin(self, name: str, req, t: Optional[float] = None
                      ) -> None:
        """Begin request `req`'s span `name` at `t` (default now); a span
        of that name still open for it is begun again."""
        key = id(req)
        if key not in self._requests and \
                len(self._requests) >= self.capacity:
            self._requests.pop(next(iter(self._requests)))
        self._requests.setdefault(key, {})[name] = (
            req.rid, now() if t is None else t)

    def request_end(self, name: str, req, t: Optional[float] = None
                    ) -> None:
        """End request `req`'s open span `name` at `t` (default now);
        nothing happens if it has none open."""
        spans = self._requests.get(id(req))
        begun = spans.pop(name, None) if spans else None
        if begun is None:
            return
        if not spans:
            del self._requests[id(req)]
        rid, start = begun
        self._append(Span(next(self._ids), name, start,
                          now() if t is None else t, None, rid, None))

    def request_done(self, req, t: Optional[float] = None) -> None:
        """A request has finished: end every span it still has open."""
        for name in list(self._requests.get(id(req), ())):
            self.request_end(name, req, t)

    def spans(self, name: Optional[str] = None) -> List[Span]:
        """The spans in the ring, oldest first; of one name if given."""
        out = list(self._ring)
        return out if name is None else [s for s in out if s.name == name]

    def open_requests(self, name: str) -> List[Tuple[int, float]]:
        """(rid, start) of each request whose span `name` is still open."""
        return [spans[name] for spans in list(self._requests.values())
                if name in spans]

    @property
    def dropped(self) -> int:
        """Spans the ring has let go to stay within its capacity."""
        return self._appended - len(self._ring)


RECORDER = Recorder()
span = RECORDER.span
request_begin = RECORDER.request_begin
request_end = RECORDER.request_end
request_done = RECORDER.request_done
