"""The readers of the program's spans (`bench/lib/spans.py` and the four
`program_span` metrics): windowing, clipping and percentiles on
synthetic spans, None where nothing was measured, and the tiny cell
traced end to end with every new metric present and in range."""
import json
import sys
import time
import types

import numpy as np
import pytest

import tiny_cell
from bench import run
from bench.lib import catalog, spans
from repro.launch import telemetry

W0, W1 = 100.0, 110.0


class _Recorder:
    """What the readers use of `telemetry.Recorder`, over given spans."""

    def __init__(self, finished, open_=(), dropped=0):
        self._spans = [telemetry.Span(i, *s) for i, s in enumerate(finished)]
        self._open = list(open_)
        self.dropped = dropped

    def spans(self, name=None):
        return [s for s in self._spans if name in (None, s.name)]

    def open_requests(self, name):
        return [(rid, t) for n, rid, t in self._open if n == name]


def _req(name, rid, start, seconds):
    return (name, start, start + seconds, None, rid, None)


def _loop(name, start, end, **counts):
    return (name, start, end, None, None, counts or None)


def _read(metric):
    cat = catalog.Catalog(run.ROOT)
    return cat.reader(metric)(types.SimpleNamespace(w0=W0, w1=W1))


@pytest.fixture
def recorder(monkeypatch):
    def use(*args, **kw):
        monkeypatch.setattr(telemetry, "RECORDER", _Recorder(*args, **kw))
    return use


def _synthetic():
    fin = [_loop("engine.admit", W0 - 0.5, W0 + 0.5),
           _loop("engine.admit", W0 + 2.0, W0 + 3.0),
           _loop("engine.admit", W1 - 0.5, W1 + 0.5),
           _loop("engine.admit", W1 + 1.0, W1 + 2.0),
           _loop("engine.prefill", W0 - 1.0, W0 - 0.5, tokens=1, rows=100),
           _loop("engine.prefill", W0 + 1.0, W0 + 1.5, tokens=26, rows=40),
           _loop("engine.prefill", W1 - 1.0, W1 + 1.0, tokens=10, rows=16),
           _loop("engine.prefill", W1, W1 + 1.0, tokens=1, rows=100)]
    for rid in range(20):               # submitted in the window
        start = W0 + 0.4 * rid
        fin.append(_req("request.queued", rid, start, 1e-3 * (rid + 1)))
        fin.append(_req("request.prefill", rid, start + 1e-3 * (rid + 1),
                        1e-2 * (rid + 1)))
    for rid, start in ((98, W0 - 0.1), (99, W1 + 0.1)):    # outside it
        fin.append(_req("request.queued", rid, start, 5.0))
        fin.append(_req("request.prefill", rid, start + 5.0, 5.0))
    return fin


def test_window_clipping_and_percentiles(recorder):
    recorder(_synthetic())
    assert _read("admit_share") == pytest.approx(100 * 2.0 / 10.0)
    assert _read("prefill_token_efficiency") == pytest.approx(
        100 * 36 / 56)
    assert _read("queue_wait_p95_ms") == pytest.approx(
        np.percentile(np.arange(1, 21), 95))
    assert _read("prefill_wait_p95_ms") == pytest.approx(
        np.percentile(10 * np.arange(1, 21), 95))


def test_a_request_still_waiting_counts_its_wait_so_far(recorder):
    now = time.perf_counter()
    rec = _Recorder([_req("request.queued", 0, now - 2.0, 0.5)],
                    open_=[("request.queued", 1, now - 1.0),
                           ("request.prefill", 0, now - 1.5)])
    got = sorted(spans.request_spans(rec, "request.queued", now=now))
    assert got == [(0, now - 2.0, pytest.approx(0.5)),
                   (1, now - 1.0, pytest.approx(1.0))]
    assert spans.request_spans(rec, "request.prefill", now=now) == [
        (0, now - 1.5, pytest.approx(1.5))]
    assert spans.p95_ms([0.5, 1.0]) == pytest.approx(975.0)
    assert spans.p95_ms([]) is None


@pytest.mark.parametrize("case", ["empty", "outside", "ring-lost-window",
                                  "no-recorder"])
def test_nothing_measured_is_none(recorder, monkeypatch, case):
    if case == "empty":
        recorder([])
    elif case == "outside":
        recorder([_loop("engine.admit", W1 + 1, W1 + 2),
                  _loop("engine.prefill", W0 - 2, W0 - 1, tokens=1,
                        rows=2),
                  _req("request.queued", 0, W0 - 1, 0.5)])
    elif case == "ring-lost-window":
        recorder([s for s in _synthetic() if s[2] > W0 + 1], dropped=7)
    else:
        monkeypatch.setitem(sys.modules, "repro.launch.telemetry", None)
    for m in ("admit_share", "prefill_token_efficiency",
              "queue_wait_p95_ms", "prefill_wait_p95_ms"):
        assert _read(m) is None, m


def test_a_ring_that_kept_the_window_is_read(recorder):
    recorder([_loop("engine.admit", W0 - 3, W0 - 2)] + _synthetic(),
             dropped=5)
    assert _read("admit_share") == pytest.approx(20.0)


def test_tiny_cell_traced_reports_the_span_metrics(tmp_path, capsys):
    base = tiny_cell.make(tmp_path)
    cat = catalog.Catalog(base)
    cell = cat.workload("tiny-cell")
    args = types.SimpleNamespace(workload="tiny-cell", seed=2 ** 33 + 11,
                                 seconds=2.0, trace=1, control=False,
                                 rates=None)
    assert run.serve_cell(args, cat, cell, cat.config(cell["config"]),
                          cat.traffic(cell["traffic"]),
                          types.SimpleNamespace(
                              platform="cpu", device_kind="TPU v5 lite",
                              memory_stats=dict), 1) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert 0 <= m["queue_wait_p95_ms"] < 2000
    assert 0 < m["prefill_wait_p95_ms"] < 2000
    assert 0 < m["admit_share"] < 100
    assert 0 < m["prefill_token_efficiency"] <= 100
    assert res["metrics"]["admit_share"]["unit"] == "%"
