"""The serve loop's spans and counts (launch/telemetry.py): spans nest
under the span that caused them, a request's queue and prefill waits
share its rid and meet, the prefill counts add up to what the engine
dispatched, the ring and the open request spans stay bounded, and the
always-on recorder changes no streamed token."""
import asyncio

import jax
import numpy as np
import pytest

from repro import configs
from repro.launch import scheduler, telemetry
from repro.launch.engine import ServeEngine
from repro.launch.frontend import AsyncFrontend
from repro.models import lm
from repro.quant.qtensor import quantize_tree_for_serving


@pytest.fixture(scope="module")
def setup():
    cfg = configs.get_reduced_config("smollm-135m")
    params = quantize_tree_for_serving(
        lm.init_params(jax.random.PRNGKey(0), cfg, max_seq=80), "w8a8")
    return cfg, params


def _engine(setup, **kw):
    cfg, params = setup
    kw.setdefault("n_slots", 4)
    kw.setdefault("max_cache_len", 64)
    kw.setdefault("segment_len", 4)
    return ServeEngine(params, cfg, chaos=None, **kw)


def _requests(cfg, lens, gen=5, rid0=0):
    rng = np.random.default_rng(rid0)
    return [scheduler.Request(rid=rid0 + i,
                              prompt=rng.integers(1, cfg.vocab, n),
                              max_new_tokens=gen)
            for i, n in enumerate(lens)]


def _since(t0, name=None):
    return [s for s in telemetry.RECORDER.spans(name) if s.start >= t0]


def test_spans_nest_under_their_cause():
    rec = telemetry.Recorder(capacity=16)
    outer = rec.span("outer")             # begun here, ended elsewhere
    with rec.span("a", n=1) as a:
        with rec.span("b") as b:
            b.count(tokens=3)
    with rec.span("empty") as e:
        e.drop()
    outer.close()
    with rec.span("after") as after:
        pass
    got = {s.name: s for s in rec.spans()}
    assert set(got) == {"outer", "a", "b", "after"}
    assert got["outer"].parent is None and after.parent is None
    assert got["a"].parent == outer.id and got["b"].parent == a.id
    assert got["a"].counts == {"n": 1} and got["b"].counts == {"tokens": 3}
    assert got["outer"].start <= got["a"].start <= got["b"].start \
        <= got["b"].end <= got["a"].end <= got["outer"].end
    assert e.end is not None


def test_ring_and_open_requests_stay_bounded(setup):
    cfg, _ = setup
    rec = telemetry.Recorder(capacity=8)
    for i in range(100):
        with rec.span(f"s{i}"):
            pass
    assert len(rec.spans()) == 8 and rec.dropped == 92
    assert [s.name for s in rec.spans()] == [f"s{i}" for i in range(92, 100)]
    reqs = _requests(cfg, [4] * 20)
    for r in reqs:
        rec.request_begin("request.queued", r)
    assert len(rec.open_requests("request.queued")) == 8
    for r in reqs:
        rec.request_done(r)
    assert rec.open_requests("request.queued") == []
    assert len(rec.spans()) == 8


def test_engine_spans_and_request_waits(setup):
    cfg, _ = setup
    eng = _engine(setup)
    reqs = _requests(cfg, [5, 9, 12, 30, 7, 20])
    t0 = telemetry.now()
    eng.run(reqs)
    spans = _since(t0)
    by_id = {s.id: s for s in spans}
    parent = {s.name: set() for s in spans}
    for s in spans:
        parent[s.name].add(by_id[s.parent].name if s.parent else None)
    assert parent["engine.admit"] == {None}
    assert parent["engine.segment"] == {None}
    assert parent["engine.prefill"] == parent["engine.scatter"] \
        == {"engine.admit"}
    assert parent["engine.sync"] == parent["engine.harvest"] \
        == {"engine.segment"}
    segs = [s for s in spans if s.name == "engine.segment"]
    assert len(segs) == eng.cache_info()["dispatch_sites"]["segment"]
    assert all(1 <= s.counts["active"] <= s.counts["bb"] <= 4 for s in segs)
    assert sum(s.counts["tokens"] for s in spans
               if s.name == "engine.harvest") \
        == eng.total_generated - len(reqs)
    assert sum(s.counts["requests"] for s in spans
               if s.name == "engine.admit") == len(reqs)
    queued = {s.rid: s for s in spans if s.name == "request.queued"}
    prefill = {s.rid: s for s in spans if s.name == "request.prefill"}
    assert set(queued) == set(prefill) == {r.rid for r in reqs}
    for rid, q in queued.items():
        p = prefill[rid]
        assert q.start <= q.end == p.start <= p.end
    assert all(start < t0 for _, start in
               telemetry.RECORDER.open_requests("request.queued"))


@pytest.mark.parametrize("kw", [
    {}, {"prefill_chunk": 8}, {"prefix_cache": 16, "prefill_chunk": 8},
    {"prefix_cache": 16}], ids=["full", "chunked", "prefix-chunked",
                                "prefix-full"])
def test_prefill_counts_match_dispatches(setup, kw):
    """Prompts 5, 9 and 12 long: buckets 8 and 16 (one of one row, one of
    two), so 26 prompt tokens in 1 x 8 + 2 x 16 = 40 rows.  A second wave
    of the same prompts, with a prefix cache, is served from the pool and
    prefills nothing."""
    cfg, _ = setup
    eng = _engine(setup, **kw)
    first = _requests(cfg, [5, 9, 12])
    t0 = telemetry.now()
    eng.run(first)
    pre = _since(t0, "engine.prefill")
    assert sum(s.counts["tokens"] for s in pre) == 26
    assert sum(s.counts["rows"] for s in pre) == 40
    assert sorted(s.counts["group"] for s in _since(t0, "engine.scatter")) \
        == [1, 2]
    if "prefix_cache" in kw:
        again = [scheduler.Request(rid=10 + r.rid, prompt=r.prompt,
                                   max_new_tokens=r.max_new_tokens)
                 for r in first]
        t1 = telemetry.now()
        eng.run(again)
        assert _since(t1, "engine.prefill") == []
        assert [r.tokens for r in again] == [r.tokens for r in first]


def _serve(eng, reqs, overlap):
    async def go():
        fe = AsyncFrontend(eng, clock=scheduler.FastForwardClock(),
                           overlap=overlap)
        toks = {}
        async with fe:
            async def one(r):
                toks[r.rid] = [t async for t in fe.generate_stream(
                    r.prompt, r.max_new_tokens, rid=r.rid)]
            await asyncio.gather(*(one(r) for r in reqs))
        return toks, fe.stats

    return asyncio.run(go())


def test_streams_identical_with_the_recorder_on(setup):
    cfg, _ = setup
    reqs = _requests(cfg, [5, 9, 12, 30, 7], gen=9)
    out = {}
    for overlap in (True, False):
        t0 = telemetry.now()
        toks, stats = _serve(_engine(setup), reqs, overlap)
        stages = _since(t0, "frontend.host_stage")
        out[overlap] = toks
        if overlap:
            assert stages
            assert stats["hidden_host_s"] == pytest.approx(
                sum(s.end - s.start for s in stages))
            segs = {s.id for s in _since(t0, "engine.segment")}
            assert {s.parent for s in stages} <= segs
            ids = {s.id for s in stages}
            assert any(s.parent in ids
                       for s in _since(t0, "frontend.publish"))
        else:
            assert stages == [] and stats["hidden_host_s"] == 0.0
        assert sum(s.counts["tokens"] for s in _since(
            t0, "frontend.publish")) == stats["streamed_tokens"]
        assert {s.rid for s in _since(t0, "request.queued")} \
            == {r.rid for r in reqs}
    assert out[True] == out[False]
    assert all(len(v) == 9 for v in out[True].values())
