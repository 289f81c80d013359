"""Continuous-batching serve engine: bucket rounding, slot
admission/eviction invariants, and bit-exact determinism against the
static `serve.generate()` path (with and without SILVIA passes)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.launch import scheduler, serve
from repro.launch.engine import ServeEngine
from repro.models import lm
from repro.quant.qtensor import quantize_tree_for_serving


@pytest.fixture(scope="module")
def setup():
    cfg = configs.get_reduced_config("smollm-135m")
    params = quantize_tree_for_serving(
        lm.init_params(jax.random.PRNGKey(0), cfg, max_seq=80), "w8a8")
    return cfg, params


def _prompts(cfg, n, s, seed=0):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n, s),
                                         0, cfg.vocab))


# ---------------------------------------------------------------------------
# scheduler: buckets + queue
# ---------------------------------------------------------------------------

def test_bucket_pow2_rounding():
    assert scheduler.bucket_pow2(1) == 1
    assert scheduler.bucket_pow2(3) == 4
    assert scheduler.bucket_pow2(4) == 4
    assert scheduler.bucket_pow2(5, minimum=2) == 8
    assert scheduler.bucket_pow2(3, minimum=8) == 8
    # maximum is an inclusive cap, not necessarily a power of two
    assert scheduler.bucket_pow2(5, minimum=1, maximum=6) == 6
    with pytest.raises(ValueError):
        scheduler.bucket_pow2(7, maximum=6)
    with pytest.raises(ValueError):
        scheduler.bucket_pow2(-1)


def test_bucket_set_covers_range():
    assert scheduler.bucket_set(1, 8) == (1, 2, 4, 8)
    assert scheduler.bucket_set(32, 96) == (32, 64, 96)
    # every admissible size rounds into the set
    for n in range(1, 97):
        assert scheduler.bucket_pow2(n, minimum=32, maximum=96) in \
            scheduler.bucket_set(32, 96)


def test_queue_arrival_gating():
    reqs = [scheduler.Request(rid=i, prompt=[1, 2], max_new_tokens=2,
                              arrival_time=t)
            for i, t in enumerate([0.5, 0.0, 2.0])]
    q = scheduler.RequestQueue(reqs)
    assert [r.rid for r in q.pop_ready(0.0, limit=5)] == [1]
    assert q.next_arrival(0.0) == 0.5
    assert [r.rid for r in q.pop_ready(1.0, limit=5)] == [0]
    assert [r.rid for r in q.pop_ready(1.0, limit=5)] == []
    assert q.next_arrival(1.0) == 2.0
    assert [r.rid for r in q.pop_ready(2.5, limit=5)] == [2]
    assert q.next_arrival(2.5) is None and len(q) == 0


# ---------------------------------------------------------------------------
# determinism vs the static path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("silvia_passes", ["off", "all"])
def test_engine_matches_static_generate(setup, silvia_passes):
    """3 requests on 2 slots (forces eviction + re-admission) must produce
    bit-identical greedy tokens to one static generate() batch."""
    cfg, params = setup
    prompts = _prompts(cfg, 3, 12)
    static = np.asarray(serve.generate(
        params, jnp.asarray(prompts), cfg, gen=8, cache_len=32,
        silvia_passes=silvia_passes))
    reqs = [scheduler.Request(rid=i, prompt=prompts[i], max_new_tokens=8)
            for i in range(3)]
    eng = ServeEngine(params, cfg, n_slots=2, max_cache_len=64,
                      segment_len=4, silvia_passes=silvia_passes)
    out = eng.run(reqs)
    for i in range(3):
        np.testing.assert_array_equal(out[i], static[i])


def test_engine_mixed_lengths_match_per_request_static(setup):
    """Ragged prompt/gen mix: every request's tokens must equal a dedicated
    static run of just that request."""
    cfg, params = setup
    plens, gens = (5, 12, 9, 16), (3, 8, 1, 6)
    prompts = [_prompts(cfg, 1, s, seed=10 + i)[0]
               for i, s in enumerate(plens)]
    reqs = [scheduler.Request(rid=i, prompt=prompts[i], max_new_tokens=g)
            for i, g in enumerate(gens)]
    eng = ServeEngine(params, cfg, n_slots=2, max_cache_len=64,
                      segment_len=4)
    out = eng.run(reqs)
    for i, g in enumerate(gens):
        static = np.asarray(serve.generate(
            params, jnp.asarray(prompts[i][None]), cfg, gen=g,
            cache_len=plens[i] + g))[0]
        np.testing.assert_array_equal(out[i], static)


def test_engine_matches_static_across_bucket_boundary(setup):
    """Regression: a still-active slot whose segment ends exactly on a
    cache-length bucket boundary (pos+segment_len == t_b) must keep
    advancing its position; an earlier clamp to t_b-1 made the next
    segment overwrite the last KV position and diverge from static."""
    cfg, params = setup
    prompts = _prompts(cfg, 1, 48, seed=5)
    static = np.asarray(serve.generate(
        params, jnp.asarray(prompts), cfg, gen=32, cache_len=80))
    eng = ServeEngine(params, cfg, n_slots=1, max_cache_len=128,
                      segment_len=16, min_len_bucket=32)
    out = eng.run([scheduler.Request(rid=0, prompt=prompts[0],
                                     max_new_tokens=32)])
    np.testing.assert_array_equal(out[0], static[0])


def test_chunked_prefill_matches_full(setup):
    """prefill_chunk pushes prompts through the decode path; tokens must
    still match the full-prefill engine (and hence the static path)."""
    cfg, params = setup
    prompts = _prompts(cfg, 3, 12, seed=3)
    reqs = lambda: [scheduler.Request(rid=i, prompt=prompts[i],
                                      max_new_tokens=6) for i in range(3)]
    full = ServeEngine(params, cfg, n_slots=2, max_cache_len=64,
                       segment_len=4).run(reqs())
    chunked = ServeEngine(params, cfg, n_slots=2, max_cache_len=64,
                          segment_len=4, prefill_chunk=4).run(reqs())
    for i in range(3):
        np.testing.assert_array_equal(chunked[i], full[i])


# ---------------------------------------------------------------------------
# slot admission / eviction invariants
# ---------------------------------------------------------------------------

def test_slot_admission_eviction_invariants(setup):
    cfg, params = setup
    gens = (2, 5, 1, 7, 3)
    reqs = [scheduler.Request(rid=i, prompt=_prompts(cfg, 1, 6, seed=i)[0],
                              max_new_tokens=g, arrival_time=0.0)
            for i, g in enumerate(gens)]
    eng = ServeEngine(params, cfg, n_slots=2, max_cache_len=32,
                      segment_len=2, min_len_bucket=16)
    for r in reqs:
        eng.submit(r)
    clock = scheduler.FastForwardClock()
    for _ in range(64):
        progressed = eng.step(clock)
        # invariant: active flags and slot assignments agree, 1:1
        live = [r for r in eng._slot_req if r is not None]
        assert len(live) == eng.n_active == int(np.sum(eng._active))
        assert len({id(r) for r in live}) == len(live)
        for slot in range(eng.n_slots):
            if eng._active[slot]:
                assert eng._slot_req[slot] is not None
                assert 0 < eng._pos[slot] <= eng.max_cache_len
                assert eng._remaining[slot] > 0
            else:
                assert eng._slot_req[slot] is None
                assert eng._remaining[slot] == 0
        assert eng.n_active <= eng.n_slots
        if not progressed and not eng.n_queued and not eng.n_active:
            break
    assert len(eng.finished) == len(reqs)
    for r in eng.finished:
        assert len(r.tokens) == r.max_new_tokens
        assert r.finish_time is not None and r.first_token_time is not None
    # slots were reused: 5 requests through 2 slots
    occ = eng.cache_info()["occupancy"]
    assert 0 < occ["active_slot_segments"] <= occ["slot_segments"]


def test_engine_rejects_oversized_and_unregistered_family(setup):
    import dataclasses

    cfg, params = setup
    eng = ServeEngine(params, cfg, n_slots=2, max_cache_len=32)
    with pytest.raises(ValueError):
        eng.submit(scheduler.Request(rid=0, prompt=np.zeros(30, np.int32),
                                     max_new_tokens=8))
    # a family with no registered slot-state impl fails with guidance
    # pointing at the registry, not a frozen family tuple
    alien = dataclasses.replace(cfg, family="rwkv")
    with pytest.raises(ValueError, match="slot_state.register"):
        ServeEngine(params, alien)
    # ssm IS served now, but its state is not prefill-chunkable
    ssm_cfg = configs.get_reduced_config("mamba2-2.7b")
    with pytest.raises(ValueError, match="chunkable"):
        ServeEngine(params, ssm_cfg, prefill_chunk=4)
    # features are encdec-only; encdec engines require enc_len
    with pytest.raises(ValueError, match="encdec"):
        eng.submit(scheduler.Request(rid=1, prompt=np.zeros(4, np.int32),
                                     max_new_tokens=2,
                                     features=np.zeros((4, cfg.d_model))))
    with pytest.raises(ValueError, match="enc_len"):
        ServeEngine(params, configs.get_reduced_config("whisper-small"))


def test_warmup_bounds_compiled_graphs(setup):
    """After warmup over the advertised traffic profile, serving that
    traffic must not add new graphs, and the census stays within the
    bucket-set bound."""
    cfg, params = setup
    plens, gens = (4, 8, 12), (2, 4, 8)
    eng = ServeEngine(params, cfg, n_slots=2, max_cache_len=64,
                      segment_len=4)
    eng.warmup(prompt_lens=plens)
    warmed = set(eng._graphs)
    assert len(warmed) <= eng.graph_bound()
    reqs = scheduler.synthetic_traffic(seed=1, n_requests=6, rate=100.0,
                                       prompt_lens=plens, gen_lens=gens,
                                       vocab=cfg.vocab)
    eng.run(reqs)
    assert eng._graphs == warmed, "traffic compiled outside the warmed grid"
    info = eng.cache_info()
    assert info["graphs"] <= info["graph_bound"]


# ---------------------------------------------------------------------------
# serve.py decode-bundle LRU
# ---------------------------------------------------------------------------

def test_lru_cache_bound_and_counters():
    c = serve.LRUCache(maxsize=2)
    built = []
    mk = lambda k: lambda: built.append(k) or k.upper()
    assert c.get_or_build("a", mk("a")) == "A"
    assert c.get_or_build("b", mk("b")) == "B"
    assert c.get_or_build("a", mk("a")) == "A"     # hit refreshes recency
    assert c.get_or_build("c", mk("c")) == "C"     # evicts b (LRU)
    assert c.get_or_build("b", mk("b")) == "B"     # rebuild after eviction
    assert built == ["a", "b", "c", "b"]
    info = c.info()
    assert info == {"hits": 1, "misses": 4, "evictions": 2, "size": 2,
                    "maxsize": 2}
    c.clear()
    assert c.info()["size"] == 0 and c.info()["misses"] == 0


def test_decode_cache_info_tracks_generate(setup):
    cfg, params = setup
    before = serve.decode_cache_info()
    prompts = jnp.asarray(_prompts(cfg, 2, 8))
    serve.generate(params, prompts, cfg, gen=2, cache_len=16)
    serve.generate(params, prompts, cfg, gen=2, cache_len=16)
    after = serve.decode_cache_info()
    assert after["hits"] > before["hits"]          # second call reuses bundle
    assert after["size"] <= after["maxsize"]
