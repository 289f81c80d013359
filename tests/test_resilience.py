"""Serving resilience: admission control (shedding, deadlines, duplicate
rids), chaos-injected fault recovery with BIT-EXACT replay for every
family (incl. SILVIA passes and the sharded mesh path), non-finite-logit
quarantine with slot scrubbing, drain, and snapshot/restore -- plus the
RestartPolicy backoff and ChaosSchedule parsing units.

The recovery contract under test is DESIGN.md sec. 8: any dispatch may
fail at any site, and every surviving request's token stream must equal
the fault-free run's bitwise (`replay_divergence == 0` is the engine's
own self-check of the same obligation)."""
import numpy as np
import jax
import pytest

from repro import configs
from repro.distributed import context as dctx
from repro.distributed.fault import RestartPolicy, SimulatedFailure
from repro.launch import resilience as res
from repro.launch import scheduler
from repro.launch.engine import ServeEngine
from repro.launch.mesh import make_mesh
from repro.models import lm

FAMILY_ARCHS = {"dense": "smollm-135m", "ssm": "mamba2-2.7b",
                "hybrid": "jamba-v0.1-52b", "encdec": "whisper-small"}
ENC_LEN = 16


@pytest.fixture(scope="module")
def family_setup():
    out = {}
    for fam, arch in FAMILY_ARCHS.items():
        cfg = configs.get_reduced_config(arch)
        params = lm.init_params(jax.random.PRNGKey(0), cfg, max_seq=80)
        out[fam] = (cfg, params)
    return out


def _requests(cfg, n=6, seed=0, stagger=0.02, gens=None, ttls=None):
    plens = (5, 12, 9, 16, 7, 11, 6, 14)[:n]
    gens = gens or (8, 6, 9, 5, 10, 7, 8, 6)[:n]
    reqs = []
    for i, (pl, g) in enumerate(zip(plens, gens)):
        prompt = np.asarray(jax.random.randint(
            jax.random.PRNGKey(seed + 10 * i), (pl,), 0, cfg.vocab))
        kw = {}
        if cfg.family == "encdec":
            rng = np.random.default_rng(seed + i)
            kw["features"] = rng.standard_normal(
                (ENC_LEN, cfg.d_model)).astype(np.float32)
        if ttls is not None and ttls[i % len(ttls)] is not None:
            kw["deadline"] = stagger * i + ttls[i % len(ttls)]
        reqs.append(scheduler.Request(rid=i, prompt=prompt,
                                      max_new_tokens=g,
                                      arrival_time=stagger * i, **kw))
    return reqs


def _engine(cfg, params, **kw):
    if cfg.family == "encdec":
        kw.setdefault("enc_len", ENC_LEN)
    kw.setdefault("n_slots", 4)
    kw.setdefault("max_cache_len", 64)
    kw.setdefault("segment_len", 4)
    return ServeEngine(params, cfg, **kw)


def _assert_bit_exact(ref, out):
    assert set(ref) == set(out)
    for rid in ref:
        np.testing.assert_array_equal(ref[rid], out[rid])


# ---------------------------------------------------------------------------
# chaos recovery: bit-exact surviving streams
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", sorted(FAMILY_ARCHS))
def test_chaos_recovery_bit_exact(family_setup, family):
    """Faults at segment AND prefill sites mid-traffic: every stream must
    match the fault-free run bitwise, and the engine's own replay check
    must agree (zero divergence)."""
    cfg, params = family_setup[family]
    ref = _engine(cfg, params, chaos=None).run(
        _requests(cfg, stagger=0.0), clock=scheduler.FastForwardClock())
    chaos = res.ChaosSchedule(
        fail_at_sites=("prefill:0", "segment:2", "segment:5"))
    eng = _engine(cfg, params, chaos=chaos)
    out = eng.run(_requests(cfg, stagger=0.0),
                  clock=scheduler.FastForwardClock())
    rb = eng.cache_info()["robustness"]
    # prefill:0 and segment:2 always occur; segment:5 only if recovery
    # stretches the run that far (dispatch counts are pace-dependent)
    assert rb["faults_injected"] >= 2
    assert rb["recoveries"] == rb["faults_injected"]
    assert rb["replay_divergence"] == 0
    assert rb["replayed_tokens"] > 0
    assert all(r.outcome == res.OK for r in eng.finished)
    _assert_bit_exact(ref, out)


def test_chaos_recovery_bit_exact_silvia_all(family_setup):
    cfg, params = family_setup["dense"]
    ref = _engine(cfg, params, silvia_passes="all", chaos=None).run(
        _requests(cfg, stagger=0.0), clock=scheduler.FastForwardClock())
    chaos = res.ChaosSchedule(fail_at_sites=("segment:1", "segment:4"))
    eng = _engine(cfg, params, silvia_passes="all", chaos=chaos)
    out = eng.run(_requests(cfg, stagger=0.0),
                  clock=scheduler.FastForwardClock())
    assert eng.cache_info()["robustness"]["replay_divergence"] == 0
    _assert_bit_exact(ref, out)


def test_chaos_recovery_bit_exact_chunked_prefill(family_setup):
    """Chunk-site faults (chunked prefill dispatches) recover too."""
    cfg, params = family_setup["dense"]
    ref = _engine(cfg, params, prefill_chunk=4, chaos=None).run(
        _requests(cfg, stagger=0.0), clock=scheduler.FastForwardClock())
    chaos = res.ChaosSchedule(fail_at_sites=("chunk:1", "segment:3"))
    eng = _engine(cfg, params, prefill_chunk=4, chaos=chaos)
    out = eng.run(_requests(cfg, stagger=0.0),
                  clock=scheduler.FastForwardClock())
    rb = eng.cache_info()["robustness"]
    assert "chunk:1" in chaos.failed       # the chunk-site fault fired
    assert rb["replay_divergence"] == 0
    _assert_bit_exact(ref, out)


def test_chaos_rate_schedule_bit_exact(family_setup):
    """Deterministic seeded-rate chaos (the $REPRO_CHAOS form CI uses):
    whatever fires, surviving streams stay bit-identical."""
    cfg, params = family_setup["dense"]
    ref = _engine(cfg, params, chaos=None).run(
        _requests(cfg, stagger=0.0), clock=scheduler.FastForwardClock())
    chaos = res.ChaosSchedule(rate=0.5, seed=7, max_failures=4)
    eng = _engine(cfg, params, chaos=chaos)
    out = eng.run(_requests(cfg, stagger=0.0),
                  clock=scheduler.FastForwardClock())
    rb = eng.cache_info()["robustness"]
    assert rb["faults_injected"] >= 1      # rate=0.5 over >=8 sites
    assert rb["replay_divergence"] == 0
    _assert_bit_exact(ref, out)


@pytest.mark.skipif(
    jax.device_count() < 2,
    reason="sharded chaos needs >1 device (XLA_FLAGS="
           "--xla_force_host_platform_device_count=8)")
def test_sharded_chaos_recovery_bit_exact(family_setup):
    """Faults under the shard_map'd engine on a (data, model) mesh: the
    rebuilt sharded state must replay to the single-device streams."""
    cfg, params = family_setup["dense"]
    ref = _engine(cfg, params, chaos=None).run(
        _requests(cfg, stagger=0.0), clock=scheduler.FastForwardClock())
    dp = min(2, jax.device_count())
    mesh = make_mesh((dp, 1), ("data", "model"))
    chaos = res.ChaosSchedule(fail_at_sites=("segment:2", "prefill:1"))
    with dctx.mesh_scope(mesh, ("data",), "model"):
        eng = _engine(cfg, params, chaos=chaos)
    out = eng.run(_requests(cfg, stagger=0.0),
                  clock=scheduler.FastForwardClock())
    rb = eng.cache_info()["robustness"]
    assert rb["faults_injected"] == 2 and rb["replay_divergence"] == 0
    _assert_bit_exact(ref, out)


def test_recovery_budget_exhaustion(family_setup):
    """A request that keeps riding recoveries past max_recoveries ends
    FAILED (structured), never crashes, and the engine still finishes."""
    cfg, params = family_setup["dense"]
    chaos = res.ChaosSchedule(fail_at_sites=tuple(
        f"segment:{i}" for i in range(8)))
    eng = _engine(cfg, params,
                  resilience=res.ResilienceConfig(max_recoveries=1),
                  chaos=chaos)
    eng.run(_requests(cfg, n=2, stagger=0.0, gens=(12, 12)),
            clock=scheduler.FastForwardClock())
    outcomes = {r.rid: r.outcome for r in eng.finished}
    assert res.FAILED in outcomes.values()
    failed = [r for r in eng.finished if r.outcome == res.FAILED]
    assert all("recovery budget" in r.error for r in failed)
    assert all(r.retries > 1 for r in failed)


# ---------------------------------------------------------------------------
# admission control: duplicates, shedding, deadlines
# ---------------------------------------------------------------------------

def test_duplicate_rid_rejected(family_setup):
    cfg, params = family_setup["dense"]
    eng = _engine(cfg, params, chaos=None)
    reqs = _requests(cfg, n=2)
    assert eng.submit(reqs[0]) == res.QUEUED
    dup = scheduler.Request(rid=reqs[0].rid, prompt=[1, 2, 3],
                            max_new_tokens=2)
    with pytest.raises(ValueError, match="duplicate request id"):
        eng.submit(dup)
    assert eng.cache_info()["robustness"]["duplicate_rejects"] == 1
    # the original queued request is untouched
    assert eng.n_queued == 1


def test_shed_reject_new(family_setup):
    cfg, params = family_setup["dense"]
    eng = _engine(cfg, params, chaos=None,
                  resilience=res.ResilienceConfig(max_queue=2))
    reqs = _requests(cfg, n=4, stagger=0.0)
    outcomes = [eng.submit(r) for r in reqs]
    assert outcomes == [res.QUEUED, res.QUEUED, res.SHED, res.SHED]
    results = eng.results()
    assert results[2].outcome == res.SHED
    assert results[3].outcome == res.SHED
    assert results[2].tokens == []
    # shed requests are finished (structured), not silently dropped
    out = eng.run(clock=scheduler.FastForwardClock())
    assert set(out) == {0, 1, 2, 3}
    assert eng.results()[0].outcome == res.OK
    assert eng.cache_info()["robustness"]["shed"] == 2


def test_shed_drop_oldest(family_setup):
    cfg, params = family_setup["dense"]
    eng = _engine(cfg, params, chaos=None,
                  resilience=res.ResilienceConfig(max_queue=2,
                                                  shed_policy="drop-oldest"))
    reqs = _requests(cfg, n=4, stagger=0.0)
    outcomes = [eng.submit(r) for r in reqs]
    # newcomers always queue; the head of the queue is shed to make room
    assert outcomes == [res.QUEUED] * 4
    assert eng.results()[0].outcome == res.SHED
    assert eng.results()[1].outcome == res.SHED
    assert eng.n_queued == 2
    eng.run(clock=scheduler.FastForwardClock())
    assert eng.results()[2].outcome == res.OK
    assert eng.results()[3].outcome == res.OK


def test_deadline_expires_queued(family_setup):
    """A queued request whose deadline passes before a slot frees is
    EXPIRED with zero tokens and never dispatched."""
    cfg, params = family_setup["dense"]
    eng = _engine(cfg, params, chaos=None)
    reqs = _requests(cfg, n=3, stagger=0.0)
    reqs[2].deadline = -1.0          # already past at arrival
    for r in reqs:
        eng.submit(r)
    eng.run(clock=scheduler.FastForwardClock())
    assert eng.results()[2].outcome == res.EXPIRED
    assert eng.results()[2].tokens == []
    assert eng.results()[0].outcome == res.OK
    assert eng.cache_info()["robustness"]["expired_queued"] == 1


def test_deadline_cancels_inflight_keeps_partial(family_setup):
    """An in-flight request past its deadline is cancelled between
    segments via slot eviction, keeping the tokens already emitted; its
    co-residents are unperturbed (bitwise)."""
    cfg, params = family_setup["dense"]
    ref = _engine(cfg, params, chaos=None).run(
        _requests(cfg, n=3, stagger=0.0, gens=(20, 20, 20)),
        clock=scheduler.FastForwardClock())
    eng = _engine(cfg, params, chaos=None)
    reqs = _requests(cfg, n=3, stagger=0.0, gens=(20, 20, 20))
    clock = scheduler.FastForwardClock()
    for r in reqs:
        eng.submit(r)
    eng.step(clock)                      # admit + first segment
    assert eng.n_active == 3
    victim = reqs[1]
    got = len(victim.tokens)
    assert got > 0
    victim.deadline = clock.now() - 1e-6     # lapse it mid-flight
    eng.run(clock=clock)
    assert eng.results()[1].outcome == res.EXPIRED
    # the partial stream is a PREFIX of the fault-free stream (bitwise)
    part = np.asarray(eng.results()[1].tokens)
    np.testing.assert_array_equal(part, np.asarray(ref[1])[:len(part)])
    # survivors still bit-exact
    np.testing.assert_array_equal(np.asarray(reqs[0].tokens), ref[0])
    np.testing.assert_array_equal(np.asarray(reqs[2].tokens), ref[2])
    assert eng.cache_info()["robustness"]["expired_inflight"] == 1


def test_default_ttl_applied_at_submit(family_setup):
    cfg, params = family_setup["dense"]
    eng = _engine(cfg, params, chaos=None,
                  resilience=res.ResilienceConfig(default_ttl_s=0.5))
    req = _requests(cfg, n=1)[0]
    eng.submit(req)
    assert req.deadline == req.arrival_time + 0.5
    # an explicit deadline is never overwritten
    eng2 = _engine(cfg, params, chaos=None,
                   resilience=res.ResilienceConfig(default_ttl_s=0.5))
    req2 = _requests(cfg, n=1)[0]
    req2.deadline = 9.0
    eng2.submit(req2)
    assert req2.deadline == 9.0


# ---------------------------------------------------------------------------
# NaN/inf quarantine
# ---------------------------------------------------------------------------

def test_nan_quarantine_isolates_and_scrubs(family_setup):
    """A request with poisoned (NaN) encoder features is FAILED with a
    structured error; co-resident and LATER tenants of the same slot stay
    bit-exact -- proving both masking isolation and the page scrub (a
    stale NaN page would leak: 0 * NaN = NaN)."""
    cfg, params = family_setup["encdec"]
    clean = _requests(cfg, n=4, stagger=0.0)
    ref = _engine(cfg, params, chaos=None, n_slots=2).run(
        clean, clock=scheduler.FastForwardClock())

    reqs = _requests(cfg, n=4, stagger=0.0)
    poison = scheduler.Request(
        rid=99, prompt=[3, 1, 4], max_new_tokens=6, arrival_time=0.0,
        features=np.full((ENC_LEN, cfg.d_model), np.nan, np.float32))
    eng = _engine(cfg, params, chaos=None, n_slots=2)
    for r in [poison] + reqs:
        eng.submit(r)
    out = eng.run(clock=scheduler.FastForwardClock())
    assert eng.results()[99].outcome == res.FAILED
    assert "non-finite" in eng.results()[99].error
    assert eng.cache_info()["robustness"]["quarantined"] == 1
    # with 2 slots the scrubbed slot is certainly reused by a clean
    # request; every clean stream is bit-identical to the poison-free run
    for rid in ref:
        np.testing.assert_array_equal(ref[rid], out[rid])


def test_engine_loop_survives_unexpected_error(family_setup):
    """A real (non-injected) dispatch exception recovers too: the request
    is requeued and replayed, counted under `errors`."""
    cfg, params = family_setup["dense"]
    ref = _engine(cfg, params, chaos=None).run(
        _requests(cfg, n=2), clock=scheduler.FastForwardClock())
    eng = _engine(cfg, params, chaos=None)
    calls = {"n": 0}
    real = eng._bundle.segment

    class Boom(RuntimeError):
        pass

    def flaky(*a, **k):
        calls["n"] += 1
        if calls["n"] == 2:
            raise Boom("transient device error")
        return real(*a, **k)

    object.__setattr__(eng._bundle, "segment", flaky)
    try:
        out = eng.run(_requests(cfg, n=2),
                      clock=scheduler.FastForwardClock())
    finally:
        object.__setattr__(eng._bundle, "segment", real)
    rb = eng.cache_info()["robustness"]
    assert rb["errors"] == 1 and rb["faults_injected"] == 0
    # the real error is kept for diagnosis; recovery hid it otherwise
    assert eng.cache_info()["first_error"] == "Boom: transient device error"
    _assert_bit_exact(ref, out)


# ---------------------------------------------------------------------------
# drain + snapshot/restore
# ---------------------------------------------------------------------------

def test_drain_finishes_inflight_keeps_queued(family_setup):
    cfg, params = family_setup["dense"]
    eng = _engine(cfg, params, chaos=None, n_slots=2)
    reqs = _requests(cfg, n=4, stagger=0.0)
    clock = scheduler.FastForwardClock()
    for r in reqs:
        eng.submit(r)
    eng.step(clock)                      # 2 in flight, 2 queued
    assert eng.n_active == 2 and eng.n_queued == 2
    eng.drain(clock)
    assert eng.n_active == 0
    assert eng.n_queued == 2             # fresh requests stay queued
    done = {r.rid for r in eng.finished}
    assert len(done) == 2
    assert eng.cache_info()["robustness"]["drains"] == 1


def test_snapshot_restore_resumes_bit_exact(family_setup, tmp_path):
    """Rolling restart: snapshot mid-flight (partial tokens in slots +
    queued requests), restore into a FRESH engine, finish.  The union of
    streams matches the uninterrupted run bitwise -- device state is
    never serialized, restore replays (DESIGN.md sec. 8)."""
    cfg, params = family_setup["dense"]
    ref = _engine(cfg, params, chaos=None, n_slots=2).run(
        _requests(cfg, n=4, stagger=0.0), clock=scheduler.FastForwardClock())

    eng = _engine(cfg, params, chaos=None, n_slots=2)
    clock = scheduler.FastForwardClock()
    for r in _requests(cfg, n=4, stagger=0.0):
        eng.submit(r)
    eng.step(clock)                      # partial progress
    eng.snapshot(str(tmp_path), step=1)
    done_before = {r.rid: np.asarray(r.tokens, np.int32)
                   for r in eng.finished}

    eng2 = _engine(cfg, params, chaos=None, n_slots=2)
    n = eng2.restore(str(tmp_path))
    assert n + len(done_before) == 4
    out = eng2.run(clock=scheduler.FastForwardClock())
    merged = dict(done_before)
    merged.update(out)
    _assert_bit_exact(ref, merged)
    # restored in-flight requests carried their partial tokens
    assert eng2.cache_info()["robustness"]["restores"] == 1


def test_snapshot_roundtrip_preserves_request_fields(tmp_path):
    reqs = [scheduler.Request(rid=5, prompt=[1, 2, 3], max_new_tokens=9,
                              arrival_time=1.5, stop_tokens=(7,),
                              deadline=4.0)]
    reqs[0].tokens = [11, 12]
    reqs[0].retries = 2
    res.snapshot_requests(str(tmp_path), 0, reqs)
    back = res.restore_requests(str(tmp_path))
    assert len(back) == 1
    r = back[0]
    assert (r.rid, r.max_new_tokens, r.arrival_time) == (5, 9, 1.5)
    assert r.stop_tokens == (7,) and r.deadline == 4.0
    assert r.tokens == [11, 12] and r.retries == 2
    np.testing.assert_array_equal(r.prompt, [1, 2, 3])
    assert res.restore_requests(str(tmp_path / "empty")) == []


# ---------------------------------------------------------------------------
# observability: counters + warm census under chaos
# ---------------------------------------------------------------------------

def test_robustness_counters_reported(family_setup):
    cfg, params = family_setup["dense"]
    eng = _engine(cfg, params, chaos=None)
    info = eng.cache_info()
    assert set(info["robustness"]) >= {
        "shed", "expired_queued", "expired_inflight", "failed",
        "quarantined", "faults_injected", "errors", "recoveries",
        "replayed_tokens", "replay_divergence", "duplicate_rejects",
        "snapshots", "restores", "drains"}
    assert info["resilience"]["chaos"] is None
    assert info["first_error"] is None
    assert info["resilience"]["shed_policy"] == "reject-new"


def test_warmup_bounds_graphs_under_chaos(family_setup):
    """A chaos-armed engine's warmup pre-compiles the recovery-replay
    grid too: after a faulty run, no graph key falls outside the warmed
    set and the census stays within graph_bound()."""
    cfg, params = family_setup["dense"]
    chaos = res.ChaosSchedule(fail_at_sites=("segment:1", "segment:3"))
    eng = _engine(cfg, params, chaos=chaos)
    reqs = _requests(cfg, stagger=0.0)
    eng.warmup(prompt_lens=sorted({r.prompt_len for r in reqs}))
    warmed = set(eng._graphs)
    eng.run(reqs, clock=scheduler.FastForwardClock())
    assert eng.cache_info()["robustness"]["faults_injected"] == 2
    assert eng._graphs == warmed
    assert len(eng._graphs) <= eng.graph_bound()


# ---------------------------------------------------------------------------
# units: queue ops, ChaosSchedule parsing, RestartPolicy backoff
# ---------------------------------------------------------------------------

def test_queue_pop_expired_and_oldest():
    reqs = [scheduler.Request(rid=i, prompt=[1], max_new_tokens=2,
                              arrival_time=float(i)) for i in range(4)]
    reqs[1].deadline = 0.5
    reqs[3].deadline = 0.5       # expires while still "in transit"
    q = scheduler.RequestQueue(reqs)
    dead = q.pop_expired(1.0)
    assert sorted(r.rid for r in dead) == [1, 3]
    assert q.pop_oldest().rid == 0
    assert [r.rid for r in q.pending()] == [2]
    assert scheduler.RequestQueue().pop_oldest() is None


def test_pop_ready_predicate_preserves_order():
    reqs = [scheduler.Request(rid=i, prompt=[1], max_new_tokens=2)
            for i in range(3)]
    reqs[1].tokens = [42]        # mid-recovery request
    q = scheduler.RequestQueue(reqs)
    got = q.pop_ready(0.0, limit=5, predicate=lambda r: bool(r.tokens))
    assert [r.rid for r in got] == [1]
    assert [r.rid for r in q.pending()] == [0, 2]


def test_chaos_schedule_parse():
    cs = res.ChaosSchedule.parse("segment:1;prefill:0,rate=0.25,seed=3,max=2")
    assert cs.fail_at_sites == ("segment:1", "prefill:0")
    assert (cs.rate, cs.seed, cs.max_failures) == (0.25, 3, 2)
    with pytest.raises(ValueError, match="bad site"):
        res.ChaosSchedule.parse("decode:1")
    with pytest.raises(ValueError, match="unknown key"):
        res.ChaosSchedule.parse("pace=0.5")
    with pytest.raises(SimulatedFailure):
        res.ChaosSchedule.parse("chunk:0").check_site("chunk:0")
    # fires at most once per site
    cs2 = res.ChaosSchedule.parse("chunk:0")
    with pytest.raises(SimulatedFailure):
        cs2.check_site("chunk:0")
    cs2.check_site("chunk:0")
    # max_failures caps rate-driven injections
    cs3 = res.ChaosSchedule(rate=1.0, max_failures=1)
    with pytest.raises(SimulatedFailure):
        cs3.check_site("segment:0")
    cs3.check_site("segment:1")


def test_chaos_from_env(monkeypatch):
    monkeypatch.delenv("REPRO_CHAOS", raising=False)
    assert res.chaos_from_env() is None
    monkeypatch.setenv("REPRO_CHAOS", "rate=0.1,seed=2")
    cs = res.chaos_from_env()
    assert cs.rate == 0.1 and cs.seed == 2


def test_restart_policy_backoff_and_reset():
    p = RestartPolicy(max_restarts=10, backoff_s=1.0, max_backoff_s=6.0,
                      jitter=0.0)
    seen = []
    for p.streak in (0, 1, 2, 3):
        seen.append(p.next_backoff())
    assert seen == [1.0, 2.0, 4.0, 6.0]           # doubled, then capped
    p.streak = 2
    p.reset()
    assert p.streak == 0 and p.next_backoff() == 1.0


def test_restart_policy_jitter_deterministic():
    a = RestartPolicy(backoff_s=1.0, jitter=0.5, seed=3)
    b = RestartPolicy(backoff_s=1.0, jitter=0.5, seed=3)
    c = RestartPolicy(backoff_s=1.0, jitter=0.5, seed=4)
    assert a.next_backoff() == b.next_backoff()   # reproducible
    assert a.next_backoff() != c.next_backoff()   # de-synchronized
    assert 1.0 <= a.next_backoff() < 1.5


def test_restart_policy_counts_granted_only():
    p = RestartPolicy(max_restarts=2)
    exc = SimulatedFailure("x")
    assert p.should_restart(exc) and p.should_restart(exc)
    # refusals do not burn attempts: restarts stays at the cap
    assert not p.should_restart(exc)
    assert not p.should_restart(exc)
    assert p.restarts == 2
