"""Property-based device-loss testing: loss events share the counted
dispatch-site namespace (``segment:/prefill:/chunk:N``) with plain
faults, so a seeded schedule of EITHER kind replays identically -- and
for RANDOM loss schedules mixed with fault arms and deadline mixes,
every surviving stream stays byte-identical to the fault-free run
(DESIGN.md sec. 9's determinism contract, stated over the schedule
space instead of hand-picked sites).

Like tests/test_resilience_property.py, the reference invariant is
prefix-wise so it is timing-robust; the twin-run invariant (two engines
armed with IDENTICAL schedules) is exact -- same fired sites, same lost
devices, same tokens.  The engines run on a virtual clock: with real
compute time on the clock, staggered arrivals would join batches
according to how long the first run spent compiling its recovery graphs,
and the twin would dispatch a different site sequence."""
import jax
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import example, given, settings, strategies as st

from repro import configs
from repro.distributed import elastic
from repro.distributed.fault import SimulatedFailure
from repro.launch import resilience as res
from repro.launch import scheduler
from repro.launch.engine import ServeEngine
from repro.models import lm

FAMILY_ARCHS = {"dense": "smollm-135m", "ssm": "mamba2-2.7b"}
PLENS = (5, 12, 9, 16, 7)
GENS = (7, 5, 8, 4, 6)
_KINDS = sorted(res.ChaosSchedule.SITE_KINDS)


@pytest.fixture(scope="module")
def setups():
    out = {}
    for fam, arch in FAMILY_ARCHS.items():
        cfg = configs.get_reduced_config(arch)
        params = lm.init_params(jax.random.PRNGKey(0), cfg, max_seq=80)
        out[fam] = (cfg, params)
    return out


def _traffic(cfg, ttls):
    reqs = []
    for i, (pl, g) in enumerate(zip(PLENS, GENS)):
        prompt = np.asarray(jax.random.randint(
            jax.random.PRNGKey(31 + 10 * i), (pl,), 0, cfg.vocab))
        r = scheduler.Request(rid=i, prompt=prompt, max_new_tokens=g,
                              arrival_time=0.01 * i)
        if ttls[i] is not None:
            r.deadline = r.arrival_time + ttls[i]
        reqs.append(r)
    return reqs


def _injector(loss, faults):
    return elastic.DeviceLossInjector(
        fail_at_sites=tuple(f"{k}:{i}" for k, i in faults),
        lose_at_sites=tuple((f"{k}:{i}", n) for k, i, n in loss))


class _TickClock(scheduler.Clock):
    """Virtual time: every reading advances it by one tick and idle waits
    jump ahead, so the engine's schedule is a pure function of its inputs
    and never of how long a compile took.  The tick is a power of two, so
    a reading never lands exactly on a 0.01*i arrival: a zero TTL lapses
    at arrival, as it does on a wall clock."""

    def __init__(self, tick: float = 2.0 ** -8):
        self._t, self._tick = 0.0, tick

    def now(self) -> float:
        self._t += self._tick
        return self._t

    def wait_until(self, t: float) -> None:
        self._t = max(self._t, t)


def _run(cfg, params, ttls, chaos):
    eng = ServeEngine(params, cfg, n_slots=3, max_cache_len=64,
                      segment_len=4, chaos=chaos)
    eng.run(_traffic(cfg, ttls), clock=_TickClock())
    return eng


# fault-free reference streams, cached per (family, deadline-mix)
_REF_CACHE: dict = {}


def _reference(setups, fam, ttls):
    key = (fam, ttls)
    if key not in _REF_CACHE:
        cfg, params = setups[fam]
        _REF_CACHE[key] = _run(cfg, params, ttls, chaos=None)
    return _REF_CACHE[key]


# a loss schedule: (site-kind, dispatch-index, devices-to-lose) triples;
# indices beyond the run's dispatch count simply never fire
_LOSS = st.lists(
    st.tuples(st.sampled_from(_KINDS), st.integers(0, 7),
              st.integers(1, 4)),
    min_size=1, max_size=2, unique_by=lambda t: t[:2])

# plain fault arms riding along (possibly colliding with a loss site:
# loss wins there, which must itself replay deterministically)
_FAULTS = st.lists(
    st.tuples(st.sampled_from(_KINDS), st.integers(0, 7)),
    min_size=0, max_size=2, unique=True)

_TTL_MIXES = st.lists(st.sampled_from([None, 1e6, 0.0]),
                      min_size=len(PLENS), max_size=len(PLENS))


@given(loss=_LOSS, faults=_FAULTS, n_sites=st.integers(1, 12))
@settings(max_examples=30, deadline=None)
def test_injector_tape_replays_identically(loss, faults, n_sites):
    """Walk the same counted-site tape with two fresh, identically-armed
    injectors: every DeviceLoss and every plain fault fires at the same
    site with the same device count, and every fired site lives in the
    shared kind:index namespace."""
    tape = [f"{k}:{i}" for i in range(n_sites) for k in _KINDS]
    logs = []
    for _ in range(2):
        inj = _injector(loss, faults)
        log = []
        for site in tape:
            try:
                inj.check_site(site)
                log.append((site, "ok", 0))
            except elastic.DeviceLoss as e:
                log.append((site, "lose", e.n_lost))
            except SimulatedFailure:
                log.append((site, "fail", 0))
        logs.append((log, dict(inj.lost_sites), frozenset(inj.failed)))
    assert logs[0] == logs[1]
    log, lost_sites, failed = logs[0]
    assert set(lost_sites) <= failed
    for site in failed:
        kind, _, idx = site.partition(":")
        assert kind in res.ChaosSchedule.SITE_KINDS and idx.isdigit()


@pytest.mark.parametrize("fam", sorted(FAMILY_ARCHS))
@given(loss=_LOSS, faults=_FAULTS, ttls=_TTL_MIXES)
@settings(max_examples=4, deadline=None)
# twins that dispatched different sites on a wall-paced clock
@example(loss=[("verify", 4, 1), ("prefill", 1, 2)],
         faults=[("prefill", 0), ("prefill", 5)],
         ttls=[1e6, None, None, 0.0, None])
@example(loss=[("segment", 0, 4), ("segment", 5, 1)], faults=[],
         ttls=[1e6, 0.0, None, 0.0, 1e6])
def test_streams_bit_identical_under_random_loss(setups, fam, loss,
                                                 faults, ttls):
    ttls = tuple(ttls)
    cfg, params = setups[fam]
    ref = _reference(setups, fam, ttls)
    eng = _run(cfg, params, ttls, _injector(loss, faults))
    twin = _run(cfg, params, ttls, _injector(loss, faults))

    rb = eng.cache_info()["robustness"]
    assert rb["replay_divergence"] == 0
    assert rb["faults_injected"] == len(eng._chaos.failed)
    assert rb["recoveries"] >= rb["faults_injected"]
    # loss accounting lives in the fault-site namespace
    assert set(eng._chaos.lost_sites) <= eng._chaos.failed

    # twin determinism: identical schedules fire identically and the
    # engines emit identical streams with identical outcomes
    assert eng._chaos.failed == twin._chaos.failed
    assert eng._chaos.lost_sites == twin._chaos.lost_sites
    a_res, b_res = eng.results(), twin.results()
    assert set(a_res) == set(b_res)
    for rid in a_res:
        np.testing.assert_array_equal(
            np.asarray(a_res[rid].tokens, np.int64),
            np.asarray(b_res[rid].tokens, np.int64))
        assert a_res[rid].outcome == b_res[rid].outcome

    # prefix-wise vs the fault-free reference (recovery adds wall-clock
    # steps, so a mid-flight deadline may lapse at a different boundary)
    got_res, ref_res = a_res, ref.results()
    assert set(ref_res) == set(got_res) == set(range(len(PLENS)))
    for rid in got_res:
        a = np.asarray(got_res[rid].tokens, np.int64)
        b = np.asarray(ref_res[rid].tokens, np.int64)
        n = min(len(a), len(b))
        np.testing.assert_array_equal(a[:n], b[:n])
        if got_res[rid].outcome == res.OK and ref_res[rid].outcome == res.OK:
            assert len(a) == len(b)
        if ttls[rid] == 0.0:
            assert got_res[rid].outcome == ref_res[rid].outcome \
                == res.EXPIRED
            assert len(a) == 0
