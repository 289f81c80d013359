"""Per-request sampling policies (launch/sampling.py): constant-size
slot-page registration, greedy bit-parity with the pre-sampling engine,
sampled-stream determinism (engine == static == repeat run), chaos-replay
byte-identity, and prefix-cache warm-run identity.

The sampler itself is held bit for bit to its formula before the
truncation and draw moved under a `lax.cond` (`_sample_before`), and the
engine's `sampler_path` counter to the branch each dispatch takes.

The contract under test is DESIGN sec. 12's purity obligation:
every sampled token is a pure function of (seed, rid, token index,
logits row), so recovery replay and warm admissions RECOMPUTE the same
bytes instead of restoring sampler state."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.launch import resilience as res
from repro.launch import sampling, scheduler, serve, telemetry
from repro.launch.engine import ServeEngine, SpecDecodeConfig
from repro.models import lm, slot_state
from repro.quant.qtensor import quantize_tree_for_serving

SP = scheduler.SamplingParams(temperature=0.9, top_k=8, seed=11)
SP_NUCLEUS = scheduler.SamplingParams(temperature=0.7, top_p=0.9, seed=3)
MIX = (SP, None, SP_NUCLEUS, scheduler.GREEDY)


@pytest.fixture(scope="module")
def setup():
    cfg = configs.get_reduced_config("smollm-135m")
    params = quantize_tree_for_serving(
        lm.init_params(jax.random.PRNGKey(0), cfg, max_seq=80), "w8a8")
    return cfg, params


def _requests(cfg, n=6, stagger=0.0, mix=MIX):
    plens = (5, 12, 9, 16, 7, 11, 6, 14)[:n]
    gens = (8, 6, 9, 5, 10, 7, 8, 6)[:n]
    return [scheduler.Request(
        rid=i,
        prompt=np.asarray(jax.random.randint(
            jax.random.PRNGKey(10 * i), (pl,), 0, cfg.vocab)),
        max_new_tokens=g, arrival_time=stagger * i,
        sampling=mix[i % len(mix)])
        for i, (pl, g) in enumerate(zip(plens, gens))]


def _engine(cfg, params, **kw):
    kw.setdefault("n_slots", 4)
    kw.setdefault("max_cache_len", 64)
    kw.setdefault("segment_len", 4)
    return ServeEngine(params, cfg, **kw)


def _assert_bit_exact(ref, out):
    assert set(ref) == set(out)
    for rid in ref:
        np.testing.assert_array_equal(ref[rid], out[rid])


# ---------------------------------------------------------------------------
# the page: a registered constant-size slot-state family
# ---------------------------------------------------------------------------

def test_sampling_page_is_constant_size_slot_family():
    """The probed spec must show slot axis 0 and NO length axis on every
    leaf -- the page admits/permutes/slices with the model caches but
    never scales with cache_len (ISSUE: 'constant-size slot page')."""
    assert "sampling" in slot_state.families()
    spec = sampling.page_spec()
    assert all(b == 0 for b in spec.batch_axes)
    assert all(la is None for la in spec.length_axes)
    page = spec.init_state(4, 1)
    assert [leaf.shape[0] for leaf in page] == [4] * len(page)


def test_host_page_round_trip():
    """write/clear/permute keep the host page a faithful slot mirror."""
    page = sampling.host_page(4)
    req = scheduler.Request(rid=7, prompt=[1, 2, 3], max_new_tokens=2,
                            sampling=SP)
    sampling.write_row(page, 2, req)
    assert page[1][2] == np.float32(SP.temperature)
    assert page[2][2] == SP.top_k and page[4][2] == 3
    assert tuple(page[0][2]) == sampling.base_key(SP.seed, 7)
    perm = np.asarray([2, 0, 1, 3])
    page = sampling.permute(page, perm)
    assert page[2][0] == SP.top_k          # the row moved with its slot
    sampling.clear_row(page, 0)
    assert page[1][0] == 0.0 and page[3][0] == 1.0


def test_sample_host_matches_batch_row():
    """One [1,V] host evaluation must equal the same row inside a [B,V]
    batch -- the property replay verification rests on."""
    rows = np.asarray(jax.random.normal(jax.random.PRNGKey(5), (4, 64)),
                      np.float32)
    key = np.asarray([sampling.base_key(SP.seed, r) for r in range(4)],
                     np.uint32)
    batch = sampling.sample(
        jnp.asarray(rows), jnp.asarray(key),
        jnp.full((4,), SP.temperature, jnp.float32),
        jnp.full((4,), SP.top_k, jnp.int32),
        jnp.full((4,), SP.top_p, jnp.float32),
        jnp.arange(4, dtype=jnp.int32))
    for r in range(4):
        assert int(batch[r]) == sampling.sample_host(rows[r], SP, r, r)


# ---------------------------------------------------------------------------
# engine streams
# ---------------------------------------------------------------------------

def test_greedy_rows_bit_identical_to_argmax_engine(setup):
    """Greedy rows in a mixed sampled batch carry the argmax bits -- the
    pre-sampling engine's stream, unchanged."""
    cfg, params = setup
    ref = _engine(cfg, params).run(
        _requests(cfg, mix=(None,)), clock=scheduler.FastForwardClock())
    out = _engine(cfg, params).run(
        _requests(cfg), clock=scheduler.FastForwardClock())
    for i, r in enumerate(_requests(cfg)):
        if sampling.is_greedy(r):
            np.testing.assert_array_equal(out[i], ref[i])


def test_sampled_streams_deterministic_across_runs(setup):
    cfg, params = setup
    a = _engine(cfg, params).run(_requests(cfg),
                                 clock=scheduler.FastForwardClock())
    b = _engine(cfg, params).run(_requests(cfg),
                                 clock=scheduler.FastForwardClock())
    _assert_bit_exact(a, b)
    # and the sampled rows actually differ from greedy (the policy bites)
    g = _engine(cfg, params).run(_requests(cfg, mix=(None,)),
                                 clock=scheduler.FastForwardClock())
    assert any(not np.array_equal(a[i], g[i]) for i in (0, 2, 4)
               if i in a)


def test_engine_matches_static_sampled_path(setup):
    """Continuous-batching sampled streams == the static serve.generate
    sampled path with the same (seed, rid) -- batch-composition
    invariance end to end."""
    cfg, params = setup
    n, s, gen = 3, 12, 8
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (n, s), 0, cfg.vocab))
    mix = [SP, scheduler.GREEDY, SP_NUCLEUS]
    static = np.asarray(serve.generate(
        params, jnp.asarray(prompts), cfg, gen=gen, cache_len=32,
        sampling=mix, rids=list(range(n))))
    eng = _engine(cfg, params, n_slots=2)   # forces eviction/re-admission
    out = eng.run([scheduler.Request(rid=i, prompt=prompts[i],
                                     max_new_tokens=gen, sampling=mix[i])
                   for i in range(n)])
    for i in range(n):
        np.testing.assert_array_equal(out[i], static[i])


def test_static_sampled_unfused_matches_fused(setup):
    cfg, params = setup
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(2), (2, 10), 0, cfg.vocab))
    kw = dict(gen=6, cache_len=32, sampling=[SP, SP_NUCLEUS], rids=[5, 9])
    fused = np.asarray(serve.generate(
        params, jnp.asarray(prompts), cfg, fused=True, **kw))
    loop = np.asarray(serve.generate(
        params, jnp.asarray(prompts), cfg, fused=False, **kw))
    np.testing.assert_array_equal(fused, loop)


# ---------------------------------------------------------------------------
# replay + prefix cache: recompute the same bytes
# ---------------------------------------------------------------------------

def test_chaos_replay_sampled_streams_bit_exact(setup):
    """Faults mid-stream: recovery replay must reproduce sampled tokens
    byte-identically (counter-based keys recompute, nothing restored)."""
    cfg, params = setup
    ref = _engine(cfg, params, chaos=None).run(
        _requests(cfg), clock=scheduler.FastForwardClock())
    chaos = res.ChaosSchedule(fail_at_sites=("segment:1", "segment:4"))
    eng = _engine(cfg, params, chaos=chaos)
    out = eng.run(_requests(cfg), clock=scheduler.FastForwardClock())
    rb = eng.cache_info()["robustness"]
    assert rb["faults_injected"] == 2
    assert rb["replay_divergence"] == 0
    _assert_bit_exact(ref, out)


def test_chaos_rate_schedule_sampled_bit_exact(setup):
    """The seeded-rate chaos form CI drives via $REPRO_CHAOS."""
    cfg, params = setup
    ref = _engine(cfg, params, chaos=None).run(
        _requests(cfg), clock=scheduler.FastForwardClock())
    chaos = res.ChaosSchedule(rate=0.5, seed=7, max_failures=4)
    eng = _engine(cfg, params, chaos=chaos)
    out = eng.run(_requests(cfg), clock=scheduler.FastForwardClock())
    assert eng.cache_info()["robustness"]["replay_divergence"] == 0
    _assert_bit_exact(ref, out)


def test_prefix_cache_warm_sampled_streams_match_cold(setup):
    """Warm admissions over a shared prefix must emit the same sampled
    bytes as the cold run: the pool stores GREEDY argmax tok0 and
    policy-free pages; sampled tok0 is recomputed per request from the
    final prefill row."""
    cfg, params = setup

    def reqs():
        base = scheduler.shared_prefix_traffic(
            seed=4, n_requests=8, rate=1e9, n_prefixes=2, prefix_len=8,
            tail_lens=(3, 5), gen_lens=(6, 8), vocab=cfg.vocab)
        for i, r in enumerate(base):
            r.sampling = MIX[i % len(MIX)]
        return base

    cold = _engine(cfg, params, prefill_chunk=4).run(
        reqs(), clock=scheduler.FastForwardClock())
    eng = _engine(cfg, params, prefill_chunk=4, prefix_cache=64)
    warm = eng.run(reqs(), clock=scheduler.FastForwardClock())
    info = eng.cache_info()["prefix_cache"]
    assert info["hits"] > 0                 # chain sharing engaged
    _assert_bit_exact(cold, warm)


def test_snapshot_restore_preserves_sampling(setup, tmp_path):
    """resilience snapshot/restore round-trips SamplingParams so a
    restarted engine resumes the same sampled stream."""
    cfg, params = setup
    ref = _engine(cfg, params).run(
        _requests(cfg), clock=scheduler.FastForwardClock())
    eng = _engine(cfg, params)
    for r in _requests(cfg):
        eng.submit(r)
    eng.snapshot(str(tmp_path), step=1)
    eng2 = _engine(cfg, params)
    assert eng2.restore(str(tmp_path)) == len(ref)
    out = eng2.run(clock=scheduler.FastForwardClock())
    _assert_bit_exact(ref, out)


# ---------------------------------------------------------------------------
# the sampler against its formula before the greedy branch
# ---------------------------------------------------------------------------

def _sample_before(last, key, temp, top_k, top_p, t):
    """`sampling.sample` as it was before the truncation and draw moved
    under a `lax.cond`: every batch sorted and drew, and greedy rows were
    selected by the final `jnp.where`.  Kept as the oracle."""
    v = last.shape[-1]
    arg = jnp.argmax(last, axis=-1).astype(jnp.int32)
    x = last.astype(jnp.float32) / jnp.maximum(temp, 1e-6)[:, None]
    srt = jnp.sort(x, axis=-1)[:, ::-1]
    kk = jnp.where(top_k > 0, jnp.clip(top_k, 1, v), v)
    thr_k = jnp.take_along_axis(srt, (kk - 1)[:, None], axis=-1)
    probs = jax.nn.softmax(srt, axis=-1)
    excl = jnp.cumsum(probs, axis=-1) - probs
    kept = excl < top_p[:, None]
    thr_p = jnp.min(jnp.where(kept, srt, jnp.inf), axis=-1, keepdims=True)
    keep = (x >= thr_k) & (x >= thr_p)
    kt = jax.vmap(jax.random.fold_in)(key, t)
    gum = jax.vmap(
        lambda k: jax.random.gumbel(k, (v,), jnp.float32))(kt)
    smp = jnp.argmax(jnp.where(keep, x + gum, -jnp.inf),
                     axis=-1).astype(jnp.int32)
    return jnp.where(temp > 0.0, smp, arg)


V = 203
# per-row policy of each case: (temperature, top_k, top_p) by row index;
# a greedy row has temperature 0
POLICIES = {
    "greedy": lambda r: (0.0, 0, 1.0),
    "mixed": lambda r: ((0.0, 0, 1.0), (0.8, 5, 1.0), (1.3, 0, 0.9),
                        (0.0, 7, 0.5))[r % 4],
    "top_k": lambda r: (0.5 + 0.25 * (r % 3), (1, 5, V + 9)[r % 3], 1.0),
    "top_p": lambda r: (0.7 + 0.1 * (r % 4), 0, (0.3, 0.9, 0.99)[r % 3]),
    "top_k_top_p": lambda r: (0.9, 3 + r % 8, 0.8),
    "temperature_only": lambda r: (0.6 + 0.2 * (r % 5), 0, 1.0),
}
SAMPLE_CASES = [(p, dt, b) for p in POLICIES
                for dt in ("bfloat16", "float32") for b in (1, 8, 32)]


def _logits(b, dtype, seed):
    """Random rows with ties at the maximum and below it, -inf entries,
    and (for b > 1) one all-NaN row."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(b, V)).astype(np.float32)
    x[:, 10:20] = np.round(x[:, 10:20], 1)          # ties below the top
    top = np.argmax(x, axis=-1)
    x[np.arange(b), (top + 37) % V] = x[np.arange(b), top]  # tied maxima
    x[rng.random((b, V)) < 0.1] = -np.inf
    if b > 1:
        x[b // 2] = np.nan
    return jnp.asarray(x, dtype)


@pytest.mark.parametrize("policy,dtype,b", SAMPLE_CASES,
                         ids=[f"{p}-{dt}-b{b}" for p, dt, b in SAMPLE_CASES])
def test_sample_matches_formula_before_greedy_branch(policy, dtype, b):
    """Greedy, mixed and sampled batches give the same tokens, bit for
    bit, as the sampler that sorted and drew for every batch."""
    last = _logits(b, dtype, seed=b)
    rows = [POLICIES[policy](r) for r in range(b)]
    key = jnp.asarray([sampling.base_key(17, r) for r in range(b)],
                      jnp.uint32)
    temp = jnp.asarray([p[0] for p in rows], jnp.float32)
    top_k = jnp.asarray([p[1] for p in rows], jnp.int32)
    top_p = jnp.asarray([p[2] for p in rows], jnp.float32)
    t = jnp.asarray(np.random.default_rng(b).integers(0, 500, b), jnp.int32)
    got = jax.jit(sampling.sample)(last, key, temp, top_k, top_p, t)
    want = jax.jit(_sample_before)(last, key, temp, top_k, top_p, t)
    assert got.dtype == want.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _primitives(eqns):
    """Names of the primitives some jaxpr equations run, sub-jaxprs
    included."""
    names = set()
    for eqn in eqns:
        names.add(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    names |= _primitives(sub.eqns)
    return names


def test_sample_sorts_only_inside_the_sampled_branch():
    """The jaxpr of `sample`: no sort outside its one `cond`, and of the
    cond's two branches only the sampled one sorts."""
    b = 4
    jaxpr = jax.make_jaxpr(sampling.sample)(
        jnp.zeros((b, V), jnp.float32), jnp.zeros((b, 2), jnp.uint32),
        jnp.zeros((b,), jnp.float32), jnp.zeros((b,), jnp.int32),
        jnp.ones((b,), jnp.float32), jnp.zeros((b,), jnp.int32)).jaxpr
    conds = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    assert len(conds) == 1
    assert "sort" not in _primitives(e for e in jaxpr.eqns
                                     if e.primitive.name != "cond")
    greedy, sampled = (_primitives(br.jaxpr.eqns)
                       for br in conds[0].params["branches"])
    assert "sort" not in greedy and "argmax" in greedy
    assert "sort" in sampled


# ---------------------------------------------------------------------------
# the engine's sampler_path counter
# ---------------------------------------------------------------------------

SP_ONE = scheduler.SamplingParams(temperature=0.8, top_k=5, seed=2)
PATH_CASES = {
    # (spec decode, sampling of each request, dispatches that sample):
    # the sampled request wants 3 tokens, its first from the prefill, so
    # it shares one segment (4 steps) or one round (k + 1 = 3) with the
    # others, and the dispatches after it are all greedy
    "greedy": (False, (None,) * 4, 0),
    "one_sampled": (False, (None, SP_ONE, None, None), 1),
    "spec_greedy": (True, (None,) * 4, 0),
    "spec_one_sampled": (True, (None, SP_ONE, None, None), 1),
}


@pytest.mark.parametrize("case", list(PATH_CASES))
def test_sampler_path_counts_each_dispatch(setup, case):
    """`cache_info()["sampler_path"]` counts every segment (or
    speculative round) once: "argmax" when no row of its batch samples,
    "sampled" when one does; each `engine.segment` span carries how many
    of its rows sample."""
    cfg, params = setup
    spec, mix, sampled = PATH_CASES[case]
    gens = (12, 3, 10, 14)
    reqs = [scheduler.Request(
        rid=i, prompt=np.asarray(jax.random.randint(
            jax.random.PRNGKey(30 + i), (6 + i,), 0, cfg.vocab)),
        max_new_tokens=g, sampling=p)
        for i, (g, p) in enumerate(zip(gens, mix))]
    kw = {"spec_decode": SpecDecodeConfig(draft_params=params,
                                          draft_cfg=cfg, k=2)} \
        if spec else {}
    eng = _engine(cfg, params, chaos=None, **kw)
    t0 = telemetry.now()
    eng.run(reqs, clock=scheduler.FastForwardClock())
    info = eng.cache_info()
    path = info["sampler_path"]
    n = info["spec_decode"]["rounds"] if spec \
        else info["dispatch_sites"]["segment"]
    assert n > 1
    assert path == {"argmax": n - sampled, "sampled": sampled}
    if not spec:
        segs = [s for s in telemetry.RECORDER.spans("engine.segment")
                if s.start >= t0]
        assert len(segs) == n
        assert all(0 <= s.counts["sampled"] <= s.counts["active"]
                   for s in segs)
        assert sum(s.counts["sampled"] > 0 for s in segs) \
            == path["sampled"]
