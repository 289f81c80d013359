"""Traffic generation and the window arithmetic (bench/lib/traffic.py)."""
import statistics

import numpy as np
import pytest

from bench.lib import traffic

CHAT = {"loop": "open", "rate_per_s": 7.0, "drain_s": 60, "order_seed": 0,
        "prompt": {"dist": "lognormal", "median": 256, "sigma": 0.8,
                   "min": 16, "max": 1536},
        "output": {"dist": "lognormal", "median": 96, "sigma": 0.6,
                   "min": 8, "max": 384}}
BATCH = {"loop": "closed", "clients_per_slot": 2, "drain_s": 60,
         "order_seed": 0,
         "prompt": {"dist": "uniform", "min": 256, "max": 640},
         "output": {"dist": "uniform", "min": 192, "max": 320}}


def _trace(mix, seed, seconds=30.0):
    return traffic.build(mix, seed, seconds, vocab=151936, n_slots=16)


@pytest.mark.parametrize("mix", [CHAT, BATCH], ids=["open", "closed"])
def test_same_seed_same_trace(mix):
    a, b = _trace(mix, 2 ** 33 + 17), _trace(mix, 2 ** 33 + 17)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new_tokens, x.at) == (y.max_new_tokens, y.at)
    c = _trace(mix, 5)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("mix", [CHAT, BATCH], ids=["open", "closed"])
def test_every_seed_gets_the_same_work(mix):
    """The same lengths and send times in the same order; the seed draws
    the tokens only.  The mix's own `order_seed` sets the order."""
    a, b = _trace(mix, 1), _trace(mix, 2 ** 40 + 3)
    key = lambda t: [(len(r.prompt), r.max_new_tokens, r.at) for r in t]
    assert key(a) == key(b)
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = _trace(dict(mix, order_seed=1), 1)
    for part in (0, 1):
        assert sorted(k[part] for k in key(c)) == \
            sorted(k[part] for k in key(a))
    assert key(c) != key(a)


def test_open_loop_fills_the_window_exactly():
    reqs = _trace(CHAT, 9, seconds=30.0)
    assert len(reqs) == 210
    at = [r.at for r in reqs]
    assert at[0] == 0.0 and at == sorted(at) and at[-1] < 30.0
    gaps = np.diff(at + [30.0])
    assert gaps.sum() == pytest.approx(30.0)
    # Poisson gaps: exponential, so the coefficient of variation is ~1
    assert 0.85 < gaps.std() / gaps.mean() < 1.15


def test_shared_prefixes_follow_zipf():
    mix = dict(CHAT, prompt={"dist": "uniform", "min": 16, "max": 64},
               prefix={"count": 4, "len": 100, "zipf": 1.2})
    reqs = _trace(mix, 2 ** 33 + 1)
    heads = [r.prompt[:100].tobytes() for r in reqs]
    counts = sorted((heads.count(h) for h in set(heads)), reverse=True)
    assert len(counts) == 4
    w = 1.0 / np.arange(1, 5) ** 1.2
    np.testing.assert_allclose(np.array(counts) / len(reqs), w / w.sum(),
                               atol=0.06)
    tails = [r.prompt[100:] for r in reqs]
    assert min(len(t) for t in tails) >= 16 and max(len(t) for t in tails) \
        <= 64
    assert len({t.tobytes() for t in tails}) == len(reqs)
    assert traffic.prompt_lengths(mix) == [116, 128, 164]


def test_bursts_send_only_in_their_on_time():
    mix = dict(CHAT, bursts={"period_s": 10.0, "on_s": 3.0})
    reqs = _trace(mix, 4, seconds=25.0)
    at = np.array([r.at for r in reqs])
    assert len(at) == 175 and (np.diff(at) >= 0).all()
    assert (at % 10.0 < 3.0).all() and at.max() < 23.0
    # the same mean rate over the window, three bursts' worth of time
    per = [np.sum((at >= k * 10) & (at < k * 10 + 3)) for k in range(3)]
    assert per[0] == pytest.approx(per[1], rel=0.35)
    assert sum(per) == 175


def test_prompts_are_distinct():
    reqs = _trace(CHAT, 3)
    assert len({r.prompt.tobytes() for r in reqs}) == len(reqs)


@pytest.mark.parametrize("spec,median", [
    (CHAT["prompt"], 256), (CHAT["output"], 96)])
def test_lognormal_quantiles(spec, median):
    n = 2001
    v = traffic.lengths(spec, n)
    assert v.min() >= spec["min"] and v.max() <= spec["max"]
    assert statistics.median(v.tolist()) == median
    # the 84th percentile sits one sigma up, as stated
    q84 = np.quantile(v, statistics.NormalDist().cdf(1.0))
    assert q84 == pytest.approx(median * np.exp(spec["sigma"]), rel=0.02)
    # clipping: the share at the upper clip is the lognormal's tail
    tail = 1 - statistics.NormalDist().cdf(
        np.log(spec["max"] / median) / spec["sigma"])
    assert np.mean(v == spec["max"]) == pytest.approx(tail, abs=0.003)


def test_uniform_quantiles():
    v = traffic.lengths(BATCH["prompt"], 3850)
    assert v.min() == 256 and v.max() == 640
    assert np.quantile(v, 0.25) == pytest.approx(352, abs=1)
    assert np.quantile(v, 0.75) == pytest.approx(544, abs=1)


def test_prompt_lengths_cover_every_bucket():
    assert traffic.prompt_lengths(CHAT) == [16, 32, 64, 128, 256, 512,
                                            1024, 1536]
    assert traffic.prompt_lengths(BATCH) == [256, 512, 640]


def test_window_arithmetic():
    R = traffic.Record
    recs = [
        # due at 1.0, sent late at 1.1; tokens at 1.5, 1.6, 1.7, 2.4
        R(scheduled=1.0, sent=1.1, token_times=[1.5, 1.6, 1.7, 2.4],
          tokens=[1, 2, 3, 4], want=4),
        # sent in the window, never finished: failed
        R(scheduled=1.9, sent=1.9, token_times=[2.5], tokens=[1], want=3,
          error="not finished by the end of the drain"),
        # sent before the window: its in-window tokens count, nothing else
        R(scheduled=-1.0, sent=-1.0, token_times=[0.5, 1.2], tokens=[1, 2],
          want=2),
    ]
    s = traffic.summarize(recs, 0.0, 2.0)
    assert (s["attempted"], s["failed"]) == (2, 1)
    assert s["window_tokens"] == 5          # 1.5 1.6 1.7 | 0.5 1.2
    assert s["tok_s"] == pytest.approx(2.5)
    assert s["ttft_p95_ms"] == pytest.approx(500.0)     # from the schedule
    assert s["tpot_p95_ms"] == pytest.approx(300.0)     # 0.9 s / 3 gaps
    assert s["stall_p95_ms"] == pytest.approx(700.0)    # 1.7 -> 2.4
    assert s["sender_late_max_ms"] == pytest.approx(100.0)


def test_percentile_matches_numpy_and_empty_is_none():
    assert traffic.percentile([], 95) is None
    v = list(range(101))
    assert traffic.percentile(v, 95) == 95.0
