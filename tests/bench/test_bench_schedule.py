"""The seeded traffic generator: deterministic, Poisson at the stated rate,
Zipf in its rank shares, the same amount of work for every seed; closed
loops made a block at a time; bursts, a moving hot set and concept drift
as data."""
import numpy as np
import pytest

from bench import traffic

CFG = {
    "tenants": 64,
    "input_dim": 5,
    "stream": {"kind": "wiener", "sigma_eta": 0.05},
}
POISSON = {
    "arrivals": "poisson", "rate": 2000.0, "read_share": 0.5,
    "keys": {"dist": "zipf", "theta": 0.99}, "warmup": 100,
    "size_watermark": 16, "age_watermark": 0.1,
}
SATURATE = {
    "arrivals": "saturate", "block": 3000, "read_share": 0.0,
    "keys": {"dist": "round_robin"}, "warmup": 10,
    "size_watermark": 16, "age_watermark": None,
}
BIG_SEED = 2**40 + 12345  # more than 32 signed bits


def _equal(a, b):
    return (all(np.array_equal(u, v) for u, v in zip(a.arrays(), b.arrays()))
            and np.array_equal(a.due, b.due) and a.warmup == b.warmup)


@pytest.mark.parametrize("mix", [POISSON, SATURATE], ids=["poisson", "saturate"])
def test_schedule_is_deterministic_in_the_seed(mix):
    a = traffic.build(mix, CFG, BIG_SEED, 2.0)
    b = traffic.build(mix, CFG, BIG_SEED, 2.0)
    c = traffic.build(mix, CFG, BIG_SEED + 1, 2.0)
    assert _equal(a, b)
    assert not _equal(a, c)
    # Every seed gets the same numbers of requests and reads.
    is_read, _, x, y = a.arrays()
    assert len(a) == len(c) and is_read.sum() == c.arrays()[0].sum()
    assert x.dtype == np.float32 and y.dtype == np.float32
    assert np.all(y[is_read] == 0.0)


def test_poisson_arrivals_at_the_stated_rate():
    seconds = 5.0
    s = traffic.build(POISSON, CFG, 7, seconds)
    due = s.due[s.warmup:]
    assert len(due) == int(POISSON["rate"] * seconds)
    assert np.all(s.due[: s.warmup] == 0.0)
    assert np.all(np.diff(due) >= 0) and 0.0 <= due[0] and due[-1] < seconds
    gaps = np.diff(due)
    # Exponential gaps: mean 1/rate, coefficient of variation 1.
    assert gaps.mean() == pytest.approx(1.0 / POISSON["rate"], rel=0.02)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.05)
    # Counts per 0.1 s bin are Poisson: variance close to the mean.
    counts = np.histogram(due, bins=50, range=(0, seconds))[0]
    assert counts.var() / counts.mean() == pytest.approx(1.0, abs=0.45)
    assert s.arrays()[0].mean() == pytest.approx(0.5, abs=1e-3)


def test_zipf_rank_shares():
    rng = np.random.default_rng(0)
    n, tenants, theta = 400_000, 100, 0.99
    ranks = traffic.zipf_ranks(rng, tenants, theta, n)
    pmf = np.arange(1, tenants + 1) ** -theta
    pmf /= pmf.sum()
    share = np.bincount(ranks, minlength=tenants) / n
    sigma = np.sqrt(pmf * (1 - pmf) / n)
    assert np.all(np.abs(share - pmf) < 5 * sigma)
    assert share[0] == pytest.approx(1 / 5.2946, abs=0.003)  # 1 / sum_r r^-0.99


def test_round_robin_keys_visit_every_tenant_in_turn():
    s = traffic.build(SATURATE, CFG, BIG_SEED, 1.0)
    assert np.all(np.diff(s.arrays()[1]) % CFG["tenants"] == 1)
    assert len(s) == SATURATE["warmup"] + SATURATE["block"]


def test_closed_loop_is_made_in_blocks_that_do_not_depend_on_timing():
    mix = {**SATURATE, "keys": {"dist": "zipf", "theta": 0.99}, "read_share": 0.25}
    a = traffic.build(mix, CFG, BIG_SEED, 1.0)
    assert len(a) == mix["warmup"] + mix["block"] and a.due is None
    assert a.extend() and a.extend()
    assert len(a) == mix["warmup"] + 3 * mix["block"]
    b = traffic.build(mix, CFG, BIG_SEED, 50.0)  # the window's length is not used
    b.extend()
    b.extend()
    for u, v in zip(a.arrays(), b.arrays()):
        assert np.array_equal(u, v)
    assert a.key_l == a.arrays()[1].tolist() and len(a.x) == len(a)
    # Each block has the same share of reads, whatever the seed.
    is_read = a.arrays()[0][mix["warmup"]:].reshape(3, -1)
    assert np.all(is_read.sum(1) == round(0.25 * mix["block"]))
    c = traffic.build(mix, CFG, BIG_SEED + 1, 1.0)
    c.extend()
    assert not np.array_equal(c.arrays()[1], a.arrays(len(c))[1])


def test_an_open_loop_is_made_whole():
    s = traffic.build(POISSON, CFG, 7, 1.0)
    n = len(s)
    assert not s.extend() and len(s) == n


def test_on_off_profile_bursts_at_the_stated_rates():
    seconds, period = 6.0, 1.0
    mix = {**POISSON, "profile": {"period_s": period,
                                  "pieces": [[0.25, 3.0], [0.75, 1.0 / 3.0]]}}
    s = traffic.build(mix, CFG, 11, seconds)
    due = s.due[s.warmup:]
    # The rate's integral: per period 0.25 * 3 + 0.75 / 3 = 1 rate-second.
    assert len(due) == int(POISSON["rate"] * seconds)
    on = (due % period) < 0.25 * period
    rate_on = on.sum() / (0.25 * seconds)
    rate_off = (~on).sum() / (0.75 * seconds)
    assert rate_on == pytest.approx(3.0 * POISSON["rate"], rel=0.05)
    assert rate_off == pytest.approx(POISSON["rate"] / 3.0, rel=0.1)
    off_only = {**POISSON, "profile": {"period_s": period,
                                       "pieces": [[0.5, 2.0], [0.5, 0.0]]}}
    due = traffic.build(off_only, CFG, 11, seconds).due[s.warmup:]
    assert np.all((due % period) <= 0.5 * period + 1e-9)


def test_zipf_hot_set_moves_by_the_stated_ranks():
    cfg = {**CFG, "tenants": 1000}
    every, by = 4000, 10
    mix = {**SATURATE, "block": 2 * every, "warmup": 0,
           "keys": {"dist": "zipf", "theta": 0.99,
                    "shift": {"every": every, "by": by}}}
    s = traffic.build(mix, cfg, 5, 1.0)
    key = s.arrays()[1]
    first = np.bincount(key[:every], minlength=1000)
    second = np.bincount(key[every:], minlength=1000)
    hot = np.argmax(first)
    assert first[hot] > 10 * max(second[hot], 1)  # the hottest tenant cooled
    # The tenant that was 'by' ranks down the list now takes its place.
    assert np.argmax(second) == s._perm[by]
    assert hot == s._perm[0]


def test_drift_draws_new_hidden_functions():
    mix = {**SATURATE, "warmup": 0, "block": 4000, "drift": {"every": 2000}}
    noiseless = {**CFG, "stream": {"kind": "wiener", "sigma_eta": 0.0}}
    s = traffic.build(mix, noiseless, 9, 1.0)
    _, key, x, y = s.arrays()

    def fits(lo, hi, params):
        k, xx = key[lo:hi], x[lo:hi].astype(np.float64)
        lin = np.sum(xx * params["w0"][k], -1)
        quad = np.sum(xx * params["w1"][k], -1)
        return np.allclose(y[lo:hi], lin + 0.1 * quad * quad, rtol=1e-5, atol=1e-4)

    p0, p1 = s._params[0], s._params[1]
    assert fits(0, 2000, p0) and not fits(0, 2000, p1)
    assert fits(2000, 4000, p1) and not fits(2000, 4000, p0)
