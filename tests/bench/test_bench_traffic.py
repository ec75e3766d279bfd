"""The traffic generator: one seed gives one trace; every seed the same
set of lengths and gaps with its own tokens, in the mix's order; lengths
on their quanta and clamps."""
import collections
import math

import numpy as np
import pytest

from bench import harness, traffic

MIXES = ["decode_offline", "longprompt_poisson"]
BIG_SEED = 2 ** 31 + 977


def mix(name):
    return harness.load_json(harness.BENCH / "traffic" / f"{name}.json")


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_trace(name):
    a = traffic.generate(mix(name), BIG_SEED, 32000, 50)
    b = traffic.generate(mix(name), BIG_SEED, 32000, 50)
    assert [(r.arrival_s, r.max_new_tokens) for r in a] == \
        [(r.arrival_s, r.max_new_tokens) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = traffic.generate(mix(name), BIG_SEED + 1, 32000, 50)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


def gaps(reqs, seconds):
    """The gaps between arrivals, the last one up to the window's close."""
    t = [r.arrival_s for r in reqs] + [seconds]
    return np.diff(t)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_same_work(name):
    """Every seed gets the same lengths and gaps; a mix ordered by the seed
    gets them in another order on each seed, a fixed one in one order."""
    m = mix(name)
    n = traffic.count(m, 50)
    schedules, gap_sets = [], []
    for seed in (0, 5, BIG_SEED, -3):
        reqs = traffic.generate(m, seed, 32000, 50)
        schedules.append([(r.arrival_s, len(r.prompt), r.max_new_tokens)
                          for r in reqs])
        gap_sets.append(np.sort(gaps(reqs, 50)))
        assert collections.Counter(len(r.prompt) for r in reqs) == \
            collections.Counter(traffic.lengths(m["prompt"], n).tolist())
        assert collections.Counter(r.max_new_tokens for r in reqs) == \
            collections.Counter(traffic.lengths(m["output"], n).tolist())
    for g in gap_sets:
        np.testing.assert_allclose(g, gap_sets[0], atol=1e-9)
    same = all(s == schedules[0] for s in schedules)
    assert same == (m.get("order", "fixed") == "fixed")


def test_seeded_order():
    """The long-prompt mix is ordered by the seed; an unknown order is an
    error."""
    m = mix("longprompt_poisson")
    assert m["order"] == "seed"
    assert traffic.order_seed(m, 7) == 7
    assert traffic.order_seed(mix("decode_offline"), 7) == 0
    with pytest.raises(ValueError):
        traffic.order_seed(dict(m, order="sorted"), 7)


@pytest.mark.parametrize("name", MIXES)
def test_lengths_on_quanta_and_clamps(name):
    m = mix(name)
    for key in ("prompt", "output"):
        spec = m[key]
        got = traffic.lengths(spec, traffic.count(m, 50))
        assert got.min() >= spec["min"] and got.max() <= spec["max"]
        assert np.all(got % spec.get("quantum", 1) == 0)
        assert np.median(got) == pytest.approx(spec["median"],
                                               rel=0.25)


def test_chunk_shapes():
    """Every chunk the decode mix makes is a multiple of 64 up to 256; the
    long-prompt mix makes only 256-token chunks."""
    p = traffic.lengths(mix("decode_offline")["prompt"], 160)
    assert set(p) <= {64, 128, 192, 256}
    q = traffic.lengths(mix("longprompt_poisson")["prompt"], 80)
    assert set(q % 256) == {0}


@pytest.mark.parametrize("seconds", [16, 50])
def test_poisson_rate(seconds):
    """The window holds rate x seconds arrivals on every seed, the first at
    its open and the last inside it, with gaps that add up to it."""
    m = mix("longprompt_poisson")
    for seed in (11, 12, BIG_SEED):
        reqs = traffic.generate(m, seed, 32000, seconds)
        assert len(reqs) == math.ceil(m["arrival"]["rate_per_s"] * seconds)
        assert reqs[0].arrival_s == 0.0
        assert 0.7 * seconds < reqs[-1].arrival_s < seconds


def test_percentile():
    assert traffic.percentile([1, 2, 3, 4], 50) == 2.5
    assert traffic.percentile([5], 95) == 5
    assert traffic.percentile(list(range(101)), 90) == 90
    with pytest.raises(ValueError):
        traffic.percentile([], 50)
