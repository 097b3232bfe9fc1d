"""The traffic generator: seeded, inside its clips, open loop, and the
same work for every seed."""
import json

import numpy as np
import pytest

from bench import spec, traffic

MIXES = sorted(p.stem for p in (spec.BENCH_DIR / "traffic").glob("*.json"))


def mix(name):
    return json.loads((spec.BENCH_DIR / "traffic" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_stream(name):
    a = traffic.generate(mix(name), 5.0, 20.0, 2 ** 31 + 11, 1000)
    b = traffic.generate(mix(name), 5.0, 20.0, 2 ** 31 + 11, 1000)
    c = traffic.generate(mix(name), 5.0, 20.0, 2 ** 31 + 12, 1000)
    key = lambda rs: [(r.arrival_s, r.prompt.tolist(), r.max_new_tokens,
                       r.temperature, r.top_p) for r in rs]
    assert key(a) == key(b)
    assert key(a) != key(c)


@pytest.mark.parametrize("name", MIXES)
def test_lengths_inside_clips_and_vocab(name):
    m = mix(name)
    reqs = traffic.generate(m, 8.0, 30.0, 3, 1000)
    pt, ot = m["prompt_tokens"], m["output_tokens"]
    for r in reqs:
        assert pt["min"] <= len(r.prompt) <= pt["max"]
        assert ot["min"] <= r.max_new_tokens <= ot["max"]
        assert r.prompt.min() >= 0 and r.prompt.max() < 1000
    lens = np.array([len(r.prompt) for r in reqs])
    assert abs(np.median(lens) - pt["median"]) <= 0.1 * pt["median"]


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_work(name):
    runs = [traffic.generate(mix(name), 5.0, 20.0, s, 1000)
            for s in (1, 2, 2 ** 31 + 5)]
    sizes = [sorted((len(r.prompt), r.max_new_tokens) for r in rs)
             for rs in runs]
    assert sorted(len(r.prompt) for r in runs[0]) == \
        sorted(len(r.prompt) for r in runs[1])
    assert sorted(r.max_new_tokens for r in runs[0]) == \
        sorted(r.max_new_tokens for r in runs[2])
    gaps = [np.sort(np.diff([r.arrival_s for r in rs])) for rs in runs]
    np.testing.assert_allclose(gaps[0], gaps[1], rtol=1e-9)
    assert sizes[0] != sizes[1]          # paired differently per seed


def test_arrivals_open_loop_over_the_window():
    rate, seconds = 6.0, 40.0
    reqs = traffic.generate(mix("chat"), rate, seconds, 9, 1000)
    due = np.array([r.arrival_s for r in reqs])
    assert len(reqs) == round(rate * seconds)
    assert due[0] == 0.0 and np.all(np.diff(due) >= 0)
    assert due[-1] < seconds
    # Due times are fixed up front from (seed, rate, window) alone, and
    # gaps follow the exponential: bursts and lulls, mean 1/rate.
    gaps = np.diff(due)
    assert abs(gaps.mean() - 1 / rate) < 0.05 / rate
    assert gaps.std() > 0.7 * gaps.mean()


def test_greedy_share_holds_the_longest_request():
    m = mix("chat")
    reqs = traffic.generate(m, 5.0, 40.0, 4, 1000)
    greedy = [r for r in reqs if r.greedy]
    assert len(greedy) == round(m["greedy_share"] * len(reqs))
    assert max(reqs, key=lambda r: r.total_len).greedy
    for r in reqs:
        if not r.greedy:
            assert r.temperature == m["sampling"]["temperature"]
            assert r.top_p == m["sampling"]["top_p"]

