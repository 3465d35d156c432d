"""The traffic generator: reproducible by seed, within its clips, and the
same work in the same order for every seed."""
import collections

import numpy as np
import pytest

from bench import catalog

traffic = catalog.load_module("generators", "lognormal", catalog.BENCH_DIR)

MIXES = ["decode_long", "prefill_heavy", "chat_short"]
BIG_SEED = 2 ** 33 + 12345


def _mix(name):
    return catalog.load_json(catalog.BENCH_DIR / "traffic" / f"{name}.json")


def _draw(mix, seed, n=200):
    s = traffic.Stream(mix, seed, vocab=50257, max_total=1024)
    return [s.next_request() for _ in range(n)]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a, b = _draw(_mix(name), BIG_SEED), _draw(_mix(name), BIG_SEED)
    assert all(np.array_equal(p, q) and m == k for (p, m), (q, k) in zip(a, b))
    c = _draw(_mix(name), 7)
    assert any(not np.array_equal(p, q) for (p, _), (q, _) in zip(a, c))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_respect_clips_and_context(name):
    mix = _mix(name)
    for p, m in _draw(mix, 3):
        assert mix["prompt"]["min"] <= len(p) <= mix["prompt"]["max"]
        assert 1 <= m <= mix["output"]["max"] and len(p) + m <= 1024
        assert p.dtype == np.int32 and 0 <= p.min() and p.max() < 50257


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_lengths_in_the_same_order(name):
    n = traffic.BLOCK * 2
    lens = [[(len(p), m) for p, m in _draw(_mix(name), s, n)]
            for s in (1, 2, BIG_SEED)]
    assert lens[0] == lens[1] == lens[2]
    strata = collections.Counter(n for n, _ in lens[0][:traffic.BLOCK])
    assert strata == collections.Counter(traffic.lognormal_lengths(
        _mix(name)["prompt"], traffic.quantiles(traffic.BLOCK)).tolist())


def test_open_loop_arrivals_keep_the_rate():
    mix = _mix("chat_short")
    s = traffic.Stream(mix, 5, vocab=50257, max_total=1024)
    t = [s.next_arrival() for _ in range(traffic.BLOCK * 4)]
    assert np.all(np.diff(t) > 0)
    assert t[-1] == pytest.approx(len(t) / mix["rate_per_s"], rel=0.02)


def test_closed_loop_first_requests_are_cut_to_spread_shares():
    mix = _mix("decode_long")
    s = traffic.Stream(mix, 9, vocab=50257, max_total=1024)
    firsts = [s.first_request(c) for c in range(mix["clients"])]
    outs = sorted(m for _, m in firsts)
    assert outs[0] < mix["output"]["min"] and outs[-1] > mix["output"]["min"]


@pytest.mark.parametrize("name", MIXES)
def test_every_mix_names_a_generator_file(name):
    assert catalog.load_generator(_mix(name)["generator"]) is not None
