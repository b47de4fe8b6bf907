"""The traffic generator: same work on every seed, in another order."""
import numpy as np

from perfbench.lib import traffic


def test_every_seed_gets_the_same_lengths_and_other_tokens():
    spec = traffic.load("decode_closed")
    a = traffic.requests(spec, 1000, 1)
    b = traffic.requests(spec, 1000, 2 ** 40 + 3)
    assert [(len(p), n) for p, n in a] == [(len(p), n) for p, n in b]
    assert not np.array_equal(a[0][0], b[0][0])
    assert all(128 <= len(p) <= 1024 and 128 <= n <= 256 for p, n in a)
    # the lengths spread over the whole range from the first requests on
    first = [len(p) for p, _ in a[:32]]
    assert min(first) < 256 and max(first) > 512


def test_a_seed_repeats_its_requests():
    spec = traffic.load("decode_closed")
    a, b = (traffic.requests(spec, 1000, 7) for _ in range(2))
    assert all(np.array_equal(p, q) and n == m for (p, n), (q, m) in zip(a, b))


def test_warmup_covers_each_power_of_two_band():
    spec = traffic.load("decode_closed")
    assert traffic.warmup_prompt_lengths(spec) == [128, 256, 512, 1024]
    spec = {"prompt_len": {"min": 100, "max": 1000}}
    assert traffic.warmup_prompt_lengths(spec) == [100, 128, 256, 512, 1000]
