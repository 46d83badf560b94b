"""The generator gives every seed the same work: the same multiset of
sizes, and in a backlog the same order of sizes, with other token ids."""
import numpy as np

from perfbench import traffic

BATCH = traffic.load("batch")
SEEDS = (1, 2 ** 31 + 11, 2 ** 33 + 1)


def sizes(planned):
    return [(len(p.prompt), p.max_new, p.phase) for p in planned]


def test_backlog_serves_one_order_of_sizes_for_every_seed():
    plans = [traffic.backlog(BATCH, s, 32, 49152) for s in SEEDS]
    wave = [sorted(sizes(p[:32])) for p in plans]
    rest = [sizes(p[32:]) for p in plans]
    assert wave[0] == wave[1] == wave[2]
    assert rest[0] == rest[1] == rest[2]
    assert not np.array_equal(plans[0][40].prompt, plans[1][40].prompt)
    assert min(p.max_new for p in plans[0]) >= 1


def test_backlog_order_spans_the_range_early():
    p = traffic.backlog(BATCH, 5, 32, 49152)[32:48]
    prompts = sorted(len(r.prompt) for r in p)
    outputs = sorted(r.max_new for r in p)
    assert prompts[0] < 100 and prompts[-1] > 220
    assert outputs[0] < 350 and outputs[-1] > 930


def test_spread_order_is_a_permutation():
    for n in (1, 7, 32, 256):
        assert sorted(traffic.spread_order(n, traffic.PHI)) == list(range(n))
