"""The load generator: the same seed gives the same schedule, every seed
the same multiset of sizes, and the stated length and sharing statistics."""
import collections

import numpy as np
import tiny  # noqa: F401  (puts the checkout on sys.path)

from benchmark.lib import traffic

SHARED = tiny.load("traffic", "sessions-shared.json")
UNSHARED = tiny.load("traffic", "batch-unshared.json")
TRAIN = tiny.load("traffic", "b8-s1024.json")
VOCAB = 50304


def test_same_seed_same_schedule():
    a = traffic.serve_requests(SHARED, VOCAB, 2 ** 31 + 7, 40.0)
    b = traffic.serve_requests(SHARED, VOCAB, 2 ** 31 + 7, 40.0)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert all((x.prompt == y.prompt).all() and
               x.output_tokens == y.output_tokens for x, y in zip(a, b))


def test_seeds_give_the_same_sizes():
    # closed loop: the same sizes in the same order, other token ids
    a = traffic.serve_requests(UNSHARED, VOCAB, 1, 40.0)
    b = traffic.serve_requests(UNSHARED, VOCAB, 2 ** 31 + 2, 40.0)
    assert [(len(r.prompt), r.output_tokens) for r in a] \
        == [(len(r.prompt), r.output_tokens) for r in b]
    assert len({len(r.prompt) for r in a}) > 32       # and they do vary
    assert not (a[0].prompt[:8] == b[0].prompt[:8]).all()
    # open loop: the same cycle of sizes and gaps, begun at another point
    sa = traffic.serve_requests(SHARED, VOCAB, 1, 40.0)
    sb = traffic.serve_requests(SHARED, VOCAB, 2, 40.0)
    pa = [(len(r.prompt), r.output_tokens) for r in sa]
    pb = [(len(r.prompt), r.output_tokens) for r in sb]
    assert pa != pb and sorted(pa) == sorted(pb)
    ga = np.round(np.diff([r.due_s for r in sa]), 9)
    gb = np.round(np.diff([r.due_s for r in sb]), 9)
    # the same gaps, all but the one at the seam of the cycle
    assert len(sa) == len(sb) and len(set(ga) ^ set(gb)) <= 2


def test_shared_mix_statistics():
    mix = dict(SHARED, arrivals={"process": "poisson", "rate_per_s": 5.0})
    reqs = traffic.serve_requests(mix, VOCAB, 3, 200.0)
    n = len(reqs)
    assert 0.9 * 1000 <= n <= 1000
    due = np.array([r.due_s for r in reqs])
    assert due[0] == 0.0 and (np.diff(due) >= 0).all() and due[-1] < 200.0
    # Poisson: the gaps' mean is 1/rate and their spread about the same
    gaps = np.diff(due)
    assert abs(gaps.mean() - 0.2) < 0.02 and abs(gaps.std() - 0.2) < 0.03
    tails = np.array([len(r.prompt) - 384 for r in reqs])
    assert tails.min() >= 65 and tails.max() <= 128
    assert abs(tails.mean() - 96.5) < 2
    outs = np.array([r.output_tokens for r in reqs])
    assert outs.min() >= 8 and outs.max() <= 128
    assert abs(np.median(outs) - 48) <= 2
    # Zipf, exponent 1, 64 prefixes: the first has 1/H_64 = 21% of requests
    by = collections.Counter(r.prefix_id for r in reqs)
    assert abs(by[0] / n - 0.2108) < 0.02
    assert by[0] > by[1] > by[3] > by[15]
    # a prefix is the same run of tokens in every request that carries it
    first = [r for r in reqs if r.prefix_id == 0]
    assert all((r.prompt[:384] == first[0].prompt[:384]).all() for r in first)
    other = next(r for r in reqs if r.prefix_id == 1)
    assert not (other.prompt[:384] == first[0].prompt[:384]).all()
    # tails are unique
    assert len({r.prompt[384:].tobytes() for r in reqs}) == n


def test_unshared_mix_shares_nothing():
    reqs = traffic.serve_requests(UNSHARED, VOCAB, 4, 40.0)
    assert len(reqs) == UNSHARED["pool"]
    assert all(r.prefix_id == -1 and r.due_s is None for r in reqs)
    assert min(len(r.prompt) for r in reqs) >= 449
    assert max(len(r.prompt) for r in reqs) <= 512
    assert len({r.prompt[:16].tobytes() for r in reqs}) == len(reqs)
    # a program fast enough to come to the pool's end gets the same sizes
    # again with token ids it has not seen: the cache still has no hit
    more = traffic.serve_requests(UNSHARED, VOCAB, 4, 40.0, cycle=1)
    assert [(len(r.prompt), r.output_tokens) for r in more] \
        == [(len(r.prompt), r.output_tokens) for r in reqs]
    assert [r.index for r in more] == list(range(len(reqs), 2 * len(reqs)))
    assert len({r.prompt[:16].tobytes() for r in reqs + more}) \
        == 2 * len(reqs)


def test_warmup_prefix_is_not_a_window_prefix():
    warm = traffic.warmup_requests(SHARED, VOCAB, 5)
    reqs = traffic.serve_requests(SHARED, VOCAB, 5, 40.0)
    assert (warm[0].prompt[:384] == warm[1].prompt[:384]).all()
    assert all(not (r.prompt[:384] == warm[0].prompt[:384]).all()
               for r in reqs)


def test_train_batches():
    a = traffic.train_batch(TRAIN, VOCAB, 2 ** 33, 0)
    b = traffic.train_batch(TRAIN, VOCAB, 2 ** 33, 0)
    c = traffic.train_batch(TRAIN, VOCAB, 2 ** 33, 1)
    assert a[0].shape == (8, 1024) and a[0].dtype == np.int32
    assert (a[0] == b[0]).all() and (a[1] == b[1]).all()
    assert not (a[0] == c[0]).all() and not (a[0] == a[1]).all()
    assert len({row.tobytes() for row in a[0]}) == 8      # rows all differ
    assert a[0].min() >= 0 and a[0].max() < VOCAB
