"""The generator: the same seed gives the same backlog, another seed other
token ids on the same lengths, and the clips hold."""

import numpy as np

from lib import traffic

MIX = {"prompt": {"dist": "lognormal", "median": 384, "sigma": 0.8, "min": 64, "max": 1536},
       "output": {"dist": "lognormal", "median": 256, "sigma": 0.6, "min": 32, "max": 512}}
BIG = 2 ** 31 + 12345


def _lens(reqs):
    return [len(r["prompt"]) for r in reqs], [r["max_new"] for r in reqs]


def test_same_seed_same_backlog():
    a, b = traffic.backlog(MIX, 300, 32, 65024, BIG), traffic.backlog(MIX, 300, 32, 65024, BIG)
    assert _lens(a) == _lens(b)
    assert all(np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, b))


def test_other_seed_other_tokens_same_lengths():
    a, b = traffic.backlog(MIX, 320, 32, 65024, BIG), traffic.backlog(MIX, 320, 32, 65024, BIG + 1)
    assert _lens(a) == _lens(b)
    assert not any(np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, b))
    # seeds that differ only above 32 bits differ too
    c = traffic.backlog(MIX, 320, 32, 65024, BIG + 2 ** 40)
    assert not np.array_equal(c[0]["prompt"], a[0]["prompt"])
    # the order within a block is not sorted
    assert _lens(a)[0][:32] != sorted(_lens(a)[0][:32])


def test_every_block_holds_the_same_lengths():
    reqs = traffic.backlog(MIX, 1024, 32, 65024, 3)
    p, o = map(np.asarray, _lens(reqs))
    pset, oset = traffic.length_set(MIX["prompt"], 32), traffic.length_set(MIX["output"], 32)
    for i in range(0, 1024, 32):
        assert sorted(p[i:i + 32]) == sorted(pset) and sorted(o[i:i + 32]) == sorted(oset)
    # at least 16 to a block, where the slots are fewer
    p2 = np.asarray(_lens(traffic.backlog(MIX, 64, 2, 65024, 3))[0])
    assert sorted(p2[:16]) == sorted(traffic.length_set(MIX["prompt"], 16))


def test_clips_and_arrivals():
    reqs = traffic.backlog(MIX, 1024, 32, 65024, 3)
    p, o = map(np.asarray, _lens(reqs))
    assert p.min() >= 64 and p.max() <= 1536 and o.min() >= 32 and o.max() <= 512
    assert p.max() == 1536  # the upper clip binds
    s = traffic.length_set(MIX["prompt"], 1000)
    assert abs(np.median(s) - 384) <= 2
    assert [r["arrival"] for r in reqs[:34]] == list(range(32)) + [32, 32]
    assert all(0 <= r["prompt"].min() and r["prompt"].max() < 65024 for r in reqs)
