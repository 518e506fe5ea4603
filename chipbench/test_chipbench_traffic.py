"""The seeded traffic generator: same seed, same work; sizes as declared."""
import json
import os

import numpy as np
import pytest

from chipbench import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(HERE, "traffic"))
               if f.endswith(".json"))
BIG_SEED = 2**33 + 12345  # seeds exceed 32 bits


def _mix(name):
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def _make(mix, seed):
    return traffic.closed_pool(mix, seed, 50304)


def _key(specs):
    return [(s.prompt.tolist(), s.max_new) for s in specs]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    mix = _mix(name)
    traffic.validate(mix)
    assert _key(_make(mix, BIG_SEED)) == _key(_make(mix, BIG_SEED))


@pytest.mark.parametrize("name", MIXES)
def test_other_seed_other_order_same_sizes(name):
    mix = _mix(name)
    a, b = _make(mix, BIG_SEED), _make(mix, BIG_SEED + 1)
    assert _key(a) != _key(b)
    assert [s.prompt.tolist() for s in a] != [s.prompt.tolist() for s in b]
    # the same multiset of sizes: the seed permutes, it does not resize
    assert sorted(len(s.prompt) for s in a) == sorted(len(s.prompt) for s in b)
    assert sorted(s.max_new for s in a) == sorted(s.max_new for s in b)


@pytest.mark.parametrize("name", MIXES)
def test_clipping_and_medians(name):
    mix = _mix(name)
    for key in ("prompt", "output"):
        dist = mix[key]
        q = traffic.quantiles(dist, 4001)
        assert q.min() >= dist["min"] and q.max() <= dist["max"]
        median = (dist["median"] if dist["dist"] == "lognormal"
                  else (dist["min"] + dist["max"]) / 2)
        assert abs(float(np.median(q)) - median) <= 1.0
    specs = _make(mix, 7)
    assert all(1 <= len(s.prompt) and s.max_new >= 1 for s in specs)
    assert max(len(s.prompt) + s.max_new for s in specs) <= traffic.max_len(mix)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_same_sizes_in_each_block(name):
    mix = _mix(name)
    b = mix["block"]
    a, c = _make(mix, BIG_SEED), _make(mix, 3)
    for i in range(0, len(a), b):
        for key in (lambda s: len(s.prompt), lambda s: s.max_new):
            assert sorted(map(key, a[i:i + b])) == sorted(map(key, c[i:i + b]))
    # each block spans the distribution: one length from each stratum
    q = np.sort(traffic.quantiles(mix["prompt"], len(a))).reshape(b, -1)
    first = sorted(len(s.prompt) for s in a[:b])
    assert all(lo <= v <= hi for v, lo, hi in zip(first, q[:, 0], q[:, -1]))


@pytest.mark.parametrize("change", [
    {"loop": "open"},
    {"block": 7},
    {"slots": 0},
    {"concurrency": 8, "slots": 16},
    {"prompt": {"dist": "lognormal", "median": 128, "sigma": 0.7,
                "min": 0, "max": 512}},
    {"output": {"dist": "uniform", "min": 512, "max": 128}},
], ids=["open_loop", "partial_block", "no_slots", "fewer_clients_than_slots", "empty_prompt",
        "min_over_max"])
def test_validate_rejects(change):
    mix = dict(_mix("batch-decode"), **change)
    with pytest.raises(ValueError):
        traffic.validate(mix)


def test_unknown_length_distribution_raises():
    with pytest.raises(ValueError):
        traffic.quantiles({"dist": "zipf", "min": 1, "max": 9}, 10)
