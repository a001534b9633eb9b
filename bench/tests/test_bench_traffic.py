"""The traffic generator: the same seed gives the same requests, every
seed the same sizes, and the sizes follow the mix's stated distribution."""
import math
from collections import Counter

import numpy as np
import pytest

from bench.harness import spec, traffic

MIXES = ("chat", "longprompt")


def mix(name):
    return spec.load_json(f"{spec.BENCH_DIR}/traffic/{name}.json")


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a = traffic.serve_requests(mix(name), 2**40 + 17, 1000)
    b = traffic.serve_requests(mix(name), 2**40 + 17, 1000)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.max_new == y.max_new and x.initial == y.initial
        np.testing.assert_array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_sizes_and_order_not_ids(name):
    m = mix(name)
    a = traffic.serve_requests(m, 5, 1000)
    b = traffic.serve_requests(m, 2**40 + 6, 1000)
    assert [(len(r.prompt), r.max_new, r.initial) for r in a] == \
        [(len(r.prompt), r.max_new, r.initial) for r in b]
    assert Counter((len(r.prompt), r.max_new) for r in a
                   if not r.initial) == Counter(traffic.sizes(m) * m["epochs"])
    assert not np.array_equal(a[-1].prompt[:8], b[-1].prompt[:8])


@pytest.mark.parametrize("name", MIXES)
def test_sizes_follow_the_stated_lognormal(name):
    m = mix(name)
    for part in ("prompt", "output"):
        s = m[part]
        x = traffic.lognormal_sizes(s, 1001)
        assert x.min() >= s["min"] and x.max() <= s["max"]
        assert abs(np.median(x) - s["median"]) <= 1
        # the unclipped middle spreads as the stated sigma: the quartiles
        # of a lognormal sit at median * exp(+-0.6745 sigma)
        q1, q3 = np.percentile(x, [25, 75])
        if q1 > s["min"] and q3 < s["max"]:
            assert math.log(q3 / q1) / (2 * 0.6745) == pytest.approx(
                s["sigma"], rel=0.02)


@pytest.mark.parametrize("name", MIXES)
def test_every_stratum_in_every_run_of_requests(name):
    m = mix(name)
    pairs = traffic.sizes(m)
    k = len(pairs) // m["strata"]
    group = {p: i // k for i, p in enumerate(pairs)}
    stream = [r for r in traffic.serve_requests(m, 9, 1000) if not r.initial]
    for start in range(0, len(stream), m["strata"]):
        run = stream[start:start + m["strata"]]
        assert sorted(group[(len(r.prompt), r.max_new)] for r in run) == \
            list(range(m["strata"]))


@pytest.mark.parametrize("name", MIXES)
def test_every_request_fits_its_slot(name):
    m = mix(name)
    reqs = traffic.serve_requests(m, 11, 1000)
    assert all(len(r.prompt) + r.max_new - 1 <= m["max_len"] for r in reqs)
    init = [r for r in reqs if r.initial]
    assert len(init) == m["slots"]
    # mid-flight requests owe only part of their output
    assert sum(r.max_new for r in init) < sum(
        o for _, o in traffic.initial_sizes(m)) + len(init) * m["output"]["max"]


def test_train_rows_differ_and_repeat():
    job = spec.load_json(f"{spec.BENCH_DIR}/traffic/train_6x2048.json")
    a = traffic.train_batch(job, 3, 0, 37984)
    assert a.shape == (job["batch"], job["seq"] + 1)
    np.testing.assert_array_equal(a, traffic.train_batch(job, 3, 0, 37984))
    rows = np.concatenate([traffic.train_batch(job, 3, k, 37984)
                           for k in range(4)])
    assert len({r.tobytes() for r in rows}) == len(rows)
    assert a.min() >= 0 and a.max() < 37984
