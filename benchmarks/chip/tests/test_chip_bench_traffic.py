"""The traffic generator: seeded, the same work for every seed."""
import os
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..",
                                    ".."))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import json  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchmarks.chip import traffic as T  # noqa: E402

MIXES = ("chat-offline", "short-poisson")


def mix(name):
    with open(os.path.join(ROOT, "benchmarks", "chip", "traffic",
                           f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a, b = T.Traffic(mix(name), 32064, 2**31 + 7), T.Traffic(mix(name), 32064,
                                                               2**31 + 7)
    for i in (0, 1, 17, len(a) - 1):
        ra, rb = a.request(i), b.request(i)
        assert np.array_equal(ra.prompt, rb.prompt)
        assert (ra.max_new, ra.due_s) == (rb.max_new, rb.due_s)
    c = T.Traffic(mix(name), 32064, 2**31 + 8)
    assert not np.array_equal(a.request(0).prompt[:8], c.request(0).prompt[:8])


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_serves_the_same_work(name):
    m = mix(name)
    a, b = T.Traffic(m, 1000, 1), T.Traffic(m, 1000, 99)
    assert sorted(a.prompt_lens) == sorted(b.prompt_lens)
    assert sorted(a.output_lens) == sorted(b.output_lens)
    assert a.prompt_lens != b.prompt_lens
    # Each block of STRATA requests holds one length from every stratum,
    # so any prefix of the queue carries nearly the same work.
    for n in (8, 64, 256):
        pa, pb = sum(a.prompt_lens[:n]), sum(b.prompt_lens[:n])
        assert abs(pa - pb) / pa < 0.15
    if m["arrival"]["kind"] == "poisson":
        ga, gb = np.diff(a.due), np.diff(b.due)
        assert (ga > 0).all() and (gb > 0).all()
        # The same gaps in another order: all but one of them appear.
        assert len(set(ga.round(9)) & set(gb.round(9))) >= len(ga) - 1
        assert abs(np.mean(ga) * m["arrival"]["rate_per_s"] - 1) < 0.05


@pytest.mark.parametrize("name", MIXES)
def test_lengths_follow_the_mix(name):
    m = mix(name)
    t = T.Traffic(m, 1000, 3)
    p, o = np.array(t.prompt_lens), np.array(t.output_lens)
    assert (p % m["prompt"]["round_to"] == 0).all()
    assert p.min() >= m["prompt"]["min"] and p.max() <= m["prompt"]["max"]
    assert o.min() >= m["output"]["min"] and o.max() <= m["output"]["max"]
    assert m["prompt"]["median"] <= np.median(p) < m["prompt"]["median"] + 64
    assert abs(np.median(o) - m["output"]["median"]) <= 1
    r = t.request(5)
    assert r.prompt.dtype == np.int32 and len(r.prompt) == p[5]
    assert r.prompt.min() >= T.FIRST_TOKEN_ID and r.prompt.max() < 1000


def test_unknown_kinds_and_exhaustion_raise():
    m = dict(mix("short-poisson"), requests=8)
    with pytest.raises(IndexError):
        T.Traffic(m, 100, 0).request(8)
    with pytest.raises(ValueError):
        T.Traffic(dict(m, arrival={"kind": "bursty"}), 100, 0)
    with pytest.raises(ValueError):
        T.Traffic(dict(m, requests=12), 100, 0)
