"""The generator is deterministic in its seed, gives every seed the same
set of sizes, and matches the distributions its mixes state."""
import json
import os
import sys
from statistics import NormalDist

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from serve_bench import traffic as tr  # noqa: E402

MIXES = os.path.join(os.path.dirname(HERE), "traffic")
NAMES = sorted(f[:-5] for f in os.listdir(MIXES) if f.endswith(".json"))
BIG_SEED = 2 ** 31 + 12345


def _mix(name):
    with open(os.path.join(MIXES, name + ".json")) as f:
        return json.load(f)


def _reqs(mix, seed, vocab=1000, max_seq=8192):
    """Every request the mix's arrival process sends, with no request
    finishing: an open loop sends its whole schedule, a closed loop one
    request per client."""
    src = tr.Traffic(mix, seed, vocab, max_seq).source(
        {"rate_per_s": 2.0, "clients": 64}, 10.0, 40.0)
    return [r for r, _ in src.take(1e9)] + [r for r, _ in src.take(0.0)]


def _poisson(t, rate, lead_s, window_s):
    return t.source({"rate_per_s": rate}, lead_s, window_s).reqs


@pytest.mark.parametrize("name", NAMES)
def test_same_seed_same_requests(name):
    a, b = _reqs(_mix(name), BIG_SEED), _reqs(_mix(name), BIG_SEED)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new, x.due, x.prefix) == (y.max_new, y.due, y.prefix)


@pytest.mark.parametrize("name", NAMES)
def test_seeds_share_the_schedule_not_the_tokens(name):
    """Two seeds send the same lengths at the same times, in the same
    order, with other token ids."""
    a, b = _reqs(_mix(name), 7), _reqs(_mix(name), BIG_SEED)
    assert [(len(r.prompt), r.max_new, r.due, r.prefix) for r in a] == \
        [(len(r.prompt), r.max_new, r.due, r.prefix) for r in b]
    own = [r for r in a if r.prefix < 0] or a
    assert not np.array_equal(own[0].prompt[-8:], b[a.index(own[0])]
                              .prompt[-8:])
    # the schedule is shuffled, not sorted
    assert [len(r.prompt) for r in a] != sorted(len(r.prompt) for r in a)


@pytest.mark.parametrize("name", NAMES)
def test_lengths_follow_the_stated_distribution(name):
    mix = _mix(name)
    for part in ("prompt", "output"):
        d = mix[part]
        x = tr.quantiles(d, 1001)
        assert x.min() >= d["min"] and x.max() <= d["max"]
        if d["dist"] == "lognormal":
            # the median of the unclipped lognormal is kept by the clip
            assert abs(np.median(x) - np.clip(d["median"], d["min"],
                                              d["max"])) <= 1
            z = NormalDist().inv_cdf(0.25)
            q1 = np.clip(d["median"] * np.exp(d["sigma"] * z), d["min"],
                         d["max"])
            assert abs(np.percentile(x, 25) - q1) <= 0.02 * q1 + 1
        else:
            assert abs(x.mean() - (d["min"] + d["max"]) / 2) <= 1


def test_open_loop_rate_and_segments():
    reqs = _poisson(tr.Traffic(_mix("chat"), 3, 1000, 8192), 2.5, 10.0, 40.0)
    due = np.array([r.due for r in reqs])
    assert (due[:25] < 0).all() and len(due) == 25 + 100
    assert due.min() >= -10.0 and due.max() < 40.0
    assert ((due >= 0) & (due < 40.0)).sum() == 100
    gaps = np.diff(due[25:])
    # exponential gaps: standard deviation close to the mean
    assert 0.8 < gaps.std() / gaps.mean() < 1.2


def test_shared_prefix_popularity_is_zipf():
    mix = _mix("sysprompt")
    t = tr.Traffic(mix, 5, 1000, 8192)
    reqs = _poisson(t, 5.0, 0.0, 100.0)
    counts = np.bincount([r.prefix for r in reqs], minlength=4)
    assert counts.sum() == 500
    assert list(counts) == list(tr.zipf_counts(4, 1.0, 500))
    assert counts[0] > counts[1] > counts[2] > counts[3]
    n = mix["prefix"]["tokens"]
    for r in reqs[:20]:
        assert np.array_equal(r.prompt[:n], t.prefixes[r.prefix])


def test_a_request_never_reaches_the_engine_clamp():
    with pytest.raises(ValueError, match="at most"):
        tr.Traffic(_mix("chat"), 0, 1000, max_seq=1024)
    tr.Traffic(_mix("chat"), 0, 1000, max_seq=1280)


def test_boundary_lengths_cover_buckets_and_page_counts():
    d = {"min": 32, "max": 1023}
    marks = [32, 128, 512, 1280] + tr.page_marks(16, 1280)
    got = tr.boundary_lengths(d, marks)
    for x in (32, 33, 128, 129, 256, 257, 512, 513, 1023):
        assert x in got
    assert min(got) == 32 and max(got) == 1023


def test_closed_loop_sends_when_a_client_frees_up():
    src = tr.Traffic(_mix("decode"), 4, 1000, 8192).source(
        {"clients": 3}, 10.0, 40.0)
    assert src.closed and src.next_due() is None
    first = src.take(-10.0)
    assert len(first) == 3 and src.take(-5.0) == []
    src.finished(first[0][0], [1, 2], -2.0)
    (r, due), = src.take(-1.0)
    assert due == -1.0 and r is not first[0][0]
    src.finished(r, [1], 39.0)
    assert src.take(40.0) == []      # none after the window's close


def test_knee_simulation_follows_littles_law():
    """Below saturation the simulated slots are busy rate x holding time /
    slots of the time, a request holding its slot one tick per token."""
    from serve_bench import knee, spec

    cell = spec.load_cell("stablelm-1.6b.chat")
    rate, tick, seconds = 0.05, 0.4, 20000.0
    got = knee.occupancy(cell, rate, seconds, tick, 0.0)
    hold = tr.quantiles(cell.mix["output"], int(rate * seconds)).mean() * tick
    want = rate * hold / cell.engine["slots"]
    assert abs(got["occupancy"] - want) < 0.03 * want
    assert got["queue"] < 0.05
    # past the knee the queue grows without end
    assert knee.occupancy(cell, 0.3, seconds, tick, 0.0)["queue"] > 50
