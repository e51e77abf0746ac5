"""The traffic generator: seeded, clipped, the same work for every seed."""

import json
import os

import pytest

from benchmarks import traffic

ROOT = os.path.join(os.path.dirname(__file__), "..", "..", "benchmarks")
LOADS = {"chat": {"rate_rps": 7.0}, "decode-heavy": {"clients": 48}}


def _requests(plan):
    if plan["loop"] == "open":
        return [r for s in plan["segments"] for r in s["requests"]]
    return [r for rnd in plan["rounds"][1:] for r in rnd]


def _build(mix_name, seed, seconds=20.0):
    mix = traffic.load_mix(mix_name, ROOT)
    return mix, traffic.build_schedule(mix, LOADS[mix_name], seed, seconds,
                                       5.0, 1000, 4096)


@pytest.mark.parametrize("mix_name", ["chat", "decode-heavy"])
def test_schedule_is_seeded_clipped_and_steady(mix_name):
    mix, a = _build(mix_name, 2 ** 31 + 5)
    _, again = _build(mix_name, 2 ** 31 + 5)
    _, other = _build(mix_name, 7)
    # The same seed gives the same schedule and lengths, another differs.
    assert json.dumps(a) == json.dumps(again)
    assert json.dumps(a) != json.dumps(other)
    pl, ol = mix["prompt_len"], mix["output_len"]
    for r in _requests(a):
        assert pl["min"] <= len(r["prompt"]) <= pl["max"]
        assert ol["min"] <= r["max_tokens"] <= ol["max"]
        assert all(1 <= t < 1000 for t in r["prompt"])
    # Every seed offers the same set of requests (and, open loop, of
    # gaps): only the order, and the token ids, differ.
    pairs = lambda p: sorted((len(r["prompt"]), r["max_tokens"])  # noqa: E731
                             for r in _requests(p))
    assert pairs(a) == pairs(other)
    if a["loop"] == "open":
        # Due times are fixed in the plan, before anything is sent: they
        # are plain numbers of the schedule, increasing, inside their
        # segment, and at the cell's rate.
        for seg in a["segments"]:
            due = [r["due"] for r in seg["requests"]]
            assert due == sorted(due)
            assert seg["start"] <= due[0] and \
                due[-1] <= seg["start"] + seg["seconds"]
            assert len(due) == round(7.0 * seg["seconds"])
        win, win2 = a["segments"][1], other["segments"][1]
        gaps = lambda s: sorted(round(y["due"] - x["due"], 6) for x, y in  # noqa: E731
                                zip(s["requests"], s["requests"][1:]))
        assert sum(gaps(win)) == pytest.approx(sum(gaps(win2)), rel=0.2)
    else:
        assert a["clients"] == 48 and all(len(r) == 48 for r in a["rounds"])
        # The first round is cut to a spread of phases.
        first = [r["max_tokens"] for r in a["rounds"][0]]
        assert min(first) < ol["min"] and max(first) <= ol["max"]


def test_blocks_carry_the_same_work_for_every_seed():
    # 7.0 requests/s x 24 s = 168 requests = 7 groups of 24 exactly.
    mix, a = _build("chat", 11, seconds=24.0)
    _, b = _build("chat", 12, seconds=24.0)
    assert traffic.BLOCK == 24

    def groups(plan):
        reqs = plan["segments"][1]["requests"]
        assert len(reqs) == 168
        return sorted(sorted((len(r["prompt"]), r["max_tokens"])
                             for r in reqs[i:i + 24])
                      for i in range(0, 168, 24))
    # The groups are the same SETS of requests for both seeds; the seed
    # orders the groups and the requests inside each.
    assert groups(a) == groups(b)
    tokens = [sum(o for _, o in g) for g in groups(a)]
    assert max(tokens) < 1.2 * min(tokens)
    assert [r["max_tokens"] for r in a["segments"][1]["requests"]] != \
        [r["max_tokens"] for r in b["segments"][1]["requests"]]


def test_stratified_lengths_follow_the_distribution():
    d = {"dist": "lognormal", "median": 384, "sigma": 0.9, "min": 32,
         "max": 3072}
    v = sorted(traffic.stratified_lengths(d, 201))
    assert v[100] == 384 and v[0] >= 32 and v[-1] == 3072
    g = traffic.stratified_gaps({"gaps": "exponential"}, 8.0, 400)
    assert g.sum() == pytest.approx(50.0) and g.min() > 0
    # the exponential's own shape: standard deviation about the mean
    assert g.std() == pytest.approx(g.mean(), rel=0.1)


def test_shape_warmup_covers_every_bucket_pair():
    stages = traffic.shape_warmup(3, 1000, 64, 2048)
    assert [s["decoders"] for s in stages] == [6, 12, 24, 48]
    for s in stages:
        totals = [s["decoders"] + len(p["prompt"]) for p in s["probes"]]
        # just under each power-of-two token level above the decoders
        assert all((t + 4) & (t + 3) == 0 for t in totals)
        assert totals[-1] == 2044


def test_scoring_requests_are_seeded():
    a = traffic.scoring_requests(2 ** 31 + 9, 500)
    assert a == traffic.scoring_requests(2 ** 31 + 9, 500)
    assert a != traffic.scoring_requests(2 ** 31 + 10, 500)
    assert [len(r["prompt"]) for r in a] == [97, 160, 209, 48]
