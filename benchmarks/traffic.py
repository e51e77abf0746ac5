"""The one general traffic generator: a mix file of shapes, a load level
and a seed in; a schedule of requests out. Imports no JAX.

A mix (``benchmarks/traffic/<mix>.json``) holds shapes only: the loop
kind, the length distributions and the distribution of the gaps between
arrivals. The load level (``rate_rps`` or ``clients``) belongs to the
cell.

What it makes is a FIXED TRACE replayed in a seeded order, not a random
process: every seed gets the same work. The lengths of one segment are
the distribution's own quantiles at (i + 0.5) / n, paired prompt with
output by a fixed shuffle: the same set of requests for every seed. The
gaps between open-loop arrivals are the quantiles of the gap
distribution, likewise the same set for every seed, so a segment always
spans the same time and offers the same tokens. An open-loop segment is
dealt into blocks of ``BLOCK`` requests that each span the whole
distribution (``block_order``); the seed orders the blocks, the
requests and gaps inside each, and draws the token ids. So arrivals
bunch and long requests meet only at scales under one block (about
``BLOCK`` / rate seconds); a window does not swing with where a seed put
the longest requests, and it does not show what bursts over several
seconds do. Due times are fixed before the first request is sent: they
cannot depend on a response.
"""

import json
import math
import os
from statistics import NormalDist

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
# Requests to a block of an open-loop segment (see ``block_order``).
BLOCK = 24


def load_mix(name: str, root: str = HERE) -> dict:
    with open(os.path.join(root, "traffic", f"{name}.json")) as f:
        return json.load(f)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def stratified_lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths: the distribution's quantiles, clipped to
    [min, max]. Kinds: lognormal (median, sigma), uniform."""
    q = _quantiles(n)
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in q])
        vals = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "uniform":
        vals = dist["min"] + q * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    lo = dist.get("min", 1)
    hi = dist.get("max", math.inf)
    return np.clip(np.rint(vals), lo, hi).astype(int)


def stratified_gaps(arrivals: dict, rate_rps: float, n: int) -> np.ndarray:
    """``n`` gaps between arrivals with mean 1 / rate: the quantiles of
    the gap distribution (``exponential``: the gaps a Poisson process
    has, as a fixed set)."""
    q = _quantiles(n)
    kind = arrivals["gaps"]
    if kind == "exponential":
        gaps = -np.log1p(-q)
    else:
        raise ValueError(f"unknown gap distribution {kind!r}")
    return gaps * (n / rate_rps / gaps.sum())


def block_order(keys: np.ndarray, block: int | None,
                rng: np.random.Generator) -> np.ndarray:
    """A seeded order (indices) of ``len(keys)`` items. With ``block``,
    the items sorted by key are dealt round-robin into groups of about
    ``block``, so every group spans the whole distribution and is the SAME
    set for every seed; the seed shuffles each group and the order of the
    groups. Any stretch of ``block`` requests then carries the same work
    whatever the seed, and a window of a few tens of seconds does not
    swing with where the seed put the longest requests."""
    n = len(keys)
    if not block or n <= block:
        return rng.permutation(n)
    groups = max(1, round(n / block))
    srt = np.argsort(keys, kind="stable")
    parts = [rng.permutation(srt[g::groups]) for g in range(groups)]
    return np.concatenate([parts[i] for i in rng.permutation(groups)])


def _segment(mix: dict, n: int, vocab: int, max_total: int,
             rng: np.random.Generator, block: int | None = None) -> list[dict]:
    """``n`` requests: the same (prompt, output) length pairs for every
    seed (stratified lengths, paired by a fixed shuffle), in a seeded
    order (in blocks by output length, given ``block``), with seeded
    token ids."""
    plen = stratified_lengths(mix["prompt_len"], n)
    olen = np.random.default_rng(n).permutation(
        stratified_lengths(mix["output_len"], n))
    out = []
    for i in block_order(olen, block, rng):
        p, o = int(min(plen[i], max_total - olen[i])), int(olen[i])
        out.append({"prompt": rng.integers(1, vocab, size=p).tolist(),
                    "max_tokens": o})
    return out


def open_segments(mix: dict, steps: list, rng: np.random.Generator,
                  vocab: int, max_model_len: int) -> dict:
    """Back-to-back open-loop segments ``(name, rate_rps, seconds)``, each
    a list of requests with ``due`` seconds from the first one's start."""
    segs, t0 = [], 0.0
    for name, rate, dur in steps:
        n = max(1, round(rate * dur))
        reqs = _segment(mix, n, vocab, max_model_len, rng, BLOCK)
        gaps = stratified_gaps(mix["arrivals"], n / dur, n)
        gaps = gaps[block_order(gaps, BLOCK, rng)]
        # A request sits in the middle of its gap: the segment neither
        # starts with a burst nor ends with a hole.
        due = t0 + np.cumsum(gaps) - gaps / 2
        for r, d in zip(reqs, due):
            r["due"] = float(d)
        segs.append({"name": name, "start": t0, "seconds": dur,
                     "rate_rps": rate, "requests": reqs})
        t0 += dur
    return {"loop": "open", "segments": segs}


def run_rng(seed: int, stream: int) -> np.random.Generator:
    """Any whole number up to a little over 2**31 (and beyond) is a seed."""
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, stream])


def build_schedule(mix: dict, load: dict, seed: int, seconds: float,
                   ramp_s: float, vocab: int, max_model_len: int) -> dict:
    """The plan's traffic part for one run.

    Open loop: ``segments`` = ramp (unmeasured) then window, each a
    list of requests with ``due`` seconds from the start of the ramp.
    Closed loop: ``rounds`` of ``clients`` requests each (every round
    the same multiset of lengths, shuffled), which clients take in
    order; the first round's outputs are cut to a spread of phases so
    the batch is in steady state after the ramp."""
    rng = run_rng(seed, 1)
    if mix["loop"] == "open":
        rate = float(load["rate_rps"])
        return open_segments(mix, [("ramp", rate, ramp_s),
                                   ("window", rate, seconds)],
                             rng, vocab, max_model_len)
    if mix["loop"] == "closed":
        clients = int(load["clients"])
        # Enough rounds that no client runs dry: a request takes at
        # least ~5 ms a token.
        shortest = float(stratified_lengths(mix["output_len"], clients).min())
        n_rounds = 2 + math.ceil((ramp_s + seconds) /
                                 max(0.25, shortest * 0.004))
        rounds = [_segment(mix, clients, vocab, max_model_len, rng)
                  for _ in range(min(n_rounds, 64))]
        for i, r in enumerate(rounds[0]):
            r["max_tokens"] = max(2, r["max_tokens"] * (i + 1) // clients)
        return {"loop": "closed", "clients": clients, "rounds": rounds,
                "ramp_s": ramp_s, "seconds": seconds}
    raise ValueError(f"unknown loop kind {mix['loop']!r}")


def shape_warmup(seed: int, vocab: int, max_num_seqs: int,
                 max_num_batched_tokens: int) -> list[dict]:
    """Stages for the load generator's ``warm_shapes``: decoders at
    three quarters of each request bucket (8, 16, ... max_num_seqs) and,
    for each, probe prompts that bring the step's token count just under
    each token bucket (16, 32, ... the token budget) above it."""
    rng = run_rng(seed, 3)

    def req(n, out):
        return {"prompt": rng.integers(1, vocab, size=n).tolist(),
                "max_tokens": out}

    stages, r = [], 8
    while r <= max_num_seqs:
        d = r * 3 // 4
        probes, level = [], 16
        while level <= max_num_batched_tokens:
            if level - d - 4 >= 1:
                probes.append(req(level - d - 4, 1))
            level *= 2
        stages.append({"decoders": d, "probes": probes, "decoder_requests": [
            req(8, 1500) for _ in range(d)]})
        r *= 2
    return stages


def scoring_requests(seed: int, vocab: int, lengths=(97, 160, 209, 48),
                     max_tokens: int = 8) -> list[dict]:
    """A handful of seeded prompts scored position by position (echo +
    logprobs) and continued greedily, for the reference comparison.
    The lengths straddle page (16) and tile boundaries."""
    rng = run_rng(seed, 2)
    return [{"prompt": rng.integers(1, vocab, size=n).tolist(),
             "max_tokens": max_tokens} for n in lengths]


def describe(schedule: dict) -> dict:
    """What was drawn, for the run's log lines."""
    if schedule["loop"] == "open":
        reqs = schedule["segments"][-1]["requests"]
    else:
        reqs = schedule["rounds"][1]
    p = sorted(len(r["prompt"]) for r in reqs)
    o = sorted(r["max_tokens"] for r in reqs)
    return {"requests": len(reqs), "prompt_tokens": sum(p),
            "output_tokens": sum(o), "prompt_len_min_med_max":
            [p[0], p[len(p) // 2], p[-1]],
            "output_len_min_med_max": [o[0], o[len(o) // 2], o[-1]]}
