"""Correctness sentinel unit mechanics (correctness_plane.py) and the
Prometheus exposition of its families (metrics/prometheus.py).

The fleet-level drills (fault-injected flip/NaN -> suspect -> fleet
quarantine) live in tests/engine/test_fleet.py; this file pins the
plane's scoring rules in isolation — journal self-seeding, the
vote/reference/logprob/timeout cause ladder, the median-based numerics
drift detector, episode hygiene (forget_replica, clean-round resets) —
and the render contract: per-replica labeled series that are NEVER
numeric-summed across replicas. The last test runs the soak, the
corruption drill and plane-on against plane-off on real engines."""

import numpy as np
import pytest

from tests.engine.test_llm_engine import (checkpoint,  # noqa: F401
                                          make_engine, run_engine)
from vllm_distributed_tpu.core.sched.scheduler import EngineCoreOutput
from vllm_distributed_tpu.correctness_plane import (CANARY_DECODE_TOKENS,
                                                    CorrectnessPlane,
                                                    NumericsTap,
                                                    canary_sampling_params,
                                                    flag_config_fingerprint,
                                                    reference_key)
from vllm_distributed_tpu.metrics.prometheus import render_metrics
from vllm_distributed_tpu.sampling_params import SamplingParams

GOLD_TOKENS = list(range(100, 100 + CANARY_DECODE_TOKENS))


@pytest.fixture()
def plane(monkeypatch):
    monkeypatch.setenv("VDT_CORRECTNESS", "1")
    monkeypatch.setenv("VDT_CANARY_INTERVAL_S", "30")
    monkeypatch.setenv("VDT_CANARY_QUARANTINE_N", "2")
    monkeypatch.setenv("VDT_NUMERICS_DRIFT_FRAC", "0.5")
    return CorrectnessPlane()


def _finish(plane, rid, tokens, lp=None):
    """Deliver one probe's full output in a single finished delta."""
    logprobs = [{tokens[-1]: lp}] if lp is not None else None
    plane.on_output(EngineCoreOutput(req_id=rid, new_token_ids=tokens,
                                     finish_reason="length",
                                     logprobs=logprobs))


def _run_round(plane, per_replica, now, lp=None):
    """Mint a round for the keyed replicas and resolve it with the
    given token streams ({replica: tokens})."""
    probes = plane.due_probes(sorted(per_replica), now=now)
    assert [i for i, _ in probes] == sorted(per_replica)
    for i, req in probes:
        _finish(plane, req.request_id, per_replica[i],
                lp=lp[i] if isinstance(lp, dict) else lp)
    assert plane._round is None  # resolved


# ---------------------------------------------------------------------------
# Canary round scoring
# ---------------------------------------------------------------------------


def test_first_unanimous_round_self_seeds_journal(plane):
    _run_round(plane, {0: GOLD_TOKENS, 1: GOLD_TOKENS}, now=0.0, lp=-0.5)
    stats = plane.get_stats()
    assert stats["journal_entries"] == 1
    assert stats["divergences"] == {}
    assert plane.suspects() == {}
    ref = next(iter(plane.journal.values()))
    assert ref["tokens"] == GOLD_TOKENS
    assert ref["lp"] == pytest.approx(-0.5)


def test_interval_gates_next_round(plane):
    _run_round(plane, {0: GOLD_TOKENS}, now=0.0)
    assert plane.due_probes([0], now=10.0) == []  # interval 30s
    assert plane.due_probes([0], now=31.0) != []


def test_two_replica_tie_breaks_on_reference(plane):
    _run_round(plane, {0: GOLD_TOKENS, 1: GOLD_TOKENS}, now=0.0)
    bad = [t + 1 for t in GOLD_TOKENS]
    # Prompt rotates per round: seed all four golden prompts so the
    # corrupted round has a reference to break the 1-1 tie.
    for r in range(1, 4):
        _run_round(plane, {0: GOLD_TOKENS, 1: GOLD_TOKENS},
                   now=31.0 * r)
    _run_round(plane, {0: GOLD_TOKENS, 1: bad}, now=31.0 * 4)
    assert plane.divergences == {1: {"reference": 1}}
    assert plane.suspects() == {1: 1}
    # The healthy replica that matched the journal stays clean.
    assert plane._canary_strikes.get(0, 0) == 0


def test_three_replica_vote_needs_no_journal(plane):
    bad = [t + 7 for t in GOLD_TOKENS]
    _run_round(plane, {0: GOLD_TOKENS, 1: GOLD_TOKENS, 2: bad}, now=0.0)
    assert plane.divergences == {2: {"vote": 1}}
    assert plane.suspects() == {2: 1}
    # A non-unanimous round never seeds the journal.
    assert plane.get_stats()["journal_entries"] == 0


def test_fleet_wide_reference_mismatch_suspects_nobody(plane):
    _run_round(plane, {0: GOLD_TOKENS, 1: GOLD_TOKENS}, now=0.0)
    drifted = [t + 3 for t in GOLD_TOKENS]
    for r in range(1, 4):  # rotate back to the seeded prompt
        _run_round(plane, {0: GOLD_TOKENS, 1: GOLD_TOKENS},
                   now=31.0 * r)
    _run_round(plane, {0: drifted, 1: drifted}, now=31.0 * 4)
    # Both replicas strayed from the journal in unison: a divergence
    # per replica for the operator, but no odd one out to suspect.
    assert plane.divergences == {0: {"reference": 1},
                                 1: {"reference": 1}}
    assert plane.suspects() == {}


def test_logprob_fingerprint_divergence(plane):
    _run_round(plane, {0: GOLD_TOKENS, 1: GOLD_TOKENS}, now=0.0,
               lp=-0.5)
    for r in range(1, 4):
        _run_round(plane, {0: GOLD_TOKENS, 1: GOLD_TOKENS},
                   now=31.0 * r, lp=-0.5)
    # Same tokens, one replica's final-position logprob drifted past
    # tolerance: quality degradation below the argmax.
    _run_round(plane, {0: GOLD_TOKENS, 1: GOLD_TOKENS}, now=31.0 * 4,
               lp={0: -0.5, 1: -0.9})
    assert plane.divergences == {1: {"logprob": 1}}
    assert plane.suspects() == {1: 1}


def test_silent_replica_times_out_on_expiry(plane):
    probes = plane.due_probes([0, 1], now=0.0)
    rid0 = probes[0][1].request_id
    _finish(plane, rid0, GOLD_TOKENS)
    # Replica 1 never answers; the NEXT injector pass past the round
    # deadline (4 intervals) expires it and scores the responders.
    assert plane.due_probes([0, 1], now=10.0) == []  # still in flight
    late = plane.due_probes([0, 1], now=500.0)
    assert late != []  # expiry frees the injector for a new round
    assert plane.divergences[1] == {"timeout": 1}


def test_stale_round_output_is_dropped(plane):
    probes = plane.due_probes([0, 1], now=0.0)
    stale_rid = probes[1][1].request_id
    _finish(plane, probes[0][1].request_id, GOLD_TOKENS)
    fresh = plane.due_probes([0, 1], now=500.0)  # expires round 0
    # Round 0's straggler streams in AFTER round 1 opened: it must not
    # pollute replica 1's round-1 slot.
    _finish(plane, stale_rid, [1, 2, 3])
    assert plane._round[1]["tokens"] == []
    for i, req in fresh:
        _finish(plane, req.request_id, GOLD_TOKENS)
    assert plane._round is None
    assert plane.divergences.get(1, {}).get("reference", 0) == 0


def test_quarantine_hint_fires_once_per_episode(plane):
    bad = [t + 1 for t in GOLD_TOKENS]
    for r in range(4):
        _run_round(plane, {0: GOLD_TOKENS, 1: GOLD_TOKENS}, now=31.0 * r)
    for r in range(4, 8):  # 4 straight corrupted rounds, quarantine_n=2
        _run_round(plane, {0: GOLD_TOKENS, 1: bad}, now=31.0 * r)
    assert plane.quarantine_hints_emitted == 1
    assert plane.quarantine_hints() == {1: "reference"}
    assert plane.quarantine_hints() == {}  # drained
    # A clean round closes the episode and re-arms the hint.
    _run_round(plane, {0: GOLD_TOKENS, 1: GOLD_TOKENS}, now=31.0 * 8)
    assert plane.suspects() == {}
    for r in range(9, 11):
        _run_round(plane, {0: GOLD_TOKENS, 1: bad}, now=31.0 * r)
    assert plane.quarantine_hints_emitted == 2


def test_forget_replica_resolves_round_with_survivors(plane):
    probes = plane.due_probes([0, 1], now=0.0)
    _finish(plane, probes[0][1].request_id, GOLD_TOKENS)
    plane.forget_replica(1)  # quarantined mid-round
    assert plane._round is None  # survivor resolved (and self-seeded)
    assert plane.get_stats()["journal_entries"] == 1
    assert plane.divergences == {}


def test_flag_fingerprint_keys_disjoint_references(monkeypatch):
    sp = canary_sampling_params()
    fp_a = flag_config_fingerprint()
    monkeypatch.setenv("VDT_FUSED_KV_WRITE", "0")
    fp_b = flag_config_fingerprint()
    assert fp_a != fp_b
    prompt = (11, 29, 7, 3, 17, 23, 5, 13)
    assert reference_key(prompt, sp, fp_a) != reference_key(prompt, sp,
                                                            fp_b)


def test_sentinel_knobs_excluded_from_fingerprint(monkeypatch):
    fp_a = flag_config_fingerprint()
    monkeypatch.setenv("VDT_CANARY_INTERVAL_S", "5")
    monkeypatch.setenv("VDT_NUMERICS_DRIFT_FRAC", "0.9")
    # Tuning the sentinel itself must not re-seed the journal.
    assert flag_config_fingerprint() == fp_a


# ---------------------------------------------------------------------------
# Numerics watch
# ---------------------------------------------------------------------------


def test_numerics_tap_excludes_poisoned_step():
    tap = NumericsTap()
    tap.dispatch(np.array([0.0, 1.5, 2.0], dtype=np.float32))
    tap.dispatch(np.array([3.0, float("nan"), 0.0], dtype=np.float32))
    s = tap.stats()  # harvests the pending poisoned step
    assert s["nan_steps"] == 1
    # The clean step landed; the poisoned step's garbage means did not.
    assert s["entropy"]["count"] == 1
    assert s["entropy_window_mean"] == pytest.approx(1.5)
    assert s["window_steps"] == 1


def test_drift_detector_uses_median_not_mean(plane):
    # 3 replicas at 1, 1, 8: the MEAN (3.3) would flag the healthy
    # pair too; the median stays with the majority and isolates the
    # poisoned replica alone.
    snap = lambda m: {"nan_steps": 0, "entropy_window_mean": m}
    plane.observe_numerics({0: snap(1.0), 1: snap(1.0), 2: snap(8.0)})
    assert plane.divergences == {2: {"numerics_drift": 1}}
    assert plane.suspects() == {2: 1}


def test_nan_delta_climbs_ladder_and_clean_poll_resets(plane):
    healthy = {"nan_steps": 0, "entropy_window_mean": 1.0}
    plane.observe_numerics({0: healthy, 1: {"nan_steps": 1,
                                            "entropy_window_mean": 1.0}})
    assert plane.divergences == {1: {"nan_logits": 1}}
    assert plane.suspects() == {1: 1}
    # Same cumulative counter, no NEW NaNs: the poll is clean and the
    # episode resets.
    plane.observe_numerics({0: healthy, 1: {"nan_steps": 1,
                                            "entropy_window_mean": 1.0}})
    assert plane.suspects() == {}


def test_single_replica_never_drifts(plane):
    # Drift is a fleet-relative signal: one replica has no peers to
    # disagree with.
    plane.observe_numerics({0: {"nan_steps": 0,
                                "entropy_window_mean": 42.0}})
    assert plane.divergences == {}


# ---------------------------------------------------------------------------
# Prometheus exposition
# ---------------------------------------------------------------------------


def _correctness_stats():
    return {
        "probes": {0: 5, 1: 4},
        "divergences": {1: {"vote": 2, "nan_logits": 1}},
        "suspects": {0: 0, 1: 1},
        "quarantine_hints": 1,
        "journal_entries": 4,
    }


def test_render_correctness_families_are_per_replica():
    text = render_metrics({"correctness": _correctness_stats()})
    assert 'vdt:canary_probes_total{replica="0"} 5' in text
    assert 'vdt:canary_probes_total{replica="1"} 4' in text
    assert ('vdt:canary_divergences_total{replica="1",cause="vote"} 2'
            in text)
    assert ('vdt:canary_divergences_total{replica="1",'
            'cause="nan_logits"} 1' in text)
    assert 'vdt:replica_suspect{replica="0"} 0' in text
    assert 'vdt:replica_suspect{replica="1"} 1' in text
    # NEVER numeric-summed: no unlabeled series, no 5+4 rollup.
    for line in text.splitlines():
        if line.startswith("vdt:canary_probes_total"):
            assert line.startswith('vdt:canary_probes_total{replica=')
            assert not line.endswith(" 9")


def test_render_numerics_keyed_and_flat():
    tap = NumericsTap()
    tap.dispatch(np.array([0.0, 1.0, 2.0], dtype=np.float32))
    snap = tap.stats()
    # DP shape: {replica: snapshot}.
    text = render_metrics({"numerics": {0: snap, 1: snap}})
    assert 'vdt:logits_nan_steps_total{replica="0"} 0' in text
    assert 'vdt:logits_nan_steps_total{replica="1"} 0' in text
    assert 'vdt:logits_entropy_bucket{replica="1"' in text
    assert 'vdt:logits_top_margin_bucket{replica="0"' in text
    # Single-engine flat snapshot renders as replica 0.
    flat = render_metrics({"numerics": snap})
    assert 'vdt:logits_nan_steps_total{replica="0"} 0' in flat
    assert 'replica="1"' not in flat


def test_render_excludes_dead_replica_mid_scrape():
    # The DP aggregator keys numerics by the ALIVE indices it polled;
    # a replica that died mid-scrape simply has no entry and must not
    # render as a zeroed ghost.
    tap = NumericsTap()
    tap.dispatch(np.array([0.0, 1.0, 2.0], dtype=np.float32))
    text = render_metrics({"numerics": {0: tap.stats()},
                           "correctness": {"probes": {0: 3}}})
    assert 'replica="0"' in text
    assert 'replica="1"' not in text


def test_render_off_by_default():
    # VDT_CORRECTNESS=0 ships no correctness/numerics keys at all.
    text = render_metrics({})
    assert "vdt:canary" not in text
    assert "vdt:logits" not in text
    assert "vdt:replica_suspect" not in text


# ---------------------------------------------------------------------------
# Real engines: a clean soak, a corrupted replica, and the plane's cost in
# tokens (none)
# ---------------------------------------------------------------------------
def _pump_probes(engine, plane, n, budget=4000):
    """Drive the DP output pump until ``n`` more canary probes have
    finished (its tick injects due probes and steps their replicas)."""
    target = sum(plane.probes.values()) + n
    while sum(plane.probes.values()) < target and budget > 0:
        engine.engine_core.get_output()
        budget -= 1
    return sum(plane.probes.values()) >= target


def test_real_engines_clean_soak_corruption_drill_and_token_parity(
        checkpoint, monkeypatch):
    """Two in-process replicas of a real engine under the sentinel:
    60 canary probes with no divergence (the false-positive budget is
    zero); replica 1's canary tokens perturbed where the plane absorbs
    them are voted out within 3 probes, replica 1 ALONE is suspect and
    a quarantine hint goes out; and tenant traffic's greedy tokens are
    the same with the plane on and off."""
    path, _ = checkpoint
    monkeypatch.setenv("VDT_CORRECTNESS", "1")
    # Interval 0: a new round every tick, so the soak and the drill are
    # paced by probes, not by the clock.
    monkeypatch.setenv("VDT_CANARY_INTERVAL_S", "0")
    monkeypatch.setenv("VDT_CANARY_QUARANTINE_N", "2")
    # The drill perturbs tokens, not logits: keep the drift detector
    # out of the attribution under test.
    monkeypatch.setenv("VDT_NUMERICS_DRIFT_FRAC", "0")
    monkeypatch.setenv("VDT_FLEET", "0")
    engine = make_engine(path, data_parallel_size=2)
    plane = engine.engine_core.correctness
    assert plane is not None
    assert _pump_probes(engine, plane, 60), "soak stalled before 60 probes"
    stats = plane.get_stats()
    assert sum(stats["probes"].values()) >= 60
    assert not any(sum(c.values()) for c in stats["divergences"].values())

    absorb = plane.on_output

    def corrupted(out):
        if plane._replica_of(out.req_id) == 1 and out.new_token_ids:
            out.new_token_ids = [t + 1 for t in out.new_token_ids]
        absorb(out)

    monkeypatch.setattr(plane, "on_output", corrupted)
    before, detected_after = plane.probes.get(1, 0), None
    for _ in range(3):
        assert _pump_probes(engine, plane, 2)
        if detected_after is None and plane.divergences.get(1):
            detected_after = plane.probes.get(1, 0) - before
    assert detected_after is not None and detected_after <= 3
    stats = plane.get_stats()
    assert [i for i, v in stats["suspects"].items() if v] == [1]
    assert stats["quarantine_hints"] >= 1
    engine.shutdown()

    # A long interval parks the canary injector: what is left of the
    # plane in a step is the numerics tap.
    monkeypatch.setenv("VDT_CANARY_INTERVAL_S", "3600")
    sp = SamplingParams(temperature=0.0, max_tokens=12, ignore_eos=True)
    rng = np.random.default_rng(20)
    prompts = [[int(x) for x in rng.integers(2, 127, size=24)]
               for _ in range(8)]
    outs = {}
    for leg, flag in (("on", "1"), ("off", "0")):
        monkeypatch.setenv("VDT_CORRECTNESS", flag)
        engine = make_engine(path, data_parallel_size=2)
        assert (engine.engine_core.correctness is not None) == (flag == "1")
        outs[leg] = [o.outputs[0].token_ids for o in run_engine(
            engine, prompts, [sp] * len(prompts))]
        engine.shutdown()
    assert len(outs["off"]) == len(prompts)
    assert outs["on"] == outs["off"]
