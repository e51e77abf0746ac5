"""Per-tenant QoS (core/sched/qos.py + the scheduler wiring): DRR
weighted fair queueing over granted tokens, soft KV page quotas with
quota-aware preemption and cooldown hysteresis, bounded tenant
bucketing, per-tenant stats/DP merge/render, and the ``VDT_QOS=0``
no-state revert.

The adversarial-flood drill here is the deterministic, scheduler-level
form of the acceptance criterion: gaps are measured in SCHEDULER STEPS
(each step is one decode iteration, so an interactive request's
inter-grant step gap IS its TPOT in step units) instead of flaky wall
clock."""

import pytest

from tests.conftest import make_config, make_request
from vllm_distributed_tpu.core.sched import qos as qm
from vllm_distributed_tpu.core.sched.output import ModelRunnerOutput
from vllm_distributed_tpu.core.sched.scheduler import Scheduler
from vllm_distributed_tpu.request import RequestStatus
from vllm_distributed_tpu.utils import fault_injection as fi


def make_scheduler(monkeypatch=None, *, qos=True, weights="",
                   quota=None, **cfg):
    if monkeypatch is not None and qos:
        monkeypatch.setenv("VDT_QOS", "1")
        if weights:
            monkeypatch.setenv("VDT_QOS_WEIGHTS", weights)
        if quota is not None:
            monkeypatch.setenv("VDT_QOS_KV_QUOTA_FRAC", str(quota))
    return Scheduler(make_config(**cfg))


def tagged(tenant, num_tokens, **kw):
    r = make_request(num_tokens=num_tokens, **kw)
    r.tenant = tenant
    return r


def step(scheduler, sample_token=42):
    """One schedule + reconcile round (tests/core/test_scheduler.py
    idiom): requests whose grant completes their known tokens sample
    one token, partial prefill chunks sample nothing."""
    out = scheduler.schedule()
    if out.total_num_scheduled_tokens == 0:
        return out, []
    req_ids, sampled = [], []
    for req_id, n in out.num_scheduled_tokens.items():
        req = scheduler.requests[req_id]
        req_ids.append(req_id)
        done = req.num_computed_tokens + n >= req.num_tokens
        # Async-off: num_computed is pre-advance at this point.
        sampled.append([sample_token] if done else [])
    mro = ModelRunnerOutput(req_ids=req_ids, sampled_token_ids=sampled)
    return out, scheduler.update_from_output(out, mro)


# ---------------------------------------------------------------------------
# VDT_QOS=0 (default): no state, byte-identical scheduling
# ---------------------------------------------------------------------------
def test_qos_off_by_default_constructs_no_state():
    s = make_scheduler()
    assert s.qos is None
    assert "tenants" not in s.get_stats()
    assert s.get_debug_state()["qos"] is None


def test_single_tenant_qos_on_matches_qos_off(monkeypatch):
    """Work-conserving gate: with one tenant and no pool pressure the
    DRR clips are all waived, so QoS on grants exactly what the
    pre-QoS scheduler (QoS off, the byte-identical default path)
    grants, step for step."""
    traces = {}
    for mode in ("off", "on"):
        if mode == "on":
            monkeypatch.setenv("VDT_QOS", "1")
        s = Scheduler(make_config(max_num_batched_tokens=32,
                                  num_blocks=128, max_model_len=512))
        reqs = [make_request(num_tokens=n, max_tokens=4, req_id=f"r{i}",
                             token_ids=list(range(501 + 100 * i,
                                                  501 + 100 * i + n)))
                for i, n in enumerate((70, 9, 33))]
        for r in reqs:
            s.add_request(r)
        trace = []
        for _ in range(30):
            out, _ = step(s)
            trace.append(sorted(out.num_scheduled_tokens.items()))
            if not s.has_requests():
                break
        traces[mode] = (trace, [list(r.output_token_ids) for r in reqs])
    assert traces["on"] == traces["off"]


# ---------------------------------------------------------------------------
# Units: weight spec, tenant bucketing, deficit carry-over
# ---------------------------------------------------------------------------
def test_parse_weights_drops_malformed_entries():
    w = qm.parse_weights("gold:3, bronze:1.5,,bad,neg:-2,zero:0,:7,"
                         "interactive:2")
    assert w == {"gold": 3.0, "bronze": 1.5, "interactive": 2.0}


def test_bucket_tenant_bounds_cardinality():
    tracked = set()
    assert qm.bucket_tenant(None, tracked, 2) == qm.DEFAULT_KEY
    assert qm.bucket_tenant("a", tracked, 2) == "a"
    assert qm.bucket_tenant("b", tracked, 2) == "b"
    # Past the cap: stable hash buckets, never new tracked ids.
    over = {qm.bucket_tenant(f"t{i}", tracked, 2) for i in range(100)}
    assert tracked == {"a", "b"}
    assert all(k.startswith("~") for k in over)
    assert len(over) <= qm.OVERFLOW_BUCKETS
    # Deterministic: the same tenant always lands in the same bucket.
    assert (qm.bucket_tenant("t7", tracked, 2)
            == qm.bucket_tenant("t7", tracked, 2))
    # Tracked ids keep resolving to themselves.
    assert qm.bucket_tenant("a", tracked, 2) == "a"


def test_deficit_carry_over_is_bounded():
    state = qm.QosState(64, 64, weights_spec="", quota_frac=0.5,
                        max_tracked=8)
    idle = tagged("idle", 8)
    for _ in range(10):  # replenished but never charged
        state.begin_step([idle], [], {})
    assert state.deficit["idle"] == qm.DEFICIT_CARRY_STEPS * 64
    # Debt from work-conserving over-grants floors symmetrically.
    for _ in range(20):
        state.charge("idle", 64)
    assert state.deficit["idle"] == -qm.DEFICIT_CARRY_STEPS * 64


def test_tenantless_requests_share_the_anon_bucket(monkeypatch):
    s = make_scheduler(monkeypatch)
    for n in (8, 12):
        s.add_request(make_request(num_tokens=n, max_tokens=2))
    step(s)
    tenants = s.get_stats()["tenants"]
    assert set(tenants) == {qm.DEFAULT_KEY}
    assert tenants[qm.DEFAULT_KEY]["granted_tokens"] == 20


# ---------------------------------------------------------------------------
# DRR grant loop
# ---------------------------------------------------------------------------
def test_drr_weights_split_prefill_bandwidth(monkeypatch):
    """Two tenants chunk-prefilling long prompts through a 64-token
    budget: granted tokens must track the 3:1 weight spec, not the
    arrival order."""
    s = make_scheduler(monkeypatch, weights="gold:3,bronze:1",
                       max_num_batched_tokens=64, num_blocks=256,
                       max_model_len=1024)
    s.add_request(tagged("bronze", 320, max_tokens=2))  # arrives first
    s.add_request(tagged("gold", 320, max_tokens=2))
    for _ in range(4):
        step(s)
    granted = s.qos.granted_tokens
    ratio = granted["gold"] / granted["bronze"]
    assert 2.5 <= ratio <= 3.5, granted
    # Weighted split of every full budget: nothing left idle.
    assert granted["gold"] + granted["bronze"] == 4 * 64


def test_class_weights_map_through_priority(monkeypatch):
    """best_effort/interactive class keys (PR 7's priority classes)
    resolve weights for tenants with no explicit entry."""
    s = make_scheduler(monkeypatch, weights="best_effort:1,interactive:3",
                      max_num_batched_tokens=64, num_blocks=256,
                      max_model_len=1024)
    flood = tagged("flood", 320, max_tokens=2, priority=1)  # best_effort
    chat = tagged("chat", 320, max_tokens=2, priority=0)    # interactive
    s.add_request(flood)
    s.add_request(chat)
    for _ in range(4):
        step(s)
    granted = s.qos.granted_tokens
    assert 2.5 <= granted["chat"] / granted["flood"] <= 3.5, granted


def test_adversarial_flood_bounded_interactive_gaps(monkeypatch):
    """The acceptance drill, in deterministic step units: a flood
    tenant chunk-prefilling a huge prompt ahead of an interactive
    tenant in the running list. QoS ON: the interactive request admits
    within a few steps and then receives its decode token EVERY step
    (max inter-grant gap 1 — the decode-headroom reservation). QoS
    OFF: the flood's chunks consume the whole budget and the
    interactive request starves for the length of the flood prefill."""
    for mode in ("on", "off"):
        if mode == "on":
            monkeypatch.setenv("VDT_QOS", "1")
        else:
            monkeypatch.delenv("VDT_QOS", raising=False)
        s = Scheduler(make_config(max_num_batched_tokens=16,
                                  num_blocks=512, max_model_len=2048))
        flood = tagged("flood", 960, max_tokens=4)
        s.add_request(flood)
        step(s)  # flood alone: work-conserving full budget
        assert s.qos is None or \
            s.qos.granted_tokens["flood"] == 16
        inter = tagged("chat", 8, max_tokens=40)
        s.add_request(inter)
        grant_steps = []
        for i in range(40):
            out, _ = step(s)
            if inter.request_id in out.num_scheduled_tokens:
                grant_steps.append(i)
        if mode == "on":
            # Admitted immediately; decode served every step after.
            assert grant_steps[0] <= 1
            gaps = [b - a for a, b in zip(grant_steps, grant_steps[1:])]
            assert max(gaps) <= 1, gaps
            # The flood still progresses (work stays conserved).
            assert flood.num_computed_tokens > 200
        else:
            # Pre-QoS behavior: the 960-token prefill walls off the
            # budget for ~960/16 = 60 steps — chat sees NOTHING in the
            # 40-step observation window.
            assert not grant_steps


# ---------------------------------------------------------------------------
# Quota-aware preemption
# ---------------------------------------------------------------------------
def test_quota_preemption_evicts_over_quota_lowest_priority(monkeypatch):
    """Pages run out while tenant "hog" is far over its soft quota:
    the victim must be hog's lowest-priority request (not the last
    running request, which is the capacity policy's pick), attributed
    cause "quota" and counted per tenant."""
    s = make_scheduler(monkeypatch, quota=0.4, policy="priority",
                       num_blocks=16, max_num_batched_tokens=64,
                       max_model_len=256)
    small = tagged("small", 7, max_tokens=30, priority=0)
    hog_hi = tagged("hog", 15, max_tokens=30, priority=1)
    hog_lo = tagged("hog", 15, max_tokens=30, priority=5)
    for r in (small, hog_hi, hog_lo):
        s.add_request(r)
    step(s)  # all prefill: 2 + 4 + 4 pages of 16; quota = 6
    for _ in range(12):
        step(s)
        if s.num_preemptions:
            break
    assert s.preemption_causes.get("quota", 0) >= 1
    assert hog_lo.num_preemptions == 1
    assert hog_hi.num_preemptions == 0
    assert small.num_preemptions == 0
    assert s.get_stats()["tenants"]["hog"]["preemptions"] >= 1


def test_quota_thrash_drill_hysteresis_bounds_the_storm(monkeypatch):
    """sched.quota_thrash forces every page-holding tenant over quota,
    so each allocation failure WANTS a quota eviction — the cooldown
    must space quota preemptions out per tenant and the scheduler must
    keep making progress (no evict/resume livelock)."""
    s = make_scheduler(monkeypatch, quota=0.5, num_blocks=12,
                       max_num_batched_tokens=64, max_model_len=256)
    fi.clear()
    fi.inject("sched.quota_thrash")
    try:
        a = tagged("osc", 15, max_tokens=25)
        b = tagged("osc", 15, max_tokens=25)
        c = tagged("other", 7, max_tokens=25)
        for r in (a, b, c):
            s.add_request(r)
        steps = 0
        while s.has_requests() and steps < 200:
            step(s)
            steps += 1
        # Progress: everything finished despite the forced storm.
        assert not s.has_requests(), (steps, s.preemption_causes)
        quota_evictions = s.preemption_causes.get("quota", 0)
        assert quota_evictions >= 1  # the drill actually fired
        # Hysteresis bound: per tenant, at most one quota eviction per
        # cooldown window (2 tenants share the storm).
        assert quota_evictions <= 2 * (steps // qm.QUOTA_COOLDOWN_STEPS
                                       + 1), (quota_evictions, steps)
    finally:
        fi.clear()


def test_over_quota_tenant_waits_at_admission_under_pressure():
    """pick_waiting_tenant passes over an over-quota tenant while an
    under-quota tenant has waiting work — but only at pool pressure,
    and never when every waiting tenant is over (work conserving)."""
    state = qm.QosState(64, 100, weights_spec="", quota_frac=0.1,
                        max_tracked=8)
    state.held = {"hog": 50, "small": 2}
    state.deficit = {"hog": 64.0, "small": 1.0}
    # Pressured: the under-quota tenant wins despite the deficit gap.
    assert state.pick_waiting_tenant(["hog", "small"], 0.95) == "small"
    # Unpressured: quota is soft — deficit order stands.
    assert state.pick_waiting_tenant(["hog", "small"], 0.5) == "hog"
    # Every candidate over quota: deficit order again (no starvation).
    state.held["small"] = 40
    assert state.pick_waiting_tenant(["hog", "small"], 0.95) == "hog"


# ---------------------------------------------------------------------------
# Stats plumbing: scheduler -> DP merge -> /metrics render
# ---------------------------------------------------------------------------
def test_tenant_stats_dp_merge_and_render():
    from vllm_distributed_tpu.engine.dp_client import DPEngineClient
    from vllm_distributed_tpu.metrics.prometheus import render_metrics

    class _FakeClient:
        def __init__(self, stats):
            self._stats = stats

        def get_stats(self):
            return dict(self._stats)

    per = [
        {"tenants": {"a": {"granted_tokens": 100, "kv_blocks": 4,
                           "preemptions": 1}}},
        {"tenants": {"a": {"granted_tokens": 50, "kv_blocks": 2,
                           "preemptions": 0},
                     "_anon": {"granted_tokens": 7, "kv_blocks": 1,
                               "preemptions": 0}}},
    ]
    dp = DPEngineClient.__new__(DPEngineClient)
    dp.clients = [_FakeClient(s) for s in per]
    dp._live = [set(), set()]
    dp._down = set()
    dp.replica_failovers = 0
    dp.replica_resurrections = 0
    agg = dp.get_stats()
    assert agg["tenants"]["a"] == {"granted_tokens": 150, "kv_blocks": 6,
                                   "preemptions": 1}
    assert agg["tenants"]["_anon"]["granted_tokens"] == 7
    text = render_metrics(agg)
    assert 'vdt:tenant_granted_tokens_total{tenant="a"} 150' in text
    assert 'vdt:tenant_kv_blocks{tenant="a"} 6' in text
    assert 'vdt:tenant_preemptions_total{tenant="a"} 1' in text
    assert 'vdt:tenant_granted_tokens_total{tenant="_anon"} 7' in text


def test_tenant_goodput_scored_and_rendered():
    from vllm_distributed_tpu.metrics.stats import (FrontendStats,
                                                    RequestTimes)
    fe = FrontendStats()
    fe.slo_ttft_ms = 100.0
    good = RequestTimes(arrival=0.0, first_token=0.05, last_token=0.2)
    bad = RequestTimes(arrival=0.0, first_token=0.5, last_token=0.9)
    fe.on_slo(good, 8, tenant="chat")
    fe.on_slo(bad, 8, tenant="flood")
    fe.on_slo(good, 8, tenant="flood")
    text = fe.render()
    assert 'vdt:tenant_goodput_frac{tenant="chat"} 1.0' in text
    assert 'vdt:tenant_goodput_frac{tenant="flood"} 0.5' in text
    # Tenantless scoring (QoS off) renders no per-tenant series.
    fe2 = FrontendStats()
    fe2.slo_ttft_ms = 100.0
    fe2.on_slo(good, 8)
    assert "tenant_goodput" not in fe2.render()
