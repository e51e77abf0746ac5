"""Elastic-fleet control loop (engine/fleet.py).

Deterministic stub-replica drills over ``FleetController``: scale-out
under pressure (with the ``fleet.scale_stall`` drill and the at-max /
budget freezes), zero-loss scale-in through the drain -> journal-migrate
ladder, wedge cycling counted on exactly the ``wedge_cycles`` rung, the
verified resurrection probe (satellite: a replica that reconnects but
fails its stats probe is NOT a resurrection), live prefill->decode pool
re-splits proven by pool-occupancy metrics, and the ``VDT_FLEET=0``
inert default. The chaos soaks at the bottom run the 2->3->1 schedule
under a seeded fault sequence with continuous traffic and a
deterministic per-session token function, so zero-loss/zero-duplication
and greedy-parity are exact assertions, not spot checks. The last test
walks 2 -> 1 -> 2 on two in-process replicas of a real engine and holds
its tokens to the static fleet's."""

import time

import numpy as np
import pytest

from tests.conftest import make_config
from tests.engine.test_llm_engine import checkpoint, make_engine  # noqa: F401
from vllm_distributed_tpu.core.sched.scheduler import EngineCoreOutput
from vllm_distributed_tpu.engine import dp_client as dp_mod
from vllm_distributed_tpu.engine.core_client import (EngineCoreClient,
                                                     EngineDeadError)
from vllm_distributed_tpu.engine.dp_client import DPEngineClient
from vllm_distributed_tpu.metrics import events as ev
from vllm_distributed_tpu.request import EngineCoreRequest
from vllm_distributed_tpu.sampling_params import SamplingParams
from vllm_distributed_tpu.utils import fault_injection as fi

pytestmark = pytest.mark.faults

PROMPT = [1, 2, 3]


def _tok(session: int, pos: int) -> int:
    """Deterministic greedy token for a session's pos-th output token:
    the parity oracle. A migrated continuation resumes at the position
    its delivered prefix encodes, so any lost, duplicated, or reordered
    token breaks the exact-match assertion."""
    return 3 + (session * 131 + pos * 17) % 97


def _expected(session: int, max_tokens: int) -> list[int]:
    return [_tok(session, p) for p in range(max_tokens)]


def _session_of(rid: str) -> int:
    if rid.startswith("vdt-canary-"):
        # Canary probes are content-addressed: healthy replicas must
        # produce the SAME stream for a golden prompt in EVERY round
        # (the reference journal replays across rounds), so the stub
        # keys the token function on the prompt slot — rounds rotate
        # through the 4 golden prompts — never on the replica.
        return int(rid.split("-")[-2]) % 4
    return int(rid.split("-")[-1])


def _coords(rid: str, req: EngineCoreRequest) -> dict:
    return {"remote_req_id": rid, "pull_host": "h", "pull_port": 7,
            "num_tokens": len(req.prompt_token_ids),
            "remote_page_ids": [0]}


class _FleetStub(EngineCoreClient):
    """Scriptable replica with a deterministic token engine.

    ``engine_core`` is set so the controller's inline snapshot refresh
    polls it like an in-process engine; ``serve()`` queues one output
    batch (one next token per pending request) the way a real step
    would, computing each token from the PURE position function — a
    request re-admitted elsewhere (drain, wedge, death) resumes
    mid-stream token-identically or not at all."""

    warm_pages = 0  # scripted kv_tier warm-start page count

    def __init__(self, config) -> None:
        self.config = config
        self.engine_core = self  # inline stats refresh marker
        self.role = config.kv_transfer_config.pool_role
        self.added: list[EngineCoreRequest] = []
        self.aborted: list[str] = []
        self.outputs: list[list[EngineCoreOutput]] = []
        self.pending: dict[str, list] = {}  # rid -> [request, emitted]
        self.stats = {"num_running_reqs": 0, "num_waiting_reqs": 0,
                      "steps_dispatched": 0}
        self.dead = False
        self.fail_restart = False
        self.fail_stats = False
        self.die_consult = False  # soak: consult engine_core.die
        self.restarts = 0
        self.shutdowns = 0

    def _check(self) -> None:
        if self.dead:
            raise EngineDeadError("stub replica is dead")

    def add_request(self, request: EngineCoreRequest) -> None:
        self._check()
        self.added.append(request)
        self.pending[request.request_id] = [request, 0]

    def abort_requests(self, request_ids) -> None:
        self._check()
        self.aborted.extend(request_ids)
        for rid in request_ids:
            self.pending.pop(rid, None)

    def recv_outputs(self, timeout_ms: int):
        if self.die_consult and not self.dead:
            try:
                fi.fire_or_raise("engine_core.die")
            except fi.InjectedFault as e:
                self.dead = True
                self.pending.clear()
                self.outputs.clear()
                raise EngineDeadError(str(e)) from e
        self._check()
        return self.outputs.pop(0) if self.outputs else None

    def get_stats(self) -> dict:
        if self.fail_stats:
            raise RuntimeError("stub stats probe failed")
        s = dict(self.stats)
        s["num_running_reqs"] = len(self.pending)
        s["kv_tier"] = {"warm_start_pages": type(self).warm_pages}
        return s

    def restart(self) -> None:
        if self.fail_restart:
            raise EngineDeadError("stub replica refuses to restart")
        self.dead = False
        self.restarts += 1
        # A restarted engine is EMPTY (the balancer journal re-admits).
        self.pending.clear()
        self.outputs.clear()

    def shutdown(self) -> None:
        self.shutdowns += 1

    # -- deterministic token engine -------------------------------------
    def serve(self) -> None:
        """Queue one step's output batch for every pending request."""
        if self.dead:
            return
        self.stats["steps_dispatched"] += 1
        if not self.pending:
            return
        batch: list[EngineCoreOutput] = []
        for rid in list(self.pending):
            req, emitted = self.pending[rid]
            session = _session_of(rid)
            if self.role == "prefill":
                # Prefill-stage copy: one token, finish with the pull
                # coordinates (the handoff swallows the token and the
                # decode home regenerates the stream from position 0).
                batch.append(EngineCoreOutput(
                    req_id=rid, new_token_ids=[_tok(session, 0)],
                    finish_reason="length",
                    kv_transfer_params=_coords(rid, req)))
                self.pending.pop(rid)
                continue
            # Decode stage (or plain DP): a handoff copy carries the
            # original prompt (resume at 0); a migrated continuation's
            # prompt absorbed its delivered prefix (resume past it).
            pos = len(req.prompt_token_ids) - len(PROMPT) + emitted
            events = None
            params = req.kv_transfer_params or {}
            if (emitted == 0 and str(params.get("remote_req_id", ""))
                    .endswith("#stalled")):
                # Stalled pull coordinates: a real decode home rides
                # the retry -> local-re-prefill ladder and ships the
                # KV_PULL_LOCAL event; disagg counts the rung from it.
                events = [(time.monotonic(), ev.KV_PULL_LOCAL, None)]
            finished = emitted + 1 >= req.sampling_params.max_tokens
            batch.append(EngineCoreOutput(
                req_id=rid, new_token_ids=[_tok(session, pos)],
                finish_reason="length" if finished else None,
                events=events))
            if finished:
                self.pending.pop(rid)
            else:
                self.pending[rid][1] = emitted + 1
        if batch:
            self.outputs.append(batch)


FLEET_ENV = {
    "VDT_FLEET": "1",
    "VDT_FLEET_TICK_S": "0",      # every _tick() evaluates
    "VDT_FLEET_EVAL_TICKS": "1",  # no hysteresis unless a test wants it
    "VDT_FLEET_STALE_S": "0",     # stale freeze off unless tested
    "VDT_FLEET_WEDGE_S": "1000",  # only the drill forces a wedge
    "VDT_FLEET_DRAIN_S": "0",     # drain deadlines land immediately
    "VDT_FLEET_MIN_REPLICAS": "1",
    "VDT_FLEET_MAX_REPLICAS": "3",
    "VDT_FLEET_ACTIONS": "50",
    "VDT_FLEET_ACTION_WINDOW_S": "300",
    # Deterministic placement (live-count round-robin): the fleet tests
    # assert exact owners; the router has its own suite.
    "VDT_ROUTER": "0",
}


def make_fleet(monkeypatch, n: int = 2, **env) -> DPEngineClient:
    for key, val in {**FLEET_ENV, **env}.items():
        monkeypatch.setenv(key, val)
    config = make_config()
    config.parallel_config.data_parallel_size = n
    ft = config.fault_tolerance_config
    ft.replica_probe_interval_s = 0.01
    ft.restart_backoff_base_s = 0.0
    ft.restart_max_attempts = 100
    monkeypatch.setattr(dp_mod, "SyncMPClient", _FleetStub)
    return DPEngineClient(config, force_mp=True)


def _req(rid: str, max_tokens: int = 8) -> EngineCoreRequest:
    return EngineCoreRequest(
        request_id=rid, prompt_token_ids=list(PROMPT),
        sampling_params=SamplingParams(temperature=0.0,
                                       max_tokens=max_tokens))


def _pressure(dp, waiting: int) -> None:
    for c in dp.clients:
        c.stats["num_waiting_reqs"] = waiting


def _tick(dp, n: int = 1) -> None:
    for _ in range(n):
        dp._tick()


# ---------------------------------------------------------------------------
# Inert default
# ---------------------------------------------------------------------------
def test_fleet_off_is_inert(monkeypatch):
    """VDT_FLEET unset: no controller, the legacy resurrection probe
    owns the output path, no fleet stats entry, no fleet state."""
    monkeypatch.setenv("VDT_ROUTER", "0")
    config = make_config()
    config.parallel_config.data_parallel_size = 2
    config.fault_tolerance_config.replica_probe_interval_s = 0.01
    config.fault_tolerance_config.restart_backoff_base_s = 0.0
    monkeypatch.setattr(dp_mod, "SyncMPClient", _FleetStub)
    dp = DPEngineClient(config, force_mp=True)
    assert dp.fleet is None
    assert dp._retired == set() and dp._no_place == set()
    agg = dp._aggregate_stats([{}, {}], indices=[0, 1])
    assert "fleet" not in agg
    # Legacy probe path still resurrects (the fold is fleet-on only).
    dp.clients[0].dead = True
    dp.add_request(_req("x-0"))
    assert 0 in dp._down and dp.replica_failovers == 1
    deadline = time.monotonic() + 5.0
    while 0 in dp._down and time.monotonic() < deadline:
        time.sleep(0.02)
        dp.recv_outputs(timeout_ms=10)
    assert 0 not in dp._down
    assert dp.replica_resurrections == 1


# ---------------------------------------------------------------------------
# Scale-out (+ scale_stall / at_max / budget freezes, warm start)
# ---------------------------------------------------------------------------
def test_scale_out_under_pressure_with_scale_stall_drill(monkeypatch):
    dp = make_fleet(monkeypatch)
    monkeypatch.setattr(_FleetStub, "warm_pages", 5)
    _pressure(dp, 20)  # occupancy 40/16 >> high watermark
    fi.inject("fleet.scale_stall", max_fires=1)
    try:
        _tick(dp)
        # First attempt stalls: budget consumed, fleet intact.
        assert len(dp.clients) == 2
        assert dp.fleet.freezes.get("scale_stall") == 1
        _tick(dp)
    finally:
        fi.clear("fleet.scale_stall")
    assert len(dp.clients) == 3
    assert dp.fleet.scale_outs == 1
    stats = dp.fleet.get_stats()
    assert stats["replicas"] == 3
    # Warm start from the shared T2 namespace, counted.
    assert stats["warm_start_pages"] == 5
    # The appended replica grew the balancer state and takes traffic.
    assert len(dp._live) == 3 and len(dp._supervisors) == 3
    for i in range(3):
        dp.add_request(_req(f"r-{i}"))
    assert {dp._owner[f"r-{i}"] for i in range(3)} == {0, 1, 2}
    # Sustained pressure at the device budget: frozen at_max, not grown.
    _tick(dp)
    assert len(dp.clients) == 3
    assert dp.fleet.freezes.get("at_max", 0) >= 1


def test_budget_exhaustion_freezes_actuation(monkeypatch):
    dp = make_fleet(monkeypatch, VDT_FLEET_ACTIONS="1",
                    VDT_FLEET_MAX_REPLICAS="4")
    _pressure(dp, 20)
    _tick(dp)
    assert len(dp.clients) == 3  # first action consumed the budget
    _tick(dp)
    assert len(dp.clients) == 3
    assert dp.fleet.freezes.get("budget", 0) >= 1


def test_slo_burn_hint_scales_out_and_clears(monkeypatch):
    """PR 19: the SLO burn-rate watchdog's degraded flag reaches the
    fleet as a zero-goodput pseudo-tenant — scale-out pressure on an
    otherwise idle fleet, cleared the moment the burn subsides."""
    dp = make_fleet(monkeypatch, VDT_FLEET_SIGNALS="1",
                    VDT_FLEET_GOODPUT_FLOOR="0.5")
    fleet = dp.fleet
    # Healthy tenant above the floor + sustained burn: the hint alone
    # (occupancy is ~0) is starvation pressure.
    fleet.observe_goodput({"tenantA": 1.0}, degraded=True)
    assert fleet._goodput["_slo_burn"] == 0.0
    _pressure(dp, 0)
    _tick(dp)
    assert fleet.scale_outs == 1 and len(dp.clients) == 3
    # Burn subsides: the pseudo-tenant clears and growth stops (the
    # idle fleet returns to ordinary scale-in consideration).
    fleet.observe_goodput({"tenantA": 1.0}, degraded=False)
    assert "_slo_burn" not in fleet._goodput
    _tick(dp)
    assert fleet.scale_outs == 1


def test_stale_stats_freeze_actuation(monkeypatch):
    """A replica whose stats went quiet freezes ALL actuation (never
    reshape the fleet on blind signals); fresh stats thaw it."""
    dp = make_fleet(monkeypatch, VDT_FLEET_STALE_S="1000")
    _pressure(dp, 20)
    dp.clients[1].fail_stats = True  # its snapshot never lands
    _tick(dp, 3)
    assert len(dp.clients) == 2
    assert dp.fleet.freezes.get("stale_stats", 0) >= 1
    dp.clients[1].fail_stats = False
    _tick(dp, 2)  # snapshot lands, then actuation resumes
    assert len(dp.clients) == 3


# ---------------------------------------------------------------------------
# Scale-in: drain -> journal-migrate -> retire, zero loss
# ---------------------------------------------------------------------------
def test_scale_in_drains_and_migrates_zero_loss(monkeypatch):
    dp = make_fleet(monkeypatch, VDT_FLEET_DRAIN_S="60")
    dp.add_request(_req("s-0", max_tokens=10))
    dp.add_request(_req("s-1", max_tokens=10))
    assert dp._owner["s-0"] == 0 and dp._owner["s-1"] == 1
    # Low occupancy: (2 live + 0 waiting) / 16 < low watermark. Equal
    # load ties retire the HIGHER index: replica 1 drains.
    _tick(dp)
    assert 1 in dp._no_place and 1 in dp.fleet._draining
    assert dp.fleet._draining[1]["mode"] == "retire"
    # Draining replica leaves PLACEMENT but keeps serving.
    dp.add_request(_req("s-2", max_tokens=10))
    assert dp._owner["s-2"] == 0
    vstub = dp.clients[1]
    vstub.serve()
    delivered = dp.recv_outputs(timeout_ms=10) or []
    assert [o.req_id for o in delivered] == ["s-1"]
    assert dp._progress["s-1"] == [_tok(1, 0)]
    # Past the drain deadline: the straggler journal-migrates as a
    # token-identical continuation. No failover counted.
    dp.fleet._draining[1]["deadline"] = 0.0
    _tick(dp)
    assert 1 in dp._retired and 1 in dp._down
    assert dp.replica_failovers == 0
    assert dp.fleet.scale_ins == 1
    assert dp.fleet.get_stats()["replicas"] == 1
    assert "s-1" in vstub.aborted
    cont = next(r for r in dp.clients[0].added if r.request_id == "s-1")
    assert cont.prompt_token_ids == PROMPT + [_tok(1, 0)]
    assert cont.sampling_params.max_tokens == 9
    # Zero loss: the migrated session finishes with the exact stream.
    tokens = list(dp._progress["s-1"])
    deadline = time.monotonic() + 5.0
    while "s-1" in dp._owner and time.monotonic() < deadline:
        dp.clients[0].serve()
        for out in dp.recv_outputs(timeout_ms=10) or []:
            if out.req_id == "s-1":
                tokens.extend(out.new_token_ids)
    assert tokens == _expected(1, 10)
    # At the min-replica floor nothing more retires.
    _tick(dp, 3)
    assert dp.fleet.get_stats()["replicas"] == 1
    # Retired slots never probe (the slot is reserved for scale-out).
    time.sleep(0.05)
    _tick(dp)
    assert vstub.restarts == 0


def test_scale_out_reuses_retired_slot(monkeypatch):
    dp = make_fleet(monkeypatch)
    _tick(dp)   # retire replica 1 (occupancy 0)
    _tick(dp)   # empty drain completes immediately
    assert dp._retired == {1}
    assert dp.fleet.get_stats()["replicas"] == 1
    old_stub = dp.clients[1]
    _pressure(dp, 20)
    _tick(dp)
    # The retired slot was reused, not appended.
    assert len(dp.clients) == 2
    assert dp._retired == set() and 1 not in dp._down
    assert dp.clients[1] is not old_stub
    assert dp.fleet.scale_outs == 1


# ---------------------------------------------------------------------------
# Wedge cycling: exactly one ladder rung
# ---------------------------------------------------------------------------
def test_wedge_cycle_counts_on_exactly_one_rung(monkeypatch):
    dp = make_fleet(monkeypatch, VDT_FLEET_LOW_WATERMARK="0")
    dp.add_request(_req("w-0", max_tokens=6))
    assert dp._owner["w-0"] == 0
    vstub = dp.clients[0]
    vstub.serve()
    delivered = dp.recv_outputs(timeout_ms=10)
    assert delivered and delivered[0].new_token_ids == [_tok(0, 0)]
    fi.inject("fleet.replica_wedge", max_fires=1)
    try:
        _tick(dp)
    finally:
        fi.clear("fleet.replica_wedge")
    # The wedge rung and ONLY the wedge rung.
    assert dp.fleet.wedge_cycles == 1
    assert dp.replica_failovers == 0
    assert 0 in dp._down and 0 not in dp._retired
    assert "w-0" in vstub.aborted
    cont = next(r for r in dp.clients[1].added if r.request_id == "w-0")
    assert cont.prompt_token_ids == PROMPT + [_tok(0, 0)]
    # The folded probe force-cycles it back through the restart budget.
    deadline = time.monotonic() + 5.0
    while 0 in dp._down and time.monotonic() < deadline:
        time.sleep(0.02)
        _tick(dp)
    assert 0 not in dp._down
    assert vstub.restarts == 1
    # The in-flight session still finishes token-identically.
    tokens = list(dp._progress["w-0"])
    deadline = time.monotonic() + 5.0
    while "w-0" in dp._owner and time.monotonic() < deadline:
        dp.clients[1].serve()
        for out in dp.recv_outputs(timeout_ms=10) or []:
            if out.req_id == "w-0":
                tokens.extend(out.new_token_ids)
    assert tokens == _expected(0, 6)


# ---------------------------------------------------------------------------
# Verified resurrection (satellite accounting fix)
# ---------------------------------------------------------------------------
def test_resurrection_not_counted_until_health_verified(monkeypatch):
    dp = make_fleet(monkeypatch)
    dp.clients[0].dead = True
    dp.add_request(_req("x-0"))  # discovers the death, fails over
    assert 0 in dp._down and dp.replica_failovers == 1
    # The probe reconnects (restart succeeds) but the replica cannot
    # answer its stats probe: NOT a resurrection, still down.
    dp.clients[0].fail_stats = True
    deadline = time.monotonic() + 5.0
    while dp.clients[0].restarts == 0 and time.monotonic() < deadline:
        time.sleep(0.02)
        _tick(dp)
    time.sleep(0.05)
    _tick(dp)
    assert dp.clients[0].restarts >= 1
    assert 0 in dp._down
    assert dp.replica_resurrections == 0
    # Health restored: the next probe verifies and counts exactly once.
    dp.clients[0].fail_stats = False
    deadline = time.monotonic() + 5.0
    while 0 in dp._down and time.monotonic() < deadline:
        time.sleep(0.02)
        _tick(dp)
    assert 0 not in dp._down
    assert dp.replica_resurrections == 1


# ---------------------------------------------------------------------------
# Live pool re-split
# ---------------------------------------------------------------------------
@pytest.fixture
def disagg_fleet(monkeypatch):
    monkeypatch.setenv("VDT_DISAGG", "1")
    monkeypatch.setenv("VDT_DISAGG_PREFILL_REPLICAS", "2")
    return make_fleet(monkeypatch, n=3,
                      VDT_FLEET_MIN_REPLICAS="2",
                      VDT_FLEET_HIGH_WATERMARK="100",
                      VDT_FLEET_LOW_WATERMARK="0.1")


def test_live_resplit_converts_prefill_to_decode(disagg_fleet):
    dp = disagg_fleet
    assert dp.disagg.prefill_pool == [0, 1]
    assert dp.disagg.decode_pool == [2]
    # In-flight prefill-stage work on one pool member.
    dp.add_request(_req("c-0", max_tokens=6))
    victim = dp._owner["c-0"]
    assert victim in (0, 1)
    other = 1 - victim
    occ_before = dp.disagg.get_stats(dp.request_counts())
    assert occ_before["pool_occupancy"]["prefill"] == 1
    # Decode pool pressured: occupancy 20/8 >> prefill * ratio.
    dp.clients[2].stats["num_waiting_reqs"] = 20
    _tick(dp)
    # The convert victim is the LEAST-LOADED donor — the prefill
    # replica without the live request.
    assert other in dp.fleet._draining
    assert dp.fleet._draining[other]["mode"] == "convert"
    _tick(dp)  # drain (no live work) completes -> rebuild as decode
    assert dp.disagg.prefill_pool == [victim]
    assert sorted(dp.disagg.decode_pool) == sorted([other, 2])
    assert dp.disagg.resplits == 1
    assert dp.fleet.get_stats()["resplits"] == 1
    # Role-appropriate respawn: the new engine is a consumer.
    rc = dp.clients[other].config
    assert rc.kv_transfer_config.kv_role == "kv_consumer"
    assert rc.kv_transfer_config.pool_role == "decode"
    # The in-flight prefill-stage request survived on the old pool.
    assert dp._owner["c-0"] == victim
    assert dp.replica_failovers == 0
    occ_after = dp.disagg.get_stats(dp.request_counts())
    assert occ_after["pools"] == {"prefill": [victim],
                                  "decode": sorted([other, 2])}
    assert occ_after["pool_occupancy"]["prefill"] == 1


def test_resplit_drains_in_flight_work_to_pool_peer(disagg_fleet):
    """A convert victim still holding prefill-stage work past the
    drain deadline journal-migrates it to the surviving prefill
    member as a fresh stage copy — nothing dropped, no death rung."""
    dp = disagg_fleet
    dp.add_request(_req("c-0", max_tokens=6))
    dp.add_request(_req("c-1", max_tokens=6))
    assert {dp._owner["c-0"], dp._owner["c-1"]} == {0, 1}
    dp.clients[2].stats["num_waiting_reqs"] = 20
    _tick(dp)
    # Equal donor load: ties convert the higher index.
    assert 1 in dp.fleet._draining
    moved = next(rid for rid in ("c-0", "c-1") if dp._owner[rid] == 1)
    _tick(dp)  # past the (zero-second) deadline: migrate + rebuild
    assert dp.disagg.prefill_pool == [0]
    assert dp._owner[moved] == 0
    copies = [r for r in dp.clients[0].added if r.request_id == moved]
    # Re-admitted as a fresh one-token prefill-stage copy.
    assert copies[-1].sampling_params.max_tokens == 1
    assert dp.replica_failovers == 0
    assert dp.disagg.fallbacks.get("prefill_death", 0) == 0


def test_asymmetric_role_tp_freezes_resplit(disagg_fleet, monkeypatch):
    dp = disagg_fleet
    monkeypatch.setattr(dp.disagg, "symmetric_roles", lambda: False)
    dp.clients[2].stats["num_waiting_reqs"] = 20
    _tick(dp, 2)
    assert dp.fleet._draining == {}
    assert dp.disagg.resplits == 0
    assert dp.fleet.freezes.get("asym_tp", 0) >= 1


# ---------------------------------------------------------------------------
# Stats aggregation + prometheus rendering + timeline events
# ---------------------------------------------------------------------------
def test_fleet_stats_aggregate_and_render(monkeypatch):
    dp = make_fleet(monkeypatch, VDT_ROUTER="1")  # scale grows the router
    _pressure(dp, 20)
    _tick(dp, 2)  # scale out to 3, then freeze at_max
    assert len(dp.clients) == 3
    agg = dp._aggregate_stats([{}, {}, {}], indices=[0, 1, 2])
    assert agg["fleet"]["replicas"] == 3
    assert agg["fleet"]["scale_outs"] == 1
    assert agg["fleet"]["freezes"].get("at_max", 0) >= 1
    # The scale-out landed on the shared timeline.
    assert any(e[2] == ev.FLEET_SCALE_OUT
               for e in agg.get("timeline_events", []))
    from vllm_distributed_tpu.metrics.prometheus import render_metrics
    text = render_metrics(agg)
    assert "vdt:fleet_replicas 3" in text
    assert "vdt:fleet_scale_outs_total 1" in text
    assert 'vdt:fleet_freezes_total{reason="at_max"}' in text


# ---------------------------------------------------------------------------
# Chaos soaks: 2 -> 3 -> 1 under a seeded fault sequence
# ---------------------------------------------------------------------------
class _Collector:
    """Delivered-output ledger: the zero-loss / zero-duplication and
    parity oracle. finished counts must end at exactly 1 per session."""

    def __init__(self) -> None:
        self.tokens: dict[str, list[int]] = {}
        self.finishes: dict[str, int] = {}

    def take(self, outs) -> None:
        for out in outs or []:
            self.tokens.setdefault(out.req_id, []).extend(
                out.new_token_ids)
            if out.finished:
                self.finishes[out.req_id] = \
                    self.finishes.get(out.req_id, 0) + 1

    def assert_exact(self, rid: str, max_tokens: int) -> None:
        assert self.finishes.get(rid) == 1, \
            f"{rid}: finished {self.finishes.get(rid, 0)} times"
        assert self.tokens[rid] == _expected(_session_of(rid),
                                             max_tokens), rid


def _pump(dp, collector) -> None:
    for c in dp.clients:
        if isinstance(c, _FleetStub):
            c.serve()
    collector.take(dp.recv_outputs(timeout_ms=10))
    time.sleep(0.001)


def _drive_until(dp, collector, done, timeout_s: float = 10.0) -> None:
    deadline = time.monotonic() + timeout_s
    while not done() and time.monotonic() < deadline:
        _pump(dp, collector)
    assert done(), "soak phase did not converge"


def test_chaos_mini_soak_scale_schedule(monkeypatch):
    """Tier-1 soak: the full 2 -> 3 -> 1 replica schedule with
    continuous traffic and the fleet drills armed (scale_stall on the
    way up, replica_wedge mid-flight), every session token-exact."""
    dp = make_fleet(monkeypatch)
    col = _Collector()
    n_sessions, mt = 8, 6
    for i in range(n_sessions):
        dp.add_request(_req(f"s-{i}", max_tokens=mt))
    # Surge: pressure 2 -> 3 through one provisioning stall.
    _pressure(dp, 20)
    fi.inject("fleet.scale_stall", max_fires=1)
    fi.inject("fleet.replica_wedge", max_fires=1)
    try:
        _drive_until(dp, col, lambda: len(dp.clients) == 3)
        _drive_until(dp, col, lambda: dp.fleet.wedge_cycles == 1,
                     timeout_s=5.0)
        _drive_until(dp, col, lambda: len(col.finishes) == n_sessions)
    finally:
        fi.clear("fleet.scale_stall")
        fi.clear("fleet.replica_wedge")
    assert dp.fleet.freezes.get("scale_stall") == 1
    assert dp.fleet.scale_outs == 1
    # One rung each: the wedge never counted as a failover.
    assert dp.fleet.wedge_cycles == 1
    assert dp.replica_failovers == 0
    # Quiesce: the fleet walks down to the min-replica floor.
    _pressure(dp, 0)
    _drive_until(dp, col,
                 lambda: dp.fleet.get_stats()["replicas"] == 1,
                 timeout_s=10.0)
    assert dp.fleet.scale_ins >= 2
    # Zero lost, zero duplicated, token-exact — every session.
    for i in range(n_sessions):
        col.assert_exact(f"s-{i}", mt)


@pytest.mark.slow
def test_chaos_soak_seeded_faults(monkeypatch):
    """Heaviest soak, two stages under seeded faults with continuous
    traffic. Stage 1 (disaggregated 1P+1D fleet): ``engine_core.die``
    and ``disagg.handoff_stall`` fire mid-stream while pressure scales
    the fleet to 3. Stage 2 (plain DP fleet): the full 2 -> 3 -> 1
    schedule with ``engine_core.die`` + ``fleet.replica_wedge``. Every
    degradation lands on exactly one ladder rung and every session is
    token-exact with zero lost/duplicated requests."""
    # ---- Stage 1: disagg fleet, handoff_stall then die ----
    monkeypatch.setenv("VDT_DISAGG", "1")
    dp = make_fleet(monkeypatch, n=2, VDT_FLEET_MIN_REPLICAS="2")
    assert dp.disagg.prefill_pool == [0]
    assert dp.disagg.decode_pool == [1]
    col = _Collector()
    mt = 6
    fi.inject("disagg.handoff_stall", max_fires=2)
    try:
        for i in range(6):
            dp.add_request(_req(f"s-{i}", max_tokens=mt))
        # Both stalled handoffs degrade to local re-prefill and the
        # first wave completes before the deaths start.
        _drive_until(dp, col, lambda: len(col.finishes) == 6,
                     timeout_s=20.0)
        assert dp.disagg.fallbacks.get("local_reprefill", 0) == 2
        # Surge: scale to 3 (grows the pressured pool).
        _pressure(dp, 20)
        _drive_until(dp, col, lambda: len(dp.clients) == 3)
        # Now seed the death: the consult rides the output poll
        # exactly like the engine-core busy loop's.
        fi.inject("engine_core.die", max_fires=1)
        for c in dp.clients:
            c.die_consult = True
        for i in range(6, 12):
            dp.add_request(_req(f"s-{i}", max_tokens=mt))
        _drive_until(dp, col, lambda: dp.replica_failovers >= 1,
                     timeout_s=5.0)
        _drive_until(dp, col, lambda: len(col.finishes) == 12,
                     timeout_s=20.0)
    finally:
        fi.clear("disagg.handoff_stall")
        fi.clear("engine_core.die")
    # One rung each: the stalled handoffs degraded to local re-prefill
    # (not a death), the death counted one failover (not a wedge).
    assert dp.disagg.fallbacks.get("local_reprefill", 0) == 2
    assert dp.replica_failovers == 1
    assert dp.fleet.wedge_cycles == 0
    assert dp.fleet.scale_outs >= 1
    for i in range(12):
        col.assert_exact(f"s-{i}", mt)

    # ---- Stage 2: plain DP fleet, 2 -> 3 -> 1 with die + wedge ----
    monkeypatch.setenv("VDT_DISAGG", "0")
    dp2 = make_fleet(monkeypatch, n=2)
    col2 = _Collector()
    for i in range(8):
        dp2.add_request(_req(f"s-{i}", max_tokens=mt))
    _pressure(dp2, 20)
    fi.inject("fleet.replica_wedge", max_fires=1)
    fi.inject("engine_core.die", max_fires=1)
    try:
        _drive_until(dp2, col2, lambda: len(dp2.clients) == 3)
        for c in dp2.clients:
            c.die_consult = True
        _drive_until(dp2, col2,
                     lambda: (dp2.fleet.wedge_cycles == 1
                              and dp2.replica_failovers >= 1),
                     timeout_s=10.0)
        _drive_until(dp2, col2, lambda: len(col2.finishes) == 8,
                     timeout_s=20.0)
    finally:
        fi.clear("fleet.replica_wedge")
        fi.clear("engine_core.die")
    assert dp2.fleet.wedge_cycles == 1
    assert dp2.replica_failovers == 1
    _pressure(dp2, 0)
    _drive_until(dp2, col2,
                 lambda: dp2.fleet.get_stats()["replicas"] == 1,
                 timeout_s=10.0)
    assert dp2.fleet.scale_ins >= 2
    for i in range(8):
        col2.assert_exact(f"s-{i}", mt)


# ---------------------------------------------------------------------------
# Correctness sentinel (ISSUE 20): canary probes -> suspicion ->
# fleet quarantine, the numerics feed, and the inert default.
# ---------------------------------------------------------------------------
def _drive_canary_rounds(dp, n: int, timeout_s: float = 5.0) -> None:
    """Serve every live stub and pump the MP receive path until ``n``
    more canary rounds have resolved (recv_outputs's tick injects due
    probes; absorption resolves the round)."""
    plane = dp.correctness
    target = plane._round_idx + n
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        for c in dp.clients:
            if not getattr(c, "dead", False):
                c.serve()
        dp.recv_outputs(timeout_ms=10)
        if plane._round_idx >= target and plane._round is None:
            return
    raise AssertionError(f"canary rounds did not resolve "
                         f"(idx={plane._round_idx}, want {target})")


def _canary_env(**extra) -> dict:
    # Pin min replicas so the idle autoscaler can't scale-in a healthy
    # replica mid-drill (canaries carry no schedulable load).
    env = {"VDT_CORRECTNESS": "1", "VDT_CANARY_INTERVAL_S": "0",
           "VDT_CANARY_QUARANTINE_N": "2", "VDT_FLEET_SIGNALS": "1",
           "VDT_NUMERICS_DRIFT_FRAC": "0.5",
           "VDT_FLEET_MIN_REPLICAS": "8"}
    env.update(extra)
    return env


def test_correctness_off_is_inert(monkeypatch):
    """VDT_CORRECTNESS unset (the default): no plane object, no canary
    traffic, no correctness/numerics stats keys — the revert pin."""
    dp = make_fleet(monkeypatch)
    assert dp.correctness is None
    dp.add_request(_req("s-0"))
    for _ in range(10):
        for c in dp.clients:
            c.serve()
        dp.recv_outputs(timeout_ms=10)
    for c in dp.clients:
        assert not any(r.request_id.startswith("vdt-canary-")
                       for r in c.added)
    agg = dp._aggregate_stats([{}, {}], indices=[0, 1])
    assert "correctness" not in agg and "numerics" not in agg


def test_canary_clean_rounds_self_seed_and_stay_quiet(monkeypatch):
    """Healthy fleet: the first round per golden prompt self-seeds the
    reference journal, every later round scores clean — zero
    divergences, zero suspects (the false-positive budget is zero)."""
    dp = make_fleet(monkeypatch, **_canary_env())
    plane = dp.correctness
    assert plane is not None
    _drive_canary_rounds(dp, 8)
    stats = plane.get_stats()
    assert sum(stats["probes"].values()) >= 16
    assert stats["divergences"] == {}
    assert plane.suspects() == {}
    assert stats["journal_entries"] == 4  # one per golden prompt
    assert stats["quarantine_hints"] == 0
    # Canaries never leaked into tenant bookkeeping.
    assert not dp._requests and not dp._progress


def test_canary_flip_token_detection_to_quarantine(monkeypatch):
    """The e2e drill: ``canary.flip_token`` perturbs replica 1's canary
    stream -> divergence within the first corrupted probe (<= 3 probe
    acceptance bound) -> suspect gauge isolates replica 1 only -> a
    second strike emits the quarantine hint -> the fleet controller
    force-cycles the replica through the shared wedge rung."""
    dp = make_fleet(monkeypatch, **_canary_env())
    plane = dp.correctness
    # Four clean rounds seed the journal for every golden prompt (a
    # 2-replica tie needs the reference as tiebreaker).
    _drive_canary_rounds(dp, 4)
    assert plane.divergences == {}
    p0 = plane.probes.get(1, 0)
    # Absorb order interleaves r0,r1 per cycle: rate 0.5 fires on every
    # 2nd delta — always replica 1.
    fi.inject("canary.flip_token", rate=0.5)
    try:
        _drive_canary_rounds(dp, 1)
        assert plane.probes.get(1, 0) - p0 <= 3  # detection bound
        assert sum(plane.divergences.get(1, {}).values()) >= 1
        assert plane.suspects() == {1: 1}
        assert plane.quarantine_hints_emitted == 0  # one strike so far
        _drive_canary_rounds(dp, 1)  # second strike
    finally:
        fi.clear("canary.flip_token")
    assert plane.quarantine_hints_emitted == 1
    assert dp.fleet.quarantines == 0  # hint pending, not yet forwarded
    _tick(dp)  # forwards the hint; fleet.tick() actuates it
    assert dp.fleet.quarantines == 1
    assert dp.fleet.get_stats()["quarantines"] == 1
    assert 1 in dp._down
    # Replica 0 was never suspected and keeps serving.
    assert 0 not in dp._down
    # The cycled slot's suspicion history died with it.
    assert plane.suspects() == {}
    # The sentinel actuated through the shared rung: no failover, no
    # wedge counted.
    assert dp.replica_failovers == 0
    assert dp.fleet.wedge_cycles == 0


def test_canary_vote_isolates_minority_on_three_replicas(monkeypatch):
    """With >= 3 replicas the cross-replica vote alone dates the odd
    one out (cause ``vote``) — no journal reference needed: corruption
    older than the journal cannot hide."""
    dp = make_fleet(monkeypatch, n=3, **_canary_env())
    plane = dp.correctness
    # Absorb order interleaves r0,r1,r2: rate 1/3 fires on every 3rd
    # delta — always replica 2. No clean round first: the vote must
    # work with an unseeded journal.
    fi.inject("canary.flip_token", rate=1 / 3)
    try:
        _drive_canary_rounds(dp, 1)
    finally:
        fi.clear("canary.flip_token")
    assert plane.divergences.get(2, {}).get("vote", 0) >= 1
    assert plane.suspects() == {2: 1}
    # The corrupted round never seeded the journal (not unanimous).
    assert plane.get_stats()["journal_entries"] == 0


def test_numerics_nan_inject_feeds_quarantine(monkeypatch):
    """``numerics.nan_inject`` poisons one replica's tap harvest; the
    nan_steps delta rides the DP stats merge into the suspicion ladder
    and (quarantine_n=1) straight to a fleet quarantine hint."""
    import numpy as np

    from vllm_distributed_tpu.correctness_plane import NumericsTap
    dp = make_fleet(monkeypatch, **_canary_env(
        VDT_CANARY_INTERVAL_S="1000", VDT_CANARY_QUARANTINE_N="1"))
    plane = dp.correctness
    tap = NumericsTap()
    clean = np.array([0.0, 1.0, 2.0], dtype=np.float32)
    fi.inject("numerics.nan_inject", rate=1.0, max_fires=1)
    try:
        tap.dispatch(clean)
        tap.dispatch(clean)  # harvests the poisoned previous step
    finally:
        fi.clear("numerics.nan_inject")
    bad = tap.stats()
    assert bad["nan_steps"] == 1
    healthy = {"nan_steps": 0, "entropy_window_mean": 1.0,
               "window_steps": 4}
    agg = dp._aggregate_stats(
        [{"numerics": healthy}, {"numerics": bad}], indices=[0, 1])
    # Per-replica numerics maps merge keyed by replica, never summed.
    assert set(agg["numerics"]) == {0, 1}
    assert agg["numerics"][1]["nan_steps"] == 1
    assert plane.suspects() == {1: 1}
    assert plane.divergences[1] == {"nan_logits": 1}
    assert plane.quarantine_hints_emitted == 1
    _tick(dp)
    assert dp.fleet.quarantines == 1
    assert 1 in dp._down and 0 not in dp._down


def test_quarantine_hint_without_signals_is_dropped(monkeypatch):
    """VDT_FLEET_SIGNALS=0: the sentinel still detects and raises the
    suspect gauge, but the fleet never actuates — hints are a gated
    SIGNAL, not a new actuation path."""
    dp = make_fleet(monkeypatch, **_canary_env(VDT_FLEET_SIGNALS="0"))
    plane = dp.correctness
    _drive_canary_rounds(dp, 4)  # seed every golden prompt
    fi.inject("canary.flip_token", rate=0.5)
    try:
        _drive_canary_rounds(dp, 2)
    finally:
        fi.clear("canary.flip_token")
    assert plane.suspects() == {1: 1}
    assert plane.quarantine_hints_emitted == 1
    _tick(dp, 3)
    assert dp.fleet.quarantines == 0
    assert 1 not in dp._down


# ---------------------------------------------------------------------
# Real engines: the diurnal walk on a two-replica in-process DP fleet
# ---------------------------------------------------------------------
DIURNAL_PHASES = (("peak1", 8), ("trough", 2), ("peak2", 8))
# Peak occupancy on ONE replica is 1.0, on two it is 0.5; the trough
# sits near 0.12: the watermarks bracket exactly the 2 -> 1 -> 2 walk.
DIURNAL_ENV = {
    "VDT_FLEET_TICK_S": "0",
    "VDT_FLEET_EVAL_TICKS": "3",
    "VDT_FLEET_STALE_S": "0",
    # Zero drain grace: retirement mid-trough journal-migrates the live
    # stragglers; token parity is what proves that path exact.
    "VDT_FLEET_DRAIN_S": "0",
    "VDT_FLEET_MIN_REPLICAS": "1",
    "VDT_FLEET_MAX_REPLICAS": "2",
    "VDT_FLEET_HIGH_WATERMARK": "0.7",
    "VDT_FLEET_LOW_WATERMARK": "0.2",
    "VDT_FLEET_ACTIONS": "20",
}


def diurnal_prompts(seed: int) -> dict:
    rng = np.random.default_rng(seed)
    return {(ph, s): [int(x) for x in rng.integers(2, 127, size=24)]
            for ph, n in DIURNAL_PHASES for s in range(n)}


def run_diurnal_phase(engine, leg, ph, n, prompts, outs, each_step=None):
    sp = SamplingParams(temperature=0.0, max_tokens=12, ignore_eos=True)
    for s in range(n):
        engine.add_request(f"{leg}-{ph}-{s}", list(prompts[(ph, s)]), sp)
    for _ in range(2000):
        if not engine.has_unfinished_requests():
            break
        for o in engine.step():
            if o.finished:
                outs[o.request_id.split("-", 1)[1]] = list(
                    o.outputs[0].token_ids)
        if each_step is not None:
            each_step()
    assert not engine.has_unfinished_requests()


def test_real_engines_walk_2_1_2_token_identical_to_static_fleet(
        checkpoint, monkeypatch):
    """A peak wave, a trough and a second peak on two in-process
    replicas of a real engine: the controller retires one replica in
    the trough (its live requests journal-migrated), brings it back
    warm at the second peak, and every request's greedy tokens equal
    the static two-replica fleet's (``VDT_FLEET=0``) on the same
    traffic."""
    path, _ = checkpoint
    prompts = diurnal_prompts(16)
    for key, val in DIURNAL_ENV.items():
        monkeypatch.setenv(key, val)
    outputs, timeline, stats = {}, [], None
    for leg, flag in (("on", "1"), ("off", "0")):
        monkeypatch.setenv("VDT_FLEET", flag)
        engine = make_engine(path, data_parallel_size=2)
        outs = outputs[leg] = {}
        for ph, n in DIURNAL_PHASES:
            run_diurnal_phase(engine, leg, ph, n, prompts, outs)
            fleet = engine.engine_core.fleet
            if fleet is not None:
                timeline.append(fleet.get_stats()["replicas"])
                # Idle ticks settle a drain in progress; none after the
                # last phase, or the counters show a retire after it.
                if ph != DIURNAL_PHASES[-1][0]:
                    for _ in range(8):
                        engine.engine_core._tick()
        if flag == "1":
            stats = engine.get_stats()
        else:
            assert engine.engine_core.fleet is None
        engine.shutdown()
    assert timeline == [2, 1, 2]
    assert stats["fleet"]["scale_ins"] >= 1
    assert stats["fleet"]["scale_outs"] >= 1
    assert stats.get("replica_failovers", 0) == 0
    assert len(outputs["off"]) == sum(n for _, n in DIURNAL_PHASES)
    assert outputs["on"] == outputs["off"]
