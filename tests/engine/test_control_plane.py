"""HA fleet control plane (engine/control_plane.py).

Split-brain and failover drills over ``HAFleetController``: leader
election with TTL-lease renewal, ``fleet.controller_die`` failover
within the TTL, the ``fleet.lease_expire`` split-brain (an ex-leader's
queued drain rung and scale decisions are rejected by the coordinator's
epoch fence — counted, never raised — with zero request loss), the
leader-crash-mid-drain journal-replay acceptance drill (the successor
completes the retire with token parity), ``coordinator.partition``
degradation (frozen placement, serving continues, local routing
fallback), the standby fenced-resurrect single-owner guard, and the
``VDT_FLEET_SIGNALS`` decision matrix (roofline phase + per-tenant
goodput shift the scale decision; occupancy-only when off). Two
front-ends are modeled as two controllers sharing one DP client,
coordinator socket, and journal directory — exactly the state two API
servers would share."""

import time

import pytest

from tests.conftest import make_config
from tests.engine.test_fleet import (DIURNAL_ENV, DIURNAL_PHASES, FLEET_ENV,
                                     _Collector, _FleetStub, _pressure,
                                     _pump, _req, _tick, _tok,
                                     diurnal_prompts, make_fleet,
                                     run_diurnal_phase)
from tests.engine.test_llm_engine import checkpoint, make_engine  # noqa: F401
from vllm_distributed_tpu.engine import dp_client as dp_mod
from vllm_distributed_tpu.engine.control_plane import HAFleetController
from vllm_distributed_tpu.engine.dp_client import DPEngineClient
from vllm_distributed_tpu.engine.fleet import FleetController
from vllm_distributed_tpu.metrics import events as ev
from vllm_distributed_tpu.metrics.prometheus import render_metrics
from vllm_distributed_tpu.utils import fault_injection as fi

pytestmark = pytest.mark.faults

# Tiny lease so takeover drills finish in well under a second; MIN=2
# keeps an idle 2-replica fleet from retiring into the drills (the
# drain tests override it back to 1).
TTL_S = 0.3
HA_ENV = {
    **FLEET_ENV,
    "VDT_FLEET_CONTROLLER": "1",
    "VDT_FLEET_LEASE_TTL_S": str(TTL_S),
    "VDT_FLEET_MIN_REPLICAS": "2",
}


@pytest.fixture
def ha(monkeypatch, tmp_path):
    """Factory for a controller-on stub fleet; tears the DP clients
    down afterwards so every spawned coordinator process is reaped."""
    created = []

    def make(n: int = 2, coordinator_routes: bool = False,
             **env) -> DPEngineClient:
        e = {**HA_ENV, "VDT_FLEET_JOURNAL_DIR": str(tmp_path), **env}
        for key, val in e.items():
            monkeypatch.setenv(key, val)
        config = make_config()
        config.parallel_config.data_parallel_size = n
        config.parallel_config.data_parallel_coordinator = \
            coordinator_routes
        ft = config.fault_tolerance_config
        ft.replica_probe_interval_s = 0.01
        ft.restart_backoff_base_s = 0.0
        ft.restart_max_attempts = 100
        monkeypatch.setattr(dp_mod, "SyncMPClient", _FleetStub)
        dp = DPEngineClient(config, force_mp=True)
        created.append(dp)
        return dp

    yield make
    fi.clear()
    for dp in created:
        try:
            dp.shutdown()
        except Exception:  # noqa: BLE001 - best-effort teardown
            pass


def _expire_lease() -> None:
    time.sleep(TTL_S + 0.05)


# ---------------------------------------------------------------------------
# Inert default: VDT_FLEET_CONTROLLER unset keeps PR-16 behavior
# ---------------------------------------------------------------------------
def test_controller_off_is_plain_fleet(monkeypatch):
    dp = make_fleet(monkeypatch)
    assert type(dp.fleet) is FleetController
    assert not getattr(dp.fleet, "ha", False)
    assert dp.coordinator is None  # no control-plane process spawned
    # The base hooks are declared no-ops: fence always passes, no
    # journal, no leader keys in the stats entry.
    assert dp.fleet._fence("scale_out") is True
    assert "leader" not in dp.fleet.get_stats()


# ---------------------------------------------------------------------------
# Leader election + lease renewal + telemetry
# ---------------------------------------------------------------------------
def test_leader_election_renewal_and_metrics(ha):
    dp = ha()
    primary = dp.fleet
    assert isinstance(primary, HAFleetController)
    assert not primary.is_leader  # nothing until the first tick
    _tick(dp)
    assert primary.is_leader and primary.epoch == 1
    assert primary.leader_transitions == 1
    # Renewal keeps the epoch (no holder change).
    _tick(dp)
    assert primary.is_leader and primary.epoch == 1
    info = dp.coordinator.lease_info()
    assert info["holder"] == primary.holder and info["epoch"] == 1
    # A second front-end's controller stays a standby.
    standby = HAFleetController(dp, dp.config, holder="standby")
    standby.tick()
    assert not standby.is_leader
    assert standby.fenced_actions == {}
    assert len(dp.clients) == 2  # nobody actuated anything
    # Leadership renders on the vdt:fleet_* families.
    agg = dp._aggregate_stats([{}, {}], indices=[0, 1])
    assert agg["fleet"]["leader"] == 1
    assert agg["fleet"]["lease_epoch"] == 1
    text = render_metrics(agg)
    assert "vdt:fleet_leader 1" in text
    assert "vdt:fleet_lease_epoch 1" in text
    assert "vdt:fleet_leader_transitions_total 1" in text


def test_controller_die_failover_within_ttl(ha):
    """Leader death (``fleet.controller_die``): the lease lapses and a
    standby's next acquire wins within the TTL; the new leader owns the
    fleet (its fenced actuations pass at the bumped epoch)."""
    dp = ha()
    primary = dp.fleet
    _tick(dp)
    standby = HAFleetController(dp, dp.config, holder="standby")
    standby.tick()
    assert primary.is_leader and not standby.is_leader
    fi.inject("fleet.controller_die", max_fires=1)
    try:
        _tick(dp)
    finally:
        fi.clear("fleet.controller_die")
    assert primary.dead and not primary.is_leader
    assert any(e[2] == ev.FLEET_CONTROLLER_DOWN
               for e in primary.drain_events())
    # The old lease is still live: the standby cannot jump the TTL.
    standby.tick()
    assert not standby.is_leader
    _expire_lease()
    standby.tick()
    assert standby.is_leader
    assert standby.epoch == 2  # takeover bumped the fencing epoch
    assert standby.leader_transitions == 2
    assert any(e[2] == ev.FLEET_LEADER_TAKEOVER
               for e in standby.drain_events())
    # A dead controller's tick stays a no-op.
    _tick(dp)
    assert primary.get_stats()["leader"] == 0
    # The new leader actuates: scale-out passes its epoch-2 fence.
    _pressure(dp, 20)
    standby.tick()
    assert len(dp.clients) == 3
    assert standby.scale_outs == 1
    assert standby.fenced_actions == {}


# ---------------------------------------------------------------------------
# Split-brain: lease expiry fences the ex-leader, zero request loss
# ---------------------------------------------------------------------------
def test_lease_expiry_fences_ex_leader_drain_zero_loss(ha, tmp_path):
    """``fleet.lease_expire``: the leader's renewals stop but it still
    believes it leads. A standby takes over at a bumped epoch and
    replays the journaled drain; the ex-leader's queued drain rung is
    rejected by the fence (counted on
    ``vdt:fleet_fenced_actions_total``, fleet state untouched) and the
    drained session finishes token-exact — zero loss, no failover."""
    dp = ha(VDT_FLEET_MIN_REPLICAS="1", VDT_FLEET_DRAIN_S="60")
    col = _Collector()
    dp.add_request(_req("s-0", max_tokens=10))
    dp.add_request(_req("s-1", max_tokens=10))
    assert dp._owner["s-0"] == 0 and dp._owner["s-1"] == 1
    primary = dp.fleet
    _tick(dp)  # elect + begin retiring replica 1 (low occupancy)
    assert primary.is_leader and primary.epoch == 1
    assert primary._draining[1]["mode"] == "retire"
    assert (tmp_path / "drain-1.json").exists()  # intent journaled
    # The draining replica keeps serving: one token lands.
    dp.clients[1].serve()
    col.take(dp.recv_outputs(timeout_ms=10))
    assert col.tokens["s-1"] == [_tok(1, 0)]
    standby = HAFleetController(dp, dp.config, holder="standby")
    standby.tick()
    assert not standby.is_leader
    fi.inject("fleet.lease_expire")
    try:
        _expire_lease()
        # The standby's acquire wins (epoch 2) and REPLAYS the journal:
        # the half-done retire is re-entered under the new leader.
        standby.tick()
        assert standby.is_leader and standby.epoch == 2
        assert standby.journal_replays == 1
        assert standby._draining[1]["mode"] == "retire"
        assert any(e[2] == ev.FLEET_JOURNAL_REPLAY
                   for e in standby.drain_events())
        # The ex-leader still believes it leads (skipped renewals);
        # its queued drain rung and follow-up retire decision are
        # both fenced off — counted, fleet state untouched.
        primary._draining[1]["deadline"] = 0.0
        _tick(dp)
        assert primary.fenced_actions.get("retire") == 1
        assert primary.fenced_actions.get("scale_in") == 1
        assert not primary.is_leader  # demoted by the rejection
        assert 1 not in primary._draining  # local record abandoned
        assert 1 not in dp._retired  # ...without touching the fleet
        assert (tmp_path / "drain-1.json").exists()
    finally:
        fi.clear("fleet.lease_expire")
    # The new leader completes the retire through the normal ladder.
    standby._draining[1]["deadline"] = 0.0
    standby.tick()
    assert 1 in dp._retired
    assert standby.scale_ins == 1
    assert standby.journal.pending() == {}
    # Quiet the ex-leader (as if its process died) so the output-path
    # ticks below cannot re-elect it mid-pump.
    fi.inject("fleet.controller_die", max_fires=1)
    try:
        _tick(dp)
    finally:
        fi.clear("fleet.controller_die")
    assert primary.dead
    # Zero loss: both sessions finish token-exact (s-1 as a migrated
    # continuation on replica 0), and none of it counted as a death.
    deadline = time.monotonic() + 10.0
    while ((col.finishes.get("s-0") != 1 or col.finishes.get("s-1") != 1)
           and time.monotonic() < deadline):
        _pump(dp, col)
        standby.tick()
    col.assert_exact("s-0", 10)
    col.assert_exact("s-1", 10)
    assert dp.replica_failovers == 0
    # The fence rejections render with their action label.
    agg = dp._aggregate_stats([{}, {}], indices=[0, 1])
    text = render_metrics(agg)
    assert 'vdt:fleet_fenced_actions_total{action="retire"} 1' in text
    assert 'vdt:fleet_fenced_actions_total{action="scale_in"} 1' in text


# ---------------------------------------------------------------------------
# Acceptance drill: leader crash mid-drain, successor replays journal
# ---------------------------------------------------------------------------
def test_leader_crash_mid_drain_journal_replay_parity(ha, tmp_path):
    """Kill the leader (``fleet.controller_die``) between a drain's
    intent record and its completion: the successor finds the journal
    entry, replays the retire, and the drained session's stream is
    token-identical — the crash is invisible to the request."""
    dp = ha(VDT_FLEET_MIN_REPLICAS="1", VDT_FLEET_DRAIN_S="60")
    col = _Collector()
    dp.add_request(_req("s-0", max_tokens=10))
    dp.add_request(_req("s-1", max_tokens=10))
    _tick(dp)  # elect + begin retiring replica 1
    primary = dp.fleet
    assert primary._draining[1]["mode"] == "retire"
    dp.clients[1].serve()  # mid-stream: one token delivered pre-crash
    col.take(dp.recv_outputs(timeout_ms=10))
    fi.inject("fleet.controller_die", max_fires=1)
    try:
        _tick(dp)
    finally:
        fi.clear("fleet.controller_die")
    assert primary.dead
    assert (tmp_path / "drain-1.json").exists()  # intent survives
    standby = HAFleetController(dp, dp.config, holder="standby")
    standby.tick()
    assert not standby.is_leader  # old lease still live
    _expire_lease()
    standby.tick()
    assert standby.is_leader and standby.journal_replays == 1
    assert standby.get_stats()["journal_replays"] == 1
    # The successor finishes the retire it never started.
    standby._draining[1]["deadline"] = 0.0
    standby.tick()
    assert 1 in dp._retired and standby.scale_ins == 1
    assert standby.journal.pending() == {}
    deadline = time.monotonic() + 10.0
    while ((col.finishes.get("s-0") != 1 or col.finishes.get("s-1") != 1)
           and time.monotonic() < deadline):
        _pump(dp, col)
        standby.tick()
    col.assert_exact("s-0", 10)
    col.assert_exact("s-1", 10)
    assert dp.replica_failovers == 0  # scheduled maintenance, no death


# ---------------------------------------------------------------------------
# Partition degradation: serving continues with frozen placement
# ---------------------------------------------------------------------------
def test_partition_freezes_placement_serving_continues(ha):
    dp = ha()
    _tick(dp)
    assert dp.fleet.is_leader
    col = _Collector()
    fi.inject("coordinator.partition")
    try:
        _tick(dp, 3)
        # Partitioned from the control plane: demoted + frozen, one
        # counted freeze per suppressed tick.
        assert not dp.fleet.is_leader
        assert dp.fleet.freezes.get("partition", 0) >= 3
        # The front-end keeps serving (placement is local here: the
        # control-plane-only coordinator never owned routing).
        dp.add_request(_req("p-0", max_tokens=4))
        deadline = time.monotonic() + 5.0
        while (col.finishes.get("p-0") != 1
               and time.monotonic() < deadline):
            _pump(dp, col)
        col.assert_exact("p-0", 4)
    finally:
        fi.clear("coordinator.partition")
    # Partition heals: the same holder re-acquires without an epoch
    # bump (the coordinator saw no other holder in between).
    _tick(dp)
    assert dp.fleet.is_leader and dp.fleet.epoch == 1


def test_partition_routing_falls_back_to_local(ha):
    """With the coordinator also owning routing
    (``data_parallel_coordinator=True``), a partition degrades
    admission to local least-loaded — requests still land, finish
    deltas are swallowed onto the freeze ladder, nothing raises."""
    dp = ha(coordinator_routes=True)
    assert dp._coord_routes
    _tick(dp)
    col = _Collector()
    dp.add_request(_req("r-0", max_tokens=4))  # coordinator-routed
    fi.inject("coordinator.partition")
    try:
        dp.add_request(_req("r-1", max_tokens=4))  # local fallback
        assert "r-1" in dp._owner
        assert dp.fleet.freezes.get("partition", 0) >= 1
        deadline = time.monotonic() + 5.0
        while ((col.finishes.get("r-0") != 1
                or col.finishes.get("r-1") != 1)
               and time.monotonic() < deadline):
            _pump(dp, col)
        col.assert_exact("r-0", 4)
        col.assert_exact("r-1", 4)
    finally:
        fi.clear("coordinator.partition")


# ---------------------------------------------------------------------------
# Single-owner actuation guard: standby resurrect is a fenced no-op
# ---------------------------------------------------------------------------
def test_standby_resurrect_is_fenced_noop(ha):
    dp = ha()
    _tick(dp)
    primary = dp.fleet
    standby = HAFleetController(dp, dp.config, holder="standby")
    standby.tick()
    dp.clients[0].dead = True
    dp.add_request(_req("x-0", max_tokens=4))  # discovers the death
    assert 0 in dp._down and dp.replica_failovers == 1
    # The standby sees the dead replica but only COUNTS the respawn
    # opportunity — scheduling probes is the leaseholder's job.
    standby.tick()
    assert standby.fenced_actions.get("resurrect") == 1
    assert dp.clients[0].restarts == 0
    assert any(e[2] == ev.FLEET_FENCED for e in standby.drain_events())
    # The leader resurrects it through the verified-probe ladder.
    deadline = time.monotonic() + 5.0
    while 0 in dp._down and time.monotonic() < deadline:
        time.sleep(0.02)
        _tick(dp)
    assert 0 not in dp._down
    assert dp.replica_resurrections == 1
    assert dp.clients[0].restarts == 1
    assert primary.is_leader


# ---------------------------------------------------------------------------
# The goodput signal (VDT_FLEET_SIGNALS): decision matrix
# ---------------------------------------------------------------------------
def test_signals_off_is_occupancy_only(monkeypatch):
    """Default: starved tenants shift nothing — the decision is
    exactly PR 16's occupancy comparison."""
    dp = make_fleet(monkeypatch, VDT_FLEET_LOW_WATERMARK="0")
    assert dp.fleet.signals is False
    _pressure(dp, 5)  # occupancy 10/16 = 0.625 < 0.85
    dp.observe_goodput({"gold": 0.1})  # stored, but not consulted
    assert dp.fleet._goodput == {"gold": 0.1}
    _tick(dp, 3)
    assert len(dp.clients) == 2 and dp.fleet.scale_outs == 0


@pytest.mark.parametrize("waiting, replicas, outs, ins", [
    (0, 1, 0, 1),   # occupancy 0 <= 0.3: one replica retires
    (5, 2, 0, 0),   # 10/16 = 0.625, between the watermarks: nothing
    (7, 3, 1, 0),   # 14/16 = 0.875 >= 0.85: one replica more
], ids=["under_low", "between", "over_high"])
def test_signals_on_with_healthy_tenants_decides_as_off(
        monkeypatch, waiting, replicas, outs, ins):
    """What is left of VDT_FLEET_SIGNALS is the goodput floor: with no
    tenant under it the decision is the occupancy comparison, tick for
    tick, on either side of both watermarks."""
    decided = {}
    for signals in ("0", "1"):
        dp = make_fleet(monkeypatch, VDT_FLEET_SIGNALS=signals,
                        VDT_FLEET_LOW_WATERMARK="0.3",
                        VDT_FLEET_HIGH_WATERMARK="0.85",
                        VDT_FLEET_EVAL_TICKS="2")
        assert dp.fleet.signals is (signals == "1")
        dp.observe_goodput({"gold": 0.9, "free": 0.5})  # floor 0.5
        _pressure(dp, waiting)
        walk = []
        for _ in range(3):
            _tick(dp)
            walk.append((len(dp._live), len(dp.fleet._active()),
                         dp.fleet.scale_outs, dp.fleet.scale_ins,
                         dict(dp.fleet.freezes)))
        decided[signals] = walk
    assert decided["1"] == decided["0"]
    assert decided["1"][-1][1:4] == (replicas, outs, ins)


def test_signals_goodput_floor_forces_out_and_vetoes_in(monkeypatch):
    """A tenant under its goodput floor is scale-out pressure at ANY
    occupancy and a standing scale-in veto; recovery re-enables the
    low-watermark path."""
    dp = make_fleet(monkeypatch, VDT_FLEET_SIGNALS="1",
                    VDT_FLEET_MAX_REPLICAS="2")
    dp.observe_goodput({"gold": 0.2})  # floor defaults to 0.5
    _tick(dp, 3)
    # Starved at zero occupancy: the out path fires every tick (frozen
    # at the device budget, proving the attempt), the in path never.
    assert dp.fleet.freezes.get("at_max", 0) >= 3
    assert dp.fleet._draining == {} and dp.fleet.scale_ins == 0
    dp.observe_goodput({"gold": 0.9})
    _tick(dp, 2)  # healthy again: low occupancy retires as before
    assert dp.fleet.scale_ins == 1


def test_goodput_floor_ignored_when_signals_off(monkeypatch):
    dp = make_fleet(monkeypatch, VDT_FLEET_MAX_REPLICAS="2")
    dp.observe_goodput({"gold": 0.2})
    _tick(dp, 2)
    assert dp.fleet.scale_ins == 1  # no veto: occupancy-only


# ---------------------------------------------------------------------------
# Real engines: the leader dies mid-scale-in, the standby finishes it
# ---------------------------------------------------------------------------
def test_real_engines_leader_killed_mid_scale_in_token_identical(
        checkpoint, monkeypatch, tmp_path):
    """The diurnal walk of ``test_fleet`` on two in-process replicas of
    a real engine with the lease-fenced controller on and a second
    front end standing by on the same coordinator and journal. The
    leader is killed between the drain's journaled intent and its
    completion; the standby takes the lease, replays the journal,
    finishes the retire and scales out again at the second peak. Every
    request's greedy tokens equal the static fleet's."""
    path, _ = checkpoint
    prompts = diurnal_prompts(17)
    env = {**DIURNAL_ENV,
           "VDT_FLEET_LEASE_TTL_S": "0.3",
           "VDT_FLEET_JOURNAL_DIR": str(tmp_path),
           # Below the trough's in-flight occupancy (2/16): scale-in
           # only accumulates on the idle ticks driven below, so the
           # kill lands between the drain's intent and its completion.
           "VDT_FLEET_LOW_WATERMARK": "0.05"}
    for key, val in env.items():
        monkeypatch.setenv(key, val)
    outputs = {}

    monkeypatch.setenv("VDT_FLEET", "0")
    monkeypatch.setenv("VDT_FLEET_CONTROLLER", "0")
    engine = make_engine(path, data_parallel_size=2)
    for ph, n in DIURNAL_PHASES:
        run_diurnal_phase(engine, "off", ph, n, prompts,
                          outputs.setdefault("off", {}))
    engine.shutdown()

    monkeypatch.setenv("VDT_FLEET", "1")
    monkeypatch.setenv("VDT_FLEET_CONTROLLER", "1")
    engine = make_engine(path, data_parallel_size=2)
    dp = engine.engine_core
    standby = None
    try:
        outs = outputs["on"] = {}
        for ph, n in DIURNAL_PHASES[:2]:
            run_diurnal_phase(engine, "on", ph, n, prompts, outs)
        primary = dp.fleet
        timeline = [primary.get_stats()["replicas"]]
        # Idle ticks walk the trough's scale-in up to (not past) the
        # drain's start: intent journaled, retire incomplete.
        for _ in range(50):
            dp._tick()
            if primary._draining:
                break
        assert primary._draining, "trough scale-in never began a drain"
        fi.inject("fleet.controller_die", max_fires=1)
        try:
            dp._tick()
        finally:
            fi.clear("fleet.controller_die")
        standby = HAFleetController(dp, dp.config, holder="fe-standby")
        deadline = time.monotonic() + 5.0
        while not standby.is_leader and time.monotonic() < deadline:
            standby.tick()
            time.sleep(0.02)
        assert standby.is_leader, "standby never acquired the lease"
        for _ in range(20):
            standby.tick()
            if standby.get_stats()["replicas"] == 1:
                break
        timeline.append(standby.get_stats()["replicas"])
        run_diurnal_phase(engine, "on", *DIURNAL_PHASES[2], prompts, outs,
                          each_step=standby.tick)
        st = standby.get_stats()
        timeline.append(st["replicas"])
    finally:
        if standby is not None:
            standby.close()
        engine.shutdown()
    assert timeline == [2, 1, 2]
    assert st["leader_transitions"] == 2
    assert st["journal_replays"] == 1
    assert outputs["on"] == outputs["off"]
