"""Self-managing elastic fleet: closed-loop autoscaling + live re-splits.

ROADMAP item 2. Every signal and actuator this loop needs already
exists as a disconnected piece — per-replica scheduler stats and
step-dispatch counters, disagg pool occupancy (engine/disagg.py), the
restart/failover ladder with its per-replica supervisor budget
(engine/core_client.py), journaled continuation migration
(engine/dp_client.py), and the shared tier-2 spill namespace that lets
a fresh engine warm-start its prefix cache (core/kv_tier.py).
``FleetController`` closes the loop:

* **scale-out** — sustained fleet occupancy above
  ``VDT_FLEET_HIGH_WATERMARK`` adds a DP replica: the lowest retired
  slot is reused (its device slice is reserved for exactly this),
  otherwise a new rank is appended (router ``grow``, coordinator
  ``resize``, disagg ``add_replica``). The new engine warm-starts from
  the shared T2 spill directory; restored pages are counted.
* **scale-in** — sustained occupancy below ``VDT_FLEET_LOW_WATERMARK``
  retires the least-loaded replica via drain (out of placement, keeps
  serving) -> journal-migrate whatever outlives ``VDT_FLEET_DRAIN_S``
  as continuations (token-identical under greedy, NOT counted as a
  failover — this is scheduled maintenance, not a death) -> remove
  from rotation. Zero requests lost.
* **live re-split** — a sustained prefill<->decode pool-pressure
  imbalance (``VDT_FLEET_RESPLIT_RATIO``) converts one replica: drain
  in the old role, rebuild the engine with the role-specialized config
  (role-appropriate token buckets and precompile lattice), re-enter
  the other pool. Gated to symmetric per-role world sizes — the
  replica keeps its device slice across the conversion.
* **wedge cycling** — a replica with live requests whose
  ``steps_dispatched`` has not advanced for ``VDT_FLEET_WEDGE_S`` is
  alive-but-not-stepping: its journaled requests migrate off and it is
  force-cycled through the PR-2 per-replica restart budget, counted on
  exactly the ``vdt:fleet_wedge_cycles_total`` rung.
* **graceful degradation** — a stale or missing stats snapshot for any
  in-rotation replica freezes ALL actuation (the router ``stale_stats``
  idiom: never reshape the fleet on blind signals); an exhausted
  action budget (``VDT_FLEET_ACTIONS`` per rolling window, a
  ``RestartSupervisor``) freezes it too, so an oscillating signal
  cannot thrash the fleet. Hysteresis (``VDT_FLEET_EVAL_TICKS``
  consecutive ticks) is the other anti-thrash half.

The controller has NO thread of its own: ``tick()`` rides the DP
client's output paths next to the resurrection probe it subsumes
(when ``VDT_FLEET=1`` the periodic probe folds into this loop — one
actuator, one budget — with restart health VERIFIED before a
resurrection is counted). ``VDT_FLEET=0`` constructs nothing and the
legacy probe path runs untouched.

Drills: ``fleet.scale_stall`` (replica construction stalls — counted,
budgeted, fleet intact) and ``fleet.replica_wedge`` (forces the wedge
detector). Telemetry: the ``fleet`` entry of the DP stats aggregation,
rendered as the ``vdt:fleet_*`` families, plus ``fleet_*`` timeline
events.
"""

import queue
import threading
import time
from typing import Optional

from vllm_distributed_tpu.config import EngineConfig
from vllm_distributed_tpu.engine.core_client import RestartSupervisor
from vllm_distributed_tpu.logger import init_logger
from vllm_distributed_tpu.metrics import events as ev
from vllm_distributed_tpu.metrics.events import EventRecorder
from vllm_distributed_tpu.utils import fault_injection

logger = init_logger(__name__)

# Freeze reasons surfaced as vdt:fleet_freezes_total{reason}. A freeze
# is one SKIPPED actuation opportunity (counted per frozen tick /
# blocked action, not per incident — a long stale window counts every
# tick it suppresses).
FREEZE_STALE_STATS = "stale_stats"  # snapshot missing/expired
FREEZE_BUDGET = "budget"  # action budget exhausted this window
FREEZE_SCALE_STALL = "scale_stall"  # replica construction failed
FREEZE_AT_MAX = "at_max"  # pressure with no slot/devices to grow into
FREEZE_ASYM_TP = "asym_tp"  # re-split blocked by asymmetric role TP
FREEZE_PARTITION = "partition"  # control plane unreachable (HA mode)


class FleetController:
    """Control loop over ``DPEngineClient``'s replica set. Every entry
    point runs under the balancer RLock (tick() takes it; observe_stats
    sticks to GIL-atomic dict assignment like the router's feed)."""

    def __init__(self, client, config: EngineConfig) -> None:
        from vllm_distributed_tpu import envs
        self.client = client
        self.config = config
        self.min_replicas = envs.VDT_FLEET_MIN_REPLICAS
        self.max_replicas = (envs.VDT_FLEET_MAX_REPLICAS
                             or len(client.clients))
        self.tick_s = envs.VDT_FLEET_TICK_S
        self.high_wm = envs.VDT_FLEET_HIGH_WATERMARK
        self.low_wm = envs.VDT_FLEET_LOW_WATERMARK
        self.eval_ticks = envs.VDT_FLEET_EVAL_TICKS
        self.stale_s = envs.VDT_FLEET_STALE_S
        self.wedge_s = envs.VDT_FLEET_WEDGE_S
        self.drain_s = envs.VDT_FLEET_DRAIN_S
        self.resplit_ratio = envs.VDT_FLEET_RESPLIT_RATIO
        # The goodput signal (VDT_FLEET_SIGNALS): a tenant under its
        # goodput floor is scale-out pressure and a scale-in veto. Off
        # (default) the decision is occupancy-only.
        self.signals = envs.VDT_FLEET_SIGNALS
        self.goodput_floor = envs.VDT_FLEET_GOODPUT_FLOOR
        self._goodput: dict[str, float] = {}
        self.max_num_seqs = max(1, config.scheduler_config.max_num_seqs)
        # Supervisor-style ACTION budget (shared across every fleet
        # action): next_delay() consumes one attempt, None = exhausted
        # until the rolling window slides — same machinery as the PR-2
        # restart budget, zero backoff (pacing is the tick's job).
        self.supervisor = RestartSupervisor(
            max_attempts=envs.VDT_FLEET_ACTIONS,
            window_s=envs.VDT_FLEET_ACTION_WINDOW_S,
            backoff_base_s=0.0, backoff_max_s=0.0)
        self.events = EventRecorder()
        # Counters (vdt:fleet_*; exact values — one controller owns the
        # whole fleet, nothing to merge).
        self.scale_outs = 0
        self.scale_ins = 0
        self.wedge_cycles = 0
        self.warm_start_pages = 0
        self.quarantines = 0
        self.freezes: dict[str, int] = {}
        # Correctness-sentinel quarantine hints (observe_quarantine):
        # replica -> cause, consumed by _check_quarantine on the next
        # tick. Only populated under VDT_FLEET_SIGNALS.
        self._quarantine_hints: dict[int, str] = {}
        # Per-replica stats snapshot + receipt instant (monotonic);
        # in-process replicas refresh synchronously each tick,
        # subprocess replicas are fed passively by the stats polls that
        # already flow through _aggregate_stats.
        self._snap: dict[int, tuple[dict, float]] = {}
        # Step-phase heartbeat: replica -> (last steps_dispatched seen,
        # instant it last ADVANCED). The wedge detector reads the age.
        self._step_marks: dict[int, tuple[int, float]] = {}
        # Replicas mid-drain: i -> {"mode": "retire"|"convert",
        # "role": new role or None, "deadline": monotonic}.
        self._draining: dict[int, dict] = {}
        self._high_ticks = 0
        self._low_ticks = 0
        self._resplit_dir: Optional[str] = None
        self._resplit_ticks = 0
        self._last_tick = float("-inf")
        logger.info(
            "fleet controller: replicas [%d, %d], watermarks "
            "[%.2f, %.2f], %d-tick hysteresis, budget %d/%.0fs",
            self.min_replicas, self.max_replicas, self.low_wm,
            self.high_wm, self.eval_ticks, self.supervisor.max_attempts,
            self.supervisor.window_s)

    # ------------------------------------------------------------------
    # Membership views
    # ------------------------------------------------------------------
    def _active(self) -> list[int]:
        """Replicas in rotation (serving): not down, not retired.
        Draining replicas still count — they hold live work."""
        c = self.client
        return [i for i in range(len(c.clients))
                if i not in c._down and i not in c._retired]

    def _placeable(self) -> list[int]:
        c = self.client
        return [i for i in self._active() if i not in c._no_place]

    # ------------------------------------------------------------------
    # Signal feed
    # ------------------------------------------------------------------
    def observe_stats(self, replica: int, stats: dict) -> None:
        """Feed one replica's stats dict (the same passive channel the
        router rides: every stats poll through _aggregate_stats)."""
        if not isinstance(stats, dict):
            return
        if ("num_running_reqs" not in stats
                and "steps_dispatched" not in stats):
            return  # not a scheduler stats dict
        now = time.monotonic()
        self._snap[replica] = (stats, now)
        steps = stats.get("steps_dispatched")
        if isinstance(steps, (int, float)):
            mark = self._step_marks.get(replica)
            if mark is None or steps != mark[0]:
                self._step_marks[replica] = (int(steps), now)

    def _refresh_snapshots(self) -> None:
        """In-process replicas answer get_stats inline (a dict build);
        subprocess replicas are never polled here — passive feed only
        (the router's maybe_refresh discipline)."""
        c = self.client
        for i in self._active():
            if getattr(c.clients[i], "engine_core", None) is None:
                continue
            try:
                self.observe_stats(i, c.clients[i].get_stats())
            except Exception:  # noqa: BLE001 - replica mid-death; the
                # output path's own poll surfaces it for failover.
                pass

    def observe_goodput(self, fracs: dict,
                        degraded: bool = False) -> None:
        """Per-tenant goodput fractions (metrics/stats.py FrontendStats
        SLO scoring, fed through the entrypoints' stats path). Only
        consulted when VDT_FLEET_SIGNALS is on. ``degraded`` — the SLO
        burn-rate watchdog's sustained-burn flag — registers as a
        zero-goodput pseudo-tenant, so under VDT_FLEET_SIGNALS with a
        goodput floor it counts as scale-out pressure and a scale-in
        veto exactly like a starved tenant; it clears as soon as the
        burn subsides."""
        if isinstance(fracs, dict):
            for tenant, frac in fracs.items():
                if isinstance(frac, (int, float)):
                    self._goodput[str(tenant)] = float(frac)
        if degraded:
            self._goodput["_slo_burn"] = 0.0
        else:
            self._goodput.pop("_slo_burn", None)

    def observe_quarantine(self, hints: dict) -> None:
        """Replica-quarantine hints from the correctness sentinel
        ({replica: cause} — sustained canary divergence or numerics
        strikes). Gated on VDT_FLEET_SIGNALS like the goodput feed: a
        hint is a SIGNAL into the existing actuator, never a new
        actuation path — _check_quarantine drains it through the same
        force-cycle rung (budget, fence, drain-migrate, probed respawn)
        the wedge detector uses."""
        if not self.signals or not isinstance(hints, dict):
            return
        for i, cause in hints.items():
            if isinstance(i, int):
                self._quarantine_hints[i] = str(cause)

    def _freeze(self, reason: str) -> None:
        self.freezes[reason] = self.freezes.get(reason, 0) + 1
        self.events.record("", ev.FLEET_FREEZE, {"reason": reason})

    # ------------------------------------------------------------------
    # HA control-plane hooks (engine/control_plane.py overrides these;
    # the in-process controller is its own single owner, so the base
    # fence always passes and the journal is a no-op).
    # ------------------------------------------------------------------
    def _fence(self, action: str) -> bool:
        """Epoch check before an actuation. Base: always allowed."""
        return True

    def _journal_begin(self, i: int, mode: str,
                       role: Optional[str]) -> None:
        """Write the intent record for a multi-step action's next rung
        BEFORE actuating it. Base: no journal."""

    def _journal_end(self, i: int) -> None:
        """The multi-step action on replica ``i`` reached a terminal
        state; drop its intent record. Base: no journal."""

    def close(self) -> None:
        """Release control-plane state (HA override relinquishes the
        lease); nothing to do in-process."""

    def _actuation_allowed(self, now: float) -> bool:
        """Stale/missing stats for ANY in-rotation replica freeze all
        actuation: the controller never reshapes the fleet on blind
        signals (scale decisions and the wedge detector both read the
        snapshots this check guards)."""
        if self.stale_s <= 0:
            return True
        for i in self._active():
            snap = self._snap.get(i)
            if snap is None or now - snap[1] > self.stale_s:
                self._freeze(FREEZE_STALE_STATS)
                return False
        return True

    def _budget_ok(self) -> bool:
        if self.supervisor.next_delay() is None:
            self._freeze(FREEZE_BUDGET)
            return False
        return True

    # ------------------------------------------------------------------
    # The loop
    # ------------------------------------------------------------------
    def tick(self) -> None:
        """One control-loop evaluation; called from the DP client's
        output paths (where the legacy resurrection probe ran). Probe
        results apply on every call; the control logic rate-limits
        itself to VDT_FLEET_TICK_S."""
        c = self.client
        with c._lock:
            self._apply_probe_results()
            now = time.monotonic()
            if now - self._last_tick < self.tick_s:
                return
            self._last_tick = now
            self._refresh_snapshots()
            self._progress_drains(now)
            self._schedule_probes(now)
            if not self._actuation_allowed(now):
                return
            self._check_wedges(now)
            self._check_quarantine(now)
            if not self._draining:
                # One structural action in flight at a time: scale and
                # re-split decisions wait for the drain to land.
                self._evaluate_scaling(now)
                self._evaluate_resplit(now)

    # -- Folded resurrection probe (satellite: one actuator, one budget)
    def _apply_probe_results(self) -> None:
        c = self.client
        while True:
            try:
                i, ok = c._probe_results.get_nowait()
            except queue.Empty:
                break
            c._probing.discard(i)
            if not ok:
                continue
            c._down.discard(i)
            c.replica_resurrections += 1
            if c.coordinator is not None:
                try:
                    c.coordinator.set_health(i, True)
                except RuntimeError:
                    # Partitioned from the control plane mid-apply: the
                    # replica serves locally; the coordinator relearns
                    # its health from the next successful RPC epoch.
                    if not c._coord_partition_degraded():
                        raise
            # Fresh engine: restart the step-phase heartbeat and give
            # the stale-stats check a grace window.
            self._mark_fresh(i)
            logger.info("DP replica %d resurrected; back in rotation", i)

    def _schedule_probes(self, now: float) -> None:
        """The legacy _maybe_resurrect scheduling, minus retired slots,
        with restart HEALTH VERIFICATION (a probe that reconnects but
        fails its warm-start stats probe reports still-down and does
        not count as a resurrection)."""
        c = self.client
        down = c._down - c._retired
        if not down or c._probe_interval <= 0:
            return
        for i in sorted(down):
            if i in c._probing or now < c._next_probe.get(i, 0):
                continue
            c._next_probe[i] = now + c._probe_interval
            if c._supervisors[i].next_delay() is None:
                continue  # replica restart budget burnt
            c._probing.add(i)
            threading.Thread(target=c._probe_restart_verified,
                             args=(i, ), name=f"dp-resurrect-{i}",
                             daemon=True).start()

    def _mark_fresh(self, i: int) -> None:
        now = time.monotonic()
        self._snap[i] = (self._snap.get(i, ({}, 0.0))[0], now)
        mark = self._step_marks.get(i)
        self._step_marks[i] = ((mark[0] if mark else 0), now)

    # -- Wedge detection ------------------------------------------------
    def _check_wedges(self, now: float) -> None:
        if self.wedge_s <= 0:
            return
        c = self.client
        for i in self._active():
            if i in self._draining or not c._live[i]:
                continue
            mark = self._step_marks.get(i)
            wedged = (mark is not None
                      and now - mark[1] > self.wedge_s)
            if fault_injection.should_fire("fleet.replica_wedge"):
                wedged = True  # drill: force the detector
            if wedged:
                self._cycle_wedged(i, now)

    def _cycle_wedged(self, i: int, now: float) -> None:
        """Force-cycle an alive-but-not-stepping replica: migrate its
        journaled requests (uncounted — the replica never died, so the
        only rung this degradation lands on is wedge_cycles), take it
        out of rotation, and let the folded probe restart it through
        its PR-2 restart budget."""
        c = self.client
        logger.error(
            "fleet: replica %d WEDGED (steps stalled > %.1fs with %d "
            "live request(s)); force-cycling", i, self.wedge_s,
            len(c._live[i]))
        if self._cycle_out(i, now):
            self.wedge_cycles += 1
            self.events.record("", ev.FLEET_WEDGE_CYCLE, {"replica": i})

    def _cycle_out(self, i: int, now: float) -> bool:
        """The shared force-cycle actuation rung (wedge detector and
        correctness quarantine): budget, fence, out of rotation,
        journal-migrate, immediate probed respawn. True when actuated
        (the caller owns the cause-specific counter/event)."""
        if not self._budget_ok():
            return False
        if not self._fence("force_cycle"):
            return False
        c = self.client
        c._down.add(i)
        if c.router is not None:
            c.router.on_replica_down(i)
        if c.coordinator is not None:
            c.coordinator.set_health(i, False, clear=True)
        c._drain_migrate_locked(i, report=False)
        c._next_probe[i] = now  # probe immediately, through the budget
        return True

    def _check_quarantine(self, now: float) -> None:
        """Drain the correctness sentinel's quarantine hints through
        the force-cycle rung: drain + respawn via the PR-16 machinery,
        never a new actuation path. A hint for a replica already out of
        rotation (or mid-drain) is dropped — its cycle is in flight."""
        if not self._quarantine_hints:
            return
        hints, self._quarantine_hints = self._quarantine_hints, {}
        c = self.client
        active = set(self._active())
        for i, cause in sorted(hints.items()):
            if i not in active or i in self._draining or i in c._down:
                continue
            logger.error(
                "fleet: replica %d QUARANTINED by correctness sentinel "
                "(%s); force-cycling", i, cause)
            if self._cycle_out(i, now):
                self.quarantines += 1
                self.events.record("", ev.FLEET_QUARANTINE,
                                   {"replica": i, "cause": cause})
                if getattr(c, "correctness", None) is not None:
                    # The slot respawns as a fresh engine: clear its
                    # suspicion so the new replica starts clean.
                    c.correctness.forget_replica(i)

    # -- Scaling --------------------------------------------------------
    def _occupancy(self, members: list[int]) -> float:
        c = self.client
        cap = len(members) * self.max_num_seqs
        if cap <= 0:
            return 1.0
        live = sum(len(c._live[i]) for i in members)
        waiting = sum(
            float(self._snap.get(i, ({}, 0.0))[0]
                  .get("num_waiting_reqs", 0)) for i in members)
        return (live + waiting) / cap

    def _evaluate_scaling(self, now: float) -> None:
        active = self._active()
        occ = self._occupancy(active)
        starved = False
        if self.signals and self.goodput_floor > 0 and self._goodput:
            # An SLO-starved tenant is scale-out pressure regardless
            # of occupancy, and vetoes scale-in.
            starved = min(self._goodput.values()) < self.goodput_floor
        self._high_ticks = self._high_ticks + 1 \
            if occ >= self.high_wm or starved else 0
        self._low_ticks = self._low_ticks + 1 \
            if occ <= self.low_wm and not starved else 0
        if self._high_ticks >= self.eval_ticks:
            self._high_ticks = 0
            self._scale_out(now)
        elif (self._low_ticks >= self.eval_ticks
              and len(active) > self.min_replicas):
            self._low_ticks = 0
            self._begin_retire(now)

    def _scale_out(self, now: float) -> None:
        c = self.client
        if len(self._active()) >= self.max_replicas:
            self._freeze(FREEZE_AT_MAX)
            return
        # Reuse the lowest retired slot (its device slice is reserved);
        # append a fresh rank only past that.
        reuse = min(c._retired) if c._retired else None
        slot = reuse if reuse is not None else len(c.clients)
        role = None
        if c.disagg is not None:
            # Grow the pressured pool (ties grow prefill: admission
            # pressure lands there first).
            pp = self._pool_occupancy("prefill")
            dp = self._pool_occupancy("decode")
            from vllm_distributed_tpu.engine.disagg import (DECODE_POOL,
                                                            PREFILL_POOL)
            role = DECODE_POOL if dp > pp else PREFILL_POOL
        if not self._budget_ok():
            return
        if not self._fence("scale_out"):
            return
        try:
            fault_injection.fire_or_raise("fleet.scale_stall")
            newc = c._spawn_replica(slot, role)
        except Exception as e:  # noqa: BLE001 - provisioning failed;
            # the action budget was consumed, so a wedged provisioner
            # converges to frozen, not thrashing.
            logger.error("fleet: scale-out of replica %d stalled: %s",
                         slot, e)
            self._freeze(FREEZE_SCALE_STALL)
            return
        c._enter_replica(slot, newc, role)
        self.scale_outs += 1
        self._count_warm_start(slot)
        self._mark_fresh(slot)
        self.events.record("", ev.FLEET_SCALE_OUT,
                           {"replica": slot, "role": role,
                            "reused": reuse is not None})
        logger.info("fleet: scaled OUT to %d replicas (replica %d%s)",
                    len(self._active()), slot,
                    f", role {role}" if role else "")

    def _begin_retire(self, now: float) -> None:
        c = self.client
        victims = [i for i in self._active() if i not in self._draining]
        if c.disagg is not None:
            # Never retire a pool's last member: disagg needs >= 1 of
            # each role to serve at all.
            victims = [i for i in victims
                       if self._pool_members(c.disagg.role_of(i),
                                             victims) != [i]]
        if not victims:
            return
        if not self._budget_ok():
            return
        if not self._fence("scale_in"):
            return
        victim = min(victims, key=lambda i: (len(c._live[i]), -i))
        self._start_drain(victim, "retire", None, now)
        logger.info("fleet: retiring replica %d (drain deadline %.1fs)",
                    victim, self.drain_s)

    def _start_drain(self, i: int, mode: str, role: Optional[str],
                     now: float) -> None:
        c = self.client
        # Intent BEFORE actuation: a leader that dies between here and
        # _finish_* leaves a record a successor replays to completion.
        self._journal_begin(i, mode, role)
        c._no_place.add(i)
        if c.coordinator is not None:
            # Out of the routing set, counts kept: the drain migration
            # reports its own deltas as requests move off.
            c.coordinator.set_health(i, False)
        self._draining[i] = {"mode": mode, "role": role,
                             "deadline": now + self.drain_s}

    def _progress_drains(self, now: float) -> None:
        c = self.client
        for i in list(self._draining):
            d = self._draining[i]
            if c._live[i] and now < d["deadline"]:
                continue
            if not self._fence(d["mode"]):
                # Deposed mid-drain: abandon the LOCAL record without
                # touching fleet state — the new leaseholder owns
                # completion through the journal.
                self._draining.pop(i)
                continue
            if c._live[i]:
                # Past the deadline: journal-migrate the stragglers as
                # continuations — token-identical under greedy, zero
                # loss, no failover counted.
                c._drain_migrate_locked(i)
            self._draining.pop(i)
            if d["mode"] == "retire":
                self._finish_retire(i)
            else:
                self._finish_convert(i, d["role"])

    def _finish_retire(self, i: int) -> None:
        c = self.client
        try:
            c.clients[i].shutdown()
        except Exception:  # noqa: BLE001 - best-effort teardown
            pass
        c._no_place.discard(i)
        c._retired.add(i)
        c._down.add(i)
        if c.router is not None:
            c.router.on_replica_down(i)
        if c.coordinator is not None:
            c.coordinator.set_health(i, False, clear=True)
        if c.disagg is not None:
            c.disagg.remove_replica(i)
        self._snap.pop(i, None)
        self._step_marks.pop(i, None)
        self.scale_ins += 1
        self._journal_end(i)
        self.events.record("", ev.FLEET_SCALE_IN, {"replica": i})
        logger.info("fleet: scaled IN to %d replicas (replica %d "
                    "retired; zero requests lost)",
                    len(self._active()), i)

    def _finish_convert(self, i: int, role: str) -> None:
        """Drained converted replica: rebuild its engine with the new
        role's specialized config (role-appropriate token buckets and
        precompile lattice) and re-enter it in the other pool."""
        c = self.client
        try:
            c.clients[i].shutdown()
        except Exception:  # noqa: BLE001 - best-effort teardown
            pass
        try:
            newc = c._spawn_replica(i, role)
        except Exception as e:  # noqa: BLE001 - conversion spawn
            # failed: the slot degrades to DOWN and the folded probe
            # owns its recovery (in the old role) through the replica's
            # restart budget.
            logger.error("fleet: re-split rebuild of replica %d "
                         "failed: %s", i, e)
            self._freeze(FREEZE_SCALE_STALL)
            c._no_place.discard(i)
            c._down.add(i)
            if c.router is not None:
                c.router.on_replica_down(i)
            if c.coordinator is not None:
                c.coordinator.set_health(i, False, clear=True)
            c._next_probe[i] = time.monotonic() + c._probe_interval
            self._journal_end(i)
            return
        c.clients[i] = newc
        c._no_place.discard(i)
        if c.router is not None:
            c.router.on_replica_down(i)  # old role's pages died
        if c.coordinator is not None:
            c.coordinator.set_health(i, True, clear=True)
        if c.disagg is not None:
            c.disagg.set_role(i, role)
        self._count_warm_start(i)
        self._mark_fresh(i)
        self._journal_end(i)
        self.events.record("", ev.FLEET_RESPLIT,
                           {"replica": i, "role": role})
        logger.info("fleet: replica %d re-entered rotation as %s "
                    "(pools now prefill=%s decode=%s)", i, role,
                    c.disagg.prefill_pool if c.disagg else None,
                    c.disagg.decode_pool if c.disagg else None)

    # -- Live pool re-split ---------------------------------------------
    def _pool_members(self, role: str,
                      within: Optional[list[int]] = None) -> list[int]:
        d = self.client.disagg
        pool = d.prefill_pool if role == "prefill" else d.decode_pool
        members = within if within is not None else self._active()
        return [i for i in pool if i in members]

    def _pool_occupancy(self, role: str) -> float:
        members = self._pool_members(role)
        return self._occupancy(members) if members else 0.0

    def _evaluate_resplit(self, now: float) -> None:
        c = self.client
        if c.disagg is None or self.resplit_ratio <= 0:
            return
        from vllm_distributed_tpu.engine.disagg import (DECODE_POOL,
                                                        PREFILL_POOL)
        pp = self._pool_occupancy(PREFILL_POOL)
        dp = self._pool_occupancy(DECODE_POOL)
        # The pressured pool must carry real load (>= the low
        # watermark) AND out-pressure the other pool by the ratio; the
        # DONOR pool must keep a member after the conversion.
        direction = None
        if (dp >= self.low_wm and dp > pp * self.resplit_ratio
                and len(self._pool_members(PREFILL_POOL)) > 1):
            direction = DECODE_POOL
        elif (pp >= self.low_wm and pp > dp * self.resplit_ratio
              and len(self._pool_members(DECODE_POOL)) > 1):
            direction = PREFILL_POOL
        if direction != self._resplit_dir:
            self._resplit_dir = direction
            self._resplit_ticks = 0
        if direction is None:
            return
        self._resplit_ticks += 1
        if self._resplit_ticks < self.eval_ticks:
            return
        self._resplit_ticks = 0
        if not c.disagg.symmetric_roles():
            # Asymmetric per-role TP: the convert would need a
            # different device footprint than the slot owns.
            self._freeze(FREEZE_ASYM_TP)
            return
        if not self._budget_ok():
            return
        donor_role = (PREFILL_POOL if direction == DECODE_POOL
                      else DECODE_POOL)
        donors = [i for i in self._pool_members(donor_role)
                  if i not in self._draining]
        if len(donors) <= 1:
            return
        if not self._fence("resplit"):
            return
        victim = min(donors, key=lambda i: (len(c._live[i]), -i))
        self._start_drain(victim, "convert", direction, now)
        logger.info(
            "fleet: re-splitting pools — converting replica %d "
            "%s -> %s (occupancy prefill=%.2f decode=%.2f)", victim,
            donor_role, direction, pp, dp)

    # -- Warm start ------------------------------------------------------
    def _count_warm_start(self, i: int) -> None:
        """Pages the fresh engine restored from the shared T2 spill
        namespace (core/kv_tier.py counts them at its disk scan)."""
        try:
            stats = self.client.clients[i].get_stats()
        except Exception:  # noqa: BLE001 - stats probe is best-effort
            return
        tier = stats.get("kv_tier")
        if isinstance(tier, dict):
            self.warm_start_pages += int(tier.get("warm_start_pages", 0))
        self.observe_stats(i, stats)

    # ------------------------------------------------------------------
    def reset(self) -> None:
        """Full-fleet restart: every surviving replica respawned with
        empty state; drains are moot (counters persist)."""
        self._draining.clear()
        self._snap.clear()
        self._step_marks.clear()
        self._quarantine_hints.clear()
        self._high_ticks = self._low_ticks = self._resplit_ticks = 0
        self._resplit_dir = None
        self._last_tick = float("-inf")

    def drain_events(self) -> list:
        return self.events.drain()

    def get_stats(self) -> dict:
        """The ``fleet`` entry of the DP stats aggregation, rendered as
        the vdt:fleet_* families."""
        c = self.client
        return {
            "replicas": len(self._active()),
            "draining": len(self._draining),
            "scale_outs": self.scale_outs,
            "scale_ins": self.scale_ins,
            "resplits": (c.disagg.resplits
                         if c.disagg is not None else 0),
            "wedge_cycles": self.wedge_cycles,
            "warm_start_pages": self.warm_start_pages,
            "quarantines": self.quarantines,
            "freezes": dict(self.freezes),
        }
