"""Data-parallel engine replication: N engine cores behind one balancer.

Reference: one ``DPEngineCoreProc`` per DP rank plus a ``DPCoordinator``
process that publishes per-engine request counts to the front-end
balancer (vllm/v1/engine/core.py:812, coordinator.py:21). TPU-native
redesign: each replica is a full engine core (scheduler + KV pool) on
its own contiguous device slice of the host mesh; the front-end client
routes by live request count (the coordinator's queue-length publishing
collapses into client-side accounting because one front-end owns all
replicas — a separate coordinator process only pays off with multiple
API servers, which multi-host serving adds later). The reference's
lockstep dummy batches / wave sync (core.py:929-969) are unnecessary
here by construction: expert parallelism spans the ``model`` mesh axis
INSIDE a replica, so idle replicas participate in no collective and can
simply sleep.

Transport per replica follows the parent config: in-process cores for
offline/sync use (each replica's worker re-asserts its own global mesh
per call), or one ZMQ subprocess per replica for serving — the
subprocess layout is what actually overlaps replica compute on CPU
hosts and keeps replicas isolated on TPU hosts.
"""

import copy
import os
import queue
import threading
import time
from typing import Optional

from vllm_distributed_tpu.config import EngineConfig
from vllm_distributed_tpu.core.sched.scheduler import EngineCoreOutput
from vllm_distributed_tpu.engine.core_client import (EngineCoreClient,
                                                     EngineDeadError,
                                                     InprocClient,
                                                     RestartSupervisor,
                                                     SyncMPClient)
from vllm_distributed_tpu.engine.core_proc import StepOutputs
from vllm_distributed_tpu.logger import init_logger
from vllm_distributed_tpu.metrics import events as ev
from vllm_distributed_tpu.request import (EngineCoreRequest,
                                          continuation_request)

logger = init_logger(__name__)


def _tag_replica(e: EngineDeadError, rank: int) -> EngineDeadError:
    """Re-raise a child client's death with its DP rank attached so the
    front-end (and the server's 503 body) can say WHICH replica died."""
    if e.replica is not None:
        return e
    return EngineDeadError(getattr(e, "reason", str(e)), replica=rank)


def make_replica_config(config: EngineConfig, rank: int) -> EngineConfig:
    """A deep copy of the engine config describing ONE replica: dp size 1
    at dp rank ``rank`` (the worker slices its devices from the rank)."""
    rc = copy.deepcopy(config)
    rc.parallel_config.data_parallel_size = 1
    rc.parallel_config.data_parallel_rank = rank
    return rc


def _require_cpu_for_subprocess_replicas() -> None:
    """A chip belongs to one process at a time, and a spawned engine
    core initializes JAX for itself and claims every chip of the host
    (worker.init_device slices its replica's devices out of
    ``jax.devices()`` afterwards). Two such cores cannot share a
    multi-chip host, so subprocess DP replicas run only where the
    platform is pinned to the CPU; on an accelerator host use the
    in-process replicas (the default), which take disjoint device
    slices inside the one process that holds the chips."""
    import os
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0] != "cpu":
        raise RuntimeError(
            "subprocess engine-core replicas (multiprocess_engine_core / "
            "VDT_ENABLE_MP_ENGINE with data_parallel_size > 1) cannot "
            "share an accelerator host: every spawned core claims all of "
            "the host's chips, and a chip belongs to one process at a "
            "time. Run the replicas in-process (the default), or set "
            "JAX_PLATFORMS=cpu")


class DPEngineClient(EngineCoreClient):
    """Balancing front-end over data_parallel_size engine replicas."""

    def __init__(self, config: EngineConfig, *,
                 force_mp: Optional[bool] = None) -> None:
        from vllm_distributed_tpu import envs
        self.config = config
        n = config.parallel_config.data_parallel_size
        assert n > 1, "DPEngineClient requires data_parallel_size > 1"
        if force_mp is None:
            force_mp = (config.parallel_config.multiprocess_engine_core
                        or envs.VDT_ENABLE_MP_ENGINE)
        self.is_mp = bool(force_mp)
        if self.is_mp:
            _require_cpu_for_subprocess_replicas()
        # Disaggregated serving tier (engine/disagg.py): when VDT_DISAGG
        # is set, the fleet splits into a prefill pool and a decode pool
        # — the plan is computed BEFORE replica construction so each
        # replica's config is specialized for its role (connector side,
        # token budget, precompile lattice, device offset).
        disagg_plan = None
        if envs.VDT_DISAGG:
            from vllm_distributed_tpu.engine.disagg import (
                DisaggCoordinator, specialize_replica_config)
            disagg_plan = DisaggCoordinator.plan_replicas(config)
        self.clients: list[EngineCoreClient] = []
        for rank in range(n):
            rc = make_replica_config(config, rank)
            if disagg_plan is not None:
                role, offset = disagg_plan[rank]
                specialize_replica_config(rc, role, offset)
            client = SyncMPClient(rc) if self.is_mp else InprocClient(rc)
            self.clients.append(client)
            # Propagate the replica-profiled KV pool size so the parent
            # config reflects reality (replicas are symmetric).
            if rc.cache_config.num_gpu_blocks:
                config.cache_config.num_gpu_blocks = \
                    rc.cache_config.num_gpu_blocks
        logger.info("DP front-end: %d engine replicas (%s)", n,
                    "subprocess" if self.is_mp else "in-process")
        # Optional out-of-process routing brain (reference:
        # coordinator.py DPCoordinator): admission/finish deltas report
        # to it and routing asks it, so multiple front-ends could share
        # the aggregated view.
        self.coordinator = None
        self._coord_proc = None
        # _coord_control_only: set when the ONLY coordinator is the one
        # spawned below for VDT_FLEET_CONTROLLER (routing didn't ask
        # for one) — it carries lease/fence/journal ops, never
        # admission accounting, so placement stays byte-identical.
        self._coord_control_only = False
        if config.parallel_config.data_parallel_coordinator:
            from vllm_distributed_tpu.engine.coordinator import (
                DPCoordinatorClient, spawn_coordinator)
            self._coord_proc, addr = spawn_coordinator(n)
            self._coord_addr = addr
            self.coordinator = DPCoordinatorClient(addr)
            logger.info("DP coordinator process at %s", addr)
        # Routing tier (engine/router.py): prefix-affinity + SLO-aware
        # placement over the alive replicas. VDT_ROUTER=0 removes it,
        # reverting placement to the live-count round-robin below. With
        # a coordinator, the router computes the preferred replica and
        # the coordinator keeps the (multi-front-end) admission counts.
        self.router = None
        if envs.VDT_ROUTER:
            from vllm_distributed_tpu.engine.router import ReplicaRouter
            self.router = ReplicaRouter(n, config)
        # Disagg handoff state machine: placement goes two-stage (least-
        # loaded prefill admission, affinity-scored decode home at
        # handoff) and finished prefills re-admit as pull continuations.
        self.disagg = None
        if disagg_plan is not None:
            self.disagg = DisaggCoordinator(self, config)
        # Front-end lifecycle recorder (metrics/events.py): placement
        # decisions and disagg handoffs, drained into the fleet-wide
        # timeline merge next to the per-core rings and fleet events.
        self.events = ev.EventRecorder()
        # Trace plane: replica-tag + clock-rebase drained core rings in
        # _aggregate_stats so the front-end assembler can stitch them.
        # Cached once (the envs registry re-reads os.environ); off
        # leaves every drained event byte-identical.
        self.trace_enabled = ev.trace_plane_enabled()
        # Per-replica monotonic clock offsets (front-end epoch −
        # replica epoch), estimated from the clock_mono reading riding
        # each get_stats response. In-process replicas share the clock
        # (offset ≈ the aggregation delay); subprocess offsets are
        # upper-bounded by one RPC latency.
        self._clock_offsets: dict[int, float] = {}
        # Balancer state: request ownership + live counts per replica
        # (the coordinator's published queue lengths, client-side).
        self._owner: dict[str, int] = {}
        self._live: list[set[str]] = [set() for _ in range(n)]
        self._rr = 0  # round-robin tiebreak cursor
        # Fan-out utility RPC bookkeeping (async/pump mode).
        self._util_id = 0
        self._pending_util: dict[int, list[tuple]] = {}
        self._util_partial: dict[int, dict[int, object]] = {}
        # Balancer-state lock: admissions arrive from AsyncLLM executor
        # threads while failover/finish bookkeeping runs on the pump
        # thread — every _owner/_live/_down/journal mutation (and the
        # client add_request sends they guard) happens under this RLock
        # (reentrant: _admit and _failover call each other). Output
        # POLLS stay outside it so admissions never wait on a poll.
        self._lock = threading.RLock()
        # Failover state: per-request journal (original request +
        # tokens delivered so far) for continuation-prefill migration,
        # replicas currently out of rotation, and a per-replica restart
        # budget gating the resurrection probe.
        self._requests: dict[str, EngineCoreRequest] = {}
        self._progress: dict[str, list[int]] = {}
        self._down: set[int] = set()
        self._supervisors = [RestartSupervisor.from_config(config)
                             for _ in range(n)]
        self._probe_interval = \
            config.fault_tolerance_config.replica_probe_interval_s
        self._next_probe: dict[int, float] = {}
        # In-flight resurrection probes (restart runs on a thread; the
        # result queue hands completion back to the caller's thread).
        self._probing: set[int] = set()
        self._probe_results: "queue.Queue[tuple[int, bool]]" = \
            queue.Queue()
        self.replica_failovers = 0
        self.replica_resurrections = 0
        # Elastic-fleet state (engine/fleet.py). Retired slots keep
        # their index (stable fleet-wide addressing) but leave rotation
        # permanently unless scale-out reuses them; _no_place holds
        # DRAINING replicas — excluded from placement, still polled.
        # Both stay empty with the fleet off, so every membership check
        # below reduces to the pre-fleet behavior.
        self._retired: set[int] = set()
        self._no_place: set[int] = set()
        self.fleet = None
        if envs.VDT_FLEET:
            if envs.VDT_FLEET_CONTROLLER:
                # HA control plane (engine/control_plane.py): lease/
                # fence/journal ops ride the coordinator socket, so
                # spawn one for the control plane if routing didn't —
                # _coord_routes stays False, placement untouched.
                if self.coordinator is None:
                    from vllm_distributed_tpu.engine.coordinator import (
                        DPCoordinatorClient, spawn_coordinator)
                    self._coord_proc, addr = spawn_coordinator(n)
                    self._coord_addr = addr
                    self.coordinator = DPCoordinatorClient(addr)
                    self._coord_control_only = True
                    logger.info("DP coordinator (control plane only) "
                                "at %s", addr)
                from vllm_distributed_tpu.engine.control_plane import \
                    HAFleetController
                self.fleet = HAFleetController(self, config)
            else:
                from vllm_distributed_tpu.engine.fleet import \
                    FleetController
                self.fleet = FleetController(self, config)
        # Correctness sentinel (correctness_plane.py): canary rounds,
        # reference journal, numerics drift, quarantine hints. None by
        # default — VDT_CORRECTNESS=0 constructs nothing and every hook
        # below is a `is not None` short-circuit.
        self.correctness = None
        if envs.VDT_CORRECTNESS:
            from vllm_distributed_tpu.correctness_plane import \
                CorrectnessPlane
            self.correctness = CorrectnessPlane(events=self.events)

    # ------------------------------------------------------------------
    def _pick_replica(self, request: Optional[EngineCoreRequest] = None,
                      count_fallbacks: bool = True) -> int:
        if len(self._down) == len(self.clients):
            raise EngineDeadError("all DP replicas are dead")
        # Draining replicas (fleet retire/convert) leave PLACEMENT but
        # keep serving their live requests; the union is only built
        # when the fleet actually has a drain in flight.
        blocked = (self._down | self._no_place if self._no_place
                   else self._down)
        pool, least_loaded = None, False
        if self.disagg is not None and request is not None:
            # Two-stage disagg placement: fresh requests go to the
            # prefill pool (least-loaded), handoff continuations to the
            # decode pool (affinity + load). An entirely-down pool
            # degrades to any-alive placement (counted once per
            # admission — retries after a failover don't re-count).
            pool = self.disagg.usable_pool(
                self.disagg.target_pool(request), blocked,
                count=count_fallbacks)
            least_loaded = (pool is not None and
                            self.disagg.prefill_least_loaded(request))
        prefer = None
        if self.router is not None:
            self.router.maybe_refresh(self.clients, self._down)
            prefer = self.router.route(request, self.request_counts(),
                                       blocked, pool=pool,
                                       least_loaded=least_loaded)
        if self.coordinator is not None and self._coord_routes:
            try:
                if pool is None:
                    # The coordinator's route() already accounts the
                    # admission (and skips replicas reported down via
                    # set_health); the router's pick rides along as a
                    # preference it honors while that replica is
                    # healthy.
                    return self.coordinator.route(prefer=prefer)
                # Disagg: the coordinator's fleet-wide least-loaded
                # pick (and its healthy-override of `prefer`) cannot
                # honor the pool restriction, so the pick stays local
                # and the admission is accounted to it explicitly —
                # keeping the invariant _admit's unwind relies on
                # (route() would have +1'd the same way).
                pick = (prefer if prefer is not None
                        else self._local_least_loaded(set(pool)))
                self.coordinator.report(pick, 1)
                return pick
            except RuntimeError:
                # Coordinator unreachable. With the HA control plane
                # on this is the coordinator.partition degradation:
                # keep serving with FROZEN placement (local least-
                # loaded below, counted on the freeze ladder). Without
                # it the failure surfaces as before.
                if not self._coord_partition_degraded():
                    raise
        if prefer is not None:
            return prefer
        return self._local_least_loaded(
            set(pool) if pool is not None else None)

    @property
    def _coord_routes(self) -> bool:
        """Whether routing/admission accounting rides the coordinator.
        A property (not an init-time snapshot) so a coordinator
        installed after construction — multi-front-end wiring, test
        stubs — gets the accounting exactly as before the HA plane."""
        return self.coordinator is not None \
            and not self._coord_control_only

    def _coord_partition_degraded(self) -> bool:
        """True iff a coordinator RPC failure should degrade to local
        routing instead of raising: only under the HA control plane,
        whose freeze ladder counts the partition."""
        fleet = self.fleet
        if fleet is None or not getattr(fleet, "ha", False):
            return False
        from vllm_distributed_tpu.engine.fleet import FREEZE_PARTITION
        fleet._freeze(FREEZE_PARTITION)
        return True

    def _coord_report_safe(self, engine: int, delta: int) -> None:
        """Admission-count delta to the coordinator, partition-tolerant:
        under the HA control plane a failed RPC degrades (counted on
        the freeze ladder) instead of raising into the serving path."""
        try:
            self.coordinator.report(engine, delta)
        except RuntimeError:
            if not self._coord_partition_degraded():
                raise

    def _local_least_loaded(self, members: Optional[set]) -> int:
        """Least-live-count replica with rotation tie-break, optionally
        restricted to a member subset (the disagg pool)."""
        n = len(self.clients)
        best, best_load = None, None
        for off in range(n):
            i = (self._rr + off) % n
            if i in self._down or i in self._no_place or (
                    members is not None and i not in members):
                continue
            load = len(self._live[i])
            if best_load is None or load < best_load:
                best, best_load = i, load
        if best is None:
            raise EngineDeadError("all DP replicas are dead")
        self._rr = (best + 1) % n
        return best

    def add_request(self, request: EngineCoreRequest) -> None:
        with self._lock:
            self._requests[request.request_id] = request
            admit_req = request
            if self.disagg is not None:
                # Handoff-eligible requests enter as their one-token
                # prefill-stage copy; the journal keeps the ORIGINAL
                # (the decode-home continuation and any failover replay
                # derive from it).
                admit_req = self.disagg.on_new_request(request)
            try:
                self._admit(admit_req)
            except Exception:
                self._requests.pop(request.request_id, None)
                self._progress.pop(request.request_id, None)
                if self.disagg is not None:
                    self.disagg.forget(request.request_id)
                raise

    def _admit(self, request: EngineCoreRequest) -> None:
        """Place a request on a healthy replica, failing over any
        replica found dead at admission time (its own journaled load
        migrates too), until the request lands or no replica is left."""
        first_pick = True
        while True:
            i = self._pick_replica(request, count_fallbacks=first_pick)
            first_pick = False
            try:
                self.clients[i].add_request(request)
            except Exception as e:
                # Unwind the admission accounting (route() already
                # incremented the coordinator's count).
                if self.coordinator is not None and self._coord_routes:
                    self._coord_report_safe(i, -1)
                if isinstance(e, EngineDeadError):
                    # Dead replica discovered at admission: take it out
                    # of rotation, migrate its load, then retry THIS
                    # request on whatever remains.
                    self._failover(i, e)
                    continue
                raise
            self._owner[request.request_id] = i
            self._live[i].add(request.request_id)
            if self.events.enabled:
                # The routing decision, on the request's causal trace:
                # which replica (and disagg stage, when split) this hop
                # landed on.
                detail: dict = {"replica": i}
                if self.disagg is not None:
                    stage = self.disagg._stage.get(request.request_id)
                    if stage is not None:
                        detail["pool"] = stage
                if self.trace_enabled:
                    detail = ev.stamp_trace(detail, request.trace_ctx)
                self.events.record(request.request_id, ev.ROUTER_PICK,
                                   detail)
            if self.router is not None:
                # Residency bookkeeping: the request's prompt pages will
                # live (and prefix-cache) on this replica. Migrated
                # continuations pass through here too — that re-admit IS
                # the affinity re-homing after a failover.
                self.router.on_admit(request, i)
            return

    def abort_requests(self, request_ids: list[str]) -> None:
        with self._lock:
            by_replica: dict[int, list[str]] = {}
            for rid in request_ids:
                self._requests.pop(rid, None)
                self._progress.pop(rid, None)
                if self.disagg is not None:
                    self.disagg.forget(rid)
                i = self._owner.pop(rid, None)
                if i is not None:
                    self._live[i].discard(rid)
                    by_replica.setdefault(i, []).append(rid)
            for i, rids in by_replica.items():
                try:
                    self.clients[i].abort_requests(rids)
                except Exception:  # noqa: BLE001 - replica dead; its
                    # journal entries are gone, so failover skips them.
                    pass
                if self.coordinator is not None and self._coord_routes:
                    self._coord_report_safe(i, -len(rids))

    def _mark_finished(
            self,
            outs: list[EngineCoreOutput]) -> list[EngineCoreOutput]:
        with self._lock:
            return self._mark_finished_locked(outs)

    def _mark_finished_locked(
            self,
            outs: list[EngineCoreOutput]) -> list[EngineCoreOutput]:
        if self.correctness is not None:
            # Canary absorption FIRST — before disagg interception and
            # before any journal/owner/coordinator bookkeeping. Canary
            # outputs never leave the DP client (that is what keeps
            # probes out of SLO scoring and the output processor), and
            # canaries were never +1'd at the coordinator, so they must
            # not reach the finished_per negative-delta loop either.
            kept = []
            for o in outs:
                if not self.correctness.owns(o.req_id):
                    kept.append(o)
                    continue
                self.correctness.on_output(o)
                if o.finished:
                    i = self._owner.pop(o.req_id, None)
                    if i is not None:
                        self._live[i].discard(o.req_id)
            outs = kept
        if self.disagg is not None:
            # Disagg interception BEFORE any journal/owner bookkeeping:
            # a finished prefill-stage output is swallowed (its sampled
            # token is regenerated by the decode home) and re-admitted
            # to the decode pool with the producer's pull coordinates.
            # Crediting it here instead would register prompt+generated
            # residency against the PREFILL replica — whose pages leave
            # with the pull — so next-turn affinity would route to a
            # replica that holds nothing (the decode-home registration
            # fix).
            outs = self.disagg.intercept(outs)
        finished_per: dict[int, int] = {}
        for o in outs:
            if o.new_token_ids and o.req_id in self._requests:
                # Failover journal: tokens already delivered downstream
                # (a migrated continuation must not regenerate them).
                self._progress.setdefault(o.req_id,
                                          []).extend(o.new_token_ids)
            if o.finished:
                orig = self._requests.pop(o.req_id, None)
                progress = self._progress.pop(o.req_id, None)
                i = self._owner.pop(o.req_id, None)
                if i is not None:
                    self._live[i].discard(o.req_id)
                    finished_per[i] = finished_per.get(i, 0) + 1
                    if self.router is not None and orig is not None:
                        # The finished sequence stays prefix-cached on
                        # its replica: index prompt+generated so the
                        # session's NEXT turn routes home page-exactly.
                        self.router.on_finish(orig, progress or [], i)
        if self.coordinator is not None and self._coord_routes:
            # One batched delta per replica (output hot path).
            for i, k in finished_per.items():
                self._coord_report_safe(i, -k)
        return outs

    # ------------------------------------------------------------------
    # Replica failover + resurrection
    # ------------------------------------------------------------------
    def _failover(self, i: int, err: Exception) -> None:
        """Take replica ``i`` out of rotation and migrate its journaled
        requests to healthy replicas as continuation prefills. Raises
        (tagged) only when no healthy replica remains."""
        with self._lock:
            self._failover_locked(i, err)

    def _failover_locked(self, i: int, err: Exception) -> None:
        if i in self._down:
            return
        self._down.add(i)
        self.replica_failovers += 1
        if self.correctness is not None:
            # The replica's suspicion history (and any in-flight canary)
            # died with it; a respawn starts clean.
            self.correctness.forget_replica(i)
        if self.router is not None:
            # The dead replica's KV pool died with it: drop every
            # affinity hint pointing there. Migrated requests re-home
            # as their continuation re-admits register the new owner.
            self.router.on_replica_down(i)
        self._next_probe[i] = time.monotonic() + self._probe_interval
        stranded = [rid for rid, owner in self._owner.items()
                    if owner == i]
        logger.error(
            "DP replica %d died (%s); failing over %d in-flight "
            "request(s)", i, err, len(stranded))
        if self.coordinator is not None and self._coord_routes:
            # Out of the routing set; clearing the count unwinds the
            # stranded admissions (migration re-reports them against
            # the replicas that absorb the load).
            try:
                self.coordinator.set_health(i, False, clear=True)
            except RuntimeError:
                if not self._coord_partition_degraded():
                    raise
        for rid in stranded:
            self._owner.pop(rid, None)
            self._live[i].discard(rid)
        for rid in stranded:
            orig = self._requests.get(rid)
            if orig is None:
                continue
            req = None
            if self.disagg is not None:
                # A prefill-stage casualty re-enters as a fresh
                # prefill-stage copy (nothing was delivered yet); a
                # decode-stage casualty takes the normal continuation
                # below and stays homed to the decode pool. Both are
                # counted as disagg fallbacks by cause.
                req = self.disagg.readmission_for(
                    rid, orig, self._progress.get(rid, []))
            if req is None:
                req = continuation_request(orig,
                                           self._progress.get(rid, []))
            try:
                self._admit(req)
            except EngineDeadError:
                # No healthy replica absorbed it: every replica is down.
                raise
            logger.info("migrated request %s to replica %d", rid,
                        self._owner[rid])

    def _check_any_alive(self) -> None:
        """Terminal check: with EVERY replica out of rotation the output
        paths would otherwise poll nothing forever — surface the
        deployment-wide death so the upstream supervisor (AsyncLLM) can
        attempt a full-fleet restart, or fail pending requests. Held
        back while a resurrection probe is in flight: a fleet restart
        would race the probe thread's exclusive use of that replica's
        transport."""
        if len(self._down) == len(self.clients) and not self._probing:
            raise EngineDeadError("all DP replicas are dead")

    def _maybe_resurrect(self) -> None:
        """Periodic probe: try to restart downed replicas, budgeted by
        their per-replica supervisor. The restart itself (spawn +
        ready handshake — minutes for a real core) runs on a probe
        THREAD so the output path keeps pumping healthy replicas;
        results apply here, on the caller's thread. A downed replica's
        sockets are untouched by the output path (it is skipped while
        in _down), so the probe thread has exclusive access."""
        with self._lock:
            while True:  # apply finished probe results first
                try:
                    i, ok = self._probe_results.get_nowait()
                except queue.Empty:
                    break
                self._probing.discard(i)
                if not ok:
                    continue
                self._down.discard(i)
                self.replica_resurrections += 1
                if self.coordinator is not None:
                    self.coordinator.set_health(i, True)
                logger.info("DP replica %d resurrected; back in "
                            "rotation", i)
            if not self._down or self._probe_interval <= 0:
                return
            now = time.monotonic()
            for i in sorted(self._down):
                if i in self._probing or now < self._next_probe.get(i, 0):
                    continue
                self._next_probe[i] = now + self._probe_interval
                if self._supervisors[i].next_delay() is None:
                    continue  # budget burnt until the window slides
                self._probing.add(i)
                threading.Thread(target=self._probe_restart, args=(i,),
                                 name=f"dp-resurrect-{i}",
                                 daemon=True).start()

    def _probe_restart(self, i: int) -> None:
        try:
            self.clients[i].restart()
        except Exception as e:  # noqa: BLE001 - still dead
            logger.warning("DP replica %d resurrection failed: %s", i, e)
            self._probe_results.put((i, False))
            return
        self._probe_results.put((i, True))

    def _probe_restart_verified(self, i: int) -> None:
        """Fleet-managed resurrection probe (engine/fleet.py): restart
        PLUS a health verification — a replica that reconnects but
        cannot answer a basic stats probe (its warm start failed)
        reports still-down, so ``replica_resurrections`` only counts
        replicas that actually came back."""
        try:
            self.clients[i].restart()
            self.clients[i].get_stats()
        except Exception as e:  # noqa: BLE001 - still dead (or alive
            # but not serving — same thing to the rotation).
            logger.warning("DP replica %d resurrection failed: %s", i, e)
            self._probe_results.put((i, False))
            return
        self._probe_results.put((i, True))

    def _tick(self) -> None:
        """Periodic maintenance hook on the output paths: the fleet
        controller's loop when VDT_FLEET=1 (which subsumes the
        resurrection probe), the legacy probe otherwise. The
        correctness sentinel's canary injector rides the same tick —
        its quarantine hints land BEFORE fleet.tick() so a hint emitted
        this tick can actuate this tick."""
        if self.correctness is not None:
            self._canary_tick()
        if self.fleet is not None:
            self.fleet.tick()
        else:
            self._maybe_resurrect()

    def _canary_tick(self) -> None:
        """Inject due canary probes and forward quarantine hints. A
        canary bypasses add_request/_admit entirely: no failover
        journal entry (a probe must pin to — and die with — its
        replica), no router residency, no coordinator admission (it is
        admission-exempt by construction). It IS registered in
        _owner/_live so the output paths keep stepping and polling its
        replica like any in-flight request."""
        plane = self.correctness
        with self._lock:
            targets = [i for i in range(len(self.clients))
                       if i not in self._down and i not in self._retired
                       and i not in self._no_place]
            for i, req in plane.due_probes(targets):
                try:
                    self.clients[i].add_request(req)
                except Exception as e:  # noqa: BLE001 - replica dying
                    # mid-probe; its health is the failover ladder's
                    # job, the round just proceeds without it.
                    logger.warning(
                        "canary submit to replica %d failed: %s", i, e)
                    plane.on_submit_failed(req.request_id)
                    continue
                self._owner[req.request_id] = i
                self._live[i].add(req.request_id)
            if self.fleet is not None:
                hints = plane.quarantine_hints()
                if hints:
                    self.fleet.observe_quarantine(hints)

    # ------------------------------------------------------------------
    # Elastic-fleet primitives (engine/fleet.py; balancer lock held)
    # ------------------------------------------------------------------
    def _drain_migrate_locked(self, i: int, report: bool = True) -> None:
        """Journal-migrate replica ``i``'s unfinished requests to the
        rest of the fleet as continuations (token-identical under
        greedy). This is PLANNED movement — a fleet drain deadline or a
        wedge cycle — so unlike _failover_locked nothing here counts as
        a failover or a disagg death fallback. ``report=False`` skips
        the coordinator's negative delta (the wedge path already
        cleared the replica's count wholesale)."""
        stranded = [rid for rid, owner in self._owner.items()
                    if owner == i]
        if not stranded:
            return
        for rid in stranded:
            self._owner.pop(rid, None)
            self._live[i].discard(rid)
        try:
            self.clients[i].abort_requests(stranded)
        except Exception:  # noqa: BLE001 - replica unresponsive; its
            # engine restarts (wedge) or shuts down (retire) anyway.
            pass
        if report and self.coordinator is not None:
            self.coordinator.report(i, -len(stranded))
        logger.info("fleet drain: migrating %d request(s) off "
                    "replica %d", len(stranded), i)
        for rid in stranded:
            orig = self._requests.get(rid)
            if orig is None:
                continue
            req = None
            if self.disagg is not None:
                from vllm_distributed_tpu.engine.disagg import (
                    PREFILL_POOL, prefill_stage_request)
                if self.disagg._stage.get(rid) == PREFILL_POOL:
                    # Prefill-stage work re-enters as a fresh one-token
                    # copy (nothing was delivered yet).
                    req = prefill_stage_request(orig)
            if req is None:
                req = continuation_request(orig,
                                           self._progress.get(rid, []))
            self._admit(req)

    def _spawn_replica(self, i: int,
                       role: Optional[str]) -> EngineCoreClient:
        """Build the engine client for slot ``i`` (fleet scale-out or a
        role conversion), specialized for its disagg role when the
        fleet is disaggregated. Blocking — the fleet controller budgets
        and rate-limits the call."""
        rc = make_replica_config(self.config, i)
        if self.disagg is not None and role is not None:
            from vllm_distributed_tpu.engine.disagg import \
                specialize_replica_config
            offset = self.disagg.device_offset_of(i)
            if offset is None:
                offset = self.disagg.next_device_offset()
            specialize_replica_config(rc, role, offset)
        return SyncMPClient(rc) if self.is_mp else InprocClient(rc)

    def _enter_replica(self, i: int, client: EngineCoreClient,
                       role: Optional[str]) -> None:
        """Wire a freshly spawned replica into rotation at slot ``i``
        — either reusing a retired slot or appending a new rank (which
        grows the router, the coordinator's count table, and the
        per-replica balancer state)."""
        if i == len(self.clients):
            self.clients.append(client)
            self._live.append(set())
            self._supervisors.append(
                RestartSupervisor.from_config(self.config))
            if self.router is not None:
                self.router.grow(1)
            if self.coordinator is not None:
                self.coordinator.resize(len(self.clients))
            if self.disagg is not None:
                self.disagg.add_replica(
                    i, role,
                    device_offset=self.disagg.next_device_offset())
        else:
            self.clients[i] = client
            self._retired.discard(i)
            self._down.discard(i)
            if self.correctness is not None:
                # A reused slot is a NEW engine to the sentinel too.
                self.correctness.forget_replica(i)
            # A reused slot is a NEW engine: fresh restart budget,
            # clean router state (on_replica_down also covers the
            # stale-residency case of a long-retired slot).
            self._supervisors[i] = \
                RestartSupervisor.from_config(self.config)
            if self.router is not None:
                self.router.on_replica_down(i)
            if self.coordinator is not None:
                self.coordinator.set_health(i, True, clear=True)
            if self.disagg is not None and role is not None:
                self.disagg.add_replica(i, role)

    def restart(self) -> None:
        """Full-fleet restart (AsyncLLM's supervisor calls this once
        every replica is dead): every replica respawns and all balancer
        state clears — the upstream journal replays the load."""
        with self._lock:
            for i, client in enumerate(self.clients):
                if i in self._retired:
                    continue  # fleet-retired: already shut down, its
                    # slot only rejoins via a scale-out reuse.
                client.restart()
                if self.coordinator is not None:
                    self.coordinator.set_health(i, True, clear=True)
            self._owner.clear()
            self._requests.clear()
            self._progress.clear()
            self._down.clear()
            self._down.update(self._retired)
            self._no_place.clear()
            self._next_probe.clear()
            for live in self._live:
                live.clear()
            if self.router is not None:
                self.router.reset()
            if self.disagg is not None:
                self.disagg.reset()
            if self.fleet is not None:
                self.fleet.reset()
            if self.correctness is not None:
                for i in range(len(self.clients)):
                    self.correctness.forget_replica(i)

    # ------------------------------------------------------------------
    def get_output(self) -> list[EngineCoreOutput]:
        """Merged next outputs across replicas.

        In-process replicas are stepped inline (each busy replica once);
        subprocess replicas are polled, blocking until at least one batch
        arrives while any request is live."""
        self._tick()
        self._check_any_alive()
        outs: list[EngineCoreOutput] = []
        if not self.is_mp:
            for i, client in enumerate(self.clients):
                if i in self._down:
                    continue
                if self._live[i] or self._has_kv_work(client):
                    # KV-transfer work (deferred sends, held pulls)
                    # needs step-polls even with no live requests.
                    try:
                        outs.extend(client.get_output())
                    except Exception as e:  # noqa: BLE001 - one
                        # replica's step failure is that replica's
                        # death, not the deployment's: fail over.
                        self._failover(i, e)
            return self._mark_finished(outs)
        while any(self._live):
            polled = False
            for i, client in enumerate(self.clients):
                if not self._live[i] or i in self._down:
                    continue
                polled = True
                try:
                    batch = client.recv_outputs(timeout_ms=20)
                except EngineDeadError as e:
                    self._failover(i, _tag_replica(e, i))
                    continue
                if batch:
                    outs.extend(batch)
            if outs:
                break
            if not polled:
                # All live work sits on downed replicas (probe in
                # flight): pace the loop instead of spinning.
                time.sleep(0.02)
                self._tick()
                self._check_any_alive()
        return self._mark_finished(outs)

    def recv_outputs(
            self, timeout_ms: int) -> Optional[list[EngineCoreOutput]]:
        """Pump-thread receive (AsyncLLM): poll every replica once within
        the timeout budget; None when nothing arrived."""
        assert self.is_mp, "recv_outputs requires subprocess replicas"
        self._tick()
        self._check_any_alive()
        per = max(timeout_ms // len(self.clients), 1)
        outs: list[EngineCoreOutput] = []
        first = None
        polled = False
        for i, client in enumerate(self.clients):
            if i in self._down:
                continue
            polled = True
            try:
                batch = client.recv_outputs(timeout_ms=per)
            except EngineDeadError as e:
                self._failover(i, _tag_replica(e, i))
                continue
            if batch:
                outs.extend(batch)
                first = batch if first is None else first
        if not polled:
            # Every replica is down (resurrection probe in flight):
            # honor the caller's poll budget instead of busy-spinning
            # the pump thread for the probe's whole duration.
            time.sleep(timeout_ms / 1000)
            return None
        outs = self._mark_finished(outs)
        if not outs:
            return None
        # One batch of several replicas' steps: it carries the stamps of
        # the first that was polled, the one that has waited longest.
        return StepOutputs(outs, getattr(first, "step", 0),
                           getattr(first, "handed_over", None))

    # ------------------------------------------------------------------
    def send_utility(self, method: str, *args) -> int:
        """Fan a utility RPC out to every replica; the combined result
        lands in fetch_result() once the pump thread drains each child
        (AsyncLLM's thread-safe stats path)."""
        assert self.is_mp
        self._util_id += 1
        self._pending_util[self._util_id] = [
            (idx, c, c.send_utility(method, *args))
            for idx, c in enumerate(self.clients)
            if idx not in self._down
        ]
        self._util_partial[self._util_id] = {}
        return self._util_id

    def fetch_result(self, call_id: int, default=None):
        pending = self._pending_util.get(call_id)
        if pending is None:
            return default
        partial = self._util_partial[call_id]
        sentinel = object()
        for idx, client, child_id in pending:
            if idx in partial:
                continue
            value = client.fetch_result(child_id, sentinel)
            if value is not sentinel:
                partial[idx] = value
        if len(partial) < len(pending):
            return default
        del self._pending_util[call_id]
        by_idx = self._util_partial.pop(call_id)
        # Key by the REPLICA index recorded at send time: with a
        # replica down, positions and replica indices diverge.
        indices = [idx for idx, _, _ in pending]
        values = [by_idx[idx] for idx in indices]
        for v in values:
            if isinstance(v, Exception):
                return v
        if all(isinstance(v, dict) for v in values):
            return self._aggregate_stats(values, indices=indices)
        return values

    @staticmethod
    def _has_kv_work(client) -> bool:
        core = getattr(client, "engine_core", None)
        return core is not None and core.has_kv_transfer_work()

    def has_unfinished_requests(self) -> bool:
        return any(self._live)

    def call_utility(self, method: str, *args):
        """Blocking fan-out RPC (sleep/wake_up/profile/...): every
        replica runs it; dict results aggregate, others come back as a
        per-replica list."""
        alive = [i for i in range(len(self.clients))
                 if i not in self._down]
        values = [self.clients[i].call_utility(method, *args)
                  for i in alive]
        if method == "get_debug_state":
            # Introspection dicts must NOT be stats-aggregated: summing
            # per-replica config/bool fields (async_scheduling,
            # batch_queue_size, ...) fabricates values. Hand back the
            # raw per-replica states under the key _core_debug_states
            # already consumes.
            return {"dp_replicas": values}
        if values and all(isinstance(v, dict) for v in values):
            return self._aggregate_stats(values, indices=alive)
        return values

    def request_counts(self) -> list[int]:
        """Per-replica live request counts (the coordinator's published
        load snapshot; exposed for /metrics and tests)."""
        return [len(s) for s in self._live]

    def _aggregate_stats(self, per: list[dict],
                         indices: Optional[list[int]] = None) -> dict:
        # getattr: stats-aggregation tests build this client via
        # __new__ with only the balancer fields they exercise.
        router = getattr(self, "router", None)
        fleet = getattr(self, "fleet", None)
        if router is not None and indices is not None:
            # Passive routing-signal feed: every stats poll that already
            # flows through here (the /metrics scrape, the admission
            # gate's KV sampler) refreshes the router's per-replica load
            # snapshots — the "existing get_stats RPC" channel.
            for i, stats in zip(indices, per):
                router.observe_stats(i, stats)
        if fleet is not None and indices is not None:
            # Same passive channel feeds the fleet controller's
            # occupancy/step-heartbeat signals (subprocess replicas are
            # never polled by the control loop itself).
            for i, stats in zip(indices, per):
                fleet.observe_stats(i, stats)
        if getattr(self, "trace_enabled", False) and indices is not None:
            # Cross-process clock alignment: each replica's clock_mono
            # reading pairs with the front-end clock sampled here. The
            # estimate over-corrects by up to one RPC latency
            # (in-process replicas share the clock, so ~0); drained
            # ring events re-base into the front-end epoch and are
            # replica-tagged so the trace assembler knows which pid
            # lane each span belongs to.
            offsets = getattr(self, "_clock_offsets", {})
            now = time.monotonic()
            for i, stats in zip(indices, per):
                cm = stats.get("clock_mono")
                if isinstance(cm, (int, float)):
                    offsets[i] = now - cm
                evs = stats.get("timeline_events")
                if evs:
                    off = offsets.get(i, 0.0)
                    stats["timeline_events"] = [
                        [e[0] + off, e[1], e[2],
                         {**(e[3] if isinstance(e[3], dict) else {}),
                          ev.REPLICA_KEY: i}]
                        for e in evs]
        agg: dict = {"dp_size": len(self.clients),
                     "dp_request_counts": self.request_counts(),
                     "dp_replicas": per,
                     "dp_replicas_down": sorted(self._down),
                     "replica_failovers": self.replica_failovers,
                     "replica_resurrections":
                         self.replica_resurrections}
        # Sum numeric leaves across replicas for the headline counters
        # (this loop is also what merges the flat vdt:ssm_* state-cache
        # families — hits/queries/evictions/checkpoints sum, and
        # bytes_held sums to the fleet's snapshot footprint);
        # ratio gauges average instead (a 4-replica deployment at 25%
        # KV usage is at 25%, not 100% — the admission gate's KV shed
        # reads this value), and peak gauges take the max (summing
        # per-replica peaks would fabricate overlap that never
        # happened: 4 sync replicas at depth 1 are depth 1, not 4).
        ratio_gauges = ("kv_cache_usage", "spec_acceptance_rate",
                        "decode_overlap_frac")
        max_gauges = ("max_concurrent_batches", )
        for stats in per:
            for k, v in stats.items():
                if k == "clock_mono":
                    continue  # per-process clock reading, not a stat
                if k in max_gauges:
                    agg[k] = max(agg.get(k, 0), v)
                elif isinstance(v, (int, float)):
                    agg[k] = agg.get(k, 0) + v
        for k in ratio_gauges:
            if k in agg and per:
                agg[k] = agg[k] / len(per)
        # Histogram-shaped entries merge element-wise so DP /metrics
        # renders the fleet histogram instead of silently dropping it.
        from vllm_distributed_tpu.metrics.prometheus import (
            HISTOGRAMS, LABELED_HISTOGRAMS)
        from vllm_distributed_tpu.metrics.stats import \
            merge_histogram_dicts
        for key in HISTOGRAMS:
            merged = merge_histogram_dicts([s.get(key) for s in per])
            if merged is not None:
                agg[key] = merged
        # Attention kernel dispatch counts: {kernel: steps}, summed per
        # kernel label across replicas (a dict, so the flat numeric-sum
        # loop above skipped it).
        call_maps = [s["attn_kernel_calls"] for s in per
                     if isinstance(s.get("attn_kernel_calls"), dict)]
        if call_maps:
            merged_calls: dict = {}
            for m in call_maps:
                for k, v in m.items():
                    merged_calls[k] = merged_calls.get(k, 0) + int(v)
            agg["attn_kernel_calls"] = merged_calls
        # Per-tenant QoS families: {tenant: {granted_tokens, kv_blocks,
        # preemptions}}, summed per tenant per leaf across replicas
        # (every scheduler buckets through qos.bucket_tenant so each
        # replica's key space is bounded; note the first-come tracked
        # set is per replica, so past VDT_QOS_MAX_TRACKED_TENANTS a
        # tenant routed to several replicas may appear tracked-by-name
        # on one and as an overflow "~n" bucket on another — the merge
        # stays bounded but such a tenant's series split across the
        # two labels. Counters and the kv_blocks gauge both sum — a
        # tenant's fleet page footprint is the sum of its per-replica
        # footprints).
        tenant_maps = [s["tenants"] for s in per
                       if isinstance(s.get("tenants"), dict)]
        if tenant_maps:
            merged_tenants: dict = {}
            for m in tenant_maps:
                for t, leaves in m.items():
                    if not isinstance(leaves, dict):
                        continue
                    dst = merged_tenants.setdefault(t, {})
                    for k, v in leaves.items():
                        dst[k] = dst.get(k, 0) + int(v)
            agg["tenants"] = merged_tenants
        # Labeled histogram families (step phases, step tokens):
        # {label value -> histogram dict}, merged per label value.
        for key in LABELED_HISTOGRAMS:
            maps = [s[key] for s in per if isinstance(s.get(key), dict)]
            if not maps:
                continue
            merged_map = {}
            for value in sorted({v for m in maps for v in m}):
                h = merge_histogram_dicts([m.get(value) for m in maps])
                if h is not None:
                    merged_map[value] = h
            agg[key] = merged_map
        # Telemetry plane: per-worker maps union (labels are
        # fleet-unique, so no counter is ever summed twice), transport
        # snapshots merge per connector/side label, block-pool stats
        # sum counts / average ratios. None of these ride the flat
        # numeric-sum loop above — summing a peak HBM gauge or a
        # replica's inflight map would fabricate fleet state.
        from vllm_distributed_tpu.metrics import telemetry
        workers = telemetry.merge_worker_telemetry(
            [s.get("workers") for s in per])
        if workers:
            agg["workers"] = workers
        transport = telemetry.merge_transport_snapshots(
            [s.get("transport") for s in per])
        if transport is not None:
            agg["transport"] = transport
        kv_cache = telemetry.merge_kv_cache_stats(
            [s.get("kv_cache") for s in per])
        if kv_cache is not None:
            agg["kv_cache"] = kv_cache
        # Hierarchical KV tiering: {pages/bytes/demotions/promotions/
        # misses: {tier: n}} sum per tier per leaf, the promotion
        # histogram merges element-wise, and the (destructively
        # drained) router transition feed was already consumed by
        # router.observe_stats above — it never reaches the merged
        # view.
        tier_maps = [s["kv_tier"] for s in per
                     if isinstance(s.get("kv_tier"), dict)]
        if tier_maps:
            merged_tier: dict = {}
            for m in tier_maps:
                for k, v in m.items():
                    if k in ("transitions", "promotion_seconds"):
                        continue
                    if isinstance(v, dict):
                        dst = merged_tier.setdefault(k, {})
                        for tier_name, n in v.items():
                            if isinstance(n, (int, float)):
                                dst[tier_name] = \
                                    dst.get(tier_name, 0) + n
                    elif isinstance(v, (int, float)):
                        merged_tier[k] = merged_tier.get(k, 0) + v
            promo = merge_histogram_dicts(
                [m.get("promotion_seconds") for m in tier_maps])
            if promo is not None:
                merged_tier["promotion_seconds"] = promo
            agg["kv_tier"] = merged_tier
        # Follower-process counter snapshots (pid-tagged by each core's
        # get_stats): merge once per distinct follower pid, excluding
        # this process — in-process cores share the front-end's
        # process-global registries, so summing them would double-count
        # what render_fault_injections / merged_qcomm_view already read
        # locally. The merged remote view makes /metrics fleet-exact.
        merged_fi: dict = {}
        merged_qc: dict = {"bytes_saved": {}, "fallbacks": {}}
        seen_fi = {os.getpid()}
        seen_qc = {os.getpid()}
        for s in per:
            snap = s.get("fault_injection_counts")
            if (isinstance(snap, dict) and snap.get("pid") not in seen_fi
                    and isinstance(snap.get("counts"), dict)):
                seen_fi.add(snap["pid"])
                for k, v in snap["counts"].items():
                    merged_fi[k] = merged_fi.get(k, 0) + int(v)
            snap = s.get("qcomm_traced")
            if isinstance(snap, dict) and snap.get("pid") not in seen_qc:
                seen_qc.add(snap.get("pid"))
                for fam in ("bytes_saved", "fallbacks"):
                    for k, v in (snap.get(fam) or {}).items():
                        dst = merged_qc[fam]
                        dst[k] = dst.get(k, 0) + int(v)
        if merged_fi:
            agg["fault_injection_counts_remote"] = merged_fi
        if merged_qc["bytes_saved"] or merged_qc["fallbacks"]:
            agg["qcomm_traced_remote"] = merged_qc
        # Lifecycle timelines: one fleet-wide event stream, time-sorted
        # (per-core rings, the fleet controller's actuations, and the
        # front-end's own placement/handoff ring).
        from vllm_distributed_tpu.metrics.events import merge_event_lists
        events = merge_event_lists(
            *(s.get("timeline_events") or [] for s in per),
            *([fleet.drain_events()] if fleet is not None else []),
            *([getattr(self, "events", None).drain()]
              if getattr(self, "events", None) is not None else []))
        if events:
            agg["timeline_events"] = events
        # Routing tier: ONE router instance owns the whole fleet's
        # placement, so its counters attach exactly — nothing to merge.
        if router is not None:
            agg["router"] = router.get_stats()
        # Disagg serving tier: one coordinator owns every handoff, so
        # its counters/histogram attach exactly too.
        disagg = getattr(self, "disagg", None)
        if disagg is not None:
            agg["disagg"] = disagg.get_stats(self.request_counts())
        # Elastic-fleet controller: one loop owns the whole fleet's
        # shape, so its counters attach exactly too.
        if fleet is not None:
            agg["fleet"] = fleet.get_stats()
        # Correctness sentinel: the runners' numerics snapshots are
        # PER-REPLICA by construction (entropy means, NaN counters,
        # histograms — a cross-replica numeric sum would erase exactly
        # the per-replica drift the sentinel exists to see), so they
        # re-key by replica index here; dead replicas fell out of
        # ``per`` at the get_stats poll, so a mid-scrape death is
        # excluded naturally. The same poll drives the plane's
        # NaN-delta / drift ladders, and the plane's own counters
        # attach exactly (one plane owns the fleet's canaries).
        plane = getattr(self, "correctness", None)
        if indices is not None:
            numerics = {i: s["numerics"] for i, s in zip(indices, per)
                        if isinstance(s.get("numerics"), dict)}
            if numerics:
                agg["numerics"] = numerics
                if plane is not None:
                    plane.observe_numerics(numerics)
        if plane is not None:
            agg["correctness"] = plane.get_stats()
        return agg

    def get_stats(self) -> dict:
        alive = [i for i in range(len(self.clients))
                 if i not in self._down]
        return self._aggregate_stats(
            [self.clients[i].get_stats() for i in alive], indices=alive)

    def observe_goodput(self, fracs: dict,
                        degraded: bool = False) -> None:
        """Per-tenant goodput feed (metrics/stats.py FrontendStats SLO
        scoring, wired from the entrypoints' stats path) into the
        fleet's VDT_FLEET_SIGNALS scale decision. ``degraded`` is the
        burn-rate watchdog's sustained-burn flag, offered as scale-up
        pressure on the same channel. No-op without a fleet
        controller."""
        if self.fleet is not None and isinstance(fracs, dict):
            self.fleet.observe_goodput(fracs, degraded=degraded)

    def shutdown(self) -> None:
        if self.fleet is not None:
            try:
                self.fleet.close()  # HA: relinquish the lease cleanly
            except Exception:  # noqa: BLE001 - best-effort teardown
                pass
        if self.coordinator is not None:
            self.coordinator.shutdown_coordinator()
            self.coordinator.close()
            if self._coord_proc is not None:
                self._coord_proc.join(timeout=5)
            from vllm_distributed_tpu.engine.coordinator import \
                cleanup_socket_dir
            cleanup_socket_dir(self._coord_addr)
        for c in self.clients:
            try:
                c.shutdown()
            except Exception:  # noqa: BLE001 - best-effort teardown
                pass
