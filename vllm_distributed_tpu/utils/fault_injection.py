"""Deterministic fault-injection registry for robustness testing.

A small process-global table of NAMED fault points threaded through the
fault-tolerance layers. Production code asks ``should_fire(name)`` /
``maybe_delay(name)`` at each point; with no faults configured every
check is a dict lookup on an empty table (one ``if`` on the hot path).

Activation is deterministic — a fault with rate r fires on exactly the
calls where ``floor(n*r)`` increments (rate 1.0 = every call, 0.5 =
every other call) — so a test that injects ``kv_pull.drop`` at 100%
observes the same failure sequence on every run, with no RNG seeding.

Configure programmatically (tests)::

    from vllm_distributed_tpu.utils import fault_injection as fi
    fi.inject("kv_pull.drop")                 # rate 1.0
    fi.inject("kv_pull.delay", delay_s=0.2)   # sleep 200ms per fire
    ...
    fi.clear()

or via the environment (survives engine-core subprocess spawn)::

    VDT_FAULT_INJECT="kv_pull.drop:1.0,kv_pull.delay:0.5@0.2"

(``name:rate`` entries, optional ``@delay_seconds`` suffix.)

Known points (layers consult this module; an unknown name is accepted
but never fired by production code):

* ``kv_pull.drop``      — consumer silently drops a staged KV pull (no
  worker report ever arrives; only the scheduler watchdog recovers).
* ``kv_pull.delay``     — injects ``delay_s`` of latency into a pull.
* ``registry.truncate`` — the P2P registry server answers one request
  with a malformed (non-msgpack) payload.
* ``engine_core.die``   — the engine-core busy loop raises on its next
  iteration (subprocess sends the dead sentinel; thread core surfaces
  the error through its output queue).
* ``heartbeat.stall``   — heartbeat senders (P2P registry client,
  engine-core liveness thread) skip their sends while active.
* ``core_proc.spawn_fail`` — engine-core construction (initial spawn or
  a supervisor restart) raises before the core comes up.
* ``restart.storm``     — each supervisor restart succeeds and then the
  fresh core immediately dies again (re-arms ``engine_core.die``),
  driving the restart budget to its circuit breaker.
* ``admission.stall``   — the API admission controller leaks one queue
  slot per fire (admitted work that never completes), deterministically
  building queue-depth pressure toward the shed watermark.
* ``step.reconcile_stall`` — fired at the engine core's batch-queue
  reconcile point (wait_model of the oldest in-flight batch). With a
  ``delay_s`` it stalls the host between device completion and
  reconciliation; without one it raises, killing the core with batches
  still in flight — the drill proving the crash-recovery ladder works
  mid-pipeline.
* ``router.stale_stats`` — the DP routing tier treats every replica's
  load snapshot as expired (refreshes are suppressed while armed), so
  tests can prove the router degrades to pure load balancing instead
  of herding affinity traffic onto one replica on blind signals.
* ``ssm.restore_corrupt`` — a restored SSM state checkpoint fails its
  checksum verification (core/state_cache.read_journal), proving the
  scheduler degrades the admission to a full re-prefill (counted in
  ``ssm_restore_corruptions``) instead of resuming from corrupt state.
* ``qcomm.scale_corrupt`` — the quantized KV-payload codec corrupts a
  scale header AFTER its checksum is computed (kv_transfer/quant.py
  encode), so the consumer's decode detects a CRC mismatch and
  degrades to re-requesting the raw-precision payload (counted in
  ``vdt:qcomm_fallbacks_total``), proving the recovery ladder holds
  under the quantized wire format.
* ``disagg.handoff_stall`` — the disagg coordinator hands the decode
  home broken pull coordinates (the producer will reject every pull
  for them), so the handoff's KV pull can never complete and the
  decode home is driven through the scheduler's full recovery ladder:
  bounded pull retries, then local re-prefill recompute (counted in
  ``vdt:disagg_fallbacks_total{reason="local_reprefill"}``). Greedy
  output must stay token-identical throughout.
* ``sched.quota_thrash`` — the QoS quota-preemption victim picker
  (core/sched/qos.py quota_victim, consulted on every allocation
  failure; requires ``VDT_QOS=1``) treats the per-tenant KV quota as
  ZERO, so every page-holding tenant reads as over-quota and each
  capacity preemption becomes a quota preemption targeting the
  biggest holder — a forced quota-preemption storm. The drill proves
  the per-tenant cooldown hysteresis bounds it: a tenant oscillating
  around its quota falls back to ordinary capacity preemption between
  quota evictions instead of livelocking in evict/resume cycles.
* ``perf.capture_stall`` — the profiler capture started by the
  profile RPC (engine/core.py) behaves as a WEDGED xprof session: the
  stop RPC fails (the stop is "lost") and only the VDT_PROFILE_MAX_S
  capture-window deadline, enforced by the step loop and stats polls,
  force-stops the trace. The drill proves a profiler client that dies
  mid-capture can never wedge serving, with
  the fire counted in ``vdt:fault_injections_total``.
* ``kv_tier.spill_corrupt`` — a tier-2 spill page file is corrupted
  after its CRC is computed, so promotion detects the mismatch and
  degrades to recompute (core/kv_tier.py).
* ``fleet.scale_stall`` — an elastic-fleet scale-out (engine/fleet.py)
  stalls at replica construction: the new replica never comes up, the
  action is counted against the fleet's supervisor budget, and the
  drill proves hysteresis + the budget stop a wedged provisioner from
  thrashing the fleet (counted in
  ``vdt:fleet_freezes_total{reason="scale_stall"}``).
* ``fleet.replica_wedge`` — the fleet's wedge detector treats a live
  replica as alive-but-not-stepping (step-phase heartbeat age beyond
  VDT_FLEET_WEDGE_S): its journaled requests migrate off and the
  replica is force-cycled through the PR-2 restart budget, counted on
  exactly the ``vdt:fleet_wedge_cycles_total`` rung (NOT as a
  failover — the replica never died).
* ``fleet.controller_die`` — the leaseholder fleet controller
  (engine/control_plane.py) dies mid-tick: it stops ticking, renewing
  its lease, and actuating, exactly as if its front-end process was
  killed. The drill proves a standby acquires the lease within the
  TTL, replays the actuation journal, and finishes half-done
  drain→retire actions with greedy token parity.
* ``fleet.lease_expire`` — the leaseholder skips its lease renewal
  (a paused-then-resumed process: GC stall, SIGSTOP, VM migration)
  while still believing it leads. A standby takes over, the epoch
  bumps, and the ex-leader's next actuation fails the coordinator's
  fence check — rejected and counted in
  ``vdt:fleet_fenced_actions_total{action=...}``, never raised into
  serving.
* ``coordinator.partition`` — the front-end's coordinator RPCs
  (engine/coordinator.py DPCoordinatorClient) fail as if the network
  partitioned. The front-end keeps serving and routing with frozen
  placement (local least-loaded fallback, no actuation — counted in
  ``vdt:fleet_freezes_total{reason="partition"}``), mirroring the
  stale-stats freeze ladder.
* ``canary.flip_token`` — one replica's canary-probe output is
  perturbed in flight (correctness_plane.py absorbs per-replica canary
  outputs in a fixed order, so rate 0.5 on a 2-replica fleet always
  corrupts the same replica). The drill proves the correctness
  sentinel's detection ladder end to end: token mismatch → isolated by
  the cross-replica vote within <= 3 probes → ``vdt:replica_suspect``
  gauge → fleet replica-quarantine hint (under ``VDT_FLEET_SIGNALS``),
  with zero false positives on the clean replicas.
* ``numerics.nan_inject`` — a single NaN lands in one step's
  pre-sampling logits (consulted by the NumericsTap harvest, so the
  poisoned step is counted in ``vdt:logits_nan_steps_total`` and
  excluded from the entropy/margin histograms). Sustained fires climb
  the numerics strike ladder into the same quarantine path as the
  canary vote.
"""

import threading
import time
from dataclasses import dataclass, field
from typing import Optional

from vllm_distributed_tpu.logger import init_logger

logger = init_logger(__name__)

FAULT_POINTS = (
    "kv_pull.drop",
    "kv_pull.delay",
    "registry.truncate",
    "engine_core.die",
    "heartbeat.stall",
    "core_proc.spawn_fail",
    "restart.storm",
    "admission.stall",
    "step.reconcile_stall",
    "router.stale_stats",
    "ssm.restore_corrupt",
    "qcomm.scale_corrupt",
    "disagg.handoff_stall",
    "sched.quota_thrash",
    "perf.capture_stall",
    "kv_tier.spill_corrupt",
    "fleet.scale_stall",
    "fleet.replica_wedge",
    "fleet.controller_die",
    "fleet.lease_expire",
    "coordinator.partition",
    "canary.flip_token",
    "numerics.nan_inject",
)


class InjectedFault(RuntimeError):
    """Raised by fault points that surface as errors. Deliberately NOT a
    subclass of OSError: the retry layer classifies it fatal, so an
    injected fault exercises the failure path, not the retry path."""


@dataclass
class _FaultSpec:
    name: str
    rate: float = 1.0
    delay_s: float = 0.0
    # Stop firing after this many fires (None = unlimited).
    max_fires: Optional[int] = None
    calls: int = 0
    fires: int = 0


@dataclass
class FaultRegistry:
    """Per-process fault table (module-level singleton below)."""

    _specs: dict[str, _FaultSpec] = field(default_factory=dict)
    _lock: threading.Lock = field(default_factory=threading.Lock)
    # Cumulative fires per point, kept across clear() for metrics.
    counters: dict[str, int] = field(default_factory=dict)

    def inject(self, name: str, rate: float = 1.0, delay_s: float = 0.0,
               max_fires: Optional[int] = None) -> None:
        with self._lock:
            self._specs[name] = _FaultSpec(name=name, rate=rate,
                                           delay_s=delay_s,
                                           max_fires=max_fires)
        logger.warning("fault injection ARMED: %s rate=%.2f delay=%.3fs",
                       name, rate, delay_s)

    def clear(self, name: Optional[str] = None) -> None:
        with self._lock:
            if name is None:
                self._specs.clear()
            else:
                self._specs.pop(name, None)

    @property
    def active(self) -> bool:
        return bool(self._specs)

    def should_fire(self, name: str) -> bool:
        """One call at the named point; True when the fault fires."""
        if not self._specs:
            return False
        with self._lock:
            spec = self._specs.get(name)
            if spec is None:
                return False
            if spec.max_fires is not None and spec.fires >= spec.max_fires:
                return False
            spec.calls += 1
            fire = int(spec.calls * spec.rate) > int(
                (spec.calls - 1) * spec.rate)
            if fire:
                spec.fires += 1
                self.counters[name] = self.counters.get(name, 0) + 1
        if fire:
            logger.warning("fault injection FIRED: %s (fire %d)", name,
                           self.counters[name])
        return fire

    def maybe_delay(self, name: str) -> float:
        """Fire a delay-style fault: sleeps and returns the injected
        seconds (0.0 when the fault does not fire)."""
        if not self._specs:
            return 0.0
        with self._lock:
            spec = self._specs.get(name)
        if spec is None or spec.delay_s <= 0:
            return 0.0
        if not self.should_fire(name):
            return 0.0
        time.sleep(spec.delay_s)
        return spec.delay_s

    def fire_or_raise(self, name: str) -> None:
        if self.should_fire(name):
            raise InjectedFault(f"injected fault: {name}")

    def delay_of(self, name: str) -> float:
        spec = self._specs.get(name)
        return spec.delay_s if spec is not None else 0.0


def _from_env() -> FaultRegistry:
    from vllm_distributed_tpu import envs
    reg = FaultRegistry()
    spec_str = envs.VDT_FAULT_INJECT
    for entry in filter(None, (s.strip() for s in spec_str.split(","))):
        try:
            name, _, tail = entry.partition(":")
            rate_s, _, delay_s = tail.partition("@")
            reg.inject(name.strip(), rate=float(rate_s or 1.0),
                       delay_s=float(delay_s or 0.0))
        except ValueError:
            logger.error("ignoring malformed VDT_FAULT_INJECT entry %r",
                         entry)
    return reg


# Process-global registry; engine-core subprocesses rebuild it from the
# inherited VDT_FAULT_INJECT environment at import time.
registry = _from_env()

# Module-level conveniences (the names production code imports).
inject = registry.inject
clear = registry.clear
should_fire = registry.should_fire
maybe_delay = registry.maybe_delay
fire_or_raise = registry.fire_or_raise


def counters() -> dict[str, int]:
    """Cumulative fires per fault point (metrics/bench)."""
    return dict(registry.counters)
