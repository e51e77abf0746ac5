"""Disaggregated prefill/decode serving tier: two pools, routed handoff.

The paper's own framing (PAPERS.md "TPLA ... for Efficient Disaggregated
Prefill and Decode Inference"; reference architecture: vLLM's NIXL
connector + P/D proxy) separates the two phases of a generation onto
replicas SPECIALIZED for them, because they want opposite things:

* **prefill** is compute-bound and wants big token buckets and chunked
  prefill — and fleet-wide, a long prompt admitted to a mixed replica
  steals decode steps from every interactive stream on it (each mixed
  wave pads to the large token bucket the prefill chunk forces);
* **decode** is bandwidth-bound and wants deep pure-decode batches,
  TPLA-shaped kernels, and a SMALL compiled lattice.

``DisaggCoordinator`` composes the pieces previous PRs built — the
versioned standard/latent KV wire formats that re-slice across
asymmetric TP meshes, the quantized payload codec, the dcn_pull
connector with its deferred-free / watchdog / local-recompute recovery
ladder, and the prefix/SLO-aware ``ReplicaRouter`` — into that topology
behind ``DPEngineClient``:

1. **Admission** — a fresh request is placed on the least-loaded
   *prefill-pool* replica as a one-token *prefill-stage* copy
   (``max_tokens=1``: the prefill replica computes the whole prompt's
   KV, samples once, and finishes — it never decodes).
2. **Handoff** — the prefill replica's final output carries the
   producer's ``kv_transfer_params`` (deferred pages + pull
   coordinates). The coordinator intercepts that finish BEFORE any
   balancer bookkeeping (its sampled token is never delivered — the
   decode home regenerates it, token-identically under greedy), picks
   the *decode home* by prefix affinity + load among the decode pool,
   and re-admits the ORIGINAL request there with the pull coordinates
   attached. The decode home pulls the prompt pages over the existing
   connector (quantized codec + latent wire format, so asymmetric
   prefill-TP <-> decode-TP meshes work), computes only the prompt
   tail, and serves the whole decode.
3. **Recovery** — the PR 1/2 ladder holds end to end: a handoff pull
   that times out, is rejected, or CRC-fails degrades through bounded
   pull retries to LOCAL re-prefill on the decode home (the decode
   pool keeps chunked prefill exactly for this, with chunks capped at
   its small token budget); a prefill replica that dies mid-handoff
   has its stranded prefill-stage requests re-admitted to the
   surviving prefill pool; a decode home that dies re-admits its
   continuations (prompt + delivered tokens) inside the decode pool.
   Every fallback is counted by reason.

Kill switch: ``VDT_DISAGG`` (default 0) — off, ``DPEngineClient`` is
byte-identical to the monolithic balancer. Telemetry:
``vdt:disagg_handoffs_total``, ``vdt:disagg_handoff_seconds``,
``vdt:disagg_fallbacks_total{reason}``, ``vdt:pool_occupancy{pool}``.
"""

import copy
import time
from typing import Optional

from vllm_distributed_tpu.config import EngineConfig
from vllm_distributed_tpu.logger import init_logger
from vllm_distributed_tpu.metrics import events as ev
from vllm_distributed_tpu.metrics.stats import TTFT_BUCKETS, Histogram
from vllm_distributed_tpu.request import EngineCoreRequest
from vllm_distributed_tpu.utils import fault_injection

logger = init_logger(__name__)

PREFILL_POOL = "prefill"
DECODE_POOL = "decode"

# Fallback reasons surfaced as vdt:disagg_fallbacks_total{reason}.
FALLBACK_LOCAL_REPREFILL = "local_reprefill"  # pull failed -> recompute
FALLBACK_PULL_RETRY = "pull_retry"  # pull failed -> bounded re-pull
FALLBACK_PREFILL_DEATH = "prefill_death"  # producer died mid-handoff
FALLBACK_DECODE_DEATH = "decode_death"  # decode home died mid-stream
FALLBACK_POOL_DOWN = "pool_down"  # target pool empty; any-alive placement
FALLBACK_NO_PULL_COORDS = "no_pull_coords"  # producer had no full pages


def plan_pools(n: int) -> tuple[list[int], list[int]]:
    """Split ``n`` DP ranks into (prefill ranks, decode ranks): the
    first ``VDT_DISAGG_PREFILL_REPLICAS`` (auto: half) prefill, the
    rest decode — always at least one of each."""
    from vllm_distributed_tpu import envs
    assert n >= 2, "disagg needs at least one replica per pool"
    k = envs.VDT_DISAGG_PREFILL_REPLICAS or n // 2
    k = max(1, min(k, n - 1))
    return list(range(k)), list(range(k, n))


def specialize_replica_config(rc: EngineConfig, role: str,
                              device_offset: Optional[int] = None) -> None:
    """Mutate one replica's (already deep-copied) config for its pool.

    Applied AFTER dataclass __post_init__ ran on the parent, so the
    connector-incompatible modes the aggregate config would have
    rejected (multi-step bursts, async scheduling) are forced off here
    explicitly."""
    from vllm_distributed_tpu import envs
    kv = rc.kv_transfer_config
    if not kv.kv_connector:
        kv.kv_connector = "DCNPullConnector"
    kv.kv_role = "kv_producer" if role == PREFILL_POOL else "kv_consumer"
    kv.pool_role = role
    extra = dict(kv.kv_connector_extra_config or {})
    extra.setdefault("pull_host", "127.0.0.1")
    # Every producer binds its own side-channel port (0 = auto); the
    # actual port travels in each handoff's kv_transfer_params.
    extra["pull_port"] = 0
    kv.kv_connector_extra_config = extra
    sched = rc.scheduler_config
    # Connector hooks run at step boundaries: the fused multi-step burst
    # and async run-ahead grants would silently skip them (same gates
    # EngineConfig.__post_init__ applies when a connector is configured
    # up front).
    sched.num_scheduler_steps = 1
    sched.async_scheduling = False
    tp = (envs.VDT_DISAGG_PREFILL_TP if role == PREFILL_POOL
          else envs.VDT_DISAGG_DECODE_TP)
    if tp:
        rc.parallel_config.tensor_parallel_size = tp
    if device_offset is not None:
        rc.parallel_config.data_parallel_device_offset = device_offset
    if role == DECODE_POOL:
        # Deep decode batches, small compiled lattice: the token budget
        # caps both the decode wave depth and the chunk size of the
        # local re-prefill fallback — the decode pool's token-bucket
        # ladder (and with it the precompile lattice) shrinks to this
        # budget instead of the parent's prefill-sized one.
        budget = envs.VDT_DISAGG_DECODE_TOKENS or max(
            sched.max_num_seqs, 2 * rc.cache_config.block_size)
        budget = min(budget, sched.max_num_batched_tokens)
        sched.max_num_batched_tokens = budget
        sched.enable_chunked_prefill = True
        if (sched.long_prefill_token_threshold <= 0
                or sched.long_prefill_token_threshold > budget):
            sched.long_prefill_token_threshold = budget


def prefill_stage_request(orig: EngineCoreRequest) -> EngineCoreRequest:
    """The one-token copy a prefill replica serves: full prompt KV is
    computed and one token sampled (discarded — the decode home
    regenerates it), then the producer's request_finished hook defers
    the pages and hands back pull coordinates."""
    # Shallow copy: the prompt list is never mutated downstream (the
    # core's Request copies it into _all_token_ids and deep-copies
    # sampling_params itself), so only the fields this function changes
    # need their own objects — a 100k-token prompt is not re-copied
    # under the balancer lock.
    req = copy.copy(orig)
    req.kv_transfer_params = None  # the prefill side never pulls
    sp = copy.deepcopy(orig.sampling_params)
    sp.max_tokens = 1
    if getattr(sp, "min_tokens", 0):
        sp.min_tokens = 0
    req.sampling_params = sp
    return req


class DisaggCoordinator:
    """Handoff state machine riding ``DPEngineClient``'s balancer lock.

    Every method is called with the balancer RLock held (admission,
    output marking and failover already serialize on it), so plain
    dict/counter state needs no further locking."""

    def __init__(self, client, config: EngineConfig) -> None:
        self.client = client
        n = len(client.clients)
        self.prefill_pool, self.decode_pool = plan_pools(n)
        self._prefill_set = set(self.prefill_pool)
        self._decode_set = set(self.decode_pool)
        # Per-slot device offsets + per-role world sizes: the live
        # re-planning surface (fleet scale-out / role conversion) needs
        # to know which device slice a slot owns and whether the two
        # roles are device-footprint-compatible.
        self._sizes = self.role_world_sizes(config)
        self._device_offsets = [off for _, off
                                in self.plan_replicas(config)]
        self.resplits = 0
        # rid -> pool stage: PREFILL_POOL while the prefill-stage copy
        # is in flight, DECODE_POOL from handoff admission to finish.
        self._stage: dict[str, str] = {}
        # rid -> handoff start (monotonic); observed into the handoff
        # histogram at the first decode-home output for the request.
        self._t0: dict[str, float] = {}
        self.handoffs = 0
        self.fallbacks: dict[str, int] = {}
        self.handoff_seconds = Histogram(TTFT_BUCKETS)
        # Pull-based connectors (dcn_pull / p2p) ship coordinates in
        # kv_transfer_params; SharedStorageConnector is content-hash
        # addressed — its handoffs legitimately carry no params (the
        # decode home hits the page files by hash), so a missing-params
        # handoff only counts as a fallback on pull-based fleets.
        conn = (client.clients[self.prefill_pool[0]]
                .config.kv_transfer_config.kv_connector)
        self._params_expected = conn != "SharedStorageConnector"
        logger.info(
            "disagg serving tier: prefill pool %s, decode pool %s "
            "(handoff connector %s)",
            self.prefill_pool, self.decode_pool, conn)

    # ------------------------------------------------------------------
    # Pool planning helpers (used at replica construction)
    # ------------------------------------------------------------------
    @staticmethod
    def role_world_sizes(config: EngineConfig) -> dict:
        """One replica world size per ROLE (world_size is a derived
        property, so evaluate it on a scratch copy with the pool's TP
        degree applied rather than re-deriving its formula here)."""
        from vllm_distributed_tpu import envs
        sizes: dict[str, int] = {}
        for role, tp in ((PREFILL_POOL, envs.VDT_DISAGG_PREFILL_TP),
                         (DECODE_POOL, envs.VDT_DISAGG_DECODE_TP)):
            per = copy.deepcopy(config.parallel_config)
            per.data_parallel_size = 1
            if tp:
                per.tensor_parallel_size = tp
            sizes[role] = per.world_size
        return sizes

    @staticmethod
    def plan_replicas(config: EngineConfig) -> list[tuple[str, int]]:
        """(role, device_offset) per DP rank. Offsets are cumulative
        because pools may run asymmetric TP degrees (different replica
        world sizes), where rank * world_size stops addressing the
        right device slice."""
        n = config.parallel_config.data_parallel_size
        prefill, _decode = plan_pools(n)
        prefill_set = set(prefill)
        sizes = DisaggCoordinator.role_world_sizes(config)
        out: list[tuple[str, int]] = []
        offset = 0
        for rank in range(n):
            role = PREFILL_POOL if rank in prefill_set else DECODE_POOL
            out.append((role, offset))
            offset += sizes[role]
        return out

    def role_of(self, replica: int) -> str:
        return (PREFILL_POOL if replica in self._prefill_set
                else DECODE_POOL)

    # ------------------------------------------------------------------
    # Live pool re-planning (engine/fleet.py; balancer lock held)
    # ------------------------------------------------------------------
    def symmetric_roles(self) -> bool:
        """True when both pools run the same replica world size, so a
        replica's device slice stays valid across a role conversion
        (an asymmetric fleet would need a different device footprint —
        the fleet controller skips conversions there)."""
        return self._sizes[PREFILL_POOL] == self._sizes[DECODE_POOL]

    def device_offset_of(self, replica: int) -> Optional[int]:
        """The device offset the replica was constructed with (slot
        reuse and role conversions keep it — same devices, new role)."""
        if replica < len(self._device_offsets):
            return self._device_offsets[replica]
        return None

    def next_device_offset(self) -> int:
        """Device offset for an APPENDED replica: past every existing
        slot's slice (retired slots keep their reservation — their
        devices come back via slot reuse, not re-planning)."""
        ends = [self._device_offsets[i] + self._sizes[self.role_of(i)]
                for i in range(len(self._device_offsets))]
        return max(ends, default=0)

    def set_role(self, replica: int, role: str) -> None:
        """Move a (drained) replica between pools — the live re-split.
        The caller has already rebuilt the replica's engine with the
        role-specialized config; this just re-plans membership."""
        if self.role_of(replica) == role:
            return
        self._prefill_set.discard(replica)
        self._decode_set.discard(replica)
        for pool in (self.prefill_pool, self.decode_pool):
            if replica in pool:
                pool.remove(replica)
        (self._prefill_set if role == PREFILL_POOL
         else self._decode_set).add(replica)
        target = (self.prefill_pool if role == PREFILL_POOL
                  else self.decode_pool)
        target.append(replica)
        target.sort()
        self.resplits += 1
        logger.info("disagg re-split: replica %d -> %s pool "
                    "(prefill %s, decode %s)", replica, role,
                    self.prefill_pool, self.decode_pool)

    def add_replica(self, replica: int, role: str,
                    device_offset: Optional[int] = None) -> None:
        """Enter a new (or slot-reused) replica into a pool."""
        if replica >= len(self._device_offsets):
            self._device_offsets.extend(
                [0] * (replica + 1 - len(self._device_offsets)))
        if device_offset is not None:
            self._device_offsets[replica] = device_offset
        self._prefill_set.discard(replica)
        self._decode_set.discard(replica)
        for pool in (self.prefill_pool, self.decode_pool):
            if replica in pool:
                pool.remove(replica)
        (self._prefill_set if role == PREFILL_POOL
         else self._decode_set).add(replica)
        target = (self.prefill_pool if role == PREFILL_POOL
                  else self.decode_pool)
        target.append(replica)
        target.sort()

    def remove_replica(self, replica: int) -> None:
        """Retire a replica from its pool (its slot index stays
        reserved fleet-wide; only pool membership changes)."""
        self._prefill_set.discard(replica)
        self._decode_set.discard(replica)
        for pool in (self.prefill_pool, self.decode_pool):
            if replica in pool:
                pool.remove(replica)

    # ------------------------------------------------------------------
    # Admission
    # ------------------------------------------------------------------
    def on_new_request(self,
                       request: EngineCoreRequest) -> EngineCoreRequest:
        """Stage a fresh admission. Returns the request object to admit
        (the one-token prefill-stage copy for handoff-eligible
        requests; the original otherwise)."""
        if request.kv_transfer_params:
            # Externally prefilled (a disagg proxy upstream): straight
            # to the decode pool, no staging of our own.
            self._stage[request.request_id] = DECODE_POOL
            return request
        sp = request.sampling_params
        if (request.pooling_params is not None
                or sp.prompt_logprobs is not None
                or (sp.max_tokens is not None and sp.max_tokens <= 1)):
            # Prefill-only work (embeddings, one-token generations) and
            # prompt_logprobs (externally-loaded positions can never be
            # scored, so the pull would be skipped anyway) serve
            # monolithically on the prefill pool: untracked, their
            # outputs flow through unintercepted.
            return request
        self._stage[request.request_id] = PREFILL_POOL
        return prefill_stage_request(request)

    def target_pool(self, request: EngineCoreRequest) -> list[int]:
        """Replica candidates for this admission (or re-admission)."""
        stage = self._stage.get(request.request_id)
        if stage == DECODE_POOL or request.kv_transfer_params:
            return self.decode_pool
        return self.prefill_pool

    def usable_pool(self, pool: list[int], down: set,
                    count: bool = True) -> Optional[list[int]]:
        """The pool minus downed replicas; None (= place anywhere
        alive, counted as a pool_down fallback) when the whole pool is
        out of rotation — availability beats pool purity. ``count=False``
        on _admit's failover-retry re-picks keeps the counter at one
        per degraded ADMISSION, not one per pick attempt."""
        alive = [i for i in pool if i not in down]
        if alive:
            return alive
        if count:
            self._count(FALLBACK_POOL_DOWN)
        logger.warning("disagg: pool %s entirely down; placing on any "
                       "alive replica", pool)
        return None

    def prefill_least_loaded(self, request: EngineCoreRequest) -> bool:
        """Prefill-pool admissions place least-loaded (the two-stage
        scheme's first stage): prefix affinity buys nothing there —
        the produced pages leave with the pull."""
        return self._stage.get(request.request_id) == PREFILL_POOL

    # ------------------------------------------------------------------
    # Output interception (the handoff itself)
    # ------------------------------------------------------------------
    def intercept(self, outs: list) -> list:
        """Filter one output batch under the balancer lock, BEFORE any
        journal/owner bookkeeping runs. Prefill-stage outputs are
        swallowed (their sampled token is regenerated by the decode
        home) and finished ones trigger the handoff; decode-stage
        outputs pass through after fallback/latency accounting."""
        kept = []
        for o in outs:
            stage = self._stage.get(o.req_id)
            if stage == PREFILL_POOL:
                if o.finished:
                    self._handoff(o)
                continue
            if stage == DECODE_POOL:
                self._observe_decode_output(o)
            kept.append(o)
        return kept

    def _handoff(self, out) -> None:
        """One finished prefill-stage request -> its decode home."""
        rid = out.req_id
        client = self.client
        # Unwind the prefill placement by hand: this output never
        # reaches the normal finish bookkeeping (and must NOT — the
        # router would credit prompt+generated residency to the
        # prefill replica, whose pages leave with the pull; the decode
        # home's on_admit/on_finish do the honest registration).
        owner = client._owner.pop(rid, None)
        if owner is not None:
            client._live[owner].discard(rid)
            if client.coordinator is not None:
                client.coordinator.report(owner, -1)
        orig = client._requests.get(rid)
        if orig is None:
            # Aborted while the finish was in flight; the producer's
            # deferred pages expire on their own send timeout.
            self._stage.pop(rid, None)
            return
        params = out.kv_transfer_params
        if params is None:
            # Pull-based fleet with a prompt shorter than one full
            # page: nothing to pull, the decode home prefills the
            # (tiny) prompt locally. Hash-addressed (shared_storage)
            # fleets never carry params — their decode homes hit the
            # page files by content hash, so nothing is counted.
            if self._params_expected:
                self._count(FALLBACK_NO_PULL_COORDS)
        elif fault_injection.should_fire("disagg.handoff_stall"):
            # Drill: break the pull coordinates so the decode home's
            # pull can never complete and the scheduler's recovery
            # ladder (bounded retries -> local re-prefill) must carry
            # the request instead.
            params = dict(params)
            params["remote_req_id"] = \
                str(params.get("remote_req_id", rid)) + "#stalled"
        req = copy.copy(orig)  # shallow: only the params field changes
        req.kv_transfer_params = params
        self._stage[rid] = DECODE_POOL
        self._t0[rid] = time.monotonic()
        self.handoffs += 1
        recorder = getattr(client, "events", None)
        if recorder is not None and recorder.enabled:
            # The producer->consumer causal link: this event (stamped
            # with the request's trace id, tagged with the producer
            # replica) is where the Perfetto export opens its flow
            # arrow; the decode home's kv_pull span closes it.
            detail: dict = {"from_replica": owner,
                            "pull": params is not None}
            if getattr(client, "trace_enabled", False):
                detail = ev.stamp_trace(detail, orig.trace_ctx)
            recorder.record(rid, ev.DISAGG_HANDOFF, detail)
        client._admit(req)

    def _observe_decode_output(self, out) -> None:
        t0 = self._t0.get(out.req_id)
        if t0 is not None and (out.new_token_ids or out.finished):
            # Handoff latency: interception -> the decode home's first
            # token back at the front end (covers routing, the pull or
            # its fallback, requeue, and the first decode step).
            self.handoff_seconds.observe(time.monotonic() - t0)
            self._t0.pop(out.req_id, None)
        for event in (out.events or ()):
            name = event[1] if len(event) > 1 else None
            if name == ev.KV_PULL_LOCAL:
                self._count(FALLBACK_LOCAL_REPREFILL)
            elif name == ev.KV_PULL_RETRY:
                self._count(FALLBACK_PULL_RETRY)
        if out.finished:
            self._stage.pop(out.req_id, None)
            self._t0.pop(out.req_id, None)

    # ------------------------------------------------------------------
    # Failure handling
    # ------------------------------------------------------------------
    def readmission_for(self, rid: str, orig: EngineCoreRequest,
                        generated: list[int]) -> Optional[EngineCoreRequest]:
        """Replacement request for one stranded rid during a replica
        failover, or None to use the default continuation path. A
        prefill-stage casualty re-enters as a fresh prefill-stage copy
        (nothing was delivered, so there is nothing to continue); a
        decode-stage casualty uses the normal continuation (the caller
        builds it) and stays homed to the decode pool."""
        stage = self._stage.get(rid)
        if stage == PREFILL_POOL:
            self._count(FALLBACK_PREFILL_DEATH)
            return prefill_stage_request(orig)
        if stage == DECODE_POOL:
            self._count(FALLBACK_DECODE_DEATH)
        return None

    def forget(self, rid: str) -> None:
        self._stage.pop(rid, None)
        self._t0.pop(rid, None)

    def reset(self) -> None:
        self._stage.clear()
        self._t0.clear()

    def _count(self, reason: str) -> None:
        self.fallbacks[reason] = self.fallbacks.get(reason, 0) + 1

    # ------------------------------------------------------------------
    def get_stats(self, live_counts: list[int]) -> dict:
        """The ``disagg`` entry of the DP stats aggregation, rendered
        as the vdt:disagg_* / vdt:pool_occupancy families."""
        return {
            "handoffs": self.handoffs,
            "fallbacks": dict(self.fallbacks),
            "handoff_seconds": self.handoff_seconds.to_dict(),
            "pool_occupancy": {
                PREFILL_POOL:
                    sum(live_counts[i] for i in self.prefill_pool),
                DECODE_POOL:
                    sum(live_counts[i] for i in self.decode_pool),
            },
            "pools": {PREFILL_POOL: list(self.prefill_pool),
                      DECODE_POOL: list(self.decode_pool)},
        }
