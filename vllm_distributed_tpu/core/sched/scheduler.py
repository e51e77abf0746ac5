"""Continuous-batching scheduler.

Reference: vllm/v1/core/sched/scheduler.py (``Scheduler.schedule``:413,
``update_from_output``:1012). One token-budget loop unifies prefill, decode,
chunked prefill and speculative verification: each step, every scheduled
request contributes ``num_new_tokens`` (a prompt chunk, or 1 + draft length
for decode) against ``max_num_batched_tokens``. Preemption pops the
lowest-priority running request and returns it to the waiting queue with its
pages freed.

TPU note: the scheduler is pure control plane (no device code) and runs on
the host exactly as in the reference; static-shape discipline lives in the
worker, which pads this scheduler's ragged output to bucketed shapes.
"""

import time
from collections import Counter, deque
from typing import Iterable, Optional

from vllm_distributed_tpu.config import EngineConfig
from vllm_distributed_tpu.core.kv_cache_manager import (
    GroupedKVCacheManager, KVCacheBlocks, KVCacheManager,
    TokenParallelKVCacheManager)
from vllm_distributed_tpu.core.sched import qos as qos_mod
from vllm_distributed_tpu.core.sched.output import (CachedRequestData,
                                                    ModelRunnerOutput,
                                                    NewRequestData,
                                                    SchedulerOutput,
                                                    TokenParallelAllocation)
from vllm_distributed_tpu.logger import init_logger
from vllm_distributed_tpu.metrics import events as ev
from vllm_distributed_tpu.metrics.stats import (QUEUE_LAG_BUCKETS,
                                                STEP_TOKEN_BUCKETS,
                                                Histogram)
from vllm_distributed_tpu.request import Request, RequestStatus

logger = init_logger(__name__)


class EngineCoreOutput:
    """Per-request delta shipped to the engine front-end
    (reference: v1/engine/__init__.py EngineCoreOutput)."""

    __slots__ = ("req_id", "new_token_ids", "finish_reason", "stop_reason",
                 "num_cached_tokens", "logprobs", "kv_transfer_params",
                 "pooled", "prompt_logprobs", "events")

    def __init__(self, req_id: str, new_token_ids: list[int],
                 finish_reason: Optional[str] = None,
                 stop_reason: Optional[int | str] = None,
                 num_cached_tokens: int = 0,
                 logprobs: Optional[list[dict[int, float]]] = None,
                 kv_transfer_params: Optional[dict] = None,
                 pooled: Optional[list[float]] = None,
                 prompt_logprobs: Optional[list] = None,
                 events: Optional[list[tuple]] = None) -> None:
        self.req_id = req_id
        self.new_token_ids = new_token_ids
        self.finish_reason = finish_reason
        self.stop_reason = stop_reason
        self.num_cached_tokens = num_cached_tokens
        self.logprobs = logprobs
        # Producer handoff coordinates on the final output (disagg;
        # reference: v1/engine/__init__.py EngineCoreOutput).
        self.kv_transfer_params = kv_transfer_params
        # Embedding result for pooling requests (reference: pooling
        # outputs on the core output path, v1/outputs.py).
        self.pooled = pooled
        # Full prompt-logprob list (entry 0 = None), attached to the
        # request's FIRST emitted output once the prompt completes
        # (reference: prompt_logprobs on the engine-core output path).
        self.prompt_logprobs = prompt_logprobs
        # Core-side lifecycle events (metrics/events.py) accumulated on
        # the request since its previous output; the front-end stitches
        # them into the request's phase timeline.
        self.events = events

    @property
    def finished(self) -> bool:
        return self.finish_reason is not None


class _PageTombstone:
    """Stand-in owner for pages of a timed-out remote-KV pull that may
    still be written by an in-flight transfer: the watchdog re-keys the
    pages to a tombstone so the request can re-queue with fresh pages
    while the old ones stay out of the pool until the worker reports
    (or the abandon backstop expires)."""

    __slots__ = ("request_id", "tknp_rank", "expires_at")

    def __init__(self, request_id: str, tknp_rank: Optional[int],
                 expires_at: float) -> None:
        self.request_id = request_id
        self.tknp_rank = tknp_rank
        self.expires_at = expires_at


class Scheduler:

    def __init__(
        self,
        config: EngineConfig,
        num_blocks: Optional[int] = None,
        kv_connector=None,
    ) -> None:
        self.config = config
        sched_cfg = config.scheduler_config
        self.max_num_batched_tokens = sched_cfg.max_num_batched_tokens
        self.max_num_seqs = sched_cfg.max_num_seqs
        self.max_model_len = sched_cfg.max_model_len
        self.enable_chunked_prefill = sched_cfg.enable_chunked_prefill
        self.long_prefill_token_threshold = \
            sched_cfg.long_prefill_token_threshold
        self.policy = sched_cfg.policy
        self.num_scheduler_steps = getattr(sched_cfg,
                                           "num_scheduler_steps", 1)

        if num_blocks is None:
            num_blocks = config.cache_config.num_gpu_blocks
        assert num_blocks is not None and num_blocks > 0, \
            "scheduler needs the page count (set cache_config.num_gpu_blocks)"
        # Token parallelism (the fork's TKNP, re-expressed for SPMD): the
        # page pool is partitioned per token-axis rank and the scheduler
        # assigns each request to a rank at admission (reference:
        # v1/core/sched/scheduler.py:55 TokenParallelScheduler).
        self.tknp_size = config.parallel_config.token_parallel_size
        # Sliding-window page freeing: only when every layer is windowed
        # AND no KV connector is attached (a connector may still read a
        # request's prompt pages for a peer pull after they leave the
        # window; its deferred-free holds don't cover mid-request frees).
        from vllm_distributed_tpu.models.loader import (
            resolve_encoder_only, resolve_free_window, resolve_stateful,
            resolve_window_group)
        free_window = (None if kv_connector is not None
                       else resolve_free_window(config.model_config))
        enable_caching = config.cache_config.enable_prefix_caching
        # Window and full layers mixed, in a family that keeps a cache
        # group for each (models/cohere_moe.py): two pools, two tables
        # (GroupedKVCacheManager). get_model refuses the combinations
        # the groups do not carry (KV transfer, token parallelism).
        window_group = resolve_window_group(config.model_config)
        if window_group is not None and enable_caching:
            logger.info(
                "prefix caching is off for this model: its window "
                "group's pages are freed behind the window and are never "
                "shared, and a hit on the full group alone is no hit")
            enable_caching = False
        if resolve_encoder_only(config.model_config):
            # Encoder-only (BERT-family) archs: a bidirectional layer
            # needs the full sequence in one step, and there is no
            # causal KV to re-enter — whole-prompt scheduling, no
            # prefix reuse (the processor bounds prompts to the token
            # budget at admission).
            if self.enable_chunked_prefill or enable_caching:
                logger.info("encoder-only model: chunked prefill and "
                            "prefix caching disabled")
            self.enable_chunked_prefill = False
            enable_caching = False
        # SSM state cache (core/state_cache.py): give fixed-size state
        # snapshots the same rights paged KV has. A snapshot at a token
        # boundary is a complete resume point, so "prefix caching" for
        # stateful models = restore the state at the shared boundary.
        self.state_cache = None
        if resolve_stateful(config.model_config):
            from vllm_distributed_tpu.core.state_cache import (
                StateCacheManager, resolve_ckpt_interval,
                resolve_state_slots, state_cache_enabled)
            from vllm_distributed_tpu.models.loader import (
                resolve_state_only, resolve_state_snapshotable)
            if (state_cache_enabled(config, True)
                    and kv_connector is None
                    and resolve_state_snapshotable(config.model_config)):
                from vllm_distributed_tpu import envs as _envs
                paged = not resolve_state_only(config.model_config)
                if paged and not enable_caching:
                    # Hybrid (Jamba/Bamba): a state restore must re-enter
                    # coherently with the attention KV of the same
                    # prefix, so the page prefix cache MUST index those
                    # pages.
                    logger.info("hybrid SSM model: prefix caching forced "
                                "on for the state cache")
                    enable_caching = True
                elif not paged:
                    # Pure SSM: pages carry no bytes; the state cache
                    # keys its own hash chains.
                    enable_caching = False
                # Hierarchical tiering (VDT_KV_TIERING): snapshot
                # eviction demotes to the journal instead of
                # discarding; without an explicit checkpoint dir the
                # journal homes under the KV tier's spill directory.
                journal_dir = _envs.VDT_SSM_CKPT_DIR
                tiering = _envs.VDT_KV_TIERING
                if tiering and not journal_dir and _envs.VDT_KV_TIER_DIR:
                    import os as _os
                    journal_dir = _os.path.join(_envs.VDT_KV_TIER_DIR,
                                                "ssm")
                self.state_cache = StateCacheManager(
                    num_slots=resolve_state_slots(config),
                    block_size=config.cache_config.block_size,
                    interval=resolve_ckpt_interval(config),
                    paged_kv=paged,
                    journal_dir=journal_dir,
                    demote_on_evict=tiering)
                logger.info(
                    "SSM state cache: %d slots, checkpoint every %d "
                    "tokens%s", self.state_cache.num_slots,
                    self.state_cache.interval,
                    f", journal {self.state_cache.journal_dir}"
                    if self.state_cache.journal_dir else "")
                if getattr(sched_cfg, "num_scheduler_steps", 1) > 1:
                    # Fused decode bursts advance state mid-burst past
                    # snapshot boundaries; keep the cadence exact.
                    logger.info("SSM state cache: multi-step decode "
                                "bursts disabled")
                    self.num_scheduler_steps = 1
            else:
                # SSM state cannot re-enter at a cached page boundary;
                # without the state cache the reference behavior stands
                # (prefix caching disabled for mamba models).
                logger.info("stateful (SSM) model: prefix caching "
                            "disabled (state cache off)")
                enable_caching = False
        # Save directives not yet attached to an output (a preempt-park
        # on a step whose grant came up empty defers to the next
        # non-empty output — the zero-token dispatch path does no
        # device work by contract).
        self._deferred_state_saves: list = []
        # Hierarchical KV tiering (core/kv_tier.py): host-RAM + disk
        # spill tiers behind the device pool. Gated to the plain paged
        # path — stateful models' second tier is the state-cache
        # journal (their admission bypasses get_computed_blocks), and
        # sliding-window models free pages the mask forbids ever
        # reading again (demoting dead-window pages would resurrect
        # unreadable content). None = untiered, byte-identical.
        self.kv_tier = None
        if (self.tknp_size == 1 and enable_caching
                and self.state_cache is None and free_window is None):
            from vllm_distributed_tpu.core.kv_tier import maybe_kv_tier
            self.kv_tier = maybe_kv_tier(config, kv_connector)
        self.window_grouped = window_group is not None
        if self.window_grouped:
            from vllm_distributed_tpu.core.kv_cache_utils import \
                config_window_group_pool_blocks
            self.kv_cache_manager = GroupedKVCacheManager(
                block_size=config.cache_config.block_size,
                num_blocks=num_blocks,
                num_window_blocks=config_window_group_pool_blocks(
                    config, window_group),
                window=window_group)
        elif self.tknp_size > 1:
            self.kv_cache_manager = TokenParallelKVCacheManager(
                block_size=config.cache_config.block_size,
                num_blocks=num_blocks,
                num_ranks=self.tknp_size,
                enable_caching=enable_caching,
            )
            # Per-rank scheduled-token counts (load-balance signal).
            self.tknp_tokens_per_rank = [0] * self.tknp_size
        else:
            self.kv_cache_manager = KVCacheManager(
                block_size=config.cache_config.block_size,
                num_blocks=num_blocks,
                enable_caching=enable_caching,
                free_window=free_window,
                tier=self.kv_tier,
            )
        # Structured output (reference: the engine core's
        # StructuredOutputManager beside the scheduler,
        # v1/structured_output/__init__.py); set by EngineCore when the
        # first structured request arrives.
        self.structured_manager = None
        # KV cache events for external prefix-aware routers (reference:
        # distributed/kv_events.py ZmqEventPublisher).
        self.kv_event_publisher = None
        ev_cfg = config.kv_events_config
        if ev_cfg.enable_kv_cache_events:
            from vllm_distributed_tpu.distributed.kv_events import \
                KVEventPublisher
            self.kv_event_publisher = KVEventPublisher(
                ev_cfg.endpoint, ev_cfg.replay_endpoint,
                ev_cfg.buffer_steps)
            for pool in self._block_pools():
                pool.enable_events()
        # Disaggregated-prefill hook (reference: scheduler holds the
        # scheduler-side KVConnector, sched/scheduler.py KVConnector calls).
        self.kv_connector = kv_connector
        if kv_connector is not None:
            # Let the connector query current block ids directly instead
            # of threading them through every hook.
            kv_connector.kv_manager = self.kv_cache_manager

        # Encoder (vision) output budget (reference:
        # v1/core/encoder_cache_manager.py); payloads live worker-side,
        # the scheduler owns admission accounting.
        from vllm_distributed_tpu.core.encoder_cache_manager import \
            EncoderCacheManager
        self.encoder_cache = EncoderCacheManager(
            config.scheduler_config.encoder_cache_budget)

        self.requests: dict[str, Request] = {}
        self.waiting: deque[Request] = deque()
        self.running: list[Request] = []
        # Finished ids to tell the workers to drop state for.
        self.finished_req_ids: set[str] = set()
        # Async KV transfer state (reference: scheduler.py
        # WAITING_FOR_REMOTE_KVS handling + nixl_connector.py:295
        # deferred free). Requests held until their KV pull lands, and
        # finished producer requests whose pages stay alive until the
        # consumer pulled them.
        self.waiting_for_remote_kv: dict[str, Request] = {}
        self.reqs_pending_send: dict[str, Request] = {}
        # Aborted while a pull was in flight: pages stay allocated until
        # the worker reports the (now moot) pull finished, so a late
        # apply can never write into reallocated pages.
        self.cancelled_remote_kv: dict[str, Request] = {}
        # Engine-core batch queue (PP microbatches, or the async
        # depth-2 pipeline): requests inside a dispatched-but-unretired
        # batch, REFCOUNTED — under async scheduling one request can sit
        # in two in-flight batches at once. In-flight requests are
        # protected from preemption (device work is writing their
        # pages) and external finishes defer until every batch holding
        # them retires; under PP (sync) they are also skipped by
        # schedule(), while async scheduling re-grants them
        # speculatively (see the schedule() running loop).
        self.in_flight_req_ids: Counter = Counter()
        self._deferred_finishes: dict[str, RequestStatus] = {}
        # Async scheduling: overlap host scheduling with device
        # execution. schedule() advances num_computed_tokens at GRANT
        # time (so the next schedule() can run ahead), grants one
        # speculative position per running decode request whose sampled
        # token is still on device (the runner chains it
        # device-to-device), and update_from_output reconciles when the
        # token lands — stop/EOS detection lags one step, and a request
        # finishing with a batch still in flight parks here until that
        # batch retires (its pages are being written).
        self.async_scheduling = getattr(sched_cfg, "async_scheduling",
                                        False)
        self._finished_pending_retire: dict[str, Request] = {}
        # Speculative (run-ahead) decode grants issued (stats).
        self.num_async_spec_grants = 0

        # Remote-KV watchdog (fault-tolerance layer): requests held in
        # WAITING_FOR_REMOTE_KVS past this deadline are swept into the
        # failed-pull requeue path instead of hanging forever.
        ft_cfg = config.fault_tolerance_config
        self.kv_pull_timeout_s = ft_cfg.kv_pull_timeout_s
        self.kv_pull_max_retries = ft_cfg.kv_pull_max_retries
        self.kv_pull_abandon_timeout_s = ft_cfg.kv_pull_abandon_timeout_s

        # Per-tenant QoS (core/sched/qos.py): deficit-round-robin
        # weighted fair queueing over tenants, soft KV page quotas with
        # quota-aware preemption, and the per-tenant accounting behind
        # the vdt:tenant_* families. None when VDT_QOS=0 (the default)
        # — every hook below is then a short-circuited None check and
        # scheduling stays byte-identical to the pre-QoS behavior.
        self.qos = qos_mod.maybe_qos_state(self.max_num_batched_tokens,
                                           num_blocks)
        # Per-step {tenant: deque of waiting requests in queue order},
        # built lazily by _qos_pick_waiting, popped by _waiting_remove.
        self._qos_waiting_by_tenant: Optional[dict[str, deque]] = None

        # Stats for the metrics subsystem.
        self.num_scheduled_steps = 0
        self.num_preemptions = 0
        # Preemption attribution: "capacity" = a lower-priority victim
        # was evicted for another request's pages, "self" = the request
        # could find no victim (token-parallel rank exhausted, or every
        # candidate in flight) and preempted itself.
        self.preemption_causes: dict[str, int] = {}
        self.watchdog_timeouts = 0
        self.kv_pull_retries = 0
        self.kv_pull_failures = 0
        # Request-lifecycle timeline (metrics/events.py): the scheduler's
        # local ring buffer, drained over the stats RPC; the per-request
        # event lists additionally ride EngineCoreOutput to the
        # front-end. `events_enabled` is cached (the envs registry
        # re-reads os.environ per access).
        self.events = ev.EventRecorder()
        self.events_enabled = self.events.enabled
        # Distributed trace plane: when on, every recorded event detail
        # carries the request's trace id so the front-end assembler can
        # stitch this replica's spans into the fleet-wide causal trace.
        # Cached like events_enabled; off means zero stamping work and
        # byte-identical event details.
        self.trace_enabled = ev.trace_plane_enabled()
        # Batch composition of the most recent non-empty step (gauges),
        # and of every non-empty step (vdt:step_tokens{kind}).
        self.last_step_prefill_tokens = 0
        self.last_step_decode_tokens = 0
        self.step_tokens = {kind: Histogram(STEP_TOKEN_BUCKETS)
                            for kind in ("prefill", "decode")}
        # add_request to the first step that grants the request tokens.
        self.queue_wait = Histogram(QUEUE_LAG_BUCKETS)

    def _record_event(self, request: Request, event: str,
                      detail: Optional[dict] = None, *,
                      force: bool = False) -> None:
        """One lifecycle transition: onto the request's own event list
        (ships with its next output) and the scheduler's ring buffer
        (ships with get_stats). ``force`` bypasses the timeline kill
        switch for the per-request list only — recovery-ladder events
        feed ACCOUNTING at the front end (disagg fallback counters),
        which must not ride a telemetry flag; the ring buffer stays
        gated."""
        if not self.events_enabled and not force:
            return
        ts = time.monotonic()
        if self.trace_enabled and request.trace_ctx is not None:
            detail = ev.stamp_trace(detail, request.trace_ctx)
        request.events.append((ts, event, detail))
        if self.events_enabled:
            self.events.record(request.request_id, event, detail, ts=ts)

    def _take_events(self, request: Request) -> Optional[list[tuple]]:
        if not request.events:
            return None
        taken = request.events
        request.events = []
        return taken

    # ------------------------------------------------------------------
    # Request intake / teardown
    # ------------------------------------------------------------------
    def add_request(self, request: Request) -> None:
        assert request.request_id not in self.requests
        self.requests[request.request_id] = request
        request.status = RequestStatus.WAITING
        request.queued_at = time.monotonic()
        self._record_event(request, ev.QUEUED,
                           {"prompt_tokens": request.num_prompt_tokens,
                            "priority": request.priority})
        if self.policy == "priority":
            self._insert_by_priority(request)
        else:
            self.waiting.append(request)

    def _insert_by_priority(self, request: Request) -> None:
        key = (request.priority, request.arrival_time)
        for i, r in enumerate(self.waiting):
            if key < (r.priority, r.arrival_time):
                self.waiting.insert(i, request)
                return
        self.waiting.append(request)

    def finish_requests(self, request_ids: str | Iterable[str],
                        status: RequestStatus) -> None:
        """External finish (abort, stop-string hit detected by the
        front-end detokenizer). Reference: scheduler.py finish_requests."""
        if isinstance(request_ids, str):
            request_ids = (request_ids, )
        for req_id in request_ids:
            request = self.requests.get(req_id)
            if request is None or request.is_finished:
                continue
            if req_id in self.in_flight_req_ids:
                # A dispatched batch is still writing this request's
                # pages; freeing them now would hand them to another
                # request mid-write. Finish when the batch retires.
                self._deferred_finishes[req_id] = status
                continue
            if request.status == RequestStatus.RUNNING:
                self.running.remove(request)
            elif request.status == RequestStatus.WAITING_FOR_REMOTE_KVS:
                # The worker's pull is still in flight; keep the pages
                # alive until it reports in, then free (see
                # _update_kv_transfer_state). The abandon backstop
                # covers this hold too — a silently-dropped pull for an
                # aborted request must not leak its pages forever.
                self.waiting_for_remote_kv.pop(req_id, None)
                request.status = status
                request.expires_at = (time.monotonic() +
                                      self.kv_pull_abandon_timeout_s)
                if self.kv_connector is not None:
                    self.kv_connector.cancel_pull(req_id)
                self.cancelled_remote_kv[req_id] = request
                if self.structured_manager is not None:
                    self.structured_manager.remove_request(req_id)
                self.finished_req_ids.add(req_id)
                del self.requests[req_id]
                continue
            else:
                try:
                    self.waiting.remove(request)
                except ValueError:
                    pass
            request.status = status
            self._free_request(request)

    def _commit_encoder_budget(self, request: Request) -> None:
        # offset < 0 marks cross-attention payloads (whisper audio):
        # they live in fixed state rows, not the encoder cache.
        budgeted = [m for m in (request.mm_inputs or ())
                    if m.offset >= 0]
        if budgeted and not self.encoder_cache.has(request.request_id):
            self.encoder_cache.allocate(
                request.request_id,
                sum(m.num_tokens for m in budgeted))

    def _free_request(self, request: Request) -> Optional[dict]:
        """Tear a finished request down. Returns the connector's
        kv_transfer_params to hand back to the client (a producer's
        pull coordinates), or None."""
        assert request.is_finished
        self.encoder_cache.free(request.request_id)
        params = None
        defer = False
        if self.kv_connector is not None:
            # Teardown hook (reference: base.py request_finished).
            # Synchronous connectors never defer the free; async
            # (pull-based) connectors return defer=True and the free
            # then waits on the worker's finished_sending notice
            # (reference: nixl_connector.py:295 deferred block free).
            defer, params = self.kv_connector.request_finished(
                request,
                self.kv_cache_manager.get_block_ids(request.request_id)
                if request.request_id in getattr(
                    self.kv_cache_manager, "req_to_blocks", {}) else [])
        if defer:
            self.reqs_pending_send[request.request_id] = request
        else:
            self.kv_cache_manager.free(request)
            self.kv_cache_manager.free_block_hashes(request)
        if self.structured_manager is not None:
            self.structured_manager.remove_request(request.request_id)
        if self.state_cache is not None:
            # Uncommitted saves die with the request (their row is about
            # to be recycled); committed snapshots outlive it — they ARE
            # the multi-turn prefix cache.
            self.state_cache.abort_pending(request.request_id)
            self.state_cache.drop_request(request.request_id)
        self.finished_req_ids.add(request.request_id)
        del self.requests[request.request_id]
        return params

    # ------------------------------------------------------------------
    # Introspection
    # ------------------------------------------------------------------
    def has_requests(self) -> bool:
        return bool(self.waiting or self.running
                    or self.waiting_for_remote_kv)

    def has_schedulable_requests(self) -> bool:
        """Work the next schedule() call could actually grant tokens to
        — gates dispatching another batch in the engine core's batch
        queue. Under PP (sync) in-flight requests are excluded; under
        async scheduling a request with known-token backlog or exactly
        one in-flight sample is speculatively re-grantable."""
        if self.waiting:
            return True
        if self.async_scheduling:
            return any(self._async_schedulable(r) for r in self.running)
        return any(r.request_id not in self.in_flight_req_ids
                   for r in self.running)

    # ------------------------------------------------------------------
    # In-flight batch bookkeeping (engine-core batch queue)
    # ------------------------------------------------------------------
    def mark_in_flight(self, req_ids: Iterable[str]) -> None:
        for req_id in req_ids:
            self.in_flight_req_ids[req_id] += 1

    def unmark_in_flight(self, req_ids: Iterable[str]) -> None:
        for req_id in req_ids:
            n = self.in_flight_req_ids[req_id] - 1
            if n > 0:
                self.in_flight_req_ids[req_id] = n
            else:
                del self.in_flight_req_ids[req_id]

    # ------------------------------------------------------------------
    # Async-scheduling predicates
    # ------------------------------------------------------------------
    @staticmethod
    def _needs_sync_step(request: Request) -> bool:
        """Requests whose next grant depends on host-side state from the
        previous step's sampled token: grammar masks (structured output)
        advance on the emitted tokens, penalty/bias sampling reads the
        host token history, prompt_logprobs and pooling key off exact
        prompt accounting. These fall back to PP-style one-batch-at-a-
        time scheduling (skip while in flight, never run ahead)."""
        sp = request.sampling_params
        return (request.pooling_params is not None
                or sp.structured is not None
                or sp.prompt_logprobs is not None
                or sp.needs_extended_static
                or sp.min_tokens > 0)

    def _can_speculate(self, request: Request) -> bool:
        """One speculative run-ahead position may be granted iff every
        known token is computed (== exactly one sample is owed by an
        in-flight batch; the runner chains it device-to-device), the
        context window has room, and the owed sample won't already cap
        max_tokens (the extra position would be guaranteed waste)."""
        return (request.num_computed_tokens == request.num_tokens
                and not request.spec_token_ids
                and request.num_computed_tokens < self.max_model_len
                and (request.num_output_tokens + 1
                     < request.sampling_params.max_tokens))

    def _async_schedulable(self, request: Request) -> bool:
        if (self._needs_sync_step(request)
                or request.request_id in self._deferred_finishes):
            return request.request_id not in self.in_flight_req_ids
        if request.num_tokens_with_spec > request.num_computed_tokens:
            return True  # known-token backlog (prefill chunks)
        return self._can_speculate(request)

    def has_kv_transfer_work(self) -> bool:
        """True while async KV transfers are in flight: held consumer
        requests or producer pages awaiting a peer's pull. The engine
        core keeps stepping (with possibly-empty scheduler outputs) so
        the worker's get_finished() poll services them."""
        return bool(self.waiting_for_remote_kv or self.reqs_pending_send
                    or self.cancelled_remote_kv)

    def has_unfinished_requests(self) -> bool:
        return self.has_requests()

    def get_num_unfinished_requests(self) -> int:
        return len(self.waiting) + len(self.running)

    # ------------------------------------------------------------------
    # The hot loop
    # ------------------------------------------------------------------
    def schedule(self) -> SchedulerOutput:
        if self.qos is not None:
            # Replenish per-tenant deficits and snapshot who competes
            # for prefill bandwidth / holds pages this step. The
            # per-tenant waiting-queue view is rebuilt lazily by the
            # first _qos_pick_waiting of the step (the queue may gain
            # requests between steps).
            self.qos.begin_step(self.waiting, self.running,
                                self._qos_held_by_tenant())
            self._qos_waiting_by_tenant = None
        scheduled_new_reqs: list[NewRequestData] = []
        cached_reqs = CachedRequestData()
        num_scheduled_tokens: dict[str, int] = {}
        scheduled_spec_tokens: dict[str, list[int]] = {}
        token_budget = self.max_num_batched_tokens
        preempted: list[Request] = []
        # Batch composition (prefill vs decode tokens) of this step.
        prefill_tokens = 0
        decode_tokens = 0
        decode_reqs = 0
        # SSM state-cache directives accumulated this step (plus any
        # deferred from empty outputs).
        state_saves: list = []
        state_restores: list = []
        # KV-tier promote directives staged by this step's admissions.
        kv_promotes: list = []

        # Multi-step decode burst: when every running request is in plain
        # decode and nothing is waiting, the worker can run N fused decode
        # steps on-device per host roundtrip. All N slots are allocated up
        # front via num_lookahead_tokens; the burst is disabled for any
        # request that could finish or hit the context window mid-burst.
        # (Token parallelism forces num_scheduler_steps=1 at config
        # normalization: the fused burst cannot refresh per-rank metadata.)
        multi_step = self.num_scheduler_steps
        if multi_step > 1:
            if self.waiting or not self.running:
                multi_step = 1
            else:
                for request in self.running:
                    sp = request.sampling_params
                    if (request.num_tokens_with_spec -
                            request.num_computed_tokens != 1
                            or request.pooling_params is not None
                            or request.spec_token_ids
                            or sp.needs_extended_static
                            or request.num_output_tokens < sp.min_tokens
                            or sp.max_tokens - request.num_output_tokens <
                            multi_step
                            or self.max_model_len -
                            request.num_computed_tokens < multi_step):
                        multi_step = 1
                        break

        # ---- 1. Running requests (decode / ongoing chunked prefill) ----
        req_index = 0
        while req_index < len(self.running) and token_budget > 0:
            request = self.running[req_index]
            if (request.request_id in self.in_flight_req_ids
                    and (not self.async_scheduling
                         or self._needs_sync_step(request)
                         or request.request_id in self._deferred_finishes)):
                # Another dispatched batch owns this request's next
                # token (PP batch queue, or an async request that needs
                # host-synchronous sampling state); it becomes
                # schedulable when that batch retires. A request whose
                # abort waits for that batch is granted nothing more:
                # run ahead again and it would never leave the device.
                req_index += 1
                continue
            num_new_tokens = (request.num_tokens_with_spec -
                              request.num_computed_tokens)
            speculative = False
            if (num_new_tokens <= 0 and self.async_scheduling
                    and not self._needs_sync_step(request)
                    and self._can_speculate(request)):
                # Async run-ahead: every known token is computed, so the
                # only thing missing is the sample an in-flight batch
                # owes. Grant the NEXT position now; the runner feeds it
                # the on-device sampled token (device-to-device chain).
                num_new_tokens = 1
                speculative = True
            if self.long_prefill_token_threshold > 0:
                num_new_tokens = min(num_new_tokens,
                                     self.long_prefill_token_threshold)
            num_new_tokens = min(num_new_tokens, token_budget)
            # Never run past the context window.
            num_new_tokens = min(
                num_new_tokens,
                self.max_model_len - request.num_computed_tokens)
            in_prefill = (request.num_computed_tokens
                          < request.num_prompt_tokens)
            if (self.qos is not None and num_new_tokens > 0
                    and in_prefill):
                # DRR: an ongoing chunked-prefill grant clips to the
                # tenant's remaining deficit while another tenant with
                # credit competes, and always leaves one-token headroom
                # per OTHER tenant's unserved running decode (decode
                # grants themselves are never clipped — stalling a
                # running decode moves everyone's TPOT).
                num_new_tokens = self.qos.prefill_allowance(
                    self.qos.key_of(request), num_new_tokens,
                    token_budget)
            if self.state_cache is not None and num_new_tokens > 0:
                # Land prefill chunks exactly on snapshot boundaries so
                # the state rows hold boundary state when the copy runs.
                num_new_tokens = self.state_cache.clip_grant(
                    request.num_computed_tokens, num_new_tokens)
            if num_new_tokens <= 0:
                req_index += 1
                continue

            scheduled = True
            skipped = False
            while True:
                new_blocks = self.kv_cache_manager.allocate_slots(
                    request, num_new_tokens,
                    num_lookahead_tokens=multi_step - 1)
                if new_blocks is not None:
                    break
                if multi_step > 1:
                    # Not enough pages for the whole burst: degrade to
                    # single-step before resorting to preemption. (Earlier
                    # requests keep their lookahead pages — they will be
                    # used by the following decode steps anyway.)
                    multi_step = 1
                    continue
                # Out of pages: preempt the lowest-priority running request
                # that has NOT been scheduled this step (evicting a
                # scheduled one would leave SchedulerOutput entries
                # pointing at freed pages).
                victim, cause = self._select_preemption_victim(
                    req_index, request)
                if (victim is request
                        and request.request_id in self.in_flight_req_ids):
                    # Async: the only preemptable candidate is this
                    # request itself, but an in-flight batch is writing
                    # its pages — evicting it would corrupt them. Skip
                    # the grant; pressure resolves once batches retire
                    # (an empty queue restores normal preemption).
                    skipped = True
                    break
                # "self" overrides only the capacity pick (the pre-QoS
                # no-eligible-victim semantics); a quota eviction keeps
                # its cause even when the over-quota tenant's lowest-
                # priority request IS the requester.
                self._preempt(victim,
                              cause=("self" if victim is request
                                     and cause == "capacity" else cause))
                preempted.append(victim)
                if victim is request:
                    scheduled = False
                    break
            if skipped:
                req_index += 1
                continue
            if not scheduled:
                # The current request itself was preempted; its slot in
                # self.running is gone — do not advance req_index.
                continue

            num_scheduled_tokens[request.request_id] = num_new_tokens
            token_budget -= num_new_tokens
            if self.qos is not None:
                self.qos.charge(self.qos.key_of(request), num_new_tokens,
                                decode=not in_prefill)
            if in_prefill:
                # Ongoing chunked prefill (num_computed is pre-advance
                # here even under async scheduling).
                prefill_tokens += num_new_tokens
                self._record_event(
                    request, ev.PREFILL_CHUNK,
                    {"computed": request.num_computed_tokens,
                     "granted": num_new_tokens})
            else:
                decode_tokens += num_new_tokens
                decode_reqs += 1
            if request.spec_token_ids:
                # Trim drafts to the granted token count (1 committed token
                # + at most num_new_tokens-1 drafts); publishing untrimmed
                # drafts would desync num_computed_tokens accounting when
                # the budget caps num_new_tokens.
                num_drafts = max(num_new_tokens - 1, 0)
                request.spec_token_ids = request.spec_token_ids[:num_drafts]
                if request.spec_token_ids:
                    scheduled_spec_tokens[request.request_id] = \
                        list(request.spec_token_ids)
            cached_reqs.req_ids.append(request.request_id)
            cached_reqs.resumed_from_preemption.append(False)
            cached_reqs.new_token_ids.append(
                request.all_token_ids[request.num_computed_tokens:
                                      request.num_computed_tokens +
                                      num_new_tokens])
            cached_reqs.new_block_ids.append(new_blocks.get_block_ids())
            if new_blocks.window_blocks is not None:
                cached_reqs.new_window_block_ids.append(
                    new_blocks.get_window_block_ids())
            cached_reqs.num_computed_tokens.append(
                request.num_computed_tokens)
            if self.state_cache is not None:
                # Snapshot when this grant lands exactly on a boundary
                # (committed in update_from_output once the step's
                # tokens reconcile — an async run-ahead that stops
                # short never enters the index).
                directive = self.state_cache.maybe_save(
                    request,
                    request.num_computed_tokens + num_new_tokens)
                if directive is not None:
                    state_saves.append(directive)
            if self.async_scheduling:
                # Advance AT GRANT TIME so the next schedule() call can
                # run ahead of this batch; update_from_output skips the
                # advance for async_scheduled batches.
                request.num_computed_tokens += num_new_tokens
                if speculative:
                    self.num_async_spec_grants += 1
                    if not request.async_spec_granted:
                        # Timeline transition: entered run-ahead mode
                        # (once per request; grants recur per step).
                        request.async_spec_granted = True
                        self._record_event(request, ev.SPEC_GRANT, None)
            req_index += 1

        # ---- 2. Waiting requests (new or resumed-from-preemption) ----
        # Don't admit new work in a step where we had to preempt.
        if not preempted:
            # A request that finished with a later batch still in
            # flight keeps its row of the worker's batch until that
            # batch retires: it counts against the rows.
            while (self.waiting and token_budget > 0
                   and len(self.running) + len(self._finished_pending_retire)
                   < self.max_num_seqs):
                request = (self.waiting[0] if self.qos is None
                           else self._qos_pick_waiting())

                if not self._lora_admittable(request):
                    # Admitting would need more distinct adapters than
                    # the runner has slots (reference: the scheduler's
                    # lora constraint); wait for a lora request to
                    # finish rather than crash the runner's slot pool.
                    break

                if request.num_prompt_tokens >= self.max_model_len:
                    # The prompt alone fills (or overflows) the context
                    # window: it could never produce a token. Reject it
                    # instead of admitting a request that can never finish.
                    logger.warning(
                        "Request %s prompt (%d tokens) exceeds "
                        "max_model_len (%d); ignoring.",
                        request.request_id, request.num_prompt_tokens,
                        self.max_model_len)
                    self._waiting_remove(request)
                    request.status = RequestStatus.FINISHED_IGNORED
                    self._free_request(request)
                    continue

                budgeted_mm = [m for m in (request.mm_inputs or ())
                               if m.offset >= 0]
                if budgeted_mm and not self.encoder_cache.has(
                        request.request_id):
                    n_enc = sum(m.num_tokens for m in budgeted_mm)
                    if n_enc > self.encoder_cache.budget:
                        logger.warning(
                            "Request %s needs %d encoder tokens; the "
                            "budget is %d; ignoring.",
                            request.request_id, n_enc,
                            self.encoder_cache.budget)
                        self._waiting_remove(request)
                        request.status = RequestStatus.FINISHED_IGNORED
                        self._free_request(request)
                        continue
                    if not self.encoder_cache.can_allocate(
                            request.request_id, n_enc):
                        break  # encoder budget full; wait
                    # NOTE: allocation is COMMITTED only at the popleft
                    # points below — a later admission failure (e.g. no
                    # KV pages) must not leave a still-waiting request
                    # holding budget, or a higher-priority arrival could
                    # deadlock the queue head against it.

                if self.tknp_size > 1 and request.tknp_rank is None:
                    self._assign_tknp_rank(request)

                num_computed_tokens = request.num_computed_tokens
                new_computed_blocks: Optional[KVCacheBlocks] = None
                state_restore = None
                state_only_admit = False
                num_tier_pages = 0
                if (num_computed_tokens == 0
                        and request.sampling_params.prompt_logprobs
                        is None):
                    # Fresh request: prefix-cache lookup. Skipped for
                    # prompt_logprobs requests — cached positions never
                    # run a forward, so their entries could not be
                    # scored (the reference likewise recomputes).
                    if self.state_cache is not None:
                        # Stateful models: the longest prefix with a
                        # live state snapshot (and, for hybrid models,
                        # its attention pages still cached) is a
                        # complete resume point — admit as a
                        # continuation at the boundary.
                        blocks, boundary, state_restore = \
                            self.state_cache.get_computed_state(
                                request, self._block_pools()[0])
                        if boundary:
                            num_computed_tokens = boundary
                            if blocks:
                                new_computed_blocks = KVCacheBlocks(
                                    blocks)
                            else:
                                # Pure-SSM models need no prefix pages;
                                # the boundary is marked computed just
                                # before allocation so allocate_slots
                                # covers the whole token range with
                                # fresh (content-free) pages.
                                state_only_admit = True
                    else:
                        new_computed_blocks, num_computed_tokens = \
                            self.kv_cache_manager.get_computed_blocks(
                                request)
                        if self.kv_tier is not None:
                            # Trailing pages of the hit live in a spill
                            # tier: their span counts as computed, but
                            # device pages must still be ALLOCATED for
                            # them (below) and a promote directive
                            # scatters the content back pre-forward.
                            num_tier_pages = \
                                self.kv_tier.pending_hit_count(
                                    request.request_id)
                    if request.num_cached_tokens < 0:
                        request.num_cached_tokens = num_computed_tokens

                # Disaggregated prefill: KV for part of the prompt may be
                # loadable from outside (reference: scheduler.py waiting
                # loop KVConnector.get_num_new_matched_tokens). External
                # pages are allocated now and filled by the worker-side
                # connector before the forward pass.
                num_external = 0
                load_async = False
                if (self.kv_connector is not None
                        and request.sampling_params.prompt_logprobs
                        is None):
                    # Externally-loaded positions never run a forward,
                    # so prompt_logprobs requests recompute locally
                    # (same reason as the prefix-cache bypass above).
                    num_external, load_async = \
                        self.kv_connector.get_num_new_matched_tokens(
                            request, num_computed_tokens)

                if load_async and num_external > 0:
                    # Async pull: allocate the external span now, then
                    # hold the request out of the queue until the worker
                    # reports the transfer landed (reference: scheduler
                    # WAITING_FOR_REMOTE_KVS + nixl start_load_kv). The
                    # local prefix hit is committed first so the pull
                    # only covers the missing pages.
                    new_blocks = self.kv_cache_manager.allocate_slots(
                        request, num_external, new_computed_blocks,
                        delay_caching=True)
                    if new_blocks is None:
                        break  # no room; retry next step
                    self._waiting_remove(request)
                    self._commit_encoder_budget(request)
                    request.status = RequestStatus.WAITING_FOR_REMOTE_KVS
                    self._record_event(request, ev.KV_PULL_WAIT,
                                       {"external_tokens": num_external})
                    request.num_computed_tokens = num_computed_tokens
                    request.num_external_computed_tokens = num_external
                    self.kv_connector.update_state_after_alloc(
                        request,
                        self.kv_cache_manager.get_block_ids(
                            request.request_id),
                        num_external)
                    # Monotonic: a wall-clock step (NTP, VM resume) must
                    # not mass-fire the sweep or the abandon backstop.
                    request.remote_kv_deadline = (
                        time.monotonic() + self.kv_pull_timeout_s
                        if self.kv_pull_timeout_s > 0 else None)
                    self.waiting_for_remote_kv[request.request_id] = request
                    continue

                num_new_tokens = (request.num_tokens - num_computed_tokens -
                                  num_external)
                if self.long_prefill_token_threshold > 0:
                    num_new_tokens = min(num_new_tokens,
                                         self.long_prefill_token_threshold)
                if num_new_tokens > token_budget:
                    if not self.enable_chunked_prefill:
                        break  # must fit in one step
                    num_new_tokens = token_budget
                if self.qos is not None and self.enable_chunked_prefill:
                    # DRR: the first chunk of the picked (max-deficit)
                    # tenant clips to its deficit — never below one
                    # token, so the selected tenant always progresses.
                    num_new_tokens = self.qos.admission_allowance(
                        self.qos.key_of(request), num_new_tokens)
                if (self.state_cache is not None
                        and self.enable_chunked_prefill):
                    num_new_tokens = self.state_cache.clip_grant(
                        num_computed_tokens, num_new_tokens)
                assert num_new_tokens > 0

                if state_only_admit:
                    request.num_computed_tokens = num_computed_tokens
                # Tier-hit pages need device pages allocated even
                # though their tokens count as computed (the content
                # scatters back pre-forward); the span rides the
                # allocation but never the token grant.
                tier_span = (num_tier_pages *
                             self.kv_cache_manager.block_size)
                new_blocks = self.kv_cache_manager.allocate_slots(
                    request, num_external + tier_span + num_new_tokens,
                    new_computed_blocks)
                if new_blocks is None:
                    if state_only_admit:
                        # Still WAITING: the next attempt re-runs the
                        # lookup (the snapshot may have been evicted by
                        # then, so the hit must not be sticky).
                        request.num_computed_tokens = 0
                    # Out of pages; retry next step. A fresh token-parallel
                    # request holding nothing un-pins from its rank so the
                    # next attempt re-picks by load (a full rank must not
                    # stall the queue head while others have room).
                    if (self.tknp_size > 1
                            and request.num_computed_tokens == 0
                            and not (new_computed_blocks
                                     and new_computed_blocks.blocks)):
                        self.kv_cache_manager.release_rank(request)
                    break

                self._waiting_remove(request)
                self._commit_encoder_budget(request)
                resumed = request.status == RequestStatus.PREEMPTED
                request.status = RequestStatus.RUNNING
                request.num_computed_tokens = num_computed_tokens
                if num_external:
                    # Externally-loaded tokens count as computed; the
                    # worker-side connector fills their pages before the
                    # step's forward.
                    self.kv_connector.update_state_after_alloc(
                        request,
                        self.kv_cache_manager.get_block_ids(
                            request.request_id),
                        num_external)
                    num_computed_tokens += num_external
                    request.num_computed_tokens = num_computed_tokens
                self.running.append(request)
                self._record_event(request,
                                   ev.RESUMED if resumed else ev.SCHEDULED,
                                   {"computed": num_computed_tokens,
                                    "granted": num_new_tokens})
                if self.kv_tier is not None and num_tier_pages:
                    # Commit the staged tier hit: the runner scatters
                    # the (already-verified, already-pinned) arrays
                    # into the first tier-span pages of this
                    # allocation before the forward.
                    hits = self.kv_tier.take_hits(request.request_id)
                    if hits:
                        from vllm_distributed_tpu.core.kv_tier import \
                            PromoteDirective
                        kv_promotes.append(PromoteDirective(
                            req_id=request.request_id,
                            page_ids=new_blocks.get_block_ids()
                            [:len(hits)],
                            keys=[h[0] for h in hits],
                            tiers=[h[1] for h in hits],
                            arrays=[(h[2], h[3]) for h in hits]))
                        self._record_event(
                            request, ev.KV_TIER_PROMOTE,
                            {"pages": len(hits),
                             "tiers": sorted({h[1] for h in hits})})
                if self.state_cache is not None:
                    # This grant rewrites the recurrence from
                    # `num_computed_tokens`; any uncommitted park of an
                    # older boundary no longer describes the row.
                    self.state_cache.abort_pending(request.request_id)
                    if state_restore is not None:
                        state_restores.append(state_restore)
                        # Hit accounting lives HERE (not in the lookup):
                        # a blocked queue head re-runs the lookup every
                        # step and must not inflate the hit rate.
                        self.state_cache.hits += 1
                        self.state_cache.resume_tokens_saved += \
                            num_computed_tokens

                num_scheduled_tokens[request.request_id] = num_new_tokens
                token_budget -= num_new_tokens
                if self.qos is not None:
                    self.qos.charge(self.qos.key_of(request),
                                    num_new_tokens)
                if self.state_cache is not None:
                    directive = self.state_cache.maybe_save(
                        request, num_computed_tokens + num_new_tokens)
                    if directive is not None:
                        state_saves.append(directive)
                if num_computed_tokens < request.num_prompt_tokens:
                    prefill_tokens += num_new_tokens
                else:
                    # Whole prompt already computed (e.g. remote-KV
                    # pull landed everything): this grant is decode.
                    decode_tokens += num_new_tokens
                    decode_reqs += 1
                if request.queued_at is not None:
                    # Once per request: a preempted request that is
                    # granted again has waited for pages, not in the
                    # queue.
                    self.queue_wait.observe(time.monotonic()
                                            - request.queued_at)
                    request.queued_at = None

                all_block_ids = self.kv_cache_manager.get_block_ids(
                    request.request_id)
                window_block_ids = (
                    self.kv_cache_manager.get_window_block_ids(
                        request.request_id)
                    if self.window_grouped else None)
                if resumed:
                    cached_reqs.req_ids.append(request.request_id)
                    cached_reqs.resumed_from_preemption.append(True)
                    cached_reqs.new_token_ids.append(
                        list(request.all_token_ids))
                    cached_reqs.new_block_ids.append(all_block_ids)
                    if self.window_grouped:
                        cached_reqs.new_window_block_ids.append(
                            window_block_ids)
                    cached_reqs.num_computed_tokens.append(
                        num_computed_tokens)
                else:
                    scheduled_new_reqs.append(
                        NewRequestData(
                            req_id=request.request_id,
                            prompt_token_ids=list(request.prompt_token_ids),
                            sampling_params=request.sampling_params,
                            block_ids=all_block_ids,
                            window_block_ids=window_block_ids,
                            num_computed_tokens=num_computed_tokens,
                            lora_request=request.lora_request,
                            pooling_params=request.pooling_params,
                            mm_inputs=request.mm_inputs,
                        ))
                if self.async_scheduling:
                    # Grant-time advance (see the running loop): the
                    # wire data above carries the pre-advance count.
                    request.num_computed_tokens += num_new_tokens

        self.num_scheduled_steps += 1
        total = sum(num_scheduled_tokens.values())
        if num_scheduled_tokens:
            self.last_step_prefill_tokens = prefill_tokens
            self.last_step_decode_tokens = decode_tokens
            self.step_tokens["prefill"].observe(prefill_tokens)
            self.step_tokens["decode"].observe(decode_tokens)
        tknp_alloc = None
        if self.tknp_size > 1:
            req_to_rank = {
                req_id: self.requests[req_id].tknp_rank
                for req_id in num_scheduled_tokens
            }
            tokens_per_rank = [0] * self.tknp_size
            for req_id, n in num_scheduled_tokens.items():
                tokens_per_rank[req_to_rank[req_id]] += n
            self.tknp_tokens_per_rank = tokens_per_rank
            tknp_alloc = TokenParallelAllocation(
                req_to_rank=req_to_rank,
                tokens_per_rank=tokens_per_rank)
        structured_masks = None
        if self.structured_manager is not None:
            masks = {
                req_id: self.structured_manager.mask_for(req_id)
                for req_id in num_scheduled_tokens
                if self.structured_manager.has(req_id)
            }
            structured_masks = masks or None
        output = SchedulerOutput(
            scheduled_new_reqs=scheduled_new_reqs,
            scheduled_cached_reqs=cached_reqs,
            num_scheduled_tokens=num_scheduled_tokens,
            total_num_scheduled_tokens=total,
            num_prefill_tokens=prefill_tokens,
            num_decode_reqs=decode_reqs,
            scheduled_spec_decode_tokens=scheduled_spec_tokens,
            finished_req_ids=self.finished_req_ids,
            multi_step=multi_step if num_scheduled_tokens else 1,
            token_parallel_allocation=tknp_alloc,
            structured_masks=structured_masks,
            async_scheduled=self.async_scheduling,
        )
        if self.state_cache is not None:
            saves = self._deferred_state_saves + state_saves
            if num_scheduled_tokens:
                # Aborted parks (their request restarted from scratch
                # or finished) must not reach the runner — the row no
                # longer holds the boundary's state. Owed journal
                # writes of already-committed async saves ride along
                # as persist_only directives.
                output.state_saves = ([
                    d for d in saves if self.state_cache.is_pending(d)
                ] + self.state_cache.take_persists()) or None
                output.state_restores = state_restores or None
                self._deferred_state_saves = []
            else:
                # The zero-token dispatch path does no device work by
                # contract; park copies wait for the next real batch.
                self._deferred_state_saves = saves
        if self.kv_tier is not None:
            # Demotes drain every step (evictions only happen inside
            # successful allocations, so a step carrying them always
            # dispatched work; the guard is defensive). Promotes were
            # staged by this step's admissions.
            output.kv_demotes = self.kv_tier.take_demotes(
                bool(num_scheduled_tokens))
            output.kv_promotes = kv_promotes or None
            if self.events_enabled and output.kv_demotes:
                # Page-level batch (no single owner request): rid="".
                self.events.record(
                    "", ev.KV_TIER_DEMOTE,
                    {"pages": len(output.kv_demotes.page_ids)})
        self.finished_req_ids = set()
        if self.kv_connector is not None:
            output.kv_connector_metadata = \
                self.kv_connector.build_connector_meta(output)
        if self.kv_event_publisher is not None:
            events = []
            for pool in self._block_pools():
                events.extend(pool.take_events())
            self.kv_event_publisher.publish(events)
        return output

    def _block_pools(self):
        mgr = self.kv_cache_manager
        if hasattr(mgr, "block_pool"):
            return [mgr.block_pool]
        return [m.block_pool for m in mgr.managers]

    def shutdown(self) -> None:
        if self.kv_event_publisher is not None:
            self.kv_event_publisher.shutdown()

    def _lora_admittable(self, request: Request) -> bool:
        """Distinct adapters among live requests + this one must fit the
        runner's slot count. ALL unfinished lora requests count —
        preempted ones still hold their worker slot until they finish
        (the runner releases at removal, not preemption)."""
        if request.lora_request is None:
            return True
        max_loras = self.config.lora_config.max_loras
        names = {r.lora_request["name"] for r in self.requests.values()
                 if r.lora_request is not None}
        names.add(request.lora_request["name"])
        return len(names) <= max_loras

    def _assign_tknp_rank(self, request: Request) -> None:
        """Assign a token-parallel rank: most free pages first, then
        lightest current token load (reference: TokenParallelScheduler
        .assign_ranks, scheduler.py:88 — round-robin made free-block and
        load aware)."""
        mgr: TokenParallelKVCacheManager = self.kv_cache_manager
        request.tknp_rank = max(
            range(self.tknp_size),
            key=lambda r: (mgr.free_blocks_on_rank(r),
                           -self.tknp_tokens_per_rank[r], -r))
        logger.debug("request %s -> token-parallel rank %d",
                     request.request_id, request.tknp_rank)

    def _select_preemption_victim(
            self, req_index: int,
            request: Request) -> tuple[Request, str]:
        """Pick a victim among requests not yet scheduled this step
        (self.running[req_index:]) and the preemption cause it will be
        attributed. Under the priority policy the lowest-priority
        *unscheduled* request is chosen — a request already granted
        tokens this step is never evicted mid-step. With QoS on, the
        quota policy is consulted first: the most-over-quota tenant's
        lowest-priority request goes before any in-quota victim
        (cause "quota"; cooldown hysteresis inside quota_victim keeps
        an oscillating tenant from livelocking in evict/resume cycles).

        Token parallelism: only same-rank victims free pages in the
        exhausted rank's pool partition, so other ranks' requests are
        never evicted for this allocation; with no same-rank candidate
        the request preempts itself."""
        candidates = [r for r in self.running[req_index:]
                      if r.request_id not in self.in_flight_req_ids]
        if not candidates:
            return request, "self"
        if self.tknp_size > 1:
            candidates = [r for r in candidates
                          if r.tknp_rank == request.tknp_rank]
            if not candidates:
                return request, "self"
        if self.qos is not None:
            victim = self.qos.quota_victim(candidates, self.qos.key_of,
                                           self.num_scheduled_steps)
            if victim is not None:
                return victim, "quota"
        if self.policy == "priority":
            return max(candidates,
                       key=lambda r: (r.priority, r.arrival_time)), \
                "capacity"
        return candidates[-1], "capacity"

    # ------------------------------------------------------------------
    # Per-tenant QoS hooks (no-ops when VDT_QOS=0: self.qos is None)
    # ------------------------------------------------------------------
    def _qos_held_by_tenant(self) -> dict[str, int]:
        """KV pages currently held per tenant bucket, across every live
        request (running, waiting-with-pages, remote-KV holds)."""
        held: dict[str, int] = {}
        for r in list(self.requests.values()):
            n = self._num_blocks_of(r.request_id)
            if n:
                k = self.qos.key_of(r)
                if k == qos_mod.CANARY_TENANT:
                    continue  # correctness probes hold no tenant quota
                held[k] = held.get(k, 0) + n
        return held

    def _qos_pick_waiting(self) -> Request:
        """The waiting request QoS admits next: the earliest queued
        request of the tenant pick_waiting_tenant chooses (largest
        deficit; over-quota tenants passed over under pool pressure).
        Queue order within a tenant is untouched, so priority/arrival
        still decide among a tenant's own requests. The per-tenant
        queue view is built ONCE per step and popped incrementally by
        _waiting_remove — rescanning the whole deque on every
        admission iteration would make the loop O(waiting^2)."""
        if self._qos_waiting_by_tenant is None:
            by_tenant: dict[str, deque] = {}
            for r in self.waiting:
                by_tenant.setdefault(self.qos.key_of(r),
                                     deque()).append(r)
            self._qos_waiting_by_tenant = by_tenant
        keys = [k for k, q in self._qos_waiting_by_tenant.items() if q]
        best = self.qos.pick_waiting_tenant(keys,
                                            self.kv_cache_manager.usage)
        return self._qos_waiting_by_tenant[best][0]

    def _waiting_remove(self, request: Request) -> None:
        """Remove an admitted/rejected request from the waiting queue.
        QoS off always operates on the queue head (the pre-QoS popleft);
        QoS may have picked a mid-queue request of another tenant and
        also owes its per-tenant queue view the matching pop."""
        if self.waiting and self.waiting[0] is request:
            self.waiting.popleft()
        else:
            self.waiting.remove(request)
        if self.qos is not None and self._qos_waiting_by_tenant:
            q = self._qos_waiting_by_tenant.get(self.qos.key_of(request))
            if q and q[0] is request:
                q.popleft()
            elif q is not None:
                try:
                    q.remove(request)
                except ValueError:
                    pass

    def _preempt(self, request: Request, cause: str = "capacity") -> None:
        self.running.remove(request)
        if self.state_cache is not None:
            # Park the state instead of discarding: when the eviction
            # boundary is snapshot-aligned the resume restores it and
            # re-prefills nothing; otherwise the latest periodic
            # snapshot bounds the re-prefill to the tail since the
            # last checkpoint. (The copy rides the next non-empty
            # output; the parked request runs no tokens until resume,
            # so its rows stay exactly at the parked state.)
            directive = self.state_cache.maybe_save(
                request, request.num_computed_tokens)
            if directive is not None:
                self._deferred_state_saves.append(directive)
        self.kv_cache_manager.free(request)
        request.status = RequestStatus.PREEMPTED
        request.num_computed_tokens = 0
        request.spec_token_ids = []
        request.num_preemptions += 1
        self.num_preemptions += 1
        self.preemption_causes[cause] = \
            self.preemption_causes.get(cause, 0) + 1
        if self.qos is not None:
            # vdt:tenant_preemptions_total counts EVERY eviction the
            # tenant suffered, whatever the cause — operators read it
            # next to kv_blocks to see who is being squeezed.
            self.qos.note_preemption(self.qos.key_of(request))
        self._record_event(request, ev.PREEMPTED,
                           {"num_preemptions": request.num_preemptions,
                            "cause": cause})
        if self.policy == "priority":
            self._insert_by_priority(request)
        else:
            self.waiting.appendleft(request)

    # ------------------------------------------------------------------
    # Post-step accounting
    # ------------------------------------------------------------------
    def update_from_output(
        self,
        scheduler_output: SchedulerOutput,
        runner_output: ModelRunnerOutput,
    ) -> list[EngineCoreOutput]:
        """Fold sampled tokens back into request state; detect token-level
        stops; free finished requests. Reference: scheduler.py:1012."""
        num_scheduled = scheduler_output.num_scheduled_tokens
        sampled_by_req: dict[str, list[int]] = {
            req_id: tokens
            for req_id, tokens in zip(runner_output.req_ids,
                                      runner_output.sampled_token_ids)
        }
        logprobs_by_req: dict[str, list[dict[int, float]]] = {}
        if runner_output.logprobs is not None:
            logprobs_by_req = {
                req_id: lps
                for req_id, lps in zip(runner_output.req_ids,
                                       runner_output.logprobs)
            }
        spec_by_req: dict[str, list[int]] = {}
        if runner_output.spec_token_ids is not None:
            spec_by_req = {
                req_id: spec
                for req_id, spec in zip(runner_output.req_ids,
                                        runner_output.spec_token_ids)
            }

        self._update_kv_transfer_state(runner_output)

        # External finishes (aborts, stop strings) that arrived while
        # the request sat in a dispatched batch: the batch has retired
        # (the engine core clears in_flight before calling here), so the
        # normal finish path is safe now.
        if self._deferred_finishes:
            ready = [req_id for req_id in self._deferred_finishes
                     if req_id not in self.in_flight_req_ids]
            for req_id in ready:
                self.finish_requests(req_id,
                                     self._deferred_finishes.pop(req_id))

        # Async scheduling: requests that FINISHED at reconcile time
        # while a later speculative batch was still writing their pages.
        # That batch has now retired (the engine core unmarks before
        # calling here), so the parked pages can finally be freed — the
        # free also queues the worker-side row cleanup.
        if self._finished_pending_retire:
            for req_id in [r for r in self._finished_pending_retire
                           if r not in self.in_flight_req_ids]:
                self._free_request(
                    self._finished_pending_retire.pop(req_id))

        pooled_map = runner_output.pooled or {}
        plp_map = runner_output.prompt_logprobs or {}
        outputs: list[EngineCoreOutput] = []
        finished: list[Request] = []
        for request in self.running:
            req_id = request.request_id
            if req_id not in num_scheduled:
                continue
            scheduled = num_scheduled[req_id]
            if not request.prompt_lp_delivered:
                # Buffered until the first emitted output (mid-prompt
                # chunks produce no EngineCoreOutput); dict-keyed so a
                # preemption re-run overwrites, not duplicates. Entries
                # scored by a preempt-resume AFTER delivery are dropped
                # (the runner also stops scoring completed prompts).
                for entry, d in plp_map.get(req_id, ()):
                    request.prompt_lp_entries[entry] = d
            if req_id in pooled_map:
                # Embedding request: the prompt finished this step; the
                # pooled hidden state IS the result (no sampling).
                if not scheduler_output.async_scheduled:
                    request.num_computed_tokens += scheduled
                request.status = RequestStatus.FINISHED_STOPPED
                finished.append(request)
                outputs.append(EngineCoreOutput(
                    req_id=req_id, new_token_ids=[],
                    finish_reason=request.get_finished_reason(),
                    num_cached_tokens=max(request.num_cached_tokens, 0),
                    pooled=pooled_map[req_id],
                    events=self._take_events(request)))
                continue
            if scheduler_output.multi_step > 1:
                # The worker computed KV for one token per fused step.
                scheduled = scheduler_output.multi_step
            generated = sampled_by_req.get(req_id, [])

            # Speculative verification: some scheduled draft tokens may
            # have been rejected (reference: scheduler.py:1012 spec path).
            num_spec = len(
                scheduler_output.scheduled_spec_decode_tokens.get(req_id, []))
            if num_spec > 0:
                num_rejected = num_spec + 1 - len(generated)
                scheduled -= max(num_rejected, 0)
            if not scheduler_output.async_scheduled:
                # Async batches advanced num_computed at grant time
                # (spec decode is config-gated off there, so the
                # rejection adjustment never applies to them).
                request.num_computed_tokens += scheduled
            request.spec_token_ids = spec_by_req.get(req_id, [])

            if not generated:
                continue  # partial prefill chunk; nothing sampled yet

            new_token_ids: list[int] = []
            stop_reason: Optional[int | str] = None
            for token_id in generated:
                request.append_output_token_ids(token_id)
                new_token_ids.append(token_id)
                stopped, stop_reason = self._check_stop(request, token_id)
                if stopped:
                    # Discard any extra accepted spec tokens past the stop.
                    request.spec_token_ids = []
                    break

            if self.structured_manager is not None and new_token_ids:
                # Advance the grammar with exactly the kept tokens (a
                # stop may have trimmed trailing accepted drafts).
                self.structured_manager.advance(req_id, new_token_ids)

            if request.is_finished:
                finished.append(request)
            # Logprobs trimmed to the tokens actually kept after stop
            # handling (a stop may discard trailing accepted spec tokens).
            logprobs = logprobs_by_req.get(req_id)
            if logprobs is not None:
                logprobs = logprobs[:len(new_token_ids)]
            prompt_lps = None
            if (request.sampling_params.prompt_logprobs is not None
                    and not request.prompt_lp_delivered):
                n_prompt = len(request.prompt_token_ids)
                prompt_lps = [None] + [
                    request.prompt_lp_entries.get(i)
                    for i in range(1, n_prompt)
                ]
                request.prompt_lp_delivered = True
                request.prompt_lp_entries.clear()
            outputs.append(
                EngineCoreOutput(
                    req_id=req_id,
                    new_token_ids=new_token_ids,
                    finish_reason=request.get_finished_reason(),
                    stop_reason=stop_reason,
                    num_cached_tokens=max(request.num_cached_tokens, 0),
                    logprobs=logprobs,
                    prompt_logprobs=prompt_lps,
                    events=self._take_events(request),
                ))

        # Commit this step's state snapshots now that its tokens have
        # reconciled: a snapshot enters the lookup index only when the
        # request really committed tokens through its boundary (an
        # async run-ahead that stopped short is discarded). Runs before
        # the finished frees below so a request that finished AT the
        # boundary still commits — its snapshot is the next turn's
        # resume point.
        if self.state_cache is not None and scheduler_output.state_saves:
            for directive in scheduler_output.state_saves:
                self.state_cache.commit_save(
                    directive, self.requests.get(directive.req_id))

        for request in finished:
            self.running.remove(request)
            if request.request_id in self.in_flight_req_ids:
                # A later (speculative) batch is still writing this
                # request's pages: the finish is emitted to the client
                # now, but the free waits until that batch retires (see
                # the pending-retire sweep above). Its discarded sample
                # is dropped there because the request left `running`.
                self._finished_pending_retire[request.request_id] = request
                continue
            params = self._free_request(request)
            if params is not None:
                # Producer handoff coordinates ride on the final output
                # (reference: EngineCoreOutput.kv_transfer_params) so the
                # client/proxy can route the decode-side request.
                for out in outputs:
                    if out.req_id == request.request_id:
                        out.kv_transfer_params = params
                        break
        return outputs

    def _update_kv_transfer_state(
            self, runner_output: ModelRunnerOutput) -> None:
        """Fold the worker's async-transfer notifications back in:
        pulled-in requests rejoin the waiting queue with their external
        span marked computed; pulled-from producer pages are freed
        (reference: scheduler.py update_from_output finished_recving/
        finished_sending handling)."""
        for req_id in (runner_output.finished_recving or ()):
            cancelled = self.cancelled_remote_kv.pop(req_id, None)
            if cancelled is not None:
                self.kv_cache_manager.free(cancelled)
                self.kv_cache_manager.free_block_hashes(cancelled)
                continue
            request = self.waiting_for_remote_kv.pop(req_id, None)
            if request is None:
                continue
            request.num_computed_tokens += \
                request.num_external_computed_tokens
            # Externally-loaded tokens were never computed locally:
            # count them as cached for stats/billing parity.
            request.num_cached_tokens = (
                max(request.num_cached_tokens, 0) +
                request.num_external_computed_tokens)
            request.num_external_computed_tokens = 0
            self._record_event(request, ev.KV_PULL_DONE, None)
            self._requeue_after_hold(request)
        for req_id in (runner_output.failed_recving or ()):
            cancelled = self.cancelled_remote_kv.pop(req_id, None)
            if cancelled is not None:
                self.kv_cache_manager.free(cancelled)
                self.kv_cache_manager.free_block_hashes(cancelled)
                continue
            request = self.waiting_for_remote_kv.pop(req_id, None)
            if request is None:
                continue
            # The span's pages were allocated but never written. Free
            # everything and rejoin the queue as a fresh request
            # (retrying the pull or recomputing locally — see
            # _handle_failed_pull). Freeing matters for ordering —
            # keeping the unwritten span pages while re-running the
            # prefix lookup could append later-cached prefix blocks
            # AFTER them, corrupting the request's page order.
            self.kv_cache_manager.free(request)
            self._handle_failed_pull(request, pull_resolved=True,
                                     reason="worker reported pull failure")
        for req_id in (runner_output.finished_sending or ()):
            request = self.reqs_pending_send.pop(req_id, None)
            if request is not None:
                self.kv_cache_manager.free(request)
                self.kv_cache_manager.free_block_hashes(request)
        # Backstop expiry for deferred frees nobody pulled: the worker's
        # serve registration expires first (send_timeout_s) and reports
        # finished_sending; this 2x backstop only fires if the worker
        # poll itself is wedged, so pages still can't leak forever.
        if self.reqs_pending_send:
            now = time.monotonic()
            timeout = 2 * (self.config.kv_transfer_config
                           .kv_connector_extra_config
                           .get("send_timeout_s", 300.0)
                           if self.config.kv_transfer_config else 300.0)
            for req_id in list(self.reqs_pending_send):
                request = self.reqs_pending_send[req_id]
                deadline = getattr(request, "_send_deadline", None)
                if deadline is None:
                    request._send_deadline = now + float(timeout)
                elif now > deadline:
                    logger.warning(
                        "deferred KV pages for %s expired unpulled after "
                        "%.0fs; freeing", req_id, float(timeout))
                    del self.reqs_pending_send[req_id]
                    self.kv_cache_manager.free(request)
                    self.kv_cache_manager.free_block_hashes(request)
        self._sweep_remote_kv_holds()

    # ------------------------------------------------------------------
    # Remote-KV watchdog (fault-tolerance layer)
    # ------------------------------------------------------------------
    def _sweep_remote_kv_holds(self) -> None:
        """Per-step deadline sweep over WAITING_FOR_REMOTE_KVS: the
        reference scheduler trusts the worker to eventually report every
        pull, so a dropped transfer (or a connector whose admission-time
        producer resolution failed after alloc) parks the request
        forever. The sweep fails such holds through the same requeue
        path as a worker-reported pull failure."""
        # Connector-reported admission failures (e.g. P2P producer
        # resolution failed after alloc): no pull was ever staged, so
        # freeing the pages immediately is unconditionally safe.
        if self.kv_connector is not None and self.waiting_for_remote_kv:
            for req_id in self.kv_connector.take_alloc_failures():
                request = self.waiting_for_remote_kv.pop(req_id, None)
                if request is None:
                    continue
                self.kv_cache_manager.free(request)
                self._handle_failed_pull(
                    request, pull_resolved=True,
                    reason="connector admission failure")
        # Deadline sweep. A swept hold's pull may still be in flight at
        # the worker, so its pages are parked, not freed.
        if self.waiting_for_remote_kv and self.kv_pull_timeout_s > 0:
            now = time.monotonic()
            for req_id in list(self.waiting_for_remote_kv):
                request = self.waiting_for_remote_kv[req_id]
                deadline = request.remote_kv_deadline
                if deadline is None or now <= deadline:
                    continue
                del self.waiting_for_remote_kv[req_id]
                self.watchdog_timeouts += 1
                self._record_event(request, ev.KV_PULL_TIMEOUT,
                                   {"timeout_s": self.kv_pull_timeout_s})
                self._park_timed_out_pages(request)
                self._handle_failed_pull(
                    request, pull_resolved=False,
                    reason=f"no pull completion within "
                           f"{self.kv_pull_timeout_s:.1f}s")
        # Backstop: parked pages whose worker report never arrived are
        # reclaimed once the abandon window expires. Safe against a
        # late-but-live transfer because the sweep/abort issued a
        # cancel_pull: the worker discards (never applies) a completed
        # pull for a cancelled id, so after the cancel lands no write
        # into these pages can happen (see DCNPullConnector.cancel_pull).
        if self.cancelled_remote_kv:
            now = time.monotonic()
            for req_id in list(self.cancelled_remote_kv):
                holder = self.cancelled_remote_kv[req_id]
                expires = getattr(holder, "expires_at", None)
                if expires is not None and now > expires:
                    logger.warning(
                        "parked pages for timed-out pull %s expired "
                        "unreported; reclaiming", req_id)
                    del self.cancelled_remote_kv[req_id]
                    self.kv_cache_manager.free(holder)
                    self.kv_cache_manager.free_block_hashes(holder)

    def _park_timed_out_pages(self, request: Request) -> None:
        """The timed-out hold's pull may still be in flight; a late
        apply writes the pages allocated at admission, so they must stay
        out of the pool until the worker reports. Ownership moves to a
        tombstone registered in cancelled_remote_kv — the same
        late-report protocol aborted holds use."""
        tomb = _PageTombstone(
            request_id=f"{request.request_id}#wd{self.watchdog_timeouts}",
            tknp_rank=request.tknp_rank,
            expires_at=time.monotonic() + self.kv_pull_abandon_timeout_s)
        self.kv_cache_manager.transfer_ownership(request.request_id,
                                                 tomb.request_id)
        if self.kv_connector is not None:
            # Tell the worker to DISCARD (never apply) this pull if it
            # completes later: after the cancel lands, nothing can write
            # the parked pages, so the abandon backstop's free is safe.
            self.kv_connector.cancel_pull(request.request_id)
        self.cancelled_remote_kv[request.request_id] = tomb

    def _handle_failed_pull(self, request: Request, *, pull_resolved: bool,
                            reason: str) -> None:
        """Requeue after a failed/timed-out pull. Degradation order:
        retry the remote pull (bounded, and only when the connector can
        cleanly re-stage one — ``pull_resolved`` says no transfer for
        this id can still be in flight), then local prefill recompute."""
        self.kv_pull_failures += 1
        request.num_computed_tokens = 0
        request.num_external_computed_tokens = 0
        request.remote_kv_deadline = None
        retry = (request.kv_transfer_params is not None
                 and request.num_kv_pull_retries < self.kv_pull_max_retries
                 and self.kv_connector is not None
                 and self.kv_connector.reset_for_retry(request,
                                                       pull_resolved))
        if retry:
            request.num_kv_pull_retries += 1
            self.kv_pull_retries += 1
            self._record_event(request, ev.KV_PULL_RETRY,
                               {"attempt": request.num_kv_pull_retries,
                                "reason": reason}, force=True)
            logger.warning(
                "KV pull for %s failed (%s); retrying pull %d/%d",
                request.request_id, reason, request.num_kv_pull_retries,
                self.kv_pull_max_retries)
        else:
            logger.warning(
                "KV pull for %s failed (%s); degrading to local prefill "
                "recompute", request.request_id, reason)
            request.kv_transfer_params = None
            self._record_event(request, ev.KV_PULL_LOCAL,
                               {"reason": reason}, force=True)
        self._requeue_after_hold(request)

    def _requeue_after_hold(self, request: Request) -> None:
        request.status = RequestStatus.WAITING
        if self.policy == "priority":
            self._insert_by_priority(request)
        else:
            self.waiting.appendleft(request)

    def _check_stop(
            self, request: Request,
            last_token_id: int) -> tuple[bool, Optional[int | str]]:
        sp = request.sampling_params
        if (request.num_tokens >= self.max_model_len
                or request.num_output_tokens >= sp.max_tokens):
            request.status = RequestStatus.FINISHED_LENGTH_CAPPED
            return True, None
        if request.num_output_tokens < sp.min_tokens:
            return False, None
        if last_token_id in sp.all_stop_token_ids:
            request.status = RequestStatus.FINISHED_STOPPED
            if last_token_id != request.eos_token_id or sp.ignore_eos:
                request.stop_reason = last_token_id
            return True, request.stop_reason
        return False, None

    # ------------------------------------------------------------------
    def _kv_cache_telemetry(self) -> dict:
        """Paged-KV introspection (get_stats / /debug/kv_cache /
        SIGUSR1): pool occupancy, tombstone-parked pages, internal
        fragmentation and the windowed prefix-cache hit rate. All reads
        of GIL-atomic containers — safe from the stats RPC while the
        core thread mutates."""
        kv = self.kv_cache_manager.kv_telemetry()
        total = kv.get("total_blocks", 0) or 1
        free = kv.get("free_blocks", 0)
        # Pages parked under watchdog/abort tombstones: allocated, but
        # owned by no live request until the worker reports (or the
        # abandon backstop reclaims them).
        tombstoned = 0
        for holder in list(self.cancelled_remote_kv.values()):
            tombstoned += self._num_blocks_of(holder.request_id) or 0
        # Internal fragmentation: the fraction of request-held page
        # slots not covered by computed tokens (partially-filled tail
        # pages + lookahead). High steady-state fragmentation says the
        # page size is too coarse for the traffic.
        held = kv.get("held_blocks", 0)
        live_tokens = sum(r.num_computed_tokens
                          for r in list(self.requests.values()))
        frag = 0.0
        if held > 0:
            page = self.config.cache_config.block_size
            covered = min(live_tokens / (held * page), 1.0)
            frag = 1.0 - covered
        wq = kv.get("window_queries", 0)
        return {
            "total_blocks": total,
            "free_blocks": free,
            "used_blocks": total - free,
            "held_blocks": held,
            "tombstoned_blocks": tombstoned,
            "cached_blocks": kv.get("cached_blocks", 0),
            "cached_free_blocks": kv.get("cached_free_blocks", 0),
            "fragmentation_frac": round(frag, 6),
            # Raw window tallies ship alongside the ratio so the DP
            # merge can compute the EXACT fleet hit rate from sums
            # instead of diluting it with idle replicas' zeros.
            "window_queries": wq,
            "window_hits": kv.get("window_hits", 0),
            "window_hit_rate": (kv.get("window_hits", 0) / wq
                                if wq else 0.0),
            "preemption_causes": dict(self.preemption_causes),
        }

    def get_stats(self) -> dict[str, float]:
        stats = {
            "num_running_reqs": len(self.running),
            "num_waiting_reqs": len(self.waiting),
            "kv_cache_usage": self.kv_cache_manager.usage,
            "kv_cache": self._kv_cache_telemetry(),
            "num_preemptions": self.num_preemptions,
            "num_async_spec_grants": self.num_async_spec_grants,
            "watchdog_timeouts": self.watchdog_timeouts,
            "kv_pull_retries": self.kv_pull_retries,
            "kv_pull_failures": self.kv_pull_failures,
            "last_step_prefill_tokens": self.last_step_prefill_tokens,
            "last_step_decode_tokens": self.last_step_decode_tokens,
            "step_tokens": {kind: h.to_dict()
                            for kind, h in self.step_tokens.items()},
            "request_queue_seconds": self.queue_wait.to_dict(),
            **self.kv_cache_manager.make_prefix_cache_stats(),
        }
        if self.state_cache is not None:
            stats.update(self.state_cache.stats())
        if self.window_grouped:
            stats.update(self.kv_cache_manager.group_stats())
        if self.kv_tier is not None:
            # Nested tier dict ({pages,bytes,demotions,promotions,
            # misses} by tier + promotion histogram + the router's
            # transition feed) — merged per leaf in dp_client, never
            # by the flat numeric-sum loop.
            stats["kv_tier"] = self.kv_tier.stats()
        if self.qos is not None:
            # {tenant: {granted_tokens, kv_blocks, preemptions}} — flat
            # numeric leaves per tenant so the DP aggregation can sum
            # them per label (vdt:tenant_* families).
            stats["tenants"] = self.qos.stats(self._qos_held_by_tenant())
        if self.tknp_size > 1:
            for r, n in enumerate(self.tknp_tokens_per_rank):
                stats[f"tknp_tokens_rank{r}"] = n
                stats[f"tknp_free_blocks_rank{r}"] = \
                    self.kv_cache_manager.free_blocks_on_rank(r)
        return stats

    def _num_blocks_of(self, req_id: str) -> Optional[int]:
        try:
            if req_id in getattr(self.kv_cache_manager,
                                 "req_to_blocks", {}):
                return len(self.kv_cache_manager.get_block_ids(req_id))
            mgrs = getattr(self.kv_cache_manager, "managers", None)
            if mgrs is not None:  # token-parallel: per-rank managers
                for m in mgrs:
                    if req_id in getattr(m, "req_to_blocks", {}):
                        return len(m.get_block_ids(req_id))
        except Exception:  # noqa: BLE001 - debug surface, never raise
            pass
        return None

    def get_debug_state(self) -> dict:
        """Live scheduler introspection for the /debug endpoints and the
        SIGUSR1 dump: every tracked request with its status, progress,
        page footprint and in-flight refcount, plus queue/hold summary.
        Read-only and cheap — safe to call while requests are in
        flight. On the in-proc/background-thread paths this runs on the
        CALLER's thread while the core thread mutates the containers,
        so take C-level (GIL-atomic) list/dict snapshots before any
        Python-level iteration — iterating the live dict/deque raises
        "changed size during iteration" mid-step."""
        waiting = list(self.waiting)
        running = list(self.running)
        reqs = []
        for request in list(self.requests.values()):
            reqs.append({
                "request_id": request.request_id,
                "status": request.status.name,
                "priority": request.priority,
                "tenant": request.tenant,
                "num_prompt_tokens": request.num_prompt_tokens,
                "num_output_tokens": request.num_output_tokens,
                "num_computed_tokens": request.num_computed_tokens,
                "num_cached_tokens": max(request.num_cached_tokens, 0),
                "num_preemptions": request.num_preemptions,
                "num_kv_pull_retries": request.num_kv_pull_retries,
                "inflight_refcount":
                    self.in_flight_req_ids.get(request.request_id, 0),
                "kv_blocks": self._num_blocks_of(request.request_id),
                "tknp_rank": request.tknp_rank,
            })
        return {
            "requests": reqs,
            "qos": (self.qos.debug() if self.qos is not None else None),
            "num_waiting": len(waiting),
            "num_running": len(running),
            "waiting_req_ids": [r.request_id for r in waiting],
            "running_req_ids": [r.request_id for r in running],
            "waiting_for_remote_kvs":
                list(self.waiting_for_remote_kv),
            "reqs_pending_send": list(self.reqs_pending_send),
            "cancelled_remote_kv": list(self.cancelled_remote_kv),
            "finished_pending_retire":
                list(self._finished_pending_retire),
            "deferred_finishes": list(self._deferred_finishes),
            "kv_cache": self._kv_cache_telemetry(),
            "kv_cache_usage": self.kv_cache_manager.usage,
            "num_preemptions": self.num_preemptions,
            "last_step_prefill_tokens": self.last_step_prefill_tokens,
            "last_step_decode_tokens": self.last_step_decode_tokens,
        }
