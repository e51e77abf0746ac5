"""Engine core: the scheduler+executor busy loop.

Reference: vllm/v1/engine/core.py:55 (``EngineCore``: step:223,
_initialize_kv_caches:133; the multiprocess EngineCoreProc/DPEngineCoreProc
variants layer transport on top — here the in-process core comes first and
the ZMQ front-ends reuse it unchanged, mirroring InprocClient).

The batch queue (reference: core.py:242 ``step_with_batch_queue``)
holds the batches that are on the device, for two overlap modes:

* **Pipeline parallelism** (depth = pipeline_parallel_size,
  ``step_with_batch_queue``): up to one scheduler output per stage is
  dispatched before blocking on the oldest, so stage p of batch i+1
  executes under stage p+1 of batch i; in-flight requests are skipped
  by the scheduler. A call dispatches or retires, never both.
* **The pipelined step** (non-PP, depth 2, ``_step_pipelined``; what
  ``vdt serve`` runs, ``SchedulerConfig.async_scheduling``; reference:
  the V1 --async-scheduling path): ONE call schedules, prepares and
  launches step N+1 and then fetches and reconciles step N, so the
  forward of N+1 is in the device's queue before the host blocks on N.
  The scheduler grants N+1 by advancing each running decode request by
  one speculative position; the runner's forward reads the input token
  the host does not know yet from the device-resident mirror the
  sampler of N wrote, and ``update_from_output`` reconciles when the
  sampled tokens land (stop/EOS detection lags one step).

On TPU the overlap itself comes from JAX async dispatch — the runner's
dispatch half enqueues programs without blocking; the queue's job is to
keep the host from blocking and the scheduler's grant state coherent.

What the queue tells the timeline (metrics/stats.py ``StepPhases``) is
the HOST's bookkeeping only: ``forward_queued`` says that a forward was
issued behind the one being fetched, not that the chip still had work,
so ``host_gap`` reads 0 on every pipelined step. Whether the chip had
run dry when a forward was issued, whether a fetch waited, and from
these how long the chip took for each step, the runner asks of the
device where the work happens (``forward_issue`` and ``fetch``:
``jax.Array.is_ready()``, which does not block).
"""

import os
import threading
import time
from collections import deque
from typing import Optional

from vllm_distributed_tpu.config import EngineConfig
from vllm_distributed_tpu.core.sched.scheduler import (EngineCoreOutput,
                                                       Scheduler)
from vllm_distributed_tpu.executor import Executor
from vllm_distributed_tpu.logger import init_logger
from vllm_distributed_tpu.metrics import events as ev
from vllm_distributed_tpu.metrics.stats import StepPhases
from vllm_distributed_tpu.request import (EngineCoreRequest, Request,
                                          RequestStatus)
from vllm_distributed_tpu.utils import fault_injection

logger = init_logger(__name__)


def _kind(scheduler_output) -> str:
    """StepPhases' kind of a batch: ``mixed`` with a prefill token."""
    return "mixed" if scheduler_output.num_prefill_tokens else "decode"


class EngineCore:

    def __init__(self, config: EngineConfig,
                 executor_class: Optional[type] = None) -> None:
        self.config = config
        # True when the most recent step() ran device work; busy loops
        # pace themselves when steps degenerate to host-only polls
        # (async KV transfers in flight, requests held on a pull).
        self.last_step_scheduled = False
        # Transport telemetry (metrics/telemetry.py): ONE recorder per
        # engine core, installed for the construction window so every
        # connector / message queue / runner built below captures it.
        # In-process DP replicas therefore record into disjoint
        # recorders and the DP stats merge can sum per label exactly.
        from vllm_distributed_tpu.metrics import telemetry
        self.transport = telemetry.TransportRecorder()
        restore = telemetry.install_recorder(self.transport)
        try:
            executor_class = executor_class or Executor.get_class(config)
            self.executor = executor_class(config)

            num_pages = self._initialize_kv_caches()
            config.cache_config.num_gpu_blocks = num_pages
            # Scheduler-side KV connector (disaggregated prefill;
            # reference: core.py constructs the connector beside the
            # scheduler).
            from vllm_distributed_tpu.distributed.kv_transfer import (
                KVConnectorRole, create_kv_connector)
            kv_connector = create_kv_connector(config,
                                               KVConnectorRole.SCHEDULER)
            self.scheduler = Scheduler(config, num_blocks=num_pages,
                                       kv_connector=kv_connector)
            if self.scheduler.state_cache is not None:
                # The scheduler never touches device arrays; hand it the
                # runner's per-slot pool bytes so vdt:ssm_state_bytes_held
                # reports real HBM. A runner without a pool (executor
                # variants the gate excludes) leaves it at 0.
                runner = getattr(getattr(self.executor, "worker", None),
                                 "model_runner", None)
                if runner is not None:
                    self.scheduler.state_cache.bytes_per_slot = getattr(
                        runner, "state_pool_slot_bytes", lambda: 0)()
                    self.scheduler.state_cache.journal_fingerprint = \
                        getattr(runner, "_state_fingerprint",
                                lambda: b"")()
            if self.scheduler.kv_tier is not None:
                # Hierarchical KV tiering: the runner executes the
                # device legs against the scheduler's (in-proc) tier
                # manager, and the manager validates disk spill files
                # against this model's wire-layout page shapes before
                # admitting a tier hit. An executor variant without a
                # reachable flat runner cannot run the directives —
                # drop the tier (byte-identical untiered behavior).
                runner = getattr(getattr(self.executor, "worker", None),
                                 "model_runner", None)
                if runner is not None and hasattr(runner, "kv_caches"):
                    from vllm_distributed_tpu.distributed.kv_transfer \
                        import page_io
                    runner.kv_tier = self.scheduler.kv_tier
                    self.scheduler.kv_tier.wire_shapes = \
                        page_io.wire_page_shapes(runner)
                else:
                    logger.info("KV tiering: no flat runner reachable; "
                                "running untiered")
                    self.scheduler.kv_tier = None
                    self.scheduler.kv_cache_manager.tier = None
                    for pool in self.scheduler._block_pools():
                        pool.on_evict = None
        finally:
            restore()
        # Batch queue: in-flight (scheduler_output, handle) pairs,
        # newest first. Depth = max(pp, 2): the stage count under
        # pipeline parallelism (a deeper queue only adds latency once
        # every stage has work), 2 for async scheduling (one batch
        # executing while the next is scheduled/dispatched).
        pp = config.parallel_config.pipeline_parallel_size
        self.async_scheduling = config.scheduler_config.async_scheduling
        self.batch_queue_size = (max(pp, 2)
                                 if pp > 1 or self.async_scheduling else 1)
        self.batch_queue: Optional[deque] = (
            deque(maxlen=self.batch_queue_size)
            if self.batch_queue_size > 1 else None)
        # Peak in-flight depth (tests/metrics: proves overlap happened).
        self.max_concurrent_batches = 0
        # Overlap observability: dispatches issued while another batch
        # was already in flight, and (pipeline parallelism's queue) the
        # host gap between a wait_model return and the next dispatch.
        self.steps_dispatched = 0
        self.steps_overlapped = 0
        self._last_wait_done: Optional[float] = None
        # Step phases and spans (metrics/stats.py StepPhases): where the
        # time of this thread goes, as vdt:step_phase_seconds{phase} and
        # its companions on /metrics and as vdt.* spans in a capture of
        # the profile RPC. Always on. The model runner times its own
        # part of the step into the same object. The synchronous and
        # the pipelined step share one timeline; only pipeline
        # parallelism's queue of ``pp`` batches has none.
        self.phases = StepPhases(sync=pp <= 1)
        runner = getattr(getattr(self.executor, "worker", None),
                         "model_runner", None)
        if hasattr(runner, "phases"):
            runner.phases = self.phases
        # Engine-level lifecycle events (batch dispatch/retire); the
        # scheduler keeps its own recorder for request transitions.
        self.events = ev.EventRecorder()
        # Hardened on-demand profiler capture (the profile RPC):
        # exactly one capture at a time, auto-named trace dirs, and a
        # monotonic deadline (VDT_PROFILE_MAX_S) after which the step
        # loop force-stops an unstopped trace — a wedged xprof client
        # (perf.capture_stall drill) can never wedge serving.
        # start_trace and stop_trace take seconds, so the front end runs
        # profile() on a thread of its own: the lock keeps a second
        # start, and the step loop's deadline sweep, out of a capture
        # that is starting or stopping.
        self._profile_lock = threading.Lock()
        self._profile_dir: Optional[str] = None
        self._profile_deadline = 0.0
        self._profile_seq = 0
        self._profile_stalled = False
        self._profile_stop_failures = 0
        # Structured output: the grammar layer needs a token-bytes table
        # (a tokenizer load + per-token decode sweep). Prefetch it off
        # the busy loop so the FIRST structured request doesn't stall
        # every in-flight stream for the load's duration.
        self._vocab_bytes_cache: Optional[list[bytes]] = None
        self._vocab_bytes_thread = None
        if (not config.model_config.skip_tokenizer_init
                and getattr(config.model_config,
                            "structured_vocab_bytes", None) is None
                and self._tokenizer_files_present()):
            # Cost/latency tradeoff: the background load burns one
            # duplicate tokenizer load per engine even if structured
            # output never arrives, but the FIRST structured request
            # then never stalls the busy loop for the load's duration.
            # The file check skips weights-only dirs (most tests).
            self._vocab_bytes_thread = threading.Thread(
                target=self._prefetch_vocab_bytes, daemon=True,
                name="vocab-bytes-prefetch")
            self._vocab_bytes_thread.start()

    def _initialize_kv_caches(self) -> int:
        num_pages = self.executor.determine_num_available_blocks()
        logger.info("allocating %d KV pages (%d tokens)", num_pages,
                    num_pages * self.config.cache_config.block_size)
        self.executor.initialize_kv_cache(num_pages)
        return num_pages

    # ------------------------------------------------------------------
    def add_request(self, request: EngineCoreRequest) -> None:
        if self.phases.idle:
            self.phases.resume()
        if request.sampling_params.structured is not None:
            self._register_structured(request)
        self.scheduler.add_request(Request.from_engine_core_request(request))

    def _register_structured(self, request: EngineCoreRequest) -> None:
        """Compile the request's grammar in the core, beside the
        scheduler (reference: v1/structured_output/__init__.py
        StructuredOutputManager). The manager (and its token-bytes
        table) is built on the first structured request."""
        if self.scheduler.structured_manager is None:
            from vllm_distributed_tpu.structured_output.manager import \
                StructuredOutputManager
            self.scheduler.structured_manager = \
                StructuredOutputManager(self._vocab_bytes())
        self.scheduler.structured_manager.add_request(
            request.request_id, request.sampling_params.structured,
            eos_token_id=request.eos_token_id)

    def _tokenizer_files_present(self) -> bool:
        import os
        path = (self.config.model_config.tokenizer
                or self.config.model_config.model)
        if not os.path.isdir(path):
            return False  # hub refs resolve lazily; don't prefetch
        return any(
            os.path.exists(os.path.join(path, f))
            for f in ("tokenizer.json", "tokenizer.model",
                      "tokenizer_config.json"))

    def _prefetch_vocab_bytes(self) -> None:
        try:
            self._vocab_bytes_cache = self._load_vocab_bytes()
        except Exception as e:  # noqa: BLE001 - surfaced on first use
            logger.debug("vocab-bytes prefetch failed (%s); structured "
                         "requests will retry inline", e)

    def _load_vocab_bytes(self) -> list[bytes]:
        from transformers import AutoTokenizer

        from vllm_distributed_tpu.structured_output.manager import \
            vocab_bytes_from_tokenizer
        tok = AutoTokenizer.from_pretrained(
            self.config.model_config.tokenizer
            or self.config.model_config.model)
        return vocab_bytes_from_tokenizer(tok)

    def _vocab_bytes(self) -> list[bytes]:
        """token id -> utf-8 bytes for grammar mask precomputation.
        Tests inject ``model_config.structured_vocab_bytes``; otherwise
        the prefetch thread's table (or an inline load as last resort)."""
        override = getattr(self.config.model_config,
                           "structured_vocab_bytes", None)
        if override is not None:
            return override
        if self._vocab_bytes_thread is not None:
            self._vocab_bytes_thread.join(timeout=120)
            self._vocab_bytes_thread = None
        if self._vocab_bytes_cache is None:
            self._vocab_bytes_cache = self._load_vocab_bytes()
        return self._vocab_bytes_cache

    def abort_requests(self, request_ids: list[str]) -> None:
        self.scheduler.finish_requests(request_ids,
                                       RequestStatus.FINISHED_ABORTED)

    # Bounded retries for a force-stop whose stop_trace itself fails
    # (full disk mid-export): retry once per window, then declare the
    # jax profiler state unknown and release the capture lane.
    _PROFILE_STOP_RETRIES = 3

    def _maybe_expire_profile(self) -> None:
        """Force-stop a profiler capture whose stop never arrived once
        its VDT_PROFILE_MAX_S window closes (checked per step and per
        stats poll — one None check on the hot path). State clears
        only AFTER stop_trace succeeds: clearing first would disarm
        this sweep while the jax trace kept running — exactly the
        wedged state the deadline exists to prevent. A stop_trace that
        itself fails re-arms the deadline for a bounded retry."""
        if (self._profile_dir is None
                or time.monotonic() < self._profile_deadline
                or not self._profile_lock.acquire(blocking=False)):
            return
        try:
            self._expire_profile_locked()
        finally:
            self._profile_lock.release()

    def _expire_profile_locked(self) -> None:
        trace_dir = self._profile_dir
        if trace_dir is None:
            return      # stopped while this sweep took the lock
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 - a broken trace must
            # never take the step loop down with it.
            self._profile_stop_failures += 1
            if self._profile_stop_failures < self._PROFILE_STOP_RETRIES:
                from vllm_distributed_tpu import envs
                self._profile_deadline = (time.monotonic() +
                                          envs.VDT_PROFILE_MAX_S)
                logger.warning(
                    "force-stopping overdue profiler capture failed "
                    "(%s); retrying next window", e)
                return
            logger.warning(
                "force-stopping overdue profiler capture failed %d "
                "times (%s); releasing the capture lane with the jax "
                "profiler state unknown", self._profile_stop_failures,
                e)
        else:
            logger.warning(
                "profiler capture exceeded its window; force-stopped "
                "-> %s", trace_dir)
        self._profile_dir = None
        self._profile_stalled = False
        self._profile_stop_failures = 0

    def step(self) -> list[EngineCoreOutput]:
        """One scheduling iteration (reference: core.py:223)."""
        if self._profile_dir is not None:
            self._maybe_expire_profile()
        self.last_step_scheduled = False
        if not (self.has_unfinished_requests()
                or self.scheduler.has_kv_transfer_work()):
            return []
        ph = self.phases
        with ph.begin_step():
            if self.batch_queue is None:
                outputs = self._step_sync()
            elif self.async_scheduling:
                outputs = self._step_pipelined()
            else:
                outputs = self.step_with_batch_queue()
        ph.end_step(more_work=self.has_unfinished_requests()
                    or self.scheduler.has_kv_transfer_work())
        return outputs

    def _step_sync(self) -> list[EngineCoreOutput]:
        """The synchronous step: its phases (and the runner's, which
        execute_model runs through) partition it, see StepPhases."""
        ph = self.phases
        with ph.span("vdt.schedule", "schedule"):
            scheduler_output = self.scheduler.schedule()
        self.last_step_scheduled = \
            scheduler_output.total_num_scheduled_tokens > 0
        ph.kind = _kind(scheduler_output)
        runner_output = self.executor.execute_model(scheduler_output)
        with ph.span("vdt.update_from_output", "update"):
            return self.scheduler.update_from_output(scheduler_output,
                                                     runner_output)

    def _step_pipelined(self) -> list[EngineCoreOutput]:
        """The depth-2 pipelined step: schedule, prepare and launch step
        N+1, THEN fetch and reconcile step N, which the call before
        launched. The forward of N+1 is in the device's queue before the
        host blocks on N, so the device goes from one forward to the
        next while the host schedules; the outputs returned are N's.

        One call is one ``vdt_step``, one forward launched and one
        period, in the phases of the synchronous step and in their
        order (StepPhases): ``schedule, update_states, prepare_inputs,
        launch`` for N+1, ``device_wait, finish, update`` for N. The
        step's ``kind`` is that of the batch it retires.

        The edges: a call that finds nothing in flight only launches
        (pipeline fill, returns nothing) unless the scheduler could
        grant nothing behind that batch (requests that need the
        synchronous step, ``Scheduler._needs_sync_step``; a last
        position): then it retires it at once, which is the synchronous
        step. A call that finds nothing to schedule only retires
        (drain). An empty grant despite schedulable work (pool
        exhausted, budget edge) still carries finished_req_ids for the
        worker's row cleanup: it runs through synchronously, which is
        safe beside a batch in flight ONLY because a zero-token batch
        does no device work (test_zero_token_dispatch_does_no_device_work),
        and the call goes on to retire, which frees pages for the next
        attempt. KV connectors are config-gated off this path."""
        ph, sched, queue = self.phases, self.scheduler, self.batch_queue
        launched = None
        if (len(queue) < self.batch_queue_size
                and sched.has_schedulable_requests()):
            with ph.span("vdt.schedule", "schedule"):
                scheduler_output = sched.schedule()
            if scheduler_output.total_num_scheduled_tokens > 0:
                launched = scheduler_output
                sched.mark_in_flight(scheduler_output.num_scheduled_tokens)
                self.steps_dispatched += 1
                if queue:
                    self.steps_overlapped += 1
                handle = self.executor.execute_model_async(scheduler_output)
                queue.appendleft((scheduler_output, handle))
                self.last_step_scheduled = True
                self.max_concurrent_batches = max(
                    self.max_concurrent_batches, len(queue))
                if self.events.enabled:
                    self.events.record("", ev.BATCH_DISPATCH, {
                        "reqs": len(scheduler_output.num_scheduled_tokens),
                        "tokens":
                            scheduler_output.total_num_scheduled_tokens,
                        "depth": len(queue),
                    })
            else:
                sched.update_from_output(
                    scheduler_output,
                    self.executor.execute_model(scheduler_output))
        if not queue:
            return []
        if (launched is not None and len(queue) < self.batch_queue_size
                and sched.has_schedulable_requests()):
            ph.kind = _kind(launched)
            return []
        scheduler_output, handle = queue.pop()
        if fault_injection.registry.active:
            self._reconcile_stall_drill()
        ph.kind = _kind(scheduler_output)
        ph.forward_queued = bool(queue)
        runner_output = self.executor.wait_model(handle)
        if self.events.enabled:
            self.events.record("", ev.BATCH_RETIRE, {
                "reqs": len(scheduler_output.num_scheduled_tokens),
                "depth": len(queue),
            })
        sched.unmark_in_flight(scheduler_output.num_scheduled_tokens)
        with ph.span("vdt.update_from_output", "update"):
            return sched.update_from_output(scheduler_output,
                                            runner_output)

    @staticmethod
    def _reconcile_stall_drill() -> None:
        """step.reconcile_stall: with delay_s it stalls the host between
        device completion and reconciliation (the window the pipeline
        keeps covered); without a delay it kills the core mid-pipeline
        so the crash-recovery ladder is exercised with batches in
        flight."""
        if fault_injection.registry.delay_of("step.reconcile_stall"):
            fault_injection.maybe_delay("step.reconcile_stall")
        else:
            fault_injection.fire_or_raise("step.reconcile_stall")

    def step_with_batch_queue(self) -> list[EngineCoreOutput]:
        """One iteration of pipeline parallelism's batch queue
        (reference: core.py:242): dispatch a fresh batch whenever there
        is room and schedulable work; otherwise retire the oldest. Each
        call does at most one of the two, so dispatches outnumber waits
        until the pipeline fills."""
        ph = self.phases
        if (len(self.batch_queue) < self.batch_queue_size
                and self.scheduler.has_schedulable_requests()):
            with ph.span("vdt.schedule", "schedule"):
                scheduler_output = self.scheduler.schedule()
            if scheduler_output.total_num_scheduled_tokens > 0:
                self.scheduler.mark_in_flight(
                    scheduler_output.num_scheduled_tokens)
                self.steps_dispatched += 1
                if self.batch_queue:
                    self.steps_overlapped += 1
                with ph.span("vdt.dispatch", "dispatch") as dispatch:
                    if self._last_wait_done is not None:
                        ph.host_gap.observe(dispatch.start
                                            - self._last_wait_done)
                        self._last_wait_done = None
                    handle = self.executor.execute_model_async(
                        scheduler_output)
                if self.events.enabled:
                    self.events.record("", ev.BATCH_DISPATCH, {
                        "reqs": len(
                            scheduler_output.num_scheduled_tokens),
                        "tokens":
                            scheduler_output.total_num_scheduled_tokens,
                        "depth": len(self.batch_queue) + 1,
                    })
                self.batch_queue.appendleft((scheduler_output, handle))
                self.last_step_scheduled = True
                self.max_concurrent_batches = max(
                    self.max_concurrent_batches, len(self.batch_queue))
                return []
            # An empty grant despite schedulable work (pool exhausted,
            # budget edge). The output still carries finished_req_ids
            # for worker-side row cleanup — run it through synchronously
            # rather than dropping it, then retire a batch to free
            # pages/slots for the next attempt. Safe to run while async
            # batches are in flight ONLY because a zero-token batch does
            # no device dispatch (dispatch_model's total==0 early return
            # — connector polls + row cleanup only; contract locked by
            # test_zero_token_dispatch_does_no_device_work).
            runner_output = self.executor.execute_model(scheduler_output)
            self.scheduler.update_from_output(scheduler_output,
                                              runner_output)
        if not self.batch_queue:
            if self.scheduler.has_kv_transfer_work():
                # No schedulable tokens and nothing in flight, but async
                # KV transfers still need the runner's get_finished poll
                # (PP + connector is rejected by PPModelRunner.__init__
                # today; this keeps the queue path honest when that gate
                # lifts).
                scheduler_output = self.scheduler.schedule()
                runner_output = self.executor.execute_model(
                    scheduler_output)
                return self.scheduler.update_from_output(
                    scheduler_output, runner_output)
            return []
        scheduler_output, handle = self.batch_queue.pop()
        if fault_injection.registry.active:
            self._reconcile_stall_drill()
        with ph.span("vdt.wait", "wait") as wait:
            runner_output = self.executor.wait_model(handle)
        self._last_wait_done = wait.end
        if self.events.enabled:
            self.events.record("", ev.BATCH_RETIRE, {
                "reqs": len(scheduler_output.num_scheduled_tokens),
                "depth": len(self.batch_queue),
            })
        self.scheduler.unmark_in_flight(
            scheduler_output.num_scheduled_tokens)
        with ph.span("vdt.update_from_output", "update"):
            return self.scheduler.update_from_output(scheduler_output,
                                                     runner_output)

    def has_unfinished_requests(self) -> bool:
        # A non-empty batch queue counts as work even when every live
        # request already finished: a trailing speculative batch must
        # still retire (its wait frees the pages parked on it).
        return (self.scheduler.has_unfinished_requests()
                or bool(self.batch_queue))

    def has_inflight_batches(self) -> bool:
        """Dispatched-but-unretired batches — busy loops must not pace
        (sleep) while a wait is pending, or the retire lags the device
        by the sleep quantum."""
        return bool(self.batch_queue)

    def has_kv_transfer_work(self) -> bool:
        """Async KV transfers needing step-polls even with no live
        requests (a producer's deferred frees)."""
        return self.scheduler.has_kv_transfer_work()

    def get_stats(self, include_events: bool = True) -> dict:
        if self._profile_dir is not None:
            # A wedged capture on an IDLE engine (no steps running the
            # sweep) still expires on the next stats poll / scrape.
            self._maybe_expire_profile()
        stats = self.scheduler.get_stats()
        stats.update(self.executor.get_stats())
        stats["inflight_batches"] = (len(self.batch_queue)
                                     if self.batch_queue is not None else 0)
        stats["max_concurrent_batches"] = self.max_concurrent_batches
        stats["steps_dispatched"] = self.steps_dispatched
        stats["steps_overlapped"] = self.steps_overlapped
        stats["decode_overlap_frac"] = (
            self.steps_overlapped / max(self.steps_dispatched, 1))
        stats.update(self.phases.to_dict())
        # Transport telemetry: per-connector KV-transfer bytes/latency/
        # inflight and shm-ring wait/lag, recorded by everything built
        # inside this core's construction window. Multi-host follower
        # snapshots (the shm ring's read side lives in those
        # processes) arrive from the executor and merge per label —
        # the standard DP-merge shape, one level earlier.
        snap = self.transport.snapshot()
        followers = stats.pop("follower_transport", None)
        if followers:
            from vllm_distributed_tpu.metrics import telemetry
            merged = telemetry.merge_transport_snapshots(
                [snap] + list(followers))
            if merged is not None:
                snap = merged
        stats["transport"] = snap
        # Lifecycle timeline: drained per stats poll, shipped over the
        # stats RPC (DP-merged by the front-end client). The drain is
        # DESTRUCTIVE — callers that may abandon the response mid-RPC
        # (the admission gate's hard-timeout poll) pass
        # include_events=False so a cancelled poll can't discard a
        # batch of events.
        if include_events:
            stats["timeline_events"] = ev.merge_event_lists(
                self.scheduler.events.drain(), self.events.drain())
        stats["timeline_events_dropped"] = (
            self.scheduler.events.num_dropped + self.events.num_dropped)
        # Cross-process clock alignment (trace plane): this process's
        # monotonic reading at snapshot time. The front-end aggregator
        # pairs it with its own clock to estimate a per-replica offset
        # and re-base drained events into the front-end's epoch.
        stats["clock_mono"] = time.monotonic()
        # Process-local counter snapshots, pid-tagged so the front-end
        # merge can dedup in-process cores (which share the front-end's
        # registries) and sum only true follower processes — the fix
        # for the fleet-inexact vdt:fault_injections_total /
        # vdt:qcomm_* noted since PR 9.
        from vllm_distributed_tpu.parallel import collectives
        pid = os.getpid()
        counts = fault_injection.counters()
        if counts:
            stats["fault_injection_counts"] = {"pid": pid,
                                               "counts": counts}
        traced = collectives.traced_snapshot()
        if traced["bytes_saved"] or traced["fallbacks"]:
            stats["qcomm_traced"] = {"pid": pid, **traced}
        return stats

    def get_debug_state(self) -> dict:
        """Live engine-core introspection (the /debug endpoints and the
        SIGUSR1 dump): scheduler state plus the batch pipeline's
        occupancy. Read-only."""
        return {
            "scheduler": self.scheduler.get_debug_state(),
            "batch_queue_depth": (len(self.batch_queue)
                                  if self.batch_queue is not None else 0),
            "batch_queue_size": self.batch_queue_size,
            "async_scheduling": self.async_scheduling,
            "steps_dispatched": self.steps_dispatched,
            "max_concurrent_batches": self.max_concurrent_batches,
        }

    def save_sharded_state(self, path: str) -> None:
        """Persist the (sharded, post-quantization) weights for fast
        reload via load_format='sharded_state' (reference:
        EngineCore.save_sharded_state, core.py:336)."""
        self.executor.worker.model_runner.save_sharded_state(path)

    def sleep(self, level: int = 1) -> int:
        """Release device memory while idle (RLHF colocation;
        reference: EngineCore.sleep -> CuMemAllocator discard/offload,
        core.py:312-319 + cumem.py:106). Requires an idle engine —
        in-flight KV would be lost."""
        if self.scheduler.has_requests() or self.batch_queue:
            raise ValueError("cannot sleep with in-flight requests")
        if self.config.parallel_config.pipeline_parallel_size > 1:
            raise ValueError("sleep/wake under pipeline parallelism "
                             "needs per-stage restore; not wired yet")
        freed = self.executor.worker.model_runner.sleep(level)
        # The device pages are gone: cached prefix blocks must stop
        # advertising their contents or post-wake requests would "hit"
        # zeroed pages (reference: sleep implies reset_prefix_cache).
        if not self.scheduler.kv_cache_manager.reset_prefix_cache():
            logger.warning("prefix cache reset failed during sleep")
        if self.scheduler.state_cache is not None:
            # Same rule for SSM snapshots: the pool's HBM was released,
            # so the index must forget every slot.
            self.scheduler.state_cache.reset()
        return freed

    def wake_up(self) -> None:
        self.executor.worker.model_runner.wake_up()

    def profile(self, action: str = "start") -> str:
        """Start/stop a device trace (reference: EngineCore.profile RPC,
        core.py:297; TPU variant tpu_worker.py:246-256 — here
        jax.profiler, viewable in TensorBoard/XProf).

        Hardened against a client that goes away: each capture gets its
        own auto-named directory under VDT_PROFILER_DIR (captures never
        overwrite each other), a second concurrent start is rejected,
        and every capture carries a VDT_PROFILE_MAX_S deadline the step
        loop enforces — so one RPC pair always yields a self-contained
        xplane dump even if the client dies before the stop lands. Fault point ``perf.capture_stall`` simulates that
        wedged client: the stop RPC fails and the deadline is what ends
        the capture, counted in vdt:fault_injections_total."""
        with self._profile_lock:
            return self._profile_locked(action)

    def _profile_locked(self, action: str) -> str:
        import jax

        from vllm_distributed_tpu import envs
        if action == "start":
            if self._profile_dir is not None:
                raise ValueError(
                    f"profiler capture already active "
                    f"({self._profile_dir}); stop it first")
            self._profile_seq += 1
            trace_dir = os.path.join(
                envs.VDT_PROFILER_DIR,
                f"trace-{os.getpid()}-{self._profile_seq:03d}")
            # The program's own spans (StepPhases) name the host side
            # of a capture; JAX's Python tracer would add a million call
            # events a second and slow the host it is measuring.
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            self._profile_dir = trace_dir
            self._profile_deadline = (time.monotonic() +
                                      envs.VDT_PROFILE_MAX_S)
            self._profile_stalled = (
                fault_injection.registry.active
                and fault_injection.registry.should_fire(
                    "perf.capture_stall"))
            logger.info("profiling started -> %s (window %.0fs)",
                        trace_dir, envs.VDT_PROFILE_MAX_S)
            return trace_dir
        if self._profile_dir is None:
            raise ValueError("no profiler capture active")
        if self._profile_stalled:
            # Drill: the xprof session is wedged — the stop is lost and
            # only the capture-window deadline ends the trace.
            raise RuntimeError(
                "profiler capture is wedged (perf.capture_stall); the "
                "capture-window deadline will force-stop it")
        trace_dir = self._profile_dir
        # Stop FIRST, clear after: if stop_trace raises (full disk
        # mid-xplane-export), the capture stays armed so the deadline
        # sweep keeps owning the cleanup instead of orphaning a live
        # jax trace with the sweep disarmed.
        jax.profiler.stop_trace()
        self._profile_dir = None
        self._profile_stop_failures = 0
        logger.info("profiling stopped -> %s", trace_dir)
        return trace_dir

    def shutdown(self) -> None:
        self.scheduler.shutdown()
        self.executor.shutdown()
