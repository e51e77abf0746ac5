"""Async engine for online serving.

Reference: vllm/v1/engine/async_llm.py:46 (``AsyncLLM``: generate :277
returning an async generator fed by per-request output queues, background
output handler :361, errored/dead_error :621). TPU-native differences:
the engine core runs either on a daemon thread (single process) or in an
EngineCoreProc subprocess (ZMQ); a pump thread marshals output batches
into the asyncio loop with call_soon_threadsafe — the GIL-friendly
equivalent of the reference's asyncio socket handler.
"""

import asyncio
import os
import threading
import time
from typing import AsyncGenerator, Optional, Union

from vllm_distributed_tpu.config import EngineConfig
from vllm_distributed_tpu.engine.core_client import (EngineDeadError,
                                                     RestartSupervisor,
                                                     SyncMPClient)
from vllm_distributed_tpu.engine.core_proc import BackgroundEngineCore
from vllm_distributed_tpu.engine.llm_engine import _load_tokenizer
from vllm_distributed_tpu.engine.output_processor import OutputProcessor
from vllm_distributed_tpu.engine.processor import Processor
from vllm_distributed_tpu.logger import init_logger
from vllm_distributed_tpu.metrics import events as ev
from vllm_distributed_tpu.metrics.stats import span
from vllm_distributed_tpu.outputs import RequestOutput, RequestOutputKind
from vllm_distributed_tpu.sampling_params import SamplingParams

logger = init_logger(__name__)

# Sentinel delivered to a generate() consumer whose request was aborted
# out-of-band (AsyncLLM.abort): ends the stream without an error.
_ABORTED = object()


class AsyncLLM:

    def __init__(self, config: EngineConfig, tokenizer=None, *,
                 load_tokenizer: bool = True) -> None:
        self.config = config
        config.model_config.maybe_load_hf_config()
        if tokenizer is None and load_tokenizer:
            tokenizer = _load_tokenizer(config)
        self.tokenizer = tokenizer
        self.processor = Processor(config, tokenizer)
        self.output_processor = OutputProcessor(config, tokenizer)

        from vllm_distributed_tpu import envs
        pc = config.parallel_config
        if pc.data_parallel_size > 1 and pc.data_parallel_mode == "engine":
            # DP replicas under the async server always run as
            # subprocesses: the pump thread needs a non-blocking poll
            # surface and the replicas must overlap compute.
            from vllm_distributed_tpu.engine.dp_client import DPEngineClient
            self.core = DPEngineClient(config, force_mp=True)
        elif pc.multiprocess_engine_core or envs.VDT_ENABLE_MP_ENGINE:
            self.core = SyncMPClient(config)
        else:
            self.core = BackgroundEngineCore(config)

        self.request_queues: dict[str, asyncio.Queue] = {}
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        # time.thread_time() of the loop's thread at the start of the
        # last step batch (vdt:frontend_step_cpu_seconds).
        self._cpu_at_batch = 0.0
        self._pump: Optional[threading.Thread] = None
        self._stopped = False
        # Event form of _stopped so recovery backoff sleeps wake
        # immediately on shutdown (a plain sleep would let the pump
        # respawn a core AFTER shutdown already tore the old one down,
        # leaking the fresh subprocess).
        self._stop_event = threading.Event()
        self._dead_error: Optional[Exception] = None

        # Crash-recovery state: the journal holds every unfinished
        # request's original EngineCoreRequest (tokens generated so far
        # live in output_processor.request_states); after a supervisor
        # restart each entry is resubmitted as a continuation prefill.
        # The core lock serializes submissions against restart+replay so
        # a request can never vanish into a dead incarnation unjournaled.
        self._journal: dict[str, "EngineCoreRequest"] = {}
        self._journal_lock = threading.Lock()
        self._core_lock = threading.Lock()
        self._supervisor = RestartSupervisor.from_config(config)

    @classmethod
    def from_engine_args(cls, engine_args) -> "AsyncLLM":
        return cls(engine_args.create_engine_config())

    # ------------------------------------------------------------------
    @property
    def engine_core(self):
        """The underlying engine client, under the attribute name the
        API server's introspection paths probe (health suspicion, the
        fleet goodput feed, /debug/correctness). The sync LLM engine
        exposes the same name directly."""
        return self.core

    @property
    def errored(self) -> bool:
        return self._dead_error is not None

    @property
    def dead_error(self) -> Exception:
        return self._dead_error or EngineDeadError("engine is dead")

    def _ensure_pump(self) -> None:
        loop = asyncio.get_running_loop()
        if self._loop is None or (self._loop is not loop
                                  and self._loop.is_closed()):
            # A recovered engine outlives asyncio.run() loops (the pump
            # thread survives core restarts): re-bind to the caller's
            # live loop once the old one is gone. What was posted to
            # the old one is lost, and the CPU clock is this thread's.
            self._loop = loop
            stats = self.output_processor.stats
            stats.batches_begun = stats.batches_posted
            self._cpu_at_batch = time.thread_time()
        if self._pump is not None:
            return
        self._pump = threading.Thread(target=self._pump_outputs,
                                      daemon=True, name="output-pump")
        self._pump.start()

    def _post(self, callback, *args) -> bool:
        """Schedule a callback onto the bound event loop from the pump
        thread; False when the loop is closed (a new generate() call
        will re-bind before more work arrives)."""
        try:
            self._loop.call_soon_threadsafe(callback, *args)
            return True
        except RuntimeError:
            return False

    def _pump_outputs(self) -> None:
        """Blocking-side reader: ships each output batch into the event
        loop (reference: async_llm.py:361 _run_output_handler). A core
        death enters the recovery ladder (supervisor restart + journal
        replay) before giving up and failing pending requests."""
        while not self._stopped:
            try:
                outs = self._blocking_recv(timeout_s=0.2)
            except Exception as e:  # noqa: BLE001 - engine died
                if self._stopped:
                    return
                self.output_processor.stats.num_engine_deaths += 1
                if self._try_recover(e):
                    continue
                if not self._post(self._fail_all, e):
                    # Loop gone (consumer's asyncio.run ended): apply
                    # the terminal state inline so errored/dead_error
                    # reflect reality for the next caller.
                    self._fail_all(e)
                return
            if outs:
                while not self._post_batch(outs):
                    # Bound loop closed between asyncio.run() calls:
                    # wait for a new consumer to re-bind it.
                    if self._stopped:
                        return
                    time.sleep(0.05)

    def _post_batch(self, outs) -> bool:
        """A step's outputs onto the event loop, counted (by this, the
        pump thread, alone) for vdt:frontend_batches_pending."""
        stats = self.output_processor.stats
        stats.batches_posted += 1
        if self._post(self._process_batch, outs):
            return True
        stats.batches_posted -= 1
        return False

    # ------------------------------------------------------------------
    # Crash recovery: restart supervisor + in-flight request replay
    # ------------------------------------------------------------------
    def _try_recover(self, err: Exception) -> bool:
        """Respawn the dead core within the supervisor's restart budget
        and replay journaled requests as continuation prefills. Returns
        False once the budget circuit-breaks (the caller then fails
        pending requests with the terminal EngineDeadError)."""
        from vllm_distributed_tpu.utils import fault_injection
        # Timeline: the death reaches every journaled request's trace.
        # (Pump-thread appends race loop-thread reads only as GIL-atomic
        # list appends; the finish path sorts a copy.)
        if self.output_processor.timeline_enabled:
            with self._journal_lock:
                journaled = list(self._journal)
            for rid in journaled:
                self.output_processor.record_event(
                    rid, ev.ENGINE_DEATH, {"error": str(err)})
        while not self._stopped:
            delay = self._supervisor.next_delay()
            if delay is None:
                if self._supervisor.max_attempts > 0:
                    logger.error(
                        "engine core restart budget exhausted (%d in "
                        "%.0fs); circuit-breaking to EngineDeadError",
                        self._supervisor.max_attempts,
                        self._supervisor.window_s)
                return False
            logger.warning("engine core died (%s); restarting in %.2fs",
                           err, delay)
            if self._stop_event.wait(delay) or self._stopped:
                return False  # shutdown won the race: do NOT respawn
            # Make sure every output batch shipped BEFORE the death has
            # been applied to the output-processor state: the replay
            # prompt below embeds "tokens generated so far", and a
            # still-queued batch would otherwise be double-generated.
            if not self._drain_loop_callbacks():
                return False  # shutdown while waiting on the barrier
            with self._core_lock:
                if self._stopped:
                    return False
                storm = fault_injection.should_fire("restart.storm")
                if storm:
                    # Storm drill: the fresh core dies again immediately,
                    # burning through the restart budget. Armed both
                    # in-process (thread cores read this registry) and
                    # via the environment (a respawned SUBPROCESS core
                    # rebuilds its registry from VDT_FAULT_INJECT at
                    # start, not from the parent's memory).
                    fault_injection.inject("engine_core.die", max_fires=1)
                    prev_env = os.environ.get("VDT_FAULT_INJECT")
                    os.environ["VDT_FAULT_INJECT"] = (
                        (prev_env + "," if prev_env else "")
                        + "engine_core.die:1.0")
                try:
                    self.core.restart()
                except Exception as e:  # noqa: BLE001 - spawn failed
                    logger.error("engine core restart failed: %s", e)
                    err = e
                    continue
                finally:
                    if storm:
                        if prev_env is None:
                            os.environ.pop("VDT_FAULT_INJECT", None)
                        else:
                            os.environ["VDT_FAULT_INJECT"] = prev_env
                self._replay_journal()
            return True
        return False

    def _drain_loop_callbacks(self) -> bool:
        """Barrier: returns True once every callback already scheduled
        onto the event loop (queued _process_batch calls) has run —
        replaying before they land would double-generate their tokens.
        A closed loop counts as drained (its queued callbacks are
        discarded, so those tokens were never delivered and MUST be
        regenerated). Only a shutdown aborts the wait (False)."""
        done = threading.Event()
        if not self._post(done.set):
            return True  # loop closed: queued callbacks never run
        while not done.wait(timeout=10):
            if self._stopped:
                return False
            if self._loop.is_closed():
                # The loop accepted the barrier callback but closed
                # before running it (asyncio.run teardown): discarded
                # callbacks can never land, so the state IS drained.
                return True
            logger.warning("event loop has not drained its callback "
                           "queue in 10s; delaying the journal replay")
        return True

    def _replay_journal(self) -> None:
        """Resubmit every unfinished journaled request to the fresh core
        as a continuation prefill: prompt = original prompt + tokens
        already delivered, remaining token budget adjusted. With greedy
        sampling the resumed stream is token-identical to an
        uninterrupted run.

        Stateful (SSM) models: the fresh core's admission consults the
        state-cache checkpoint journal (core/state_cache.py,
        VDT_SSM_CKPT_DIR), so a replayed request resumes from its last
        checksummed checkpoint and re-prefills at most
        VDT_SSM_CKPT_INTERVAL tokens instead of the whole continuation
        prompt — O(1) recovery where re-prefill used to be O(prompt)."""
        with self._journal_lock:
            pending = list(self._journal.items())
        for rid, orig in pending:
            req = self._continuation_request(rid, orig)
            req.submitted_at = time.monotonic()
            try:
                self.core.add_request(req)
            except Exception as e:  # noqa: BLE001 - fail THIS request
                # (leaving it journaled-but-unsubmitted would hang its
                # consumer forever while the fresh core serves others).
                logger.error("replay of %s failed: %s", rid, e)
                with self._journal_lock:
                    self._journal.pop(rid, None)
                replay_err = EngineDeadError(
                    f"request {rid} could not be replayed after an "
                    f"engine restart: {e}")
                if not self._post(self._fail_request, rid, replay_err):
                    self._fail_request(rid, replay_err)
                continue
            self.output_processor.stats.num_requests_replayed += 1
            delivered = (len(req.prompt_token_ids)
                         - len(orig.prompt_token_ids))
            self.output_processor.record_event(
                rid, ev.JOURNAL_REPLAY, {"delivered": delivered})
            logger.info("replayed request %s (%d tokens already "
                        "delivered)", rid, delivered)

    def _continuation_request(self, rid: str, orig):
        from vllm_distributed_tpu.request import continuation_request
        state = self.output_processor.request_states.get(rid)
        generated = (list(state.output_token_ids)
                     if state is not None else [])
        return continuation_request(orig, generated)

    def _blocking_recv(self, timeout_s: float):
        if isinstance(self.core, BackgroundEngineCore):
            import queue
            try:
                item = self.core.output_queue.get(timeout=timeout_s)
            except queue.Empty:
                # Nothing arrived: make sure that is "idle", not "the
                # core thread is dead/wedged" (health monitor raises
                # EngineDeadError; the pump then fails pending
                # requests instead of blocking forever).
                self.core.check_health()
                return None
            if isinstance(item, Exception):
                raise item
            return item
        return self.core.recv_outputs(timeout_ms=int(timeout_s * 1000))

    def _process_batch(self, core_outputs) -> None:
        """One step's outputs (core_proc.StepOutputs, under either
        transport: the step's number and when the engine core handed
        them over) to the request queues, on the event loop."""
        stats = self.output_processor.stats
        stats.batches_begun += 1
        cpu = time.thread_time()
        stats.step_cpu.observe(cpu - self._cpu_at_batch)
        self._cpu_at_batch = cpu
        with span("vdt.process_outputs", stats.process_outputs,
                  step=core_outputs.step):
            processed = self.output_processor.process_outputs(
                core_outputs)
        self._abort_in_core(processed.reqs_to_abort)
        # Journal reaping keys off the RAW core outputs plus front-end
        # finishes (stop strings): even a request whose front-end state
        # is already gone (abort races, replayed ghosts) must leave the
        # journal once the core finishes it.
        done = [o.req_id for o in core_outputs if o.finished]
        done += processed.reqs_to_abort
        if done:
            with self._journal_lock:
                for rid in done:
                    self._journal.pop(rid, None)
        for ro in processed.request_outputs:
            q = self.request_queues.get(ro.request_id)
            if q is None:
                continue
            q.put_nowait(ro)
            if ro.finished:
                self.request_queues.pop(ro.request_id, None)
        stats.output_lag.observe(
            time.monotonic() - core_outputs.handed_over)

    def _fail_request(self, request_id: str, err: Exception) -> None:
        """Terminal error for ONE request (replay rejection) while the
        engine itself stays healthy."""
        self.output_processor.abort_requests([request_id])
        q = self.request_queues.pop(request_id, None)
        if q is not None:
            q.put_nowait(err)

    def _fail_all(self, err: Exception) -> None:
        # Pending requests always surface a STRUCTURED EngineDeadError
        # (the OpenAI server maps it to 503 + detail), whatever the
        # core's terminal exception actually was.
        if not isinstance(err, EngineDeadError):
            err = EngineDeadError(f"{type(err).__name__}: {err}")
        self._dead_error = err
        logger.error("engine core died: %s", err)
        with self._journal_lock:
            self._journal.clear()
        for q in self.request_queues.values():
            q.put_nowait(err)
        self.request_queues.clear()

    # ------------------------------------------------------------------
    async def generate(
        self,
        prompt: Union[str, list[int]],
        sampling_params: Optional[SamplingParams] = None,
        request_id: Optional[str] = None,
        priority: int = 0,
        tenant: Optional[str] = None,
        lora_request: Optional[dict] = None,
        pooling_params: Optional[dict] = None,
        multi_modal_data: Optional[dict] = None,
        received_at: Optional[float] = None,
        output_kind: RequestOutputKind = RequestOutputKind.CUMULATIVE,
    ) -> AsyncGenerator[RequestOutput, None]:
        """Async stream of RequestOutputs for one request (reference:
        async_llm.py:277): accumulated by default, the step's own under
        ``RequestOutputKind.DELTA``, one at the finish under
        ``FINAL_ONLY``. ``received_at`` is ``time.monotonic()`` at the
        top of the HTTP handler, where vdt:frontend_admit_seconds starts
        (here, for other callers)."""
        if received_at is None:
            received_at = time.monotonic()
        if self._dead_error is not None:
            raise self._dead_error
        self._ensure_pump()
        if request_id is None:
            from vllm_distributed_tpu.utils import random_uuid
            request_id = random_uuid()
        sampling_params = sampling_params or SamplingParams()
        core_req = self.processor.process_inputs(
            request_id, prompt, sampling_params, priority=priority,
            tenant=tenant, lora_request=lora_request,
            pooling_params=pooling_params,
            multi_modal_data=multi_modal_data)
        queue: asyncio.Queue = asyncio.Queue()
        self.request_queues[request_id] = queue
        self.output_processor.add_request(
            core_req, prompt=prompt if isinstance(prompt, str) else None,
            output_kind=output_kind)
        try:
            # Submission runs off-loop: during a supervisor restart the
            # core lock is held for the respawn's duration, and the
            # event loop must stay responsive (health checks, other
            # consumers) while this add waits its turn.
            await asyncio.get_running_loop().run_in_executor(
                None, self._submit_to_core, core_req)
            self._note_admission(core_req, received_at)
            while True:
                item = await queue.get()
                if item is _ABORTED:
                    return
                if isinstance(item, Exception):
                    raise item
                yield item
                if item.finished:
                    return
        finally:
            if self.request_queues.pop(request_id, None) is not None:
                # Consumer cancelled / errored mid-stream: abort upstream.
                with self._journal_lock:
                    self._journal.pop(request_id, None)
                self.output_processor.abort_requests([request_id])
                self._abort_in_core([request_id])

    def _note_admission(self, core_req, received_at: float) -> None:
        """The request's way in, once it is on the transport: observed
        here, on the event loop, like everything of FrontendStats, and
        on the request's timeline where one is kept."""
        out = self.output_processor
        out.stats.admit.observe(core_req.submitted_at - received_at)
        out.record_event(core_req.request_id, ev.RECEIVED, ts=received_at)
        out.record_event(core_req.request_id, ev.SUBMITTED,
                         ts=core_req.submitted_at)

    def _submit_to_core(self, core_req) -> None:
        with self._core_lock:
            # The put's stamp rides on the request; the engine thread
            # observes vdt:input_queue_seconds where it takes it.
            core_req.submitted_at = time.monotonic()
            self.core.add_request(core_req)
            # Journaled only once the add landed in the CURRENT core
            # incarnation (both under the core lock): a restart+replay
            # can then never race this submission into a double add.
            with self._journal_lock:
                self._journal[core_req.request_id] = core_req

    def _abort_in_core(self, request_ids: list[str]) -> None:
        """Core-side abort from the event loop. The abort must never be
        DROPPED (a request left decoding to max_tokens holds KV pages
        for its whole budget), but the loop must also never stall on the
        core lock for a restart's duration — so the lock wait happens on
        an executor thread. Ordering with a concurrent restart is safe
        either way: the journal entries are already popped, so a replay
        skips these requests, and aborting an id the fresh core never
        saw is a scheduler no-op."""
        if not request_ids:
            return

        def _do() -> None:
            with self._core_lock:
                try:
                    self.core.abort_requests(request_ids)
                except Exception:  # noqa: BLE001 - dead/racing shutdown
                    pass

        try:
            asyncio.get_running_loop().run_in_executor(None, _do)
        except RuntimeError:
            # No running loop (teardown path): do it inline.
            _do()

    async def abort(self, request_id: str) -> None:
        q = self.request_queues.pop(request_id, None)
        if q is not None:
            # Wake any generate() consumer blocked on this queue.
            q.put_nowait(_ABORTED)
        with self._journal_lock:
            self._journal.pop(request_id, None)
        self.output_processor.abort_requests([request_id])
        self._abort_in_core([request_id])

    async def encode(self, prompt,
                     request_id: Optional[str] = None,
                     pooling_params: Optional[dict] = None):
        """Embedding request: returns the terminal PoolingOutput
        (reference: AsyncLLM.encode). The processor fills the pooling
        default per model kind (last for decoders, cls for encoders)."""
        async for out in self.generate(
                prompt, SamplingParams(temperature=0.0, max_tokens=1),
                request_id=request_id,
                pooling_params=pooling_params or {}):
            if getattr(out, "finished", True):
                return out
        raise RuntimeError("encode stream ended without a result")

    async def get_stats(self, include_events: bool = True) -> dict:
        """include_events=False skips the core-side event-ring drain —
        REQUIRED for callers that may cancel the await (wait_for
        timeouts): the drain is destructive, and an abandoned response
        silently discards the drained batch."""
        stats = await self._utility("get_stats", include_events)
        # Core-side lifecycle events are drained (destructively) per
        # stats poll; retain them front-side for /debug recent-events.
        events = stats.pop("timeline_events", None)
        if events:
            self.output_processor.core_events.absorb(events)
            if self.output_processor.assembler is not None:
                self.output_processor.assembler.feed(events)
        return stats

    async def get_debug_state(self) -> dict:
        """Live engine-core introspection (scheduler queues, per-request
        progress, batch-pipeline occupancy) for the /debug endpoints
        and the SIGUSR1 dump."""
        return await self._utility("get_debug_state")

    def supervisor_state(self) -> dict:
        """Restart-supervisor snapshot for /debug/engine. Uses the
        supervisor's read-only peek(): _expire()/exhausted REBUILD the
        attempts list, and this runs on the event-loop thread while the
        death handler may be inside next_delay() — a concurrent rebuild
        could discard a just-granted attempt and weaken the circuit
        breaker."""
        sup = self._supervisor
        in_window, exhausted = sup.peek()
        return {
            "max_attempts": sup.max_attempts,
            "window_s": sup.window_s,
            "attempts_in_window": in_window,
            "exhausted": exhausted,
            "engine_deaths": self.stats_engine_deaths(),
            "journal_depth": len(self._journal),
            "errored": self.errored,
            "dead_error": (str(self._dead_error)
                           if self._dead_error is not None else None),
            "core": type(self.core).__name__,
        }

    def stats_engine_deaths(self) -> int:
        return self.output_processor.stats.num_engine_deaths

    async def profile(self, action: str = "start"):
        """Start/stop a device trace on the core (reference:
        AsyncLLM.start_profile/stop_profile RPCs). Returns the trace
        dir, or a per-replica list under multiprocess DP."""
        return await self._utility("profile", action)

    def _send_utility_locked(self, method: str, args: tuple) -> int:
        # Same discipline as _submit_to_core: the zmq input socket is
        # not thread-safe, and submissions/aborts/restarts all touch it
        # under _core_lock from other threads.
        with self._core_lock:
            return self.core.send_utility(method, *args)

    async def _utility(self, method: str, *args):
        if isinstance(self.core, BackgroundEngineCore):
            call = getattr(self.core.core, method)
            if method == "profile":
                # start_trace and stop_trace take seconds: off the loop,
                # which keeps serving (EngineCore.profile holds its own
                # lock against the step's deadline sweep).
                return await asyncio.get_running_loop().run_in_executor(
                    None, call, *args)
            return call(*args)
        # MP core: the pump thread owns the output socket; poll for the
        # stashed result. The send runs off-loop under the core lock.
        call_id = await asyncio.get_running_loop().run_in_executor(
            None, self._send_utility_locked, method, args)
        sentinel = object()
        for _ in range(500):
            value = self.core.fetch_result(call_id, sentinel)
            if value is not sentinel:
                if isinstance(value, Exception):
                    raise value
                return value
            await asyncio.sleep(0.02)
        raise TimeoutError(f"{method} RPC timed out")

    def shutdown(self) -> None:
        self._stopped = True
        self._stop_event.set()
        if self._pump is not None:
            self._pump.join(timeout=5)
        # Under the core lock: a supervisor restart already in flight
        # must finish before teardown, or the freshly respawned core
        # would outlive this shutdown with no owner.
        with self._core_lock:
            self.core.shutdown()
