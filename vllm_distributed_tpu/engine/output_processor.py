"""Engine-core outputs -> user-facing RequestOutputs.

Reference: vllm/v1/engine/output_processor.py (per-request state in the
client process: detokenize, stop-string detection -> abort signal back to
the core, RequestOutput assembly).
"""

from dataclasses import dataclass, field
from typing import Optional

from vllm_distributed_tpu.config import EngineConfig
from vllm_distributed_tpu.core.sched.scheduler import EngineCoreOutput
from vllm_distributed_tpu.engine.detokenizer import IncrementalDetokenizer
from vllm_distributed_tpu.metrics import events as ev
from vllm_distributed_tpu.metrics.stats import RequestTimes
from vllm_distributed_tpu.outputs import (CompletionOutput,
                                          PoolingOutput,
                                          RequestOutput,
                                          RequestOutputKind)
from vllm_distributed_tpu.request import EngineCoreRequest
from vllm_distributed_tpu.sampling_params import SamplingParams

# Completed-phase duration samples kept per phase for percentile
# reporting (bench); oldest dropped beyond this.
_MAX_PHASE_SAMPLES = 8192


@dataclass
class RequestState:
    request_id: str
    prompt: Optional[str]
    prompt_token_ids: list[int]
    params: SamplingParams
    detokenizer: Optional[IncrementalDetokenizer]
    output_kind: RequestOutputKind = RequestOutputKind.CUMULATIVE
    output_token_ids: list[int] = field(default_factory=list)
    logprobs: list[dict[int, float]] = field(default_factory=list)
    # Running sum of the sampled tokens' logprobs (None without any).
    cumulative_logprob: Optional[float] = None
    num_cached_tokens: int = 0
    finished: bool = False
    finish_reason: Optional[str] = None
    stop_reason: Optional[int | str] = None
    kv_transfer_params: Optional[dict] = None
    # Per-prompt-token logprob dicts (entry 0 None), delivered once by
    # the core after the prompt completes.
    prompt_logprobs: Optional[list] = None
    times: Optional["RequestTimes"] = None
    # Bucketed QoS tenant key (qos.bucket_tenant; None when the QoS
    # plane is off) for the per-tenant goodput family.
    tenant: Optional[str] = None
    # Merged lifecycle timeline: (monotonic_ts, event, detail) — the
    # front-end's own events (arrived/first_token/replay/finished) plus
    # the core-side events riding each EngineCoreOutput. Stitched into
    # phase child spans when the request finishes.
    timeline: list[tuple] = field(default_factory=list)
    # Trace context minted at admission (None when the trace plane is
    # off): stamped onto front-end events so the assembler can resolve
    # them even after its request-id map evicts.
    trace_ctx: Optional[dict] = None


@dataclass
class ProcessedOutputs:
    request_outputs: list[RequestOutput]
    # Requests the front-end decided to finish (stop string hit): the
    # caller must abort them in the scheduler.
    reqs_to_abort: list[str]


class OutputProcessor:

    def __init__(self, config: EngineConfig, tokenizer) -> None:
        self.config = config
        self.tokenizer = tokenizer
        self.request_states: dict[str, RequestState] = {}
        # Front-end latency/throughput stats (reference:
        # v1/metrics/stats.py IterationStats maintained in the output
        # path); rendered into /metrics beside the core's stats.
        from vllm_distributed_tpu import envs
        from vllm_distributed_tpu.metrics.stats import FrontendStats
        self.stats = FrontendStats()
        # SLO goodput targets, read ONCE at construction (the envs
        # registry re-reads os.environ per access; scoring runs per
        # finished request).
        self.stats.slo_ttft_ms = envs.VDT_SLO_TTFT_MS
        self.stats.slo_tpot_ms = envs.VDT_SLO_TPOT_MS
        # Burn-rate watchdog over the goodput plane: only meaningful
        # when at least one SLO target is set (otherwise every request
        # scores good and the burn rate is identically zero).
        if self.stats.slo_enabled:
            from vllm_distributed_tpu.metrics.stats import \
                BurnRateWatchdog
            self.stats.burn = BurnRateWatchdog(
                target=envs.VDT_SLO_TARGET,
                threshold=envs.VDT_SLO_BURN_THRESHOLD)
        # Per-tenant goodput accounting (vdt:tenant_goodput_frac) rides
        # the QoS plane: bucketing shares qos.bucket_tenant with the
        # scheduler so both label spaces stay bounded and agree. Read
        # once, like the SLO targets.
        self._qos_tenants = envs.VDT_QOS
        self._tenant_tracked: set = set()
        self._max_tracked_tenants = envs.VDT_QOS_MAX_TRACKED_TENANTS
        # Per-request spans (reference: tracing.py spans emitted from
        # the output path; gated by otlp_traces_endpoint).
        from vllm_distributed_tpu.tracing import init_tracer
        self.tracer = init_tracer(
            config.observability_config.otlp_traces_endpoint)
        # Front-end lifecycle ledger (arrivals, sheds, deaths, replays)
        # for the /debug endpoints; per-request timelines live on the
        # RequestState. Cached enable flag — envs re-reads os.environ.
        self.events = ev.EventRecorder()
        self.timeline_enabled = self.events.enabled
        # Core-side events (scheduler/engine rings) absorbed from the
        # stats RPC by the engine's get_stats — retained here so the
        # /debug recent-events view spans every component. Always
        # enabled: absorption only happens when recording was on.
        self.core_events = ev.EventRecorder(enabled=True)
        # Fleet-wide causal trace assembly (VDT_TRACE_PLANE): front-end
        # events feed it directly; core/router events arrive via the
        # get_stats drain (already clock-rebased and replica-tagged by
        # the DP client when running multi-replica).
        self.assembler = None
        if ev.trace_plane_enabled():
            from vllm_distributed_tpu.trace_plane import TraceAssembler
            self.assembler = TraceAssembler()
        # Completed per-phase durations (seconds) for percentile
        # reporting; bounded FIFO per phase.
        self.phase_durations: dict[str, list[float]] = {}

    def add_request(
        self, request: EngineCoreRequest, prompt: Optional[str] = None,
        output_kind: RequestOutputKind = RequestOutputKind.CUMULATIVE
    ) -> None:
        params = request.sampling_params
        detok = None
        if self.tokenizer is not None and params.detokenize:
            detok = IncrementalDetokenizer(self.tokenizer, params)
        import time as _time
        arrival = _time.monotonic()
        tenant = None
        if self._qos_tenants:
            from vllm_distributed_tpu.core.sched.qos import bucket_tenant
            tenant = bucket_tenant(request.tenant, self._tenant_tracked,
                                   self._max_tracked_tenants)
        state = RequestState(
            request_id=request.request_id,
            prompt=prompt,
            prompt_token_ids=request.prompt_token_ids,
            params=params,
            detokenizer=detok,
            output_kind=output_kind,
            times=RequestTimes(arrival=arrival),
            tenant=tenant,
            trace_ctx=request.trace_ctx,
        )
        if self.assembler is not None and request.trace_ctx is not None:
            self.assembler.note_admission(request.request_id,
                                          request.trace_ctx)
        if self.timeline_enabled:
            state.timeline.append((arrival, ev.ARRIVED, None))
            detail = {"prompt_tokens": len(request.prompt_token_ids)}
            if state.trace_ctx is not None:
                detail = ev.stamp_trace(detail, state.trace_ctx)
            self.events.record(request.request_id, ev.ARRIVED, detail,
                               ts=arrival)
            if self.assembler is not None:
                self.assembler.add_event(arrival, request.request_id,
                                         ev.ARRIVED, detail)
        self.request_states[request.request_id] = state

    def abort_requests(self, request_ids: list[str]) -> None:
        for req_id in request_ids:
            state = self.request_states.pop(req_id, None)
            if state is not None and self.timeline_enabled:
                detail = ev.stamp_trace(None, state.trace_ctx)
                self.events.record(req_id, ev.ABORTED, detail)
                if self.assembler is not None:
                    import time as _time
                    self.assembler.add_event(_time.monotonic(), req_id,
                                             ev.ABORTED, detail)

    def record_event(self, request_id: str, event: str,
                     detail: Optional[dict] = None,
                     ts: Optional[float] = None) -> None:
        """External lifecycle events (AsyncLLM's engine-death/replay and
        the stamps of a request's way in, the admission gate's sheds)
        onto the request's timeline and the front-end ledger, at ``ts``
        (``time.monotonic()``; now by default)."""
        if not self.timeline_enabled:
            return
        if ts is None:
            import time as _time
            ts = _time.monotonic()
        state = self.request_states.get(request_id)
        if state is not None:
            state.timeline.append((ts, event, detail))
            if state.trace_ctx is not None:
                detail = ev.stamp_trace(detail, state.trace_ctx)
        self.events.record(request_id, event, detail, ts=ts)
        if self.assembler is not None:
            self.assembler.add_event(ts, request_id, event, detail)

    def _finish_timeline(self, state: RequestState,
                         event: str = ev.FINISHED
                         ) -> Optional[list[dict]]:
        """Close a request's timeline: append the terminal event,
        compute its phase intervals, and bank per-phase durations for
        percentile reporting. Returns the phases (None when the
        timeline is disabled)."""
        if not self.timeline_enabled:
            return None
        import time as _time
        now = _time.monotonic()
        detail = {"reason": state.finish_reason}
        state.timeline.append((now, event, detail))
        if self.assembler is not None:
            self.assembler.add_event(
                now, state.request_id, event,
                ev.stamp_trace(detail, state.trace_ctx))
        # Re-base BEFORE sorting: events absorbed from a restarted core
        # carry a fresh monotonic epoch (timestamps behind the old
        # core's by its whole uptime) — sorting raw would interleave
        # the replayed lifecycle into the pre-death one and phase math
        # would go negative. Then sort a COPY and swap it in
        # (_emit_span reuses it): the AsyncLLM pump thread may append
        # ENGINE_DEATH concurrently, and an in-place sort of a
        # mutating list raises ValueError.
        state.timeline = sorted(ev.rebase_epochs(state.timeline),
                                key=lambda e: e[0])
        phases = ev.phases_from_timeline(state.timeline, now=now)
        for name, dur in ev.phase_durations(phases).items():
            bank = self.phase_durations.setdefault(name, [])
            bank.append(dur)
            if len(bank) > _MAX_PHASE_SAMPLES:
                del bank[:len(bank) - _MAX_PHASE_SAMPLES]
        return phases

    def get_num_unfinished_requests(self) -> int:
        return len(self.request_states)

    def has_unfinished_requests(self) -> bool:
        return bool(self.request_states)

    # ------------------------------------------------------------------
    def process_outputs(
            self, core_outputs: list[EngineCoreOutput]) -> ProcessedOutputs:
        request_outputs: list[RequestOutput] = []
        reqs_to_abort: list[str] = []
        for out in core_outputs:
            state = self.request_states.get(out.req_id)
            if state is None:
                continue  # aborted while output was in flight
            if out.events and self.timeline_enabled:
                # Core-side lifecycle events riding this output.
                state.timeline.extend(out.events)
            if out.pooled is not None:
                # Embedding request: one terminal pooled result.
                self.stats.on_finished(state.times,
                                       len(state.prompt_token_ids))
                state.finished = True
                state.finish_reason = out.finish_reason
                phases = self._finish_timeline(state)
                if self.tracer is not None:
                    self._emit_span(state, phases)
                request_outputs.append(PoolingOutput(
                    request_id=out.req_id, embedding=out.pooled,
                    num_prompt_tokens=len(state.prompt_token_ids)))
                del self.request_states[out.req_id]
                continue
            state.output_token_ids.extend(out.new_token_ids)
            if out.new_token_ids:
                first = state.times.first_token is None
                self.stats.on_tokens(state.times, len(out.new_token_ids))
                if first and self.timeline_enabled:
                    state.timeline.append(
                        (state.times.first_token, ev.FIRST_TOKEN, None))
            if out.logprobs:
                state.logprobs.extend(out.logprobs)
                state.cumulative_logprob = sum(
                    (next(iter(lp.values())) for lp in out.logprobs),
                    state.cumulative_logprob or 0.0)
            state.num_cached_tokens = out.num_cached_tokens

            stop_str = None
            detok = state.detokenizer
            if detok is not None:
                stop_str = detok.update(out.new_token_ids)
                self.stats.detokenized_tokens[detok.path] += len(
                    out.new_token_ids)

            finish_reason = out.finish_reason
            stop_reason = out.stop_reason
            if stop_str is not None and finish_reason is None:
                # Front-end stop: tell the core to abort the request.
                finish_reason = "stop"
                stop_reason = stop_str
                reqs_to_abort.append(out.req_id)

            finished = finish_reason is not None
            state.finished = finished
            state.finish_reason = finish_reason
            state.stop_reason = stop_reason
            if out.kv_transfer_params is not None:
                state.kv_transfer_params = out.kv_transfer_params
            if out.prompt_logprobs is not None:
                state.prompt_logprobs = [
                    ({int(k): float(v) for k, v in d.items()}
                     if d is not None else None)
                    for d in out.prompt_logprobs
                ]
            if finished:
                self.stats.on_finished(state.times,
                                       len(state.prompt_token_ids))
                self.stats.on_slo(state.times,
                                  len(state.output_token_ids),
                                  tenant=state.tenant)
                phases = self._finish_timeline(
                    state, ev.ABORTED if finish_reason == "abort"
                    else ev.FINISHED)
                if self.tracer is not None:
                    self._emit_span(state, phases)
                if state.detokenizer is not None:
                    # Emit any text held back waiting for more context.
                    state.detokenizer.flush()

            kind = state.output_kind
            if finished or kind is not RequestOutputKind.FINAL_ONLY:
                request_outputs.append(
                    self._make_request_output(state, out))
                self.stats.request_outputs[kind.value] += 1
            if finished:
                del self.request_states[out.req_id]
        return ProcessedOutputs(request_outputs, reqs_to_abort)

    def _emit_span(self, state: RequestState,
                   phases: Optional[list[dict]] = None) -> None:
        """One parent span per request; the lifecycle timeline's phase
        intervals (queue, kv_pull, prefill, decode, stalls) ride as
        child spans. A replayed continuation keeps its original request
        id, so the parent span survives an engine restart with the
        journal/replay events on its timeline."""
        import time as _time

        from vllm_distributed_tpu.tracing import SpanAttributes as SA
        now = _time.monotonic()
        t = state.times
        events = None
        if state.timeline:
            # _finish_timeline already sorted the timeline in place.
            t0 = state.timeline[0][0]
            events = [[round(ts - t0, 6), event, detail]
                      for ts, event, detail in state.timeline]
        attrs = {
            SA.GEN_AI_REQUEST_ID: state.request_id,
            SA.GEN_AI_REQUEST_MAX_TOKENS: state.params.max_tokens,
            SA.GEN_AI_REQUEST_TEMPERATURE: state.params.temperature,
            SA.GEN_AI_USAGE_PROMPT_TOKENS: len(state.prompt_token_ids),
            SA.GEN_AI_USAGE_COMPLETION_TOKENS:
                len(state.output_token_ids),
            SA.GEN_AI_LATENCY_TIME_TO_FIRST_TOKEN:
                (t.first_token - t.arrival
                 if t and t.first_token is not None else None),
            SA.GEN_AI_LATENCY_E2E: (now - t.arrival) if t else None,
            SA.GEN_AI_RESPONSE_FINISH_REASON: state.finish_reason,
        }
        if state.trace_ctx is not None:
            attrs[SA.GEN_AI_TRACE_ID] = state.trace_ctx.get("trace_id")
        self.tracer.emit(attrs, phases=phases, events=events)

    def _make_request_output(self, state: RequestState,
                             out: EngineCoreOutput) -> RequestOutput:
        detok = state.detokenizer
        if state.output_kind is RequestOutputKind.DELTA:
            # The step's own: nothing of the request's whole is copied.
            text = detok.new_text if detok is not None else ""
            token_ids = out.new_token_ids
            logprobs = out.logprobs or None
        else:
            text = detok.output_text if detok is not None else ""
            token_ids = list(state.output_token_ids)
            logprobs = list(state.logprobs) if state.logprobs else None
        completion = CompletionOutput(
            index=0,
            text=text,
            token_ids=token_ids,
            logprobs=logprobs,
            cumulative_logprob=state.cumulative_logprob,
            finish_reason=state.finish_reason,
            stop_reason=state.stop_reason,
        )
        return RequestOutput(
            request_id=state.request_id,
            prompt=state.prompt,
            prompt_token_ids=state.prompt_token_ids,
            outputs=[completion],
            finished=state.finished,
            num_cached_tokens=state.num_cached_tokens,
            kv_transfer_params=state.kv_transfer_params,
            prompt_logprobs=state.prompt_logprobs,
        )
