"""Engine-core request state (reference: vllm/v1/request.py).

A ``Request`` is the scheduler-side record of one in-flight generation: its
token ids, how many tokens have KV computed, its lifecycle status, and the
bookkeeping the KV-cache manager needs (block hashes are kept separately in
the manager).
"""

import copy
import enum
import time
from dataclasses import dataclass, field
from typing import Any, Optional

from vllm_distributed_tpu.sampling_params import SamplingParams


class RequestStatus(enum.IntEnum):
    """Lifecycle of a request (reference: v1/request.py RequestStatus)."""

    WAITING = 0
    RUNNING = 1
    PREEMPTED = 2
    # Held out of the waiting queue until an async KV pull lands
    # (reference: v1/request.py WAITING_FOR_REMOTE_KVS).
    WAITING_FOR_REMOTE_KVS = 3
    # Terminal states below.
    FINISHED_STOPPED = 4
    FINISHED_LENGTH_CAPPED = 5
    FINISHED_ABORTED = 6
    FINISHED_IGNORED = 7

    @staticmethod
    def is_finished(status: "RequestStatus") -> bool:
        return status >= RequestStatus.FINISHED_STOPPED

    @staticmethod
    def get_finished_reason(status: "RequestStatus") -> Optional[str]:
        return _FINISHED_REASONS.get(status)


_FINISHED_REASONS: dict[RequestStatus, str] = {
    RequestStatus.FINISHED_STOPPED: "stop",
    RequestStatus.FINISHED_LENGTH_CAPPED: "length",
    RequestStatus.FINISHED_ABORTED: "abort",
    RequestStatus.FINISHED_IGNORED: "length",
}


@dataclass
class EngineCoreRequest:
    """Wire format between the engine front-end and the core
    (reference: v1/engine/__init__.py EngineCoreRequest)."""

    request_id: str
    prompt_token_ids: list[int]
    sampling_params: SamplingParams
    eos_token_id: Optional[int] = None
    # Epoch timestamp (user-facing stats), never deadline arithmetic.
    arrival_time: float = field(default_factory=time.time)  # wallclock-ok
    # Priority class (lower = more important, matching the scheduler's
    # priority policy): <= 0 is interactive, > 0 is best-effort — the
    # admission gate sheds best-effort traffic first under overload.
    priority: int = 0
    # Tenant identity (the OpenAI body's "tenant"/"user" field): labels
    # per-class shedding and debug introspection; never trusted for
    # isolation.
    tenant: Optional[str] = None
    # Disaggregated prefill routing (reference: kv_transfer_params on the
    # request, nixl_connector.py:205).
    kv_transfer_params: Optional[dict[str, Any]] = None
    # Multi-LoRA: {"name": ..., "path": ...} selecting the adapter
    # (reference: LoRARequest on add_request, vllm/lora/request.py).
    lora_request: Optional[dict[str, str]] = None
    # Embedding/pooling request: {"type": "last"} (reference:
    # vllm/pooling_params.py; pooled hidden state instead of sampling).
    pooling_params: Optional[dict[str, Any]] = None
    # Multimodal: positioned pre-computed encoder outputs, one per image
    # (multimodal/__init__.py MultiModalInput; reference: the mm_inputs
    # of v1/engine/__init__.py EngineCoreRequest).
    mm_inputs: Optional[list] = None
    # Distributed trace plane (VDT_TRACE_PLANE): {"trace_id": hex,
    # "span_id": hex} minted at admission. Deep-copied by
    # continuation_request and re-admitted verbatim by the disagg
    # handoff, so every hop of one request stamps the SAME trace id —
    # that is the cross-replica causal link. None when the plane is off
    # (serial.py then omits the key: old-wire byte-identical).
    trace_ctx: Optional[dict[str, Any]] = None
    # ``time.monotonic()`` of the front end putting the request on the
    # transport to the engine core (AsyncLLM._submit_to_core); the core
    # observes vdt:input_queue_seconds where it takes the request. None
    # from a caller that stamps nothing (the synchronous engine, an old
    # peer): taken and not observed.
    submitted_at: Optional[float] = None


def continuation_request(orig: EngineCoreRequest,
                         generated: list[int]) -> EngineCoreRequest:
    """Continuation prefill for a crash-recovery replay: the journaled
    request's prompt absorbs the tokens already delivered downstream and
    the sampling budget shrinks by the same amount, so a respawned core
    (or a failover replica) resumes exactly where the dead one stopped —
    with greedy sampling the resumed stream is token-identical to an
    uninterrupted run."""
    req = copy.deepcopy(orig)
    req.submitted_at = None     # whoever puts it on a transport stamps it
    # Never replay a remote-KV pull: by replay time the producer's
    # deferred-free registration is consumed or expired, so re-entering
    # WAITING_FOR_REMOTE_KVS would only burn the watchdog ladder before
    # degrading anyway — go straight to local (re)compute.
    req.kv_transfer_params = None
    if not generated:
        return req
    req.prompt_token_ids = list(orig.prompt_token_ids) + list(generated)
    sp = req.sampling_params
    if sp.max_tokens is not None:
        sp.max_tokens = max(1, sp.max_tokens - len(generated))
    if getattr(sp, "min_tokens", 0):
        sp.min_tokens = max(0, sp.min_tokens - len(generated))
    return req


class Request:
    """Scheduler-side mutable request state."""

    def __init__(
        self,
        request_id: str,
        prompt_token_ids: list[int],
        sampling_params: SamplingParams,
        eos_token_id: Optional[int] = None,
        arrival_time: Optional[float] = None,
        priority: int = 0,
        kv_transfer_params: Optional[dict[str, Any]] = None,
        lora_request: Optional[dict[str, str]] = None,
        pooling_params: Optional[dict[str, Any]] = None,
        mm_inputs: Optional[list] = None,
        tenant: Optional[str] = None,
        trace_ctx: Optional[dict[str, Any]] = None,
    ) -> None:
        self.request_id = request_id
        self.prompt_token_ids = prompt_token_ids
        # Deep-copy: the engine mutates stop sets / max_tokens below, and
        # callers routinely share one SamplingParams across a batch.
        self.sampling_params = copy.deepcopy(sampling_params)
        sampling_params = self.sampling_params
        self.eos_token_id = eos_token_id
        self.arrival_time = (time.time()  # wallclock-ok: epoch stat
                             if arrival_time is None else arrival_time)
        self.priority = priority
        self.tenant = tenant
        self.kv_transfer_params = kv_transfer_params
        self.lora_request = lora_request
        self.pooling_params = pooling_params
        self.mm_inputs = mm_inputs
        self.trace_ctx = trace_ctx
        # Content hash of the images, salted into the block hashes so
        # identical placeholder token ids with different images never
        # share prefix-cache pages (kv_cache_utils.hash_request_tokens).
        self.mm_hash: Optional[bytes] = None
        if mm_inputs:
            from vllm_distributed_tpu.multimodal import mm_content_hash
            self.mm_hash = mm_content_hash(mm_inputs)

        self.status = RequestStatus.WAITING
        # time.monotonic() of Scheduler.add_request until the request's
        # first grant (vdt:request_queue_seconds), None afterwards.
        self.queued_at: Optional[float] = None
        self.stop_reason: Optional[int | str] = None

        # Lifecycle timeline (metrics/events.py): (monotonic_ts, event,
        # detail) tuples recorded by the scheduler at every transition,
        # drained onto the next EngineCoreOutput for this request so
        # the front-end can stitch phase spans. Appended only at
        # lifecycle TRANSITIONS, never per token — which is why the
        # async run-ahead grant is recorded once (first grant), not per
        # speculative step.
        self.events: list[tuple] = []
        self.async_spec_granted = False

        # All token ids: prompt + generated. The scheduler appends sampled
        # tokens in update_from_output.
        self._all_token_ids: list[int] = list(prompt_token_ids)
        self.output_token_ids: list[int] = []
        self.spec_token_ids: list[int] = []

        # Prompt-logprob entries scored so far (entry index ->
        # {token: lp}); assembled into the first emitted output once
        # the prompt completes. Dict-keyed so a preemption re-run
        # overwrites rather than duplicates.
        self.prompt_lp_entries: dict[int, dict] = {}
        self.prompt_lp_delivered = False

        # Tokens whose KV is present on device. Grows by num_scheduled
        # each step (speculative: adjusted down on rejection).
        self.num_computed_tokens = 0
        # Prefix-cache hits recorded at first schedule, for stats.
        self.num_cached_tokens = -1
        # Tokens an async KV pull will make computed once it lands
        # (WAITING_FOR_REMOTE_KVS bookkeeping; applied by the scheduler
        # when the worker reports finished_recving).
        self.num_external_computed_tokens = 0
        # Watchdog bookkeeping for the WAITING_FOR_REMOTE_KVS hold:
        # sweep deadline (unix seconds; set at hold entry) and how many
        # times the pull was retried before degrading to local prefill.
        self.remote_kv_deadline: Optional[float] = None
        self.num_kv_pull_retries = 0
        # Number of preemptions experienced (stats).
        self.num_preemptions = 0
        # Token-parallel rank owning this request's KV (assigned by the
        # scheduler at admission when token_parallel_size > 1; sticky
        # across preemption so resume refills the same shard's pool).
        self.tknp_rank: Optional[int] = None

        sampling_params.update_from_tokenizer(eos_token_id)

        if sampling_params.max_tokens is None:
            sampling_params.max_tokens = 2**31

    @classmethod
    def from_engine_core_request(cls, req: EngineCoreRequest) -> "Request":
        return cls(
            request_id=req.request_id,
            prompt_token_ids=req.prompt_token_ids,
            sampling_params=req.sampling_params,
            eos_token_id=req.eos_token_id,
            arrival_time=req.arrival_time,
            priority=req.priority,
            kv_transfer_params=req.kv_transfer_params,
            lora_request=req.lora_request,
            pooling_params=req.pooling_params,
            mm_inputs=req.mm_inputs,
            tenant=req.tenant,
            trace_ctx=req.trace_ctx,
        )

    # ------------------------------------------------------------------
    @property
    def num_prompt_tokens(self) -> int:
        return len(self.prompt_token_ids)

    @property
    def num_output_tokens(self) -> int:
        return len(self.output_token_ids)

    @property
    def num_tokens(self) -> int:
        return len(self._all_token_ids)

    @property
    def num_tokens_with_spec(self) -> int:
        return len(self._all_token_ids) + len(self.spec_token_ids)

    @property
    def all_token_ids(self) -> list[int]:
        return self._all_token_ids

    def append_output_token_ids(self, token_ids: int | list[int]) -> None:
        if isinstance(token_ids, int):
            token_ids = [token_ids]
        self.output_token_ids.extend(token_ids)
        self._all_token_ids.extend(token_ids)

    @property
    def is_finished(self) -> bool:
        return RequestStatus.is_finished(self.status)

    def get_finished_reason(self) -> Optional[str]:
        return RequestStatus.get_finished_reason(self.status)

    def __repr__(self) -> str:
        return (f"Request(id={self.request_id}, status={self.status.name}, "
                f"prompt={self.num_prompt_tokens}t, "
                f"out={self.num_output_tokens}t, "
                f"computed={self.num_computed_tokens}t)")
