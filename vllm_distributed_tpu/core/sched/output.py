"""Scheduler <-> worker wire types.

Reference: vllm/v1/core/sched/output.py (``SchedulerOutput`` carrying
NewRequestData/CachedRequestData, plus the fork's ``TokenParallelAllocation``
at output.py:84 carried on SchedulerOutput at output.py:168) and
vllm/v1/outputs.py (``ModelRunnerOutput``).
"""

from dataclasses import dataclass, field
from typing import Any, Optional

from vllm_distributed_tpu.sampling_params import SamplingParams


@dataclass
class NewRequestData:
    """First time a request is handed to the workers."""

    req_id: str
    prompt_token_ids: list[int]
    sampling_params: SamplingParams
    block_ids: list[int]
    num_computed_tokens: int
    # The window group's page ids, slot for slot beside ``block_ids``
    # (core/kv_cache_manager.py GroupedKVCacheManager); None for a model
    # with one cache group.
    window_block_ids: "list[int] | None" = None
    # Multi-LoRA adapter selection ({"name", "path"}; see models/lora.py).
    lora_request: "dict | None" = None
    # Pooling/embedding request marker ({"type": "last"}).
    pooling_params: "dict | None" = None
    # Positioned pre-computed image embeddings (multimodal/
    # MultiModalInput list); the runner substitutes their rows at the
    # placeholder positions during prefill.
    mm_inputs: "list | None" = None


@dataclass
class CachedRequestData:
    """Incremental update for requests the workers already know."""

    req_ids: list[str] = field(default_factory=list)
    resumed_from_preemption: list[bool] = field(default_factory=list)
    # Tokens appended since last step (resumed requests carry all tokens).
    new_token_ids: list[list[int]] = field(default_factory=list)
    new_block_ids: list[list[int]] = field(default_factory=list)
    # Beside ``new_block_ids``, entry for entry, under two cache groups;
    # empty for a model with one.
    new_window_block_ids: list[list[int]] = field(default_factory=list)
    num_computed_tokens: list[int] = field(default_factory=list)


@dataclass
class TokenParallelAllocation:
    """Which token-parallel rank owns each scheduled request's KV.

    TPU analogue of the fork's TokenParallelAllocation
    (v1/core/sched/output.py:84): rank indexes the "token" mesh axis.
    Carried for observability/stats and wire parity — the runner itself
    derives ownership from each request's page range (every page of a
    request lives inside its rank's pool partition), which stays correct
    across preemption and needs no extra trust in the wire data.
    """

    req_to_rank: dict[str, int] = field(default_factory=dict)
    tokens_per_rank: list[int] = field(default_factory=list)


@dataclass
class SchedulerOutput:
    scheduled_new_reqs: list[NewRequestData] = field(default_factory=list)
    scheduled_cached_reqs: CachedRequestData = field(
        default_factory=CachedRequestData)
    # req_id -> tokens to run this step (new prompt chunk or 1 + spec len).
    num_scheduled_tokens: dict[str, int] = field(default_factory=dict)
    total_num_scheduled_tokens: int = 0
    # The grant's composition: tokens of requests still in their prompt,
    # and the number of requests past it (for the step's spans).
    num_prefill_tokens: int = 0
    num_decode_reqs: int = 0
    # req_id -> speculative draft tokens being verified this step.
    scheduled_spec_decode_tokens: dict[str, list[int]] = \
        field(default_factory=dict)
    finished_req_ids: set[str] = field(default_factory=set)
    # Disaggregated-prefill metadata piggybacking on the step, consumed by
    # the worker-side connector (reference: base.py build_connector_meta).
    kv_connector_metadata: Optional[Any] = None
    # Structured output: req_id -> [V] bool numpy mask for the request's
    # next sampled token (reference: the grammar bitmask shipped with the
    # scheduler output and applied at gpu_model_runner.py:1433).
    structured_masks: Optional[dict[str, Any]] = None
    # Token-parallel ownership for this step (None when tknp disabled).
    token_parallel_allocation: Optional[TokenParallelAllocation] = None
    # >1: the worker runs this many fused decode steps device-side before
    # returning (TPU analogue of the reference's multi-step scheduling +
    # csrc/prepare_inputs/advance_step.cu — the host loop costs one
    # roundtrip per burst instead of per token). Slots for all steps are
    # pre-allocated via num_lookahead_tokens.
    multi_step: int = 1
    # SSM state cache (core/state_cache.py): snapshot copies the runner
    # executes AFTER this step's forward (each request's state rows ->
    # its assigned pool slot; preempt-parks ride here too — a parked
    # request runs no tokens, so pre/post makes no difference for it),
    # and restores it executes BEFORE the forward (pool slot or host
    # checkpoint file -> the request's state rows, so the segmented
    # scan re-enters mid-sequence via its has_init carry path). Only
    # attached to outputs with scheduled tokens (the zero-token
    # dispatch path does no device work by contract).
    state_saves: "list | None" = None
    state_restores: "list | None" = None
    # Hierarchical KV tiering (core/kv_tier.py), in-proc only like the
    # state directives. ``kv_demotes`` is ONE batched DemoteDirective:
    # pages evicted+reassigned this step whose contents the runner
    # gathers to the host tier BEFORE the forward overwrites them
    # (the gather's DMA overlaps the forward). ``kv_promotes`` are
    # per-request PromoteDirectives: staged tier-hit pages scattered
    # into freshly allocated device pages before the forward, also in
    # dispatch program order AFTER the demote gather (a promote target
    # may be the very page a demote is reading).
    kv_demotes: "object | None" = None
    kv_promotes: "list | None" = None
    # True when the scheduler granted this batch under async scheduling:
    # request.num_computed_tokens was already advanced AT SCHEDULE TIME
    # (so step N+1 could be granted while step N executes), and
    # update_from_output must not advance it again.
    async_scheduled: bool = False


EMPTY_MODEL_RUNNER_OUTPUT: "ModelRunnerOutput"


@dataclass
class ModelRunnerOutput:
    """Per-step result shipped from workers back to the scheduler
    (reference: vllm/v1/outputs.py ModelRunnerOutput)."""

    # Requests in batch order.
    req_ids: list[str] = field(default_factory=list)
    # Sampled token ids per request (len 0 for partial-prefill steps,
    # >1 with accepted speculative tokens).
    sampled_token_ids: list[list[int]] = field(default_factory=list)
    # Optional per-request, per-token logprobs: list aligned with
    # sampled_token_ids; each entry maps token_id -> logprob.
    logprobs: Optional[list[list[dict[int, float]]]] = None
    # Draft tokens proposed for the *next* step (spec decode).
    spec_token_ids: Optional[list[list[int]]] = None
    # KV-transfer completion notifications (disagg).
    finished_sending: Optional[set[str]] = None
    finished_recving: Optional[set[str]] = None
    # Pulls that errored (peer unreachable / timed out): the scheduler
    # re-queues these requests for LOCAL prefill of the span instead of
    # marking never-written pages computed.
    failed_recving: Optional[set[str]] = None
    # Pooled hidden states for embedding requests that completed their
    # prompt this step: req_id -> list[float].
    pooled: Optional[dict[str, list[float]]] = None
    # Prompt logprobs scored this step: req_id -> list of
    # (prompt_position, {token_id: logprob}) chunk entries; the
    # scheduler buffers them on the request until its first emitted
    # output (reference: prompt_logprobs_dict of v1/outputs.py).
    prompt_logprobs: Optional[dict[str, list]] = None


EMPTY_MODEL_RUNNER_OUTPUT = ModelRunnerOutput()
