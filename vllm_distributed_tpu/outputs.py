"""User-facing request outputs (reference: vllm/outputs.py)."""

import enum
from dataclasses import dataclass, field
from typing import Optional


class RequestOutputKind(enum.Enum):
    """What each RequestOutput of a request's stream holds (reference:
    vllm/sampling_params.py RequestOutputKind). The CALLER of
    ``generate`` chooses it from what its own request says; the values
    are vdt:frontend_request_outputs_total's ``kind`` labels."""

    # The whole completion so far, in every output.
    CUMULATIVE = "cumulative"
    # What the step added: its text, its token ids, their logprobs.
    DELTA = "delta"
    # Nothing until the request finishes, then the whole completion.
    FINAL_ONLY = "final"


@dataclass
class CompletionOutput:
    """One generated completion for a request."""

    index: int
    # Under RequestOutputKind.DELTA text, token_ids and logprobs are the
    # step's own; cumulative_logprob is the request's so far in any kind.
    text: str
    token_ids: list[int]
    cumulative_logprob: Optional[float] = None
    logprobs: Optional[list[dict[int, float]]] = None
    finish_reason: Optional[str] = None  # "stop" | "length" | "abort"
    stop_reason: Optional[int | str] = None

    def finished(self) -> bool:
        return self.finish_reason is not None


@dataclass
class RequestOutput:
    """Aggregated output returned from LLMEngine.step() / AsyncLLM.generate."""

    request_id: str
    prompt: Optional[str]
    prompt_token_ids: list[int]
    outputs: list[CompletionOutput]
    finished: bool
    metrics: Optional[dict] = None
    num_cached_tokens: int = 0
    # Disaggregated prefill: a producer's final output carries the pull
    # coordinates the decode-side request needs (reference: vllm/outputs.py
    # RequestOutput.kv_transfer_params).
    kv_transfer_params: Optional[dict] = None
    # Per-prompt-token logprob dicts when SamplingParams.prompt_logprobs
    # was set: entry 0 is None, entry i maps token_id -> logprob of
    # prompt[i] given the prefix (reference: vllm/outputs.py
    # RequestOutput.prompt_logprobs).
    prompt_logprobs: Optional[list] = None

    @property
    def text(self) -> str:
        return self.outputs[0].text if self.outputs else ""


@dataclass
class PoolingOutput:
    """Embedding/pooling result (reference: vllm/outputs.py pooling path)."""

    request_id: str
    embedding: list[float] = field(default_factory=list)
    num_prompt_tokens: int = 0
    finished: bool = True
