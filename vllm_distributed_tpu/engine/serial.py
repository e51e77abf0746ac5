"""msgpack serialization for the client <-> engine-core boundary.

Reference: vllm/v1/serial_utils.py (MsgpackEncoder/Decoder over msgspec).
msgspec is not in this image, so the wire format is plain msgpack with
explicit to/from-dict converters for the two dataclasses that cross the
process boundary (EngineCoreRequest in, EngineCoreOutput out). Tensors
never cross this boundary — token ids and logprobs are plain ints/floats.
"""

from dataclasses import asdict
from typing import Any

import msgpack
import numpy as np

from vllm_distributed_tpu.core.sched.scheduler import EngineCoreOutput
from vllm_distributed_tpu.multimodal import MultiModalInput
from vllm_distributed_tpu.request import EngineCoreRequest
from vllm_distributed_tpu.sampling_params import SamplingParams


def pack(obj: Any) -> bytes:
    return msgpack.packb(obj, use_bin_type=True)


def unpack(data: bytes) -> Any:
    # strict_map_key=False: logprob maps are keyed by int token ids.
    return msgpack.unpackb(data, raw=False, strict_map_key=False)


# ---------------------------------------------------------------------------
def encode_request(req: EngineCoreRequest) -> dict:
    sp = asdict(req.sampling_params)
    sp.pop("_all_stop_token_ids", None)
    d = {
        "request_id": req.request_id,
        "prompt_token_ids": req.prompt_token_ids,
        "sampling_params": sp,
        "eos_token_id": req.eos_token_id,
        "arrival_time": req.arrival_time,
        "priority": req.priority,
        "tenant": req.tenant,
        "kv_transfer_params": req.kv_transfer_params,
        "lora_request": req.lora_request,
        "pooling_params": req.pooling_params,
        "mm_inputs": ([{
            "embeds": np.ascontiguousarray(m.embeds).tobytes(),
            "shape": list(m.embeds.shape),
            "dtype": str(m.embeds.dtype),
            "offset": m.offset,
        } for m in req.mm_inputs] if req.mm_inputs else None),
    }
    # Additive wire key, emitted ONLY when a trace context exists:
    # with VDT_TRACE_PLANE=0 nothing mints one, so the encoded map (and
    # its msgpack bytes) are byte-identical to the pre-trace-plane
    # wire. Old decoders construct from known keys and ignore extras,
    # so a trace-stamped request is also accepted by a pre-trace-plane
    # peer (tolerance pinned by tests/engine/test_serial_trace.py).
    if req.trace_ctx is not None:
        d["trace_ctx"] = req.trace_ctx
    # Likewise additive: the front end's stamp of the put.
    if req.submitted_at is not None:
        d["submitted_at"] = req.submitted_at
    return d


def decode_request(d: dict) -> EngineCoreRequest:
    return EngineCoreRequest(
        request_id=d["request_id"],
        prompt_token_ids=list(d["prompt_token_ids"]),
        sampling_params=SamplingParams(**d["sampling_params"]),
        eos_token_id=d["eos_token_id"],
        arrival_time=d["arrival_time"],
        priority=d["priority"],
        tenant=d.get("tenant"),
        kv_transfer_params=d["kv_transfer_params"],
        lora_request=d.get("lora_request"),
        pooling_params=d.get("pooling_params"),
        # .get(): absent on the pre-trace-plane wire (old peer).
        trace_ctx=d.get("trace_ctx"),
        submitted_at=d.get("submitted_at"),
        mm_inputs=([
            MultiModalInput(
                embeds=np.frombuffer(m["embeds"],
                                     dtype=m["dtype"]).reshape(
                                         m["shape"]),
                offset=m["offset"]) for m in d["mm_inputs"]
        ] if d.get("mm_inputs") else None),
    )


def encode_output(out: EngineCoreOutput) -> list:
    return [out.req_id, out.new_token_ids, out.finish_reason,
            out.stop_reason, out.num_cached_tokens, out.logprobs,
            out.kv_transfer_params, out.pooled, out.prompt_logprobs,
            ([list(e) for e in out.events] if out.events else None)]


def decode_output(v: list) -> EngineCoreOutput:
    (req_id, new_token_ids, finish_reason, stop_reason, cached, lps,
     kv_params, pooled, prompt_lps, events) = v
    return EngineCoreOutput(
        req_id=req_id,
        new_token_ids=list(new_token_ids),
        finish_reason=finish_reason,
        stop_reason=stop_reason,
        num_cached_tokens=cached,
        logprobs=lps,
        kv_transfer_params=kv_params,
        pooled=pooled,
        prompt_logprobs=prompt_lps,
        events=([tuple(e) for e in events] if events else None),
    )
