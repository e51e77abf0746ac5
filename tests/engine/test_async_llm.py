"""AsyncLLM streaming semantics (reference: tests/v1/engine/
test_async_llm.py — generate streams, cancellation aborts upstream)."""

import asyncio

import pytest

from tests.engine.test_llm_engine import checkpoint, hf_greedy  # noqa: F401
from vllm_distributed_tpu.engine.arg_utils import EngineArgs
from vllm_distributed_tpu.engine.async_llm import AsyncLLM
from vllm_distributed_tpu.sampling_params import SamplingParams


def make_async_engine(path, **overrides) -> AsyncLLM:
    args = dict(model=path, dtype="float32", block_size=4,
                num_gpu_blocks_override=128, max_model_len=64,
                max_num_batched_tokens=64, max_num_seqs=8)
    args.update(overrides)
    return AsyncLLM(EngineArgs(**args).create_engine_config(),
                    load_tokenizer=False)


def test_async_generate_streams_and_matches_hf(checkpoint):
    path, hf = checkpoint
    engine = make_async_engine(path)

    async def run():
        prompt = [3, 17, 92, 45, 8]
        sp = SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True)
        seen = []
        async for out in engine.generate(prompt, sp, request_id="a1"):
            seen.append(list(out.outputs[0].token_ids))
        return seen

    try:
        seen = asyncio.run(run())
    finally:
        engine.shutdown()
    _, hf_model = checkpoint
    want = hf_greedy(hf_model, [3, 17, 92, 45, 8], 8)
    assert seen[-1] == want
    assert len(seen) >= 2, "outputs must stream incrementally"
    for a, b in zip(seen, seen[1:]):
        assert b[:len(a)] == a, "streamed outputs must be monotone"


def test_async_concurrent_requests(checkpoint):
    path, hf = checkpoint
    engine = make_async_engine(path)
    prompts = [[3, 17, 92, 45, 8], [5, 9, 101], [120, 44]]

    async def one(i, prompt):
        sp = SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True)
        final = None
        async for out in engine.generate(prompt, sp, request_id=f"c{i}"):
            final = out
        return final.outputs[0].token_ids

    async def run():
        return await asyncio.gather(
            *(one(i, p) for i, p in enumerate(prompts)))

    try:
        results = asyncio.run(run())
    finally:
        engine.shutdown()
    for prompt, got in zip(prompts, results):
        assert got == hf_greedy(hf, prompt, 6)


def test_async_cancellation_aborts(checkpoint):
    path, _ = checkpoint
    engine = make_async_engine(path)

    async def run():
        sp = SamplingParams(temperature=0.0, max_tokens=40,
                            ignore_eos=True)
        gen = engine.generate([7, 8, 9], sp, request_id="cancel-me")
        async for _ in gen:
            break  # consume one output then drop the stream
        await gen.aclose()
        # Give the abort a moment to reach the core thread.
        for _ in range(100):
            if not engine.core.core.has_unfinished_requests():
                return True
            await asyncio.sleep(0.05)
        return False

    try:
        aborted = asyncio.run(run())
    finally:
        engine.shutdown()
    assert aborted, "cancelled stream must abort the core request"
    assert not engine.request_queues


def test_async_mp_core(checkpoint, monkeypatch):
    monkeypatch.setenv("VDT_RPC_TIMEOUT", "300")
    path, hf = checkpoint
    engine = make_async_engine(path, multiprocess_engine_core=True)

    async def run():
        sp = SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True)
        final = None
        async for out in engine.generate([3, 17, 92, 45, 8], sp,
                                         request_id="mp1"):
            final = out
        stats = await engine.get_stats()
        return final.outputs[0].token_ids, stats

    try:
        got, stats = asyncio.run(run())
    finally:
        engine.shutdown()
    assert got == hf_greedy(hf, [3, 17, 92, 45, 8], 6)
    assert isinstance(stats, dict) and "num_running_reqs" in stats
