"""The step's spans in a capture of the profile RPC (ISSUE 25): counts and
structure only, on the CPU. One AsyncLLM on the in-process transport, so
that both threads are in the trace: the engine core's, with ``vdt_step``
and the ``vdt.*`` spans of the step path, and the API thread's, with
``vdt.process_outputs``."""

import asyncio
import glob
import os

import pytest
from jax.profiler import ProfileData
from transformers import LlamaConfig

from vllm_distributed_tpu.config import (CacheConfig, EngineConfig,
                                         LoadConfig, ModelConfig,
                                         SchedulerConfig)
from vllm_distributed_tpu.engine.async_llm import AsyncLLM
from vllm_distributed_tpu.sampling_params import SamplingParams

HF = dict(vocab_size=128, hidden_size=64, intermediate_size=128,
          num_hidden_layers=2, num_attention_heads=4,
          num_key_value_heads=2, max_position_embeddings=256,
          architectures=["LlamaForCausalLM"])

# Spans that lie inside a step, on the engine-core thread.
STEP_SPANS = {"vdt.schedule", "vdt.dispatch_model", "vdt._update_states",
              "vdt._prepare_inputs", "vdt.launch", "vdt._launch_sample",
              "vdt.device_wait", "vdt.finish", "vdt.update_from_output"}
# Events of the runtime's own (host tracer level 2) that a step may add
# on the two threads, beside the program's eleven spans: a generous
# bound, three orders of magnitude under what the Python tracer writes.
MAX_EVENTS_PER_STEP = 400


def make_config() -> EngineConfig:
    config = EngineConfig(
        model_config=ModelConfig(model="tiny-span-dummy", dtype="float32",
                                 max_model_len=256, hf_overrides=HF,
                                 skip_tokenizer_init=True),
        cache_config=CacheConfig(block_size=4,
                                 num_gpu_blocks_override=256),
        scheduler_config=SchedulerConfig(max_num_batched_tokens=64,
                                         max_num_seqs=8,
                                         max_model_len=256),
        load_config=LoadConfig(load_format="dummy"))
    config.model_config.hf_config = LlamaConfig(**HF)
    return config


async def _generate(engine, first: int, n: int) -> None:
    sp = SamplingParams(temperature=0.0, max_tokens=12, ignore_eos=True)

    async def one(i):
        prompt = [2 + (i * 7 + j) % 120 for j in range(10 + 30 * (i % 2))]
        async for _ in engine.generate(prompt, sp, request_id=f"r{i}"):
            pass

    await asyncio.gather(*[one(i) for i in range(first, first + n)])


@pytest.fixture(scope="module")
def capture(tmp_path_factory):
    """{line index: [(name, start_ns, end_ns, stats)]} of the host plane
    of one capture taken through ``EngineCore.profile``."""
    trace_root = tmp_path_factory.mktemp("spans")

    async def run():
        engine = AsyncLLM(make_config(), load_tokenizer=False)
        try:
            await _generate(engine, 0, 3)        # compile outside it
            trace_dir = await engine.profile("start")
            await _generate(engine, 3, 4)
            assert await engine.profile("stop") == trace_dir
            return trace_dir
        finally:
            engine.shutdown()

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("VDT_PROFILER_DIR", str(trace_root))
        trace_dir = asyncio.run(run())
    (path, ) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True)
    (host, ) = [p for p in ProfileData.from_file(path).planes
                if p.name == "/host:CPU"]
    return [[(e.name, e.start_ns, e.start_ns + e.duration_ns,
              dict(e.stats)) for e in line.events] for line in host.lines]


def _engine_and_api_lines(capture):
    engine = [ln for ln in capture if any(e[0] == "vdt_step" for e in ln)]
    api = [ln for ln in capture
           if any(e[0] == "vdt.process_outputs" for e in ln)]
    assert len(engine) == 1 and len(api) == 1 and engine[0] is not api[0]
    return engine[0], api[0]


def test_every_span_name_is_in_the_capture(capture):
    engine, api = _engine_and_api_lines(capture)
    names = {e[0] for e in engine}
    assert STEP_SPANS | {"vdt_step", "vdt.loop"} <= names
    assert not any(e[0].startswith("vdt") for e in api
                   if e[0] != "vdt.process_outputs")


def test_spans_lie_inside_their_step_and_carry_its_number(capture):
    engine, _ = _engine_and_api_lines(capture)
    steps = sorted((e for e in engine if e[0] == "vdt_step"),
                   key=lambda e: e[1])
    assert len(steps) >= 10
    nums = [e[3]["step_num"] for e in steps]
    assert nums == list(range(nums[0], nums[0] + len(nums)))
    by_num = {e[3]["step_num"]: e for e in steps}
    seen = {n: set() for n in nums}
    for name, start, end, stats in engine:
        if name not in STEP_SPANS:
            continue
        if stats["step"] not in by_num:
            continue      # a step the capture caught only the end of
        _, s0, s1, _ = by_num[stats["step"]]
        assert s0 <= start and end <= s1, (name, stats)
        seen[stats["step"]].add(name)
    assert all(seen[n] == STEP_SPANS for n in nums[1:-1]), seen
    # vdt.loop lies between a step and the next, and carries the first's.
    for name, start, end, stats in engine:
        if name == "vdt.loop" and stats["step"] in by_num \
                and stats["step"] + 1 in by_num:
            assert by_num[stats["step"]][2] <= start
            assert end <= by_num[stats["step"] + 1][1]
    # The grant's composition rides vdt.dispatch_model.
    grants = [e[3] for e in engine if e[0] == "vdt.dispatch_model"]
    assert all({"tokens", "prefill_tokens", "decode_reqs"} <= set(g)
               for g in grants)
    assert any(g["prefill_tokens"] > 0 for g in grants)
    assert any(g["decode_reqs"] > 0 and g["prefill_tokens"] == 0
               for g in grants)


def test_process_outputs_carries_the_step_of_its_batch(capture):
    engine, api = _engine_and_api_lines(capture)
    ends = {e[3]["step_num"]: e[2] for e in engine if e[0] == "vdt_step"}
    handled = [e for e in api if e[0] == "vdt.process_outputs"]
    assert len(handled) >= 10
    known = [e for e in handled if e[3]["step"] in ends]
    assert len(known) >= len(handled) - 2
    # Effect after cause, on one clock: a batch is handled after the
    # step that produced it has ended.
    for _, start, _, stats in known:
        assert start >= ends[stats["step"]]


def test_the_capture_holds_no_python_tracer_events(capture):
    engine, api = _engine_and_api_lines(capture)
    assert not any(e[0].startswith("$") for ln in capture for e in ln)
    steps = sum(1 for e in engine if e[0] == "vdt_step")
    assert len(engine) + len(api) <= MAX_EVENTS_PER_STEP * steps
