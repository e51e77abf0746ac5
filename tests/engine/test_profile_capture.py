"""The hardened profiler capture: one capture at a time, auto-named
dirs, and the ``perf.capture_stall`` drill proving a wedged xprof
session is bounded by VDT_PROFILE_MAX_S without wedging serving."""

import time

import pytest
from transformers import LlamaConfig

from vllm_distributed_tpu.config import (CacheConfig, EngineConfig,
                                         LoadConfig, ModelConfig,
                                         SchedulerConfig)
from vllm_distributed_tpu.engine.llm_engine import LLMEngine
from vllm_distributed_tpu.sampling_params import SamplingParams

HF = dict(vocab_size=128, hidden_size=64, intermediate_size=128,
          num_hidden_layers=2, num_attention_heads=4,
          num_key_value_heads=2, max_position_embeddings=256,
          architectures=["LlamaForCausalLM"])

B, P, D = 3, 10, 5


def make_engine() -> LLMEngine:
    config = EngineConfig(
        model_config=ModelConfig(model="tiny-perf-dummy",
                                 dtype="float32", max_model_len=256,
                                 hf_overrides=HF,
                                 skip_tokenizer_init=True),
        cache_config=CacheConfig(block_size=4,
                                 num_gpu_blocks_override=256),
        scheduler_config=SchedulerConfig(max_num_batched_tokens=256,
                                         max_num_seqs=8,
                                         max_model_len=256),
        load_config=LoadConfig(load_format="dummy"))
    config.model_config.hf_config = LlamaConfig(**HF)
    return LLMEngine(config, load_tokenizer=False)


def run_greedy(engine) -> dict:
    sp = SamplingParams(temperature=0.0, max_tokens=D, ignore_eos=True)
    for i in range(B):
        engine.add_request(f"r{i}",
                           [2 + i * 17 + j for j in range(P)], sp)
    toks = {}
    for _ in range(200):
        for out in engine.step():
            if out.finished:
                toks[out.request_id] = list(out.outputs[0].token_ids)
        if not engine.has_unfinished_requests():
            break
    assert len(toks) == B
    return toks


def test_profiler_capture_hardening(monkeypatch, tmp_path):
    monkeypatch.setenv("VDT_PROFILER_DIR", str(tmp_path))
    engine = make_engine()
    core = engine.engine_core.engine_core
    try:
        with pytest.raises(ValueError, match="no profiler capture"):
            core.profile("stop")
        d1 = core.profile("start")
        assert str(tmp_path) in d1
        with pytest.raises(ValueError, match="already active"):
            core.profile("start")
        assert core.profile("stop") == d1
        # Auto-naming: a second capture gets a DIFFERENT directory.
        d2 = core.profile("start")
        assert d2 != d1
        core.profile("stop")
    finally:
        engine.engine_core.shutdown()


def test_capture_stall_drill_bounded_by_deadline(monkeypatch,
                                                 tmp_path):
    """perf.capture_stall: the stop RPC is lost (wedged xprof client);
    the VDT_PROFILE_MAX_S deadline force-stops the capture from the
    step loop while serving keeps producing tokens, and the fault fire
    is counted."""
    from vllm_distributed_tpu.utils import fault_injection as fi
    monkeypatch.setenv("VDT_PROFILER_DIR", str(tmp_path))
    monkeypatch.setenv("VDT_PROFILE_MAX_S", "0.2")
    engine = make_engine()
    core = engine.engine_core.engine_core
    fi.inject("perf.capture_stall")
    try:
        core.profile("start")
        assert core._profile_stalled
        with pytest.raises(RuntimeError, match="wedged"):
            core.profile("stop")
        assert core._profile_dir is not None
        time.sleep(0.25)
        toks = run_greedy(engine)  # serving survives the wedge
        assert all(len(t) == D for t in toks.values())
        assert core._profile_dir is None, "deadline must force-stop"
        assert fi.counters().get("perf.capture_stall", 0) >= 1
        # The lane is free again: a fresh capture starts cleanly.
        fi.clear("perf.capture_stall")
        d = core.profile("start")
        assert core.profile("stop") == d
    finally:
        fi.clear()
        engine.engine_core.shutdown()
