"""End-to-end token parallelism: greedy decode through the full engine on
a token-parallel mesh must match the single-device baseline exactly.

TPU analogue of the fork's TKNP inference benchmarks / tests
(examples/offline_inference/TKNP/): the KV cache page axis is sharded
over the ``token`` mesh axis, the scheduler assigns each request's pages
to one rank's partition, and attention merges per-rank outputs with a
psum. Runs on the 8-device virtual CPU mesh from tests/conftest.py.
"""

import pytest
import torch
from transformers import LlamaConfig
from transformers import LlamaForCausalLM as HFLlama

from tests.engine.test_llm_engine import PALLAS_GEOMETRY
from vllm_distributed_tpu.engine.arg_utils import EngineArgs
from vllm_distributed_tpu.engine.llm_engine import LLMEngine
from vllm_distributed_tpu.sampling_params import SamplingParams


@pytest.fixture(scope="module")
def checkpoint(tmp_path_factory):
    torch.manual_seed(0)
    cfg = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=64,
                      eos_token_id=1)
    hf = HFLlama(cfg).eval()
    path = tmp_path_factory.mktemp("tiny_llama_tknp")
    hf.save_pretrained(path, safe_serialization=True)
    return str(path)


def make_engine(path, **overrides) -> LLMEngine:
    args = dict(model=path, dtype="float32", block_size=4,
                num_gpu_blocks_override=128, max_model_len=64,
                max_num_batched_tokens=64, max_num_seqs=8,
                skip_tokenizer_init=True)
    args.update(overrides)
    return LLMEngine(EngineArgs(**args).create_engine_config())


PROMPTS = [
    [3, 17, 92, 45, 8],
    [5, 9, 33, 71],
    [11, 12, 13, 14, 15, 16],
    [7, 44, 101, 13, 2, 64, 99],
]


def run(engine, prompts, tag, max_tokens=8):
    sps = [SamplingParams(temperature=0.0, max_tokens=max_tokens,
                          ignore_eos=True) for _ in prompts]
    for i, (p, sp) in enumerate(zip(prompts, sps)):
        engine.add_request(f"{tag}-{i}", p, sp)
    done = {}
    for _ in range(300):
        for out in engine.step():
            if out.finished:
                done[out.request_id] = out
        if not engine.has_unfinished_requests():
            break
    assert not engine.has_unfinished_requests()
    order = sorted(done, key=lambda s: int(s.split("-")[-1]))
    return [done[k].outputs[0].token_ids for k in order]


@pytest.fixture(scope="module")
def baseline(checkpoint):
    return run(make_engine(checkpoint), PROMPTS, "base")


def test_tknp2_matches_baseline(checkpoint, baseline):
    got = run(make_engine(checkpoint, token_parallel_size=2), PROMPTS,
              "tknp2")
    assert got == baseline


def test_tknp2_tp2_matches_baseline(checkpoint, baseline):
    got = run(make_engine(checkpoint, token_parallel_size=2,
                          tensor_parallel_size=2), PROMPTS, "tknp2tp2")
    assert got == baseline


def test_tknp4_matches_baseline(checkpoint, baseline):
    got = run(make_engine(checkpoint, token_parallel_size=4), PROMPTS,
              "tknp4")
    assert got == baseline


def test_tknp2_pallas_matches_baseline(checkpoint, baseline, monkeypatch):
    """Token parallelism through the Pallas kernels (interpret mode):
    per-rank seq lists + local page tables + the in-place KV-write runs.
    An interpreted kernel is lowered once a token bucket:
    ``PALLAS_GEOMETRY``'s pages (a sequence of 13-15 tokens still spans
    two of its rank's partition), and a budget of 16 tokens a step, which
    keeps the 22 prompt tokens and the decode steps in one bucket."""
    monkeypatch.setenv("VDT_ATTENTION_BACKEND", "pallas")
    got = run(make_engine(checkpoint, token_parallel_size=2,
                          max_num_batched_tokens=16, **PALLAS_GEOMETRY),
              PROMPTS, "tknp2pl")
    assert got == baseline


def test_tknp2_chunked_prefill_matches_baseline(checkpoint, baseline):
    """Chunked prefill across token-parallel ranks (small step budget
    forces multi-chunk prefill)."""
    got = run(make_engine(checkpoint, token_parallel_size=2,
                          max_num_batched_tokens=8), PROMPTS, "tknp2cp")
    assert got == baseline
