"""End-to-end pipeline parallelism: greedy decode through the staged
runner must match the single-program baseline exactly (model: reference
tests/distributed/test_pipeline_parallel.py comparing configs)."""

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
    # 3 layers: pp=2 gets an UNEVEN split (2+1), exercising remainder
    # handling in partition_layers.
    cfg = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=3, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=64,
                      eos_token_id=1)
    hf = HFLlama(cfg).eval()
    path = tmp_path_factory.mktemp("tiny_llama_pp")
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


def test_pp2_matches_baseline(checkpoint, baseline):
    got = run(make_engine(checkpoint, pipeline_parallel_size=2), PROMPTS,
              "pp2")
    assert got == baseline


def test_pp2_tp2_matches_baseline(checkpoint, baseline):
    got = run(make_engine(checkpoint, pipeline_parallel_size=2,
                          tensor_parallel_size=2), PROMPTS, "pp2tp2")
    assert got == baseline


def test_pp2_tp2_dp2_matches_baseline(checkpoint, baseline):
    """The full 8-device dp x pp x tp mesh."""
    got = run(make_engine(checkpoint, pipeline_parallel_size=2,
                          tensor_parallel_size=2, data_parallel_size=2),
              PROMPTS, "pp2tp2dp2")
    assert got == baseline


def test_pp3_uneven_layers_matches_baseline(checkpoint, baseline):
    """pp=3 over 3 layers: one layer per stage."""
    got = run(make_engine(checkpoint, pipeline_parallel_size=3), PROMPTS,
              "pp3")
    assert got == baseline


def test_pp2_pallas_matches_baseline(checkpoint, baseline, monkeypatch):
    """An interpreted kernel is lowered once a stage and token bucket:
    ``PALLAS_GEOMETRY``'s pages (a sequence of 13-15 tokens still spans
    two), and a budget of 16 tokens a step, which keeps the 15 prompt
    tokens and the decode steps in one bucket (two steps of prefill, the
    second beside a decode row)."""
    monkeypatch.setenv("VDT_ATTENTION_BACKEND", "pallas")
    got = run(make_engine(checkpoint, pipeline_parallel_size=2,
                          max_num_batched_tokens=16, **PALLAS_GEOMETRY),
              PROMPTS, "pp2pl")
    assert got == baseline


def test_pp2_chunked_prefill_matches_baseline(checkpoint, baseline):
    got = run(make_engine(checkpoint, pipeline_parallel_size=2,
                          max_num_batched_tokens=8), PROMPTS, "pp2cp")
    assert got == baseline


def test_pp2_spec_decode_matches_baseline(checkpoint, baseline):
    got = run(make_engine(checkpoint, pipeline_parallel_size=2,
                          speculative_method="ngram",
                          num_speculative_tokens=3), PROMPTS, "pp2spec")
    assert got == baseline


def test_pp2_batch_queue_overlaps_microbatches(checkpoint):
    """The PP engine core must keep >1 batch in flight (reference:
    core.py:242 step_with_batch_queue): with a token budget that fits
    only half the requests per batch, the two halves pipeline — and
    the interleaved decode still matches the sequential baseline."""
    prompts = [[i * 7 + j for j in range(1, 9)] for i in range(4)]
    baseline = run(make_engine(checkpoint), prompts, "bq-base")

    engine = make_engine(checkpoint, pipeline_parallel_size=2,
                         max_num_batched_tokens=16)
    core = engine.engine_core.engine_core
    assert core.batch_queue is not None
    assert core.batch_queue_size == 2
    got = run(engine, prompts, "bq")
    assert got == baseline
    # The load was split into >=2 concurrent microbatches at some point
    # (prefill splits 4x8 tokens over a 16-token budget, decode then
    # alternates the two halves through the queue).
    assert core.max_concurrent_batches == 2


def test_pp2_batch_queue_abort_in_flight_is_safe(checkpoint):
    """Aborting a request while its batch is dispatched defers the
    finish until the batch retires; other requests are unaffected."""
    prompts = [[i * 7 + j for j in range(1, 9)] for i in range(4)]
    engine = make_engine(checkpoint, pipeline_parallel_size=2,
                         max_num_batched_tokens=16)
    core = engine.engine_core.engine_core
    sp = SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True)
    for i, p in enumerate(prompts):
        engine.add_request(f"ab-{i}", p, sp)
    # Step until at least one batch is in flight, then abort a request
    # that is part of it.
    aborted = None
    for _ in range(50):
        engine.step()
        if core.scheduler.in_flight_req_ids:
            aborted = next(iter(core.scheduler.in_flight_req_ids))
            engine.abort_request([aborted])
            break
    assert aborted is not None
    done = set()
    for _ in range(300):
        for out in engine.step():
            if out.finished:
                done.add(out.request_id)
        if not engine.has_unfinished_requests():
            break
    assert not engine.has_unfinished_requests()
    assert done == {f"ab-{i}" for i in range(4)} - {aborted}
    assert not core.scheduler._deferred_finishes
    # All pages returned (no leak from the deferred finish).
    pool = core.scheduler.kv_cache_manager.block_pool
    assert pool.get_num_free_blocks() == pool.num_blocks
