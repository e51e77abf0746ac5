"""Cascade (shared-prefix) attention: numeric parity with the plain
ragged path, the merge helper, and the end-to-end detection trigger
(model: reference cascade path of flash_attn.py + merge_attn_states).
The same trigger on the Pallas backend is in
``tests/engine/test_llm_engine.py``, beside the engine it compares with."""

import numpy as np
import pytest
import torch
from transformers import LlamaConfig
from transformers import LlamaForCausalLM as HFLlama

import jax.numpy as jnp

from vllm_distributed_tpu.ops.attention import (
    cascade_ragged_paged_attention, merge_attention_states,
    ragged_paged_attention)


def test_cascade_matches_plain_ragged():
    rng = np.random.default_rng(0)
    T, Hq, Hkv, D, PS, P = 12, 4, 2, 16, 4, 8
    S = 3
    N = 32
    q = jnp.asarray(rng.standard_normal((T, Hq, D)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((N, Hkv, PS, D)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((N, Hkv, PS, D)).astype(np.float32))
    # Two requests sharing the first S pages.
    shared = [5, 9, 11]
    bt = np.zeros((4, P), np.int32)
    bt[0, :6] = shared + [1, 2, 3]
    bt[1, :5] = shared + [7, 8]
    block_tables = jnp.asarray(bt)
    req_idx = jnp.asarray([0] * 6 + [1] * 6, jnp.int32)
    q_pos = jnp.asarray(list(range(14, 20)) + list(range(12, 18)),
                        jnp.int32)

    want = ragged_paged_attention(q, k, v, block_tables, req_idx, q_pos,
                                  sm_scale=0.25)
    got = cascade_ragged_paged_attention(
        q, k, v, block_tables, req_idx, q_pos,
        jnp.asarray(shared, jnp.int32), sm_scale=0.25)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_merge_attention_states_exact():
    """Merging disjoint-range partial states must equal one-shot
    softmax attention over the union."""
    rng = np.random.default_rng(1)
    scores = rng.standard_normal((2, 3, 8)).astype(np.float32)
    values = rng.standard_normal((2, 3, 8, 4)).astype(np.float32)

    def partial(lo, hi):
        s = jnp.asarray(scores[..., lo:hi])
        m = s.max(axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = p.sum(axis=-1, keepdims=True)
        acc = jnp.einsum("abj,abjd->abd", p,
                         jnp.asarray(values[..., lo:hi, :]))
        return m, l, acc[..., None, :].squeeze(-2)

    m, l, acc = merge_attention_states(partial(0, 5), partial(5, 8))
    got = np.asarray(acc / l)
    w = np.exp(scores - scores.max(-1, keepdims=True))
    w = w / w.sum(-1, keepdims=True)
    want = np.einsum("abj,abjd->abd", w, values)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.fixture(scope="module")
def checkpoint(tmp_path_factory):
    torch.manual_seed(0)
    cfg = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=64,
                      eos_token_id=1)
    hf = HFLlama(cfg).eval()
    path = tmp_path_factory.mktemp("tiny_llama_casc")
    hf.save_pretrained(path, safe_serialization=True)
    return str(path)


def test_cascade_end_to_end_fires_and_matches(checkpoint, monkeypatch):
    """Shared-prefix batch: detection fires (prefix cache makes the page
    tables literally share pages) and outputs equal the non-cascade
    engine exactly."""
    monkeypatch.setenv("VDT_CASCADE_ATTENTION", "1")
    monkeypatch.setenv("VDT_CASCADE_SHARED_PAGES", "2")
    from vllm_distributed_tpu.engine.arg_utils import EngineArgs
    from vllm_distributed_tpu.engine.llm_engine import LLMEngine
    from vllm_distributed_tpu.sampling_params import SamplingParams

    def make_engine():
        return LLMEngine(EngineArgs(
            model=checkpoint, dtype="float32", block_size=4,
            num_gpu_blocks_override=128, max_model_len=64,
            max_num_batched_tokens=64, max_num_seqs=8,
            skip_tokenizer_init=True).create_engine_config())

    prefix = [3, 17, 92, 45, 8, 21, 33, 64]  # 2 full pages
    prompts = [prefix + [50 + i] for i in range(3)]
    sp = SamplingParams(temperature=0.0, max_tokens=5, ignore_eos=True)

    def run(engine):
        for i, p in enumerate(prompts):
            engine.add_request(f"c-{i}", p, sp)
        done = {}
        for _ in range(200):
            for out in engine.step():
                if out.finished:
                    done[out.request_id] = out.outputs[0].token_ids
            if not engine.has_unfinished_requests():
                break
        return [done[f"c-{i}"] for i in range(3)]

    cascade_engine = make_engine()
    got = run(cascade_engine)
    runner = (cascade_engine.engine_core.engine_core.executor
              .worker.model_runner)
    assert runner.cascade_steps > 0, "cascade never triggered"

    monkeypatch.setenv("VDT_CASCADE_ATTENTION", "0")
    want = run(make_engine())
    assert got == want
