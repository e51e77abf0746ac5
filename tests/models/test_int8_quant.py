"""Int8 weight quantization (w8a16 quantize-on-load): parity within
tolerance vs full precision, and weight bytes actually halved (model:
reference tests/tpu/test_quantization_accuracy.py +
quantization/tpu_int8.py semantics)."""

import jax
import numpy as np
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
    path = tmp_path_factory.mktemp("tiny_llama_q8")
    hf.save_pretrained(path, safe_serialization=True)
    return str(path)


def make_engine(path, **overrides) -> LLMEngine:
    args = dict(model=path, dtype="float32", block_size=4,
                num_gpu_blocks_override=64, max_model_len=64,
                max_num_batched_tokens=64, max_num_seqs=8,
                skip_tokenizer_init=True)
    args.update(overrides)
    return LLMEngine(EngineArgs(**args).create_engine_config())


def first_logprobs(engine, prompt, k=5):
    engine.add_request("q", prompt,
                       SamplingParams(temperature=0.0, max_tokens=1,
                                      ignore_eos=True, logprobs=k))
    for _ in range(50):
        for out in engine.step():
            if out.finished:
                return out.outputs[0].logprobs[0]
    raise AssertionError("did not finish")


def param_bytes(engine):
    runner = engine.engine_core.engine_core.executor.worker.model_runner
    return sum(x.nbytes for x in jax.tree_util.tree_leaves(runner.params))


PROMPT = [3, 17, 92, 45, 8, 21, 33]


@pytest.mark.parametrize("scheme,tol,dtype_name", [
    ("int8", 0.15, "int8"),
    ("fp8", 0.25, "float8_e4m3fn"),
    # int4: a native 4-bit weight datapath (XLA packs int4 two-per-byte
    # in TPU HBM; the CPU test backend stores bytes, so only the dtype
    # is asserted here, not the footprint).
    ("int4", 0.9, "int4"),
    # w8a8: int8 weights AND dynamic per-token int8 activations — the
    # dots run int8 x int8 with int32 accumulation (MXU-native).
    ("w8a8", 0.3, "int8"),
])
def test_quant_logit_parity_and_memory(checkpoint, scheme, tol,
                                       dtype_name):
    fp = make_engine(checkpoint)
    q8 = make_engine(checkpoint, quantization=scheme)

    lp_fp = first_logprobs(fp, PROMPT)
    lp_q8 = first_logprobs(q8, PROMPT)
    # Same top-1 and close logprobs for the shared top tokens.
    assert max(lp_fp, key=lp_fp.get) == max(lp_q8, key=lp_q8.get)
    common = set(lp_fp) & set(lp_q8)
    assert len(common) >= 3
    for tok in common:
        assert abs(lp_fp[tok] - lp_q8[tok]) < tol, (
            tok, lp_fp[tok], lp_q8[tok])

    # Weight footprint: ~4x smaller vs float32 engine weights (8-bit
    # payloads, scales negligible; embed/lm_head stay fp). int4 packs
    # only on real TPU HBM, so the byte assertion covers 8-bit schemes.
    if scheme != "int4":
        b_fp, b_q8 = param_bytes(fp), param_bytes(q8)
        assert b_q8 < 0.55 * b_fp, (b_q8, b_fp)

    # The runner's weight tree really holds quantized leaves.
    runner = q8.engine_core.engine_core.executor.worker.model_runner
    dtypes = {str(x.dtype)
              for x in jax.tree_util.tree_leaves(runner.params)}
    assert dtype_name in dtypes


@pytest.mark.parametrize("scheme", ["int8", "w8a8"])
def test_quant_greedy_decode_stable_under_tp(checkpoint, scheme):
    """Quantized + TP=2 must equal the single-device engine. int8:
    scale sharding must match the weight sharding. w8a8: the per-token
    activation absmax must cover the FULL feature row (GSPMD reduces
    across shards for the row-parallel dots)."""
    base = make_engine(checkpoint, quantization=scheme)
    tp2 = make_engine(checkpoint, quantization=scheme,
                      tensor_parallel_size=2)
    sp = SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True)

    def run(engine):
        engine.add_request("r", PROMPT, sp)
        for _ in range(100):
            for out in engine.step():
                if out.finished:
                    return out.outputs[0].token_ids
        raise AssertionError("did not finish")

    got_base = run(base)
    got_tp2 = run(tp2)
    assert got_base == got_tp2


def test_int8_quant_error_bounded():
    """Unit check of the quantizer itself: per-channel int8 round-trip
    error stays within one scale step."""
    from vllm_distributed_tpu.models.llama import (LlamaArchConfig,
                                                   LlamaForCausalLM)
    cfg = LlamaArchConfig(vocab_size=32, hidden_size=16,
                          intermediate_size=32, num_layers=1,
                          num_q_heads=2, num_kv_heads=2, head_dim=8,
                          quantization="int8", dtype=np.float32)
    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(0)
    w = rng.standard_normal((1, 16, 16)).astype(np.float32)
    params = {"layers": {"wq": w.copy()}}
    out = model.quantize_params(params)
    q = np.asarray(out["layers"]["wq"])
    s = np.asarray(out["layers"]["wq_scale"])
    assert q.dtype == np.int8
    recon = q.astype(np.float32) * s
    err = np.abs(recon - w)
    assert float(err.max()) <= float(s.max()) * 0.5 + 1e-6


def test_fp8_kv_cache_parity_and_footprint(checkpoint):
    """--kv-cache-dtype fp8: halved KV bytes, outputs within quant
    tolerance (reference: the kv_cache_dtype flag + fp8 cache
    kernels; scale 1.0)."""
    fp = make_engine(checkpoint)
    q = make_engine(checkpoint, kv_cache_dtype="fp8")
    lp_fp = first_logprobs(fp, PROMPT)
    lp_q = first_logprobs(q, PROMPT)
    assert max(lp_fp, key=lp_fp.get) == max(lp_q, key=lp_q.get)
    common = set(lp_fp) & set(lp_q)
    for tok in common:
        assert abs(lp_fp[tok] - lp_q[tok]) < 0.15

    def cache_bytes(engine):
        runner = engine.engine_core.engine_core.executor.worker \
            .model_runner
        return sum(x.nbytes
                   for x in jax.tree_util.tree_leaves(runner.kv_caches))

    assert cache_bytes(q) <= 0.3 * cache_bytes(fp)  # fp8 vs float32

    # Greedy decode stays stable over several tokens.
    sp = SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True)
    q.add_request("d", PROMPT, sp)
    for _ in range(60):
        done = [o for o in q.step() if o.finished]
        if done:
            assert len(done[0].outputs[0].token_ids) == 6
            break
    else:
        raise AssertionError("fp8 decode did not finish")


def test_fp8_kv_cache_under_tp2(checkpoint):
    """fp8 pages + GSPMD TP: the head-sharded cache keeps parity with
    the single-device fp8 engine."""
    base = make_engine(checkpoint, kv_cache_dtype="fp8")
    tp2 = make_engine(checkpoint, kv_cache_dtype="fp8",
                      tensor_parallel_size=2)
    sp = SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True)

    def run(engine):
        engine.add_request("r", PROMPT, sp)
        for _ in range(100):
            for out in engine.step():
                if out.finished:
                    return out.outputs[0].token_ids
        raise AssertionError("did not finish")

    assert run(base) == run(tp2)


def test_quant_decode_via_fused_pallas_kernel(checkpoint, monkeypatch):
    """With the pallas backend on one chip, decode-sized weight-only
    dots route through the fused dequant-GEMM kernel
    (ops/pallas_quant_matmul.py): greedy output must match the XLA
    dequant-in-dot path exactly."""
    monkeypatch.setenv("VDT_ATTENTION_BACKEND", "xla")
    base = make_engine(checkpoint, quantization="int4")
    sp = [SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True)]

    def run_one(engine):
        engine.add_request("k", PROMPT, sp[0])
        for _ in range(100):
            for out in engine.step():
                if out.finished:
                    return out.outputs[0].token_ids
        raise AssertionError("did not finish")

    want = run_one(base)
    monkeypatch.setenv("VDT_ATTENTION_BACKEND", "pallas")
    # The attention kernel comes along with the backend, and an
    # interpreted one is a minute a program to lower under this file's
    # 4-token pages: PALLAS_GEOMETRY's. The weights' kernel, which this
    # test is about, does not see the pages.
    fused = make_engine(checkpoint, quantization="int4", **PALLAS_GEOMETRY)
    got = run_one(fused)
    assert got == want
