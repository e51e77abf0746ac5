"""Tiny-DeepSeek parity vs HF through the full engine: MLA (latent KV
cache, absorbed decode path), q LoRA projections, group-limited and
noaux_tc routing, shared experts (model: reference
vllm/model_executor/models/deepseek_v2.py + the MLA backends,
v1/attention/backends/mla/common.py)."""

import numpy as np
import pytest
import torch
from transformers import DeepseekV2Config, DeepseekV3Config
from transformers import DeepseekV2ForCausalLM as HFDeepseekV2
from transformers import DeepseekV3ForCausalLM as HFDeepseekV3

from vllm_distributed_tpu.engine.arg_utils import EngineArgs
from vllm_distributed_tpu.engine.llm_engine import LLMEngine
from vllm_distributed_tpu.sampling_params import SamplingParams

PROMPTS = [
    [3, 17, 92, 45, 8],
    [5, 9, 33, 71],
    [11, 12, 13, 14, 15, 16],
]

_V2_DIMS = dict(
    vocab_size=128, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=48, num_hidden_layers=3,
    num_attention_heads=4, num_key_value_heads=4,
    q_lora_rank=None, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=4,
    num_experts_per_tok=2, n_shared_experts=1, first_k_dense_replace=1,
    routed_scaling_factor=1.0, topk_method="greedy", n_group=1,
    topk_group=1, norm_topk_prob=False, max_position_embeddings=64,
    eos_token_id=1, head_dim=8,
)


def _save(tmp_path_factory, hf_cls, cfg, tag):
    torch.manual_seed(0)
    hf = hf_cls(cfg).eval()
    path = tmp_path_factory.mktemp(tag)
    hf.save_pretrained(path, safe_serialization=True)
    return str(path), hf


@pytest.fixture(scope="module")
def v2_checkpoint(tmp_path_factory):
    return _save(tmp_path_factory, HFDeepseekV2,
                 DeepseekV2Config(**_V2_DIMS), "tiny_dsv2")


@pytest.fixture(scope="module")
def v2_qlora_grouped_checkpoint(tmp_path_factory):
    dims = dict(_V2_DIMS, q_lora_rank=24,
                topk_method="group_limited_greedy", n_group=2,
                topk_group=1, routed_scaling_factor=2.0)
    return _save(tmp_path_factory, HFDeepseekV2,
                 DeepseekV2Config(**dims), "tiny_dsv2q")


@pytest.fixture(scope="module")
def v3_checkpoint(tmp_path_factory):
    dims = dict(_V2_DIMS, q_lora_rank=24, n_group=2, topk_group=2,
                norm_topk_prob=True, routed_scaling_factor=1.5)
    dims.pop("topk_method")
    cfg = DeepseekV3Config(**dims)
    torch.manual_seed(0)
    hf = HFDeepseekV3(cfg).eval()
    # Exercise the aux-loss-free correction bias (zeros at init).
    with torch.no_grad():
        for block in hf.model.layers[cfg.first_k_dense_replace:]:
            block.mlp.gate.e_score_correction_bias.uniform_(-0.05, 0.05)
    path = tmp_path_factory.mktemp("tiny_dsv3")
    hf.save_pretrained(path, safe_serialization=True)
    return str(path), hf


def make_engine(path, **overrides) -> LLMEngine:
    args = dict(model=path, dtype="float32", block_size=4,
                num_gpu_blocks_override=128, max_model_len=64,
                max_num_batched_tokens=64, max_num_seqs=8,
                skip_tokenizer_init=True)
    args.update(overrides)
    return LLMEngine(EngineArgs(**args).create_engine_config())


def hf_greedy(hf, prompt, n):
    with torch.no_grad():
        out = hf.generate(torch.tensor([prompt]), max_new_tokens=n,
                          do_sample=False, eos_token_id=None)
    return out[0].tolist()[len(prompt):]


def run(engine, prompts, tag, max_tokens=6):
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
def v2_engine(v2_checkpoint):
    """The V2 checkpoint's engine with ``make_engine``'s own arguments,
    for the tests that want no other (an engine built compiles its
    programs again); each serves prompts that share no page with
    another's."""
    return make_engine(v2_checkpoint[0])


def test_v2_greedy_matches_hf(v2_checkpoint, v2_engine):
    path, hf = v2_checkpoint
    got = run(v2_engine, PROMPTS, "ds")
    want = [hf_greedy(hf, p, 6) for p in PROMPTS]
    assert got == want


def test_v2_qlora_grouped_routing_matches_hf(v2_qlora_grouped_checkpoint):
    """q_a/q_b low-rank query path + group-limited-greedy expert
    selection + routed scaling."""
    path, hf = v2_qlora_grouped_checkpoint
    got = run(make_engine(path), PROMPTS, "dsq")
    want = [hf_greedy(hf, p, 6) for p in PROMPTS]
    assert got == want


def test_v3_noaux_tc_matches_hf(v3_checkpoint):
    """V3 sigmoid scoring + correction bias + top-2-sum group select +
    normalized weights."""
    path, hf = v3_checkpoint
    got = run(make_engine(path), PROMPTS, "ds3")
    want = [hf_greedy(hf, p, 6) for p in PROMPTS]
    assert got == want


def test_v2_tp2_matches_hf(v2_checkpoint):
    """MLA under tensor parallelism: q heads shard, the latent cache
    replicates (MQA), experts run TP-inside-FFN."""
    path, hf = v2_checkpoint
    got = run(make_engine(path, tensor_parallel_size=2), PROMPTS, "dstp")
    want = [hf_greedy(hf, p, 6) for p in PROMPTS]
    assert got == want


def test_v2_expert_parallel_matches_hf(v2_checkpoint):
    path, hf = v2_checkpoint
    got = run(make_engine(path, tensor_parallel_size=2,
                          enable_expert_parallel=True), PROMPTS, "dsep")
    want = [hf_greedy(hf, p, 6) for p in PROMPTS]
    assert got == want


def test_v2_prefill_logprobs_match_hf(v2_checkpoint, v2_engine):
    path, hf = v2_checkpoint
    engine = v2_engine
    prompt = PROMPTS[0][::-1]      # no cached page of the test above
    k = 5
    engine.add_request("lg-0", prompt,
                       SamplingParams(temperature=0.0, max_tokens=1,
                                      ignore_eos=True, logprobs=k))
    outs = []
    for _ in range(50):
        outs += [o for o in engine.step() if o.finished]
        if not engine.has_unfinished_requests():
            break
    (out, ) = outs
    got = out.outputs[0].logprobs[0]
    with torch.no_grad():
        hf_logits = hf(torch.tensor([prompt])).logits[0, -1]
    hf_lp = torch.log_softmax(hf_logits.float(), dim=-1)
    want_vals, want_ids = torch.topk(hf_lp, k)
    assert set(got) >= set(want_ids.tolist())
    for tok, val in zip(want_ids.tolist(), want_vals.tolist()):
        assert abs(got[tok] - val) < 5e-3, (tok, got[tok], val)


def test_latent_cache_is_an_order_smaller(v2_engine):
    """The point of MLA: page bytes store Lkv+R per token instead of
    2 * heads * head_dim per layer."""
    engine = v2_engine
    runner = (engine.engine_core.engine_core.executor
              .worker.model_runner)
    model = runner.model
    c = model.cfg
    page = 4
    latent = model.kv_cache_page_bytes(page)
    # A same-shape GQA cache would cost 2 * kv_heads * head_dim wide
    # rows; the latent row is kv_lora_rank + rope dim.
    dense_equiv = (2 * c.num_layers * page * 4 * (16 + 8) *
                   np.dtype(np.float32).itemsize)
    assert latent < dense_equiv
    caches = model.make_kv_caches(8, page)
    assert set(caches) == {"c"}
    assert caches["c"].shape == (c.num_layers, 8, page, 32 + 8)


def test_chunked_prefill_matches_hf(v2_checkpoint):
    """Long prompt fed through a small token budget: the absorbed MLA
    path must be exact under chunked prefill."""
    path, hf = v2_checkpoint
    engine = make_engine(path, max_num_batched_tokens=8)
    prompt = list(range(2, 34))
    got = run(engine, [prompt], "dschunk")
    want = [hf_greedy(hf, prompt, 6)]
    assert got == want


def test_yarn_rope_matches_transformers():
    """yarn_inv_freq mirrors transformers' _compute_yarn_parameters
    (DeepSeek checkpoints ship yarn rope_scaling with mscale factors)."""
    import torch as _torch
    from transformers import LlamaConfig as _Cfg
    from transformers.modeling_rope_utils import _compute_yarn_parameters

    from vllm_distributed_tpu.models.common import yarn_inv_freq

    scaling = {"rope_type": "yarn", "factor": 40.0,
               "original_max_position_embeddings": 4096,
               "mscale": 1.0, "mscale_all_dim": 1.0,
               "beta_fast": 32, "beta_slow": 1}
    cfg = _Cfg(rope_theta=10000.0, hidden_size=512,
               num_attention_heads=8, head_dim=64,
               max_position_embeddings=163840, rope_scaling=dict(scaling))
    want_freq, want_att = _compute_yarn_parameters(cfg, _torch.device("cpu"))
    got_freq, got_att = yarn_inv_freq(64, 10000.0, scaling, 163840)
    np.testing.assert_allclose(np.asarray(got_freq),
                               want_freq.numpy(), rtol=1e-6)
    assert abs(got_att - want_att) < 1e-9


def test_v3_yarn_mscale_matches_hf(tmp_path_factory):
    """Real V3/R1 checkpoints ship yarn rope_scaling with mscale_all_dim;
    HF folds yarn mscale^2 into the attention scale for V3 (and only
    V3) — parity locks both the scaled scores and the yarn cos/sin."""
    dims = dict(_V2_DIMS, q_lora_rank=24, n_group=2, topk_group=2,
                norm_topk_prob=True,
                rope_scaling={"rope_type": "yarn", "factor": 4.0,
                              "mscale": 1.0, "mscale_all_dim": 1.0,
                              "original_max_position_embeddings": 16,
                              "beta_fast": 32, "beta_slow": 1})
    dims.pop("topk_method")
    path, hf = _save(tmp_path_factory, HFDeepseekV3,
                     DeepseekV3Config(**dims), "tiny_dsv3_yarn")
    got = run(make_engine(path), PROMPTS, "ds3y")
    want = [hf_greedy(hf, p, 6) for p in PROMPTS]
    assert got == want


def test_v2_pallas_backend_matches_hf(v2_checkpoint, monkeypatch):
    """End to end on the Pallas backend (interpret): the latent kernel
    (ops/pallas_mla.py) carries the MLA attention."""
    monkeypatch.setenv("VDT_ATTENTION_BACKEND", "pallas")
    monkeypatch.setenv("VDT_PALLAS_INTERPRET", "1")
    path, hf = v2_checkpoint
    got = run(make_engine(path, block_size=8), PROMPTS, "dspl")
    want = [hf_greedy(hf, p, 6) for p in PROMPTS]
    assert got == want


@pytest.mark.parametrize("checkpoint,tp", [
    ("v2_checkpoint", 1), ("v3_checkpoint", 1),
    # Heads shard over two ranks, the latent cache replicated: each
    # rank up-projects its heads' K and V and absorbs over its own.
    ("v2_checkpoint", 2)])
def test_pallas_chunk_steps_match_hf(checkpoint, tp, request, monkeypatch):
    """A 38-token prompt goes in as chunks of a 16-token budget beside a
    short request that is already decoding: every step but the first
    holds a decoding row (the absorbed kernel, gathered) and a later
    chunk of a request whose earlier chunks are in the pages (the
    multi-head kernel), then both decode through the cache. Greedy
    tokens are HF's, in float32."""
    from vllm_distributed_tpu.ops import mla
    monkeypatch.setenv("VDT_ATTENTION_BACKEND", "pallas")
    monkeypatch.setenv("VDT_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("VDT_TPLA", "0")
    mixed, traced = mla._mixed_latent_attention, []
    monkeypatch.setattr(
        mla, "_mixed_latent_attention",
        lambda *a, **kw: traced.append(a[3].max_q) or mixed(*a, **kw))
    path, hf = request.getfixturevalue(checkpoint)
    prompts = [PROMPTS[0], list(range(2, 40))]
    engine = make_engine(path, block_size=8, max_num_batched_tokens=16,
                         tensor_parallel_size=tp)
    got = run(engine, prompts, "dsmix", max_tokens=8)
    assert got == [hf_greedy(hf, p, 8) for p in prompts]
    # The chunk programs took the mixed path, a decode program never.
    assert traced and min(traced) > 1


# ---------------------------------------------------------------------
# What the two programs of a latent-attention model hold, read off the
# jaxpr at GigaChat3's published widths (nothing runs, nothing compiles)
# ---------------------------------------------------------------------
def _eqns(jaxpr, times=1):
    """Every equation of ``jaxpr`` and of the jaxprs inside it, with how
    often it runs (a scan's length multiplies its body's)."""
    for eqn in jaxpr.eqns:
        yield eqn, times
        inner = times * eqn.params.get("length", 1) \
            if eqn.primitive.name == "scan" else times
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v, )):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub, inner)


@pytest.fixture(scope="module")
def gigachat_programs():
    """program(max_q) -> ({kernel name: launches}, the float32 products
    against ``w_uk`` / ``w_uv`` as (token rows, weight shape)) of the forward
    of `gigachat3-702b-ep16` on the latent kernels, the configuration's
    heads, widths, depth and engine limits from its file."""
    import collections
    import json
    import os

    import jax
    import jax.numpy as jnp

    from benchmarks import run as bench_run
    from vllm_distributed_tpu.models.common import AttentionBatch
    from vllm_distributed_tpu.models.deepseek import DeepseekV3ForCausalLM
    from vllm_distributed_tpu.models.llama import LlamaArchConfig
    from vllm_distributed_tpu.parallel import mesh as mesh_state
    with open(os.path.join(bench_run.HERE, "configs",
                           "gigachat3-702b-ep16.json")) as f:
        cf = json.load(f)
    hf = DeepseekV3Config(**{
        k: v for k, v in bench_run.hf_config(cf).items()
        if k not in ("architectures", "model_type") and v is not None})
    arch = LlamaArchConfig.from_hf_config(hf, dtype=jnp.bfloat16)
    DeepseekV3ForCausalLM.configure_arch(arch, hf)
    arch.expert_parallel = False
    arch.quantization = None
    model = DeepseekV3ForCausalLM(arch)
    ea = cf["engine_args"]
    reqs, ps = ea["max_num_seqs"], ea["block_size"]
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    caches = jax.eval_shape(lambda: model.make_kv_caches(64, ps))
    # einsum hands dot_general its operands in an order of its own:
    # a weight is known by its sizes.
    weights = {tuple(sorted((cf["kv_lora_rank"], cf["num_attention_heads"],
                             d)))
               for d in (cf["qk_nope_head_dim"], cf["v_head_dim"])}
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731

    def program(max_q):
        T = reqs + 1 if max_q == 1 else max_q + min(max_q, 128)

        def fwd(params, caches, tok, req, pos, slot, tables, lens, info, n):
            return model.forward(params, caches, tok, AttentionBatch(
                req_idx=req, positions=pos, slot_mapping=slot,
                block_tables=tables, seq_lens=lens, seq_info=info,
                num_seqs=n, max_q=max_q))

        # One chip: no mesh of an engine that another test started.
        with pytest.MonkeyPatch.context() as mp, \
                mesh_state.global_mesh(None):
            mp.setenv("VDT_ATTENTION_BACKEND", "pallas")
            mp.setenv("VDT_PALLAS_INTERPRET", "1")
            jaxpr = jax.make_jaxpr(fwd)(
                params, caches, i32(T), i32(T), i32(T), i32(T),
                i32(reqs, ea["max_model_len"] // ps), i32(reqs),
                i32(reqs, 4), i32(1))
        kernels, absorbing = collections.Counter(), []
        for eqn, times in _eqns(jaxpr.jaxpr):
            if eqn.primitive.name == "pallas_call":
                name = str(eqn.params.get("name")
                           or eqn.params["name_and_src_info"])
                kernels[name.split(" ")[0]] += times
            elif eqn.primitive.name == "dot_general":
                (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
                for w, x, used in ((eqn.invars[1], eqn.invars[0], lc + lb),
                                   (eqn.invars[0], eqn.invars[1], rc + rb)):
                    if (tuple(sorted(w.aval.shape)) in weights
                            and w.aval.dtype == jnp.float32):
                        # The activation's free dimension: its rows.
                        (rows, ) = (d for i, d in enumerate(x.aval.shape)
                                    if i not in used)
                        absorbing.append((rows, w.aval.shape))
        return kernels, absorbing

    return program, cf


def test_decode_program_holds_the_absorbed_kernel_alone(gigachat_programs):
    """``max_q == 1``: no multi-head kernel, exactly one launch of the
    latent kernel a latent layer, the two contractions over the decode
    batch: the parent's program."""
    from vllm_distributed_tpu.ops.pallas_mla import (KERNEL_NAME,
                                                     PREFILL_KERNEL_NAME)
    program, cf = gigachat_programs
    kernels, absorbing = program(1)
    assert kernels[PREFILL_KERNEL_NAME] == 0
    assert kernels[KERNEL_NAME] == cf["num_hidden_layers"]
    rows = cf["engine_args"]["max_num_seqs"] + 1
    assert len(absorbing) == 2 * cf["num_hidden_layers"]
    assert {r for r, _ in absorbing} == {rows}


@pytest.mark.parametrize("t_bucket", [16, 2048])
def test_chunk_program_absorbs_over_the_decoding_rows_only(
        gigachat_programs, t_bucket):
    """A program that can hold a chunk has both kernels once a latent
    layer, and its float32 absorbing contractions (``q_nope . w_uk``,
    ``out_l . w_uv``) run over ``max_num_seqs`` gathered rows, not over
    the token bucket: what PR 35's program lacked."""
    from vllm_distributed_tpu.ops.pallas_mla import (KERNEL_NAME,
                                                     PREFILL_KERNEL_NAME)
    program, cf = gigachat_programs
    kernels, absorbing = program(t_bucket)
    layers = cf["num_hidden_layers"]
    assert kernels[KERNEL_NAME] == kernels[PREFILL_KERNEL_NAME] == layers
    assert len(absorbing) == 2 * layers
    assert {r for r, _ in absorbing} == {cf["engine_args"]["max_num_seqs"]}
