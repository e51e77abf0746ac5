"""Granite 4.0-H with routed experts (``models/granite_hybrid.py`` with
``num_local_experts`` > 0) at a toy width, float32, on the CPU: the
engine's steps against the plain reference
(``benchmarks/references/granite_moe_hybrid.py``) under an expert share
and through the scan over two periods, the shares adding up to the uncut
layer, the router's two orders, the checkpoint's names against
transformers' own model (whole, one period), the tree without experts,
and the refusals. The harness (engine fixtures, ``serve``,
``jit_logprobs``) is ``test_granite_hybrid.py``'s; its docstring says what
a case costs."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch
from transformers import PretrainedConfig

from benchmarks import run
from benchmarks.references import granite_moe_hybrid as reference
from test_granite_hybrid import (DATA, jit_logprobs, make_engine,  # noqa: F401
                                 prompt, runner_of, serve, served)
from vllm_distributed_tpu.engine.arg_utils import EngineArgs
from vllm_distributed_tpu.engine.llm_engine import LLMEngine
from vllm_distributed_tpu.models.granite_hybrid import \
    GraniteMoeHybridForCausalLM
from vllm_distributed_tpu.models.llama import LlamaArchConfig
from vllm_distributed_tpu.models.mixtral import MixtralForCausalLM

CELL = "tiny-granite-moe.tiny-decode"
# Those a chip run must catch, and the two held in float32 alone.
CONTROLS = (*reference.CONTROLS["GraniteMoeHybridForCausalLM"], "no_renorm",
            "all_experts")


@pytest.fixture(scope="module")
def tiny():
    return run.load_cell(CELL, DATA)["config_file"]


@pytest.fixture(scope="module")
def model_dir(tiny, tmp_path_factory):
    work = run.WORK
    run.WORK = str(tmp_path_factory.mktemp("granite-moe"))
    try:
        return run.write_model_dir("tiny-granite-moe", tiny)
    finally:
        run.WORK = work


@pytest.fixture(scope="module")
def engine(model_dir):
    with pytest.MonkeyPatch.context() as mp:
        return make_engine(model_dir, mp)


def reference_logprobs(engine, tiny, tokens, control=None):
    return jit_logprobs(reference, runner_of(engine).params,
                        run.hf_config(tiny), tokens, control)


def test_the_share_is_served_from_a_buffer_a_layer(engine):
    """Share 1 of 2 holds experts 4-7 of 8; the experts' stacks reach the
    programs as one buffer a layer (what ``ragged_dot`` reads in place),
    the router keeps its published width, and the walk is a scan over
    two periods."""
    runner = runner_of(engine)
    cfg = runner.model.cfg
    assert cfg.experts_held == (4, 4) and cfg.router_experts == 8
    assert (cfg.num_experts_per_tok, cfg.moe_intermediate_size,
            cfg.intermediate_size) == (3, 32, 96)
    lay = runner.params["layers"]
    for kind, depth in (("a_", 2), ("m_", 6)):
        assert lay[kind + "router"].shape == (depth, 64, 8)
        assert lay[kind + "gate"].shape == (depth, 64, 96)
        for name, shape in (("w_gate", (4, 64, 32)), ("w_up", (4, 64, 32)),
                            ("w_down", (4, 32, 64))):
            leaves = lay[kind + name]
            assert isinstance(leaves, tuple) and len(leaves) == depth
            assert all(x.shape == shape for x in leaves)
    assert runner.model._period() == 4
    stacked = runner.model.canonical_layout(lay)
    assert stacked["m_w_gate"].shape == (6, 4, 64, 32)


def test_prefill_then_decode_matches_the_reference(engine, tiny):
    """Three requests in one batch (a prompt across several 8-token
    chunks, one under a chunk, one of two tokens), prefill then six
    decode steps through the cache: every logprob is the reference's
    one full forward, and every control moves it."""
    got = serve(engine, [prompt(37), prompt(7, 1), prompt(2, 2)], 6)
    for tokens, lps in got:
        want = reference_logprobs(engine, tiny, tokens)
        np.testing.assert_allclose(lps, want, atol=2e-5)
    tokens, lps = got[0]
    dev = np.abs(np.asarray(lps) - reference_logprobs(engine, tiny,
                                                      tokens)).max()
    for control in CONTROLS:
        broken = reference_logprobs(engine, tiny, tokens, control)
        assert np.abs(np.asarray(lps) - broken).max() > 20 * dev + 2e-5, \
            control


def test_prefill_split_over_two_steps_is_one_pass(engine, model_dir, tiny,
                                                  monkeypatch):
    """A 24-token prompt under a budget of 16 tokens a step (an engine
    of its own: ``max_num_batched_tokens=16``) goes in over two steps,
    each resuming the row's state and the pages; a budget of 64 takes it
    in one. Both equal the reference."""
    (one, ) = serve(engine, [prompt(24, 3)])
    (two, ), _ = served(model_dir, monkeypatch, [prompt(24, 3)],
                        max_num_batched_tokens=16)
    assert one[0] == two[0]
    np.testing.assert_allclose(two[1], one[1], atol=2e-5)
    np.testing.assert_allclose(
        one[1], reference_logprobs(engine, tiny, one[0]), atol=2e-5)


# ---------------------------------------------------------------------
# One layer, without an engine
# ---------------------------------------------------------------------
def build(tiny, **over):
    cfg = dict(run.hf_config(tiny), **over)
    if cfg.get("expert_share") is None:
        cfg.pop("expert_share", None)
    hf = PretrainedConfig(**cfg)
    arch = LlamaArchConfig.from_hf_config(hf, dtype=jnp.float32)
    GraniteMoeHybridForCausalLM.configure_arch(arch, hf)
    arch.quantization = None
    arch.state_slots = 2
    return GraniteMoeHybridForCausalLM(arch), cfg


def layer_of(params, kind, i, lo=0, n=None):
    """The program's ``lp`` of one layer, its experts ``[lo, lo + n)``."""
    lp = {k[2:]: v[i] for k, v in params["layers"].items()
          if k.startswith(kind)}
    for k in ("w_gate", "w_up", "w_down"):
        lp[k] = lp[k][lo:lo + n] if n else lp[k]
    return lp


def test_the_shares_add_up(tiny):
    """8 experts as 2 shares of 4: the routed parts of both shares (the
    program's ``_share_dispatch`` on each half of the uncut layer's
    experts) plus the shared MLP counted once are the uncut reference's
    layer; each share's routed part is the reference's for that share;
    and the program's uncut layer is the same sum."""
    whole, cfg_whole = build(tiny, num_local_experts=8, expert_share=None)
    params = jax.jit(whole.init_params)(jax.random.PRNGKey(3))
    # At 0.02 and 64 channels every weight is alike: spread them.
    params["layers"]["m_router"] = 20 * params["layers"]["m_router"]
    for k in ("m_w_gate", "m_w_up", "m_w_down", "m_gate", "m_up", "m_down"):
        params["layers"][k] = 10 * params["layers"][k]
    x = jax.random.normal(jax.random.PRNGKey(4), (33, 64), jnp.float32)
    stacks = {k: params["layers"]["m_" + k]
              for k in ("router", "w_gate", "w_up", "w_down")}
    lp_ref = {k: v[2] for k, v in params["layers"].items()
              if k.startswith("m_") and k[2:4] != "w_"}
    with jax.default_matmul_precision("highest"):
        uncut = reference.moe(x, stacks, 2, cfg_whole) + \
            reference.shared_mlp(x, lp_ref, "m_")
        got_whole = whole._mlp(layer_of(params, "m_", 2), x)
        parts = []
        for index in (0, 1):
            share = {"published_experts": 8, "chips": 2, "index": index}
            model, cfg = build(tiny, expert_share=share)
            assert model.cfg.experts_held == (4 * index, 4)
            lp = layer_of(params, "m_", 2, 4 * index, 4)
            routed = MixtralForCausalLM.mlp_block(model, lp, x)
            held = {k: (v if k == "router" else v[:, 4 * index:4 * index + 4])
                    for k, v in stacks.items()}
            np.testing.assert_allclose(
                routed, reference.moe(x, held, 2, cfg), atol=2e-6)
            assert float(jnp.abs(routed).max()) > 1e-3
            parts.append(routed)
        shared = reference.shared_mlp(x, lp_ref, "m_")
    np.testing.assert_allclose(parts[0] + parts[1] + shared, uncut,
                               atol=3e-6)
    np.testing.assert_allclose(got_whole, uncut, atol=3e-6)
    assert float(jnp.abs(parts[0] - parts[1]).max()) > 1e-3


def test_top_k_then_softmax_is_softmax_top_k_renormalised(tiny):
    """The published order (the ten largest logits, a softmax over
    them) and Mixtral's (a softmax over all, the largest, renormalised)
    pick the same experts with the same weights; the reference's
    ``route`` scatters the same; without the renormalisation the weights
    no longer sum to one."""
    model, cfg = build(tiny)
    assert model.cfg.norm_topk_prob
    x = jax.random.normal(jax.random.PRNGKey(5), (40, 64), jnp.float32)
    lp = {"router": 0.1 * jax.random.normal(jax.random.PRNGKey(6), (64, 8))}
    idx, vals = model._route(lp, x)
    idx2, vals2 = MixtralForCausalLM._route(model, lp, x)
    np.testing.assert_array_equal(idx, idx2)
    np.testing.assert_allclose(vals, vals2, rtol=2e-6)
    np.testing.assert_allclose(vals.sum(-1), 1.0, rtol=1e-6)
    table = reference.route(x, lp["router"], cfg)
    np.testing.assert_allclose(
        jnp.take_along_axis(table, idx, 1), vals, rtol=2e-6)
    assert int((table > 0).sum()) == 40 * 3
    loose = reference.route(x, lp["router"], cfg, "no_renorm")
    assert float(loose.sum(-1).max()) < 0.95


# ---------------------------------------------------------------------
# The checkpoint's names
# ---------------------------------------------------------------------
ONE_PERIOD = dict(num_hidden_layers=4,
                  layer_types=["mamba", "mamba", "attention", "mamba"])


def hf_moe(tiny, **over):
    from transformers import (GraniteMoeHybridConfig,
                              GraniteMoeHybridForCausalLM as HF)
    keys = {k: v for k, v in run.hf_config(tiny).items()
            if k not in ("architectures", "model_type", "torch_dtype",
                         "expert_share")}
    keys.update(vocab_size=128, eos_token_id=1, **ONE_PERIOD, **over)
    torch.manual_seed(0)
    hf = HF(GraniteMoeHybridConfig(**keys))
    with torch.no_grad():
        for name, p in hf.named_parameters():
            if name.endswith(("conv1d.bias", "dt_bias")):
                p.normal_(std=0.2)
            elif name.endswith(("q_proj.weight", "k_proj.weight")):
                p.mul_(15.0)
            elif name.endswith("norm.weight"):
                p.uniform_(0.5, 1.5)
            elif "block_sparse_moe" in name:
                p.mul_(8.0)          # experts that weigh, picks that differ
    return hf.eval()


def test_loader_maps_the_checkpoint_s_names(tiny, tmp_path, monkeypatch):
    """A checkpoint saved by transformers' own GraniteMoeHybridForCausalLM
    with experts (``block_sparse_moe.input_linear`` ``[E, 2 I, H]`` gate
    rows over up rows, ``output_linear`` ``[E, H, I]``, ``router.layer``
    ``[E, H]``): the engine's logprobs of prompt and greedy tokens are
    that model's own forward (whole, one period: the layer's buffers are
    picked outright, no switch), and the tensors lie where the program
    reads them."""
    hf = hf_moe(tiny)
    sd = hf.state_dict()
    moe = "model.layers.{}.block_sparse_moe."
    assert sd[moe.format(0) + "input_linear.weight"].shape == (4, 64, 64)
    assert sd[moe.format(0) + "output_linear.weight"].shape == (4, 64, 32)
    assert sd[moe.format(2) + "router.layer.weight"].shape == (4, 64)
    hf.save_pretrained(tmp_path, safe_serialization=True)
    (got, ), engine = served(str(tmp_path), monkeypatch,
                             [[t % 128 for t in prompt(21)]], 5,
                             load_format="auto")
    tokens, lps = got
    with torch.no_grad():
        logits = hf(torch.tensor([tokens])).logits[0]
    want = torch.log_softmax(logits.float(), -1)[
        torch.arange(len(tokens) - 1), torch.tensor(tokens[1:])]
    np.testing.assert_allclose(lps, want.numpy(), atol=5e-5)
    runner = runner_of(engine)
    assert runner.model._period() == 4 and runner.model.cfg.experts_held \
        is None
    lay = runner.params["layers"]
    fused = sd[moe.format(3) + "input_linear.weight"].numpy()
    down = sd[moe.format(3) + "output_linear.weight"].numpy()
    for e in range(4):
        np.testing.assert_array_equal(lay["m_w_gate"][2][e], fused[e, :32].T)
        np.testing.assert_array_equal(lay["m_w_up"][2][e], fused[e, 32:].T)
        np.testing.assert_array_equal(lay["m_w_down"][2][e], down[e].T)
    np.testing.assert_array_equal(
        lay["a_router"][0], sd[moe.format(2) + "router.layer.weight"].numpy().T)
    # The plain reference on the same checkpoint, whole.
    cfg = dict(run.hf_config(tiny), vocab_size=128, **ONE_PERIOD)
    cfg.pop("expert_share")
    np.testing.assert_allclose(
        jit_logprobs(reference, runner.params, cfg, tokens), want.numpy(),
        atol=5e-5)


def test_a_share_takes_its_experts_slabs_from_the_checkpoint(tiny):
    """Under ``expert_share`` the loader keeps the held experts of the
    checkpoint's eight and the router whole."""
    hf = hf_moe(tiny, num_local_experts=8)
    tensors = {k: v.numpy() for k, v in hf.state_dict().items()}
    share = {"published_experts": 8, "chips": 2, "index": 1}
    model, _ = build(tiny, vocab_size=128, expert_share=share, **ONE_PERIOD)
    lay = model.params_from_hf_state_dict(tensors)["layers"]
    fused = tensors["model.layers.1.block_sparse_moe.input_linear.weight"]
    assert lay["m_w_gate"].shape == (3, 4, 64, 32)
    assert lay["m_router"].shape == (3, 64, 8)
    np.testing.assert_array_equal(lay["m_w_up"][1][0], fused[4, 32:].T)


def test_no_experts_builds_the_tree_it_built(tiny):
    """``num_local_experts`` 0 (the Micro family): no router, no expert
    stack, the dense MLP at ``shared_intermediate_size``, the same
    seeded values as Bamba's tree with Granite's own scales, and the
    dense ``_mlp``."""
    micro = run.load_cell("tiny-granite-h.tiny-decode", DATA)["config_file"]
    model, _ = build(micro)
    assert model.cfg.num_experts == 0 and model.cfg.experts_held is None
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    assert sorted(shapes["layers"]) == sorted(
        ["a_ln", "a_wq", "a_wk", "a_wv", "a_wo", "a_pre_ln", "a_gate",
         "a_up", "a_down", "m_norm", "m_gated_norm", "m_in_gate", "m_in_x",
         "m_in_bc", "m_in_dt", "m_conv_x_w", "m_conv_bc_w", "m_dt_bias",
         "m_A_log", "m_D", "m_out_proj", "m_pre_ln", "m_gate", "m_up",
         "m_down", "m_conv_x_b", "m_conv_bc_b"])
    assert sorted(shapes) == ["embed", "final_ln", "layers"]
    assert set(model.param_specs()["layers"]) == set(shapes["layers"])
    assert model.serving_layout(dict(shapes["layers"])) == shapes["layers"]
    # With experts the rest of the tree is seeded as without them.
    moe, _ = build(tiny)
    a = jax.jit(model.init_params)(jax.random.PRNGKey(7))["layers"]
    b = jax.jit(moe.init_params)(jax.random.PRNGKey(7))["layers"]
    for k in ("m_in_x", "m_A_log", "a_wq", "m_conv_x_b"):
        np.testing.assert_array_equal(a[k], b[k])
    assert set(b) - set(a) == {kind + name for kind in ("a_", "m_") for name
                               in ("router", "w_gate", "w_up", "w_down")}


# ---------------------------------------------------------------------
# Refusals
# ---------------------------------------------------------------------
@pytest.mark.parametrize("over,engine_over,match", [
    ({"expert_share": {"published_experts": 12, "chips": 2, "index": 0}},
     {}, "does not describe num_local_experts=4 held experts"),
    ({"expert_share": {"published_experts": 8, "chips": 2, "index": 2}},
     {}, "index one of the chips"),
    ({"num_experts_per_tok": 0}, {}, "num_local_experts=4 needs"),
    ({"num_experts_per_tok": 9}, {}, "the router's width"),
    ({}, {"tensor_parallel_size": 2},
     "expert_share .* tensor parallelism over a state-space stack"),
    ({}, {"tensor_parallel_size": 2, "enable_expert_parallel": True},
     "expert parallelism over stateful hybrid models"),
    ({}, {"pipeline_parallel_size": 2},
     "pipeline parallelism over stateful"),
    ({"position_embedding_type": "rope"}, {},
     "position_embedding_type='rope'"),
])
def test_what_is_not_wired_is_refused_at_load_by_name(tiny, tmp_path, over,
                                                      engine_over, match):
    cfg = dict(run.hf_config(tiny), **over)
    (tmp_path / "config.json").write_text(json.dumps(cfg))
    with pytest.raises(ValueError, match=match):
        LLMEngine(EngineArgs(
            model=str(tmp_path), load_format="dummy", dtype="float32",
            max_model_len=64, skip_tokenizer_init=True, **engine_over
        ).create_engine_config())
