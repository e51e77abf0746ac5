"""One chip's share of a DeepSeek-V3 expert layer (``expert_share`` in
``config.json``; models/deepseek.py) against the plain reference
(benchmarks/references/deepseek.py): the shares sum to the uncut layer,
the engine's logits through the paged latent cache agree with the
reference's one full forward, YaRN as the reference states it, the
gauges, and what the loader refuses with a share."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from transformers import DeepseekV3Config

from benchmarks import run
from benchmarks.references import deepseek as ref
from vllm_distributed_tpu.models.deepseek import DeepseekV3ForCausalLM
from vllm_distributed_tpu.models.llama import LlamaArchConfig
from vllm_distributed_tpu.utils import cdiv

DATA = os.path.join(os.path.dirname(__file__), "..", "benchmarking", "data")


def tiny_cfg(**over) -> dict:
    cfg = run.hf_config(run.load_json(DATA, "configs",
                                      "tiny-gigachat.json"))
    cfg.update(over)
    return {k: v for k, v in cfg.items() if v is not None}


def build(cfg: dict) -> DeepseekV3ForCausalLM:
    hf = DeepseekV3Config(**{k: v for k, v in cfg.items()
                             if k not in ("architectures", "model_type")})
    arch = LlamaArchConfig.from_hf_config(hf, dtype=jnp.float32)
    DeepseekV3ForCausalLM.configure_arch(arch, hf)
    arch.expert_parallel = False
    arch.quantization = None
    return DeepseekV3ForCausalLM(arch)


def moe_layer(params: dict, j: int = 0) -> dict:
    return {k: params["layers"][k][j] for k in (
        "router", "router_bias", "w_gate", "w_up", "w_down",
        "shared_gate", "shared_up", "shared_down")}


def test_all_shares_sum_to_the_uncut_layer():
    """Over the four shares of a 32-expert deployment: the routed parts
    that each share computes, summed, with the shared expert counted
    once, are the uncut reference's layer output. The program and the
    reference are each given every share."""
    chips, held = 4, 8
    uncut_cfg = tiny_cfg(n_routed_experts=chips * held, expert_share=None)
    uncut = build(uncut_cfg)
    full = moe_layer(uncut.init_params(jax.random.PRNGKey(3)))
    x = jax.random.normal(jax.random.PRNGKey(4), (37, 128), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.moe(x, full, uncut_cfg)
        shared = ref.moe(x, full, uncut_cfg) - ref.moe(
            x, full, uncut_cfg, control="no_shared_expert")
        routed_prog, routed_ref, picks = [], [], 0
        for index in range(chips):
            cfg = tiny_cfg(expert_share={"published_experts": chips * held,
                                         "chips": chips, "index": index})
            model = build(cfg)
            assert model.cfg.experts_held == (index * held, held)
            assert model.cfg.router_experts == chips * held
            lo = index * held
            lp = dict(full, **{k: full[k][lo:lo + held]
                               for k in ("w_gate", "w_up", "w_down")})
            top_idx, top_vals = model._route(lp, x)
            assert top_idx.max() >= held  # routed over all 32
            picks += int(((top_idx >= lo) & (top_idx < lo + held)).sum())
            routed_prog.append(model.moe_dispatch(lp, x, top_idx, top_vals))
            # mlp_block is the routed part plus the whole shared expert.
            np.testing.assert_allclose(
                model.mlp_block(lp, x), routed_prog[-1] + shared,
                atol=2e-6)
            routed_ref.append(ref.moe(x, lp, cfg,
                                      control="no_shared_expert"))
    # Every pick landed on exactly one share.
    assert picks == x.shape[0] * uncut_cfg["num_experts_per_tok"]
    assert float(jnp.abs(want).mean()) > 1e-3
    np.testing.assert_allclose(sum(routed_prog) + shared, want, atol=2e-6)
    np.testing.assert_allclose(sum(routed_ref) + shared, want, atol=2e-6)
    # A share alone is not the layer: the absent experts' part is left out.
    assert float(jnp.abs(routed_prog[1] + shared - want).max()) > 1e-3


def test_share_must_describe_the_held_experts():
    with pytest.raises(ValueError, match="expert_share"):
        build(tiny_cfg(expert_share={"published_experts": 24, "chips": 4,
                                     "index": 0}))
    with pytest.raises(ValueError, match="expert_share"):
        build(tiny_cfg(expert_share={"published_experts": 32, "chips": 4,
                                     "index": 4}))


def test_yarn_tables_and_score_scale_match_the_reference_statement():
    """The program's pairwise tables and `_sm_scale` against the
    reference's written-out YaRN, with both mscales set, on positions
    past the original context (64 here, 4096 published) and at the
    published rope size, base and factor."""
    from vllm_distributed_tpu.models.common import \
        compute_rope_cos_sin_pairwise
    for cfg in (tiny_cfg(), tiny_cfg(qk_rope_head_dim=64, rope_scaling={
            "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
            "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
            "rope_type": "yarn"}), tiny_cfg(rope_scaling={
                "beta_fast": 32, "beta_slow": 1, "factor": 16,
                "mscale": 0.707, "mscale_all_dim": 1.0,
                "original_max_position_embeddings": 64,
                "rope_type": "yarn"})):
        model = build(cfg)
        S = 6000 if cfg["qk_rope_head_dim"] == 64 else 300
        cos, sin, score = ref.yarn_tables(cfg, S)
        c = model.cfg
        got_cos, got_sin = compute_rope_cos_sin_pairwise(
            jnp.arange(S), c.qk_rope_head_dim, c.rope_theta,
            c.rope_scaling, c.max_position_embeddings)
        np.testing.assert_allclose(got_cos, cos, atol=2e-4)
        np.testing.assert_allclose(got_sin, sin, atol=2e-4)
        plain = (c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5
        assert model._sm_scale() == pytest.approx(plain * score, rel=1e-6)
    # mscale = mscale_all_dim = 1 at factor 64: tables carry 1, the
    # scores (0.1 ln 64 + 1)^2.
    _, _, score = ref.yarn_tables(tiny_cfg(), 4)
    assert score == pytest.approx((0.1 * np.log(64) + 1) ** 2)


@pytest.fixture(scope="module")
def tiny_engine(tmp_path_factory):
    from vllm_distributed_tpu import LLM
    cell = run.load_cell("tiny-gigachat.tiny-decode", DATA)
    cf = cell["config_file"]
    work = run.WORK
    run.WORK = str(tmp_path_factory.mktemp("share"))
    try:
        model_dir = run.write_model_dir(cell["config"], cf)
    finally:
        run.WORK = work
    args = run.engine_args(model_dir, cf, seed=11)
    llm = LLM(model=model_dir, load_format="dummy", seed=args.seed,
              skip_tokenizer_init=True, **cf["engine_args"])
    yield llm, run.hf_config(cf), args
    del llm


def seeded_params(args):
    """The engine's own weights, made again by its loader from the seed
    (as benchmarks.run.compare_with_reference does)."""
    from vllm_distributed_tpu.models.loader import get_model
    from vllm_distributed_tpu.parallel.mesh import build_mesh
    config = args.create_engine_config()
    config.model_config.maybe_load_hf_config()
    mesh = build_mesh(config.parallel_config, jax.devices()[:1])
    return get_model(config, mesh)[1]


def test_prefill_then_decode_logits_match_the_full_forward(tiny_engine):
    """Prompts of 97-209 tokens go in as chunks of 64 and are decoded 8
    steps through the paged latent cache; at every position the 20
    largest log-softmaxed logits the engine reports are the reference's
    (one non-absorbed forward of the whole sequence). Tolerance 2e-4:
    both are float32 and differ in the order of their sums only (the
    absorbed form contracts 576 lanes where the reference contracts 32
    per head, pages where it has one row)."""
    from benchmarks import traffic
    from vllm_distributed_tpu import SamplingParams
    llm, cfg, args = tiny_engine
    params = seeded_params(args)
    reqs = traffic.scoring_requests(9, cfg["vocab_size"])
    outs = llm.generate(
        [r["prompt"] for r in reqs],
        SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True,
                       logprobs=20, prompt_logprobs=20))
    checked = 0
    # One program a sequence length, where eager dispatch compiles each
    # of the reference's operations at each length on its own.
    reference = jax.jit(lambda params, ids: ref.logprobs(params, cfg, ids))
    for r, o in zip(reqs, outs):
        c = o.outputs[0]
        seq = r["prompt"] + list(c.token_ids)
        with jax.default_matmul_precision("highest"):
            table = np.asarray(reference(
                params, jnp.asarray(seq, jnp.int32)))
        rows = list(o.prompt_logprobs[1:]) + list(c.logprobs)
        assert len(rows) == len(seq) - 1
        for pos, row in enumerate(rows):
            assert len(row) >= 20
            for tok, lp in row.items():
                assert abs(table[pos, tok] - lp) < 2e-4, (pos, tok)
                checked += 1
    assert checked >= 20 * sum(len(r["prompt"]) + 7 for r in reqs)


def test_share_gauges_flow_to_metrics(tiny_engine):
    from vllm_distributed_tpu.metrics.prometheus import render_metrics
    llm = tiny_engine[0]
    stats = llm.llm_engine.get_stats()
    entry = next(iter(stats["workers"].values()))
    assert entry["moe_experts_held"] == 8
    assert entry["moe_experts_published"] == 32
    # 3 layers x (32 + 16) lanes x 4 bytes: one token's latent rows.
    assert entry["mla_latent_token_bytes"] == 3 * 48 * 4
    assert entry["mla_latent_page_bytes"] == 16 * 3 * 48 * 4
    text = render_metrics(stats)
    for family in ("vdt:moe_experts_held{", "vdt:moe_experts_published{",
                   "vdt:mla_latent_token_bytes{",
                   "vdt:step_kv_tokens_read_sum"):
        assert family in text, family


@pytest.mark.parametrize("kwargs,what", [
    (dict(enable_expert_parallel=True, tensor_parallel_size=2),
     "enable_expert_parallel"),
    (dict(tensor_parallel_size=2), "TPLA"),
    (dict(pipeline_parallel_size=2), "pipeline parallelism")])
def test_loader_refuses_what_a_share_cannot_be_combined_with(
        kwargs, what, tmp_path, monkeypatch):
    from vllm_distributed_tpu.engine.arg_utils import EngineArgs
    from vllm_distributed_tpu.models.loader import get_model
    from vllm_distributed_tpu.parallel.mesh import build_mesh
    monkeypatch.setenv("VDT_TPLA", "1")
    monkeypatch.setattr(run, "WORK", str(tmp_path))
    cell = run.load_cell("tiny-gigachat.tiny-decode", DATA)
    cf = cell["config_file"]
    model_dir = run.write_model_dir(cell["config"], cf)
    config = EngineArgs(model=model_dir, load_format="dummy",
                        skip_tokenizer_init=True, **cf["engine_args"],
                        **kwargs).create_engine_config()
    n = (kwargs.get("tensor_parallel_size", 1)
         * kwargs.get("pipeline_parallel_size", 1))
    mesh = build_mesh(config.parallel_config, jax.devices()[:n])
    with pytest.raises(ValueError, match="expert_share.*" + what):
        get_model(config, mesh)


def test_mtp_module_is_not_loaded():
    """A checkpoint that carries the multi-token-prediction module (a
    further `model.layers.<L>` with its own projections) loads without
    it: the tree holds `num_hidden_layers` layers and nothing more."""
    cfg = tiny_cfg(num_nextn_predict_layers=1)
    model = build(cfg)
    c = model.cfg
    L, H = c.num_layers, c.hidden_size
    rng = np.random.default_rng(0)

    def w(*shape):
        return rng.standard_normal(shape).astype(np.float32) * 0.02

    N, Pn, R, V = c.num_q_heads, 16, 16, 24
    tensors = {"model.embed_tokens.weight": w(c.vocab_size, H),
               "model.norm.weight": np.ones(H, np.float32),
               "lm_head.weight": w(c.vocab_size, H)}
    for i in range(L + 1):   # layer L is the MTP module's block
        p = f"model.layers.{i}."
        tensors.update({
            p + "input_layernorm.weight": np.ones(H, np.float32),
            p + "post_attention_layernorm.weight": np.ones(H, np.float32),
            p + "self_attn.q_a_proj.weight": w(48, H),
            p + "self_attn.q_a_layernorm.weight": np.ones(48, np.float32),
            p + "self_attn.q_b_proj.weight": w(N * (Pn + R), 48),
            p + "self_attn.kv_a_proj_with_mqa.weight": w(32 + R, H),
            p + "self_attn.kv_a_layernorm.weight": np.ones(32, np.float32),
            p + "self_attn.kv_b_proj.weight": w(N * (Pn + V), 32),
            p + "self_attn.o_proj.weight": w(H, N * V)})
        if i < 1:
            tensors.update({p + f"mlp.{k}_proj.weight": w(*s) for k, s in (
                ("gate", (192, H)), ("up", (192, H)), ("down", (H, 192)))})
            continue
        tensors[p + "mlp.gate.weight"] = w(32, H)
        tensors[p + "mlp.gate.e_score_correction_bias"] = w(32)
        for e in range(8):
            tensors.update({p + f"mlp.experts.{e}.{k}_proj.weight": w(*s)
                            for k, s in (("gate", (64, H)), ("up", (64, H)),
                                         ("down", (H, 64)))})
        tensors.update({p + f"mlp.shared_experts.{k}_proj.weight": w(*s)
                        for k, s in (("gate", (64, H)), ("up", (64, H)),
                                     ("down", (H, 64)))})
    tensors[f"model.layers.{L}.eh_proj.weight"] = w(H, 2 * H)
    params = model.params_from_hf_state_dict(tensors)
    lay = params["layers"]
    assert lay["wo"].shape[0] == L and lay["router"].shape == (L - 1, H, 32)
    assert isinstance(lay["w_gate"], tuple) and len(lay["w_gate"]) == L - 1
    assert lay["w_gate"][0].shape == (8, H, 64)
    assert lay["router_bias"].shape == (L - 1, 32)


def test_rows_past_the_groups_may_hold_anything(monkeypatch):
    """On the TPU `jax.lax.ragged_dot` leaves the rows past
    `sum(group_sizes)` uninitialised (NaN among them: measured on a v5e,
    PR 28); on the CPU it zeroes them. The share's dispatch must give
    the same result either way."""
    cfg = tiny_cfg()
    model = build(cfg)
    lp = moe_layer(model.init_params(jax.random.PRNGKey(5)))
    x = jax.random.normal(jax.random.PRNGKey(6), (21, 128), jnp.float32)
    top_idx, top_vals = model._route(lp, x)
    want = model.moe_dispatch(lp, x, top_idx, top_vals)
    plain = jax.lax.ragged_dot

    def poisoned(lhs, rhs, group_sizes, **kw):
        out = plain(lhs, rhs, group_sizes, **kw)
        past = jnp.arange(lhs.shape[0]) >= group_sizes.sum()
        return jnp.where(past[:, None], jnp.nan, out)

    monkeypatch.setattr(jax.lax, "ragged_dot", poisoned)
    got = model.moe_dispatch(lp, x, top_idx, top_vals)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, atol=1e-6)


def forced_picks(case: str, T: int, k: int, lo: int, n: int, C: int,
                 routed) -> jax.Array:
    """[T, k] expert ids of a routing the router itself would not give.
    Absent picks land on expert 0 (this share holds 8-15)."""
    flat = np.zeros(T * k, np.int32)
    if case == "every_pick_held":
        flat[:] = lo + np.arange(T * k) % n
    elif case == "no_pick_held":
        flat[:] = np.where(np.arange(T * k) % 2, lo - 1, lo + n)
    elif case == "one_held_expert":
        flat[:] = lo + 3
    elif case in ("held_at_capacity", "held_one_past_capacity"):
        # Scattered over the tokens, not the first rows of the sort.
        held = C + (case == "held_one_past_capacity")
        where = np.random.default_rng(8).permutation(T * k)[:held]
        flat[where] = lo + np.arange(held) * 5 % n
    else:
        return routed
    return jnp.asarray(flat.reshape(T, k))


@pytest.mark.parametrize("case,T,passes", [
    ("even_routing", 512, 1), ("every_pick_held", 512, 3),
    ("no_pick_held", 512, 0), ("one_held_expert", 512, 3),
    ("decode_bucket_with_padding_rows", 16, 1),
    ("held_at_capacity", 512, 1), ("held_one_past_capacity", 512, 2)])
def test_dispatch_takes_the_passes_its_held_picks_need(case, T, passes,
                                                       monkeypatch):
    """The share's dispatch works on `share_capacity` rows a pass (896
    here for 512 tokens of 4 picks, a quarter held: seven tiles of 128
    for the 768 that are a half over the even share; all 64 picks of a
    16-row bucket) and takes as many passes as the held picks need;
    whatever the routing, its result is the reference's expert sum
    (benchmarks/references/deepseek.py `moe` without the shared expert,
    given the same picks as a dense weight), in float32 to the
    tolerance of the tests above."""
    cfg = tiny_cfg()
    model = build(cfg)
    lo, n = model.cfg.experts_held
    k = cfg["num_experts_per_tok"]
    C = model.share_capacity(T, k)
    assert C == {512: 896, 16: 64}[T]
    lp = moe_layer(model.init_params(jax.random.PRNGKey(7)))
    x = jax.random.normal(jax.random.PRNGKey(8), (T, 128), jnp.float32)
    if case == "decode_bucket_with_padding_rows":
        x = x.at[9:].set(x[8])           # as the runner pads a bucket
    routed, top_vals = model._route(lp, x)
    top_idx = forced_picks(case, T, k, lo, n, C, routed)
    held = int(((top_idx >= lo) & (top_idx < lo + n)).sum())
    assert cdiv(held, C) == passes, (held, C)
    dense = jnp.zeros((T, model.cfg.router_experts), jnp.float32).at[
        jnp.arange(T)[:, None], top_idx].add(top_vals)
    monkeypatch.setattr(ref, "route", lambda *a, **kw: dense)
    with jax.default_matmul_precision("highest"):
        want = ref.moe(x, lp, cfg, control="no_shared_expert")
        got = jax.jit(model.moe_dispatch)(lp, x, top_idx, top_vals)
    assert (held == 0) == (float(jnp.abs(want).max()) == 0.0)
    np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.parametrize("T,k,held,published,rows", [
    (2176, 8, 16, 256, 1664),    # GigaChat's chunk step: 13 tiles for 1 632
    (2176, 8, 128, 512, 6528),   # Ling's: 51 tiles
    (65, 8, 16, 256, 520),       # a decode step is worked on whole
    (97, 8, 128, 512, 776),
    (129, 8, 16, 256, 128),      # 1 032 picks, 65 expected, 97 wanted
    (1040, 8, 16, 256, 896),     # 780 wanted: 7 tiles
    (1200, 8, 16, 256, 1152),    # 900 wanted: 8 tiles, made 9
    (2176, 8, 200, 256, 17408),  # never above T x k
])
def test_capacity_follows_the_shapes(T, k, held, published, rows):
    """`share_capacity` at the benchmark configurations' shapes and at
    its edges: a half over the even share in an odd count of 128-row
    tiles, the whole step at 1 024 picks or fewer, never above T x k."""
    model = build(tiny_cfg())
    model.cfg.experts_held = (0, held)
    model.cfg.router_experts = published
    C = model.share_capacity(T, k)
    assert C == rows
    if C < T * k:
        assert C % 256 == 128 and C >= 1.5 * T * k * held / published
