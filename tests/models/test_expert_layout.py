"""Where the Mixtral family's expert weights live (ISSUE 29).

``get_model`` returns the canonical layer-stacked tree; the runner hands
the jitted programs ``model.serving_layout`` of it: expert weights as
tuples of per-layer buffers, so that every ``jax.lax.ragged_dot`` reads a
buffer that is an argument of the program. These tests hold

* the mechanism, structurally: in the traced forward the weight operand
  of every ``ragged_dot`` IS a top-level input (through the layer scan
  as a constant, through the per-layer switch as a branch operand), and
  a dense model's forward is still exactly the scans it was;
* the equivalence: per family, logits over the serving layout against
  HF's float32 forward of the same weights and against the canonical
  tree through the scan branch;
* what else meets the layout: EPLB install and a live rebalance, a
  pipeline stage's slice, int8 expert weights, sleep and wake.
"""

import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch
import transformers
from jax.extend.core import Var

from vllm_distributed_tpu.engine.arg_utils import EngineArgs
from vllm_distributed_tpu.models import mixtral
from vllm_distributed_tpu.models.common import AttentionBatch
from vllm_distributed_tpu.models.loader import get_model
from vllm_distributed_tpu.parallel.mesh import build_mesh

PAGE_SIZE = 4
NUM_PAGES = 16
PROMPT = [3, 17, 92, 45, 8, 77, 23, 55, 10]
_COMMON = dict(vocab_size=128, hidden_size=64, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2,
               max_position_embeddings=64, eos_token_id=1)


def _configs() -> dict:
    t = transformers
    return {
        "mixtral": (t.MixtralForCausalLM, t.MixtralConfig(
            **_COMMON, intermediate_size=96, num_local_experts=4,
            num_experts_per_tok=2)),
        "olmoe": (t.OlmoeForCausalLM, t.OlmoeConfig(
            **_COMMON, intermediate_size=96, num_experts=4,
            num_experts_per_tok=2, norm_topk_prob=False)),
        "qwen2moe": (t.Qwen2MoeForCausalLM, t.Qwen2MoeConfig(
            **_COMMON, intermediate_size=128, moe_intermediate_size=32,
            shared_expert_intermediate_size=64, num_experts=4,
            num_experts_per_tok=2, decoder_sparse_step=1,
            mlp_only_layers=[])),
        "gptoss": (t.GptOssForCausalLM, t.GptOssConfig(
            **_COMMON, intermediate_size=64, num_local_experts=4,
            num_experts_per_tok=2, sliding_window=8, head_dim=16)),
        # A _DensePrefixMoe: one dense layer, then two routed ones.
        "glm4moe": (t.Glm4MoeForCausalLM, t.Glm4MoeConfig(
            **dict(_COMMON, num_hidden_layers=3), intermediate_size=128,
            n_routed_experts=4, num_experts_per_tok=2,
            moe_intermediate_size=48, n_shared_experts=1,
            first_k_dense_replace=1, head_dim=16, use_qk_norm=True,
            partial_rotary_factor=0.5, routed_scaling_factor=1.5,
            norm_topk_prob=True, n_group=2, topk_group=2,
            pad_token_id=0)),
        "jamba": (t.JambaForCausalLM, t.JambaConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=4, num_attention_heads=4,
            num_key_value_heads=2, mamba_d_state=8, mamba_d_conv=4,
            mamba_expand=2, mamba_dt_rank=4, attn_layer_period=4,
            attn_layer_offset=2, expert_layer_period=2,
            expert_layer_offset=1, num_experts=4, num_experts_per_tok=2,
            max_position_embeddings=64, eos_token_id=1,
            tie_word_embeddings=False, use_mamba_kernels=False)),
        "llama": (t.LlamaForCausalLM, t.LlamaConfig(
            **_COMMON, intermediate_size=128)),
        "olmo2": (t.Olmo2ForCausalLM, t.Olmo2Config(
            **_COMMON, intermediate_size=128)),
    }


MOE_FAMILIES = ["mixtral", "olmoe", "qwen2moe", "gptoss", "glm4moe",
                "jamba"]


@pytest.fixture(scope="module")
def checkpoints(tmp_path_factory):
    """name -> (path, hf model), built on first use."""
    made = {}

    def get(name):
        if name not in made:
            cls, cfg = _configs()[name]
            torch.manual_seed(7)
            hf = cls(cfg).eval()
            path = str(tmp_path_factory.mktemp(f"layout_{name}"))
            hf.save_pretrained(path, safe_serialization=True)
            made[name] = (path, hf)
        return made[name]

    return get


def load(path, **overrides):
    """(model, canonical params) exactly as a runner gets them."""
    args = dict(model=path, dtype="float32", block_size=PAGE_SIZE,
                num_gpu_blocks_override=NUM_PAGES, max_model_len=64,
                max_num_batched_tokens=64, max_num_seqs=4,
                skip_tokenizer_init=True)
    args.update(overrides)
    config = EngineArgs(**args).create_engine_config()
    config.model_config.maybe_load_hf_config()
    mesh = build_mesh(config.parallel_config, jax.devices()[:1])
    return get_model(config, mesh)


def served(model, params) -> dict:
    """The serving tree beside the canonical one (which stays whole: the
    dict is copied, the stacks are shared until they are split)."""
    return dict(params, layers=model.serving_layout(dict(params["layers"])))


def one_request_batch(T: int) -> AttentionBatch:
    pos = np.arange(T)
    bt = np.zeros((1, 8), np.int32)
    bt[0, :4] = (1, 2, 3, 4)
    slot = bt[0, pos // PAGE_SIZE] * PAGE_SIZE + pos % PAGE_SIZE
    return AttentionBatch(
        req_idx=jnp.zeros((T, ), jnp.int32),
        positions=jnp.asarray(pos, jnp.int32),
        slot_mapping=jnp.asarray(slot, jnp.int32),
        block_tables=jnp.asarray(bt),
        seq_lens=jnp.asarray([T], jnp.int32),
        seq_info=jnp.asarray([[0, T, T, 0]], jnp.int32),
        num_seqs=jnp.asarray([1], jnp.int32))


def forward(model, params, tokens=PROMPT):
    kv = model.make_kv_caches(NUM_PAGES, PAGE_SIZE)
    hidden, _ = model.forward(params, kv, jnp.asarray(tokens, jnp.int32),
                              one_request_batch(len(tokens)))
    return model.compute_logits(params, hidden)


def logits(model, params, tokens=PROMPT) -> np.ndarray:
    return np.asarray(jax.jit(lambda p: forward(model, p, tokens))(params))


# ---------------------------------------------------------------------
# The mechanism, read off the traced program
# ---------------------------------------------------------------------
_CALLS = {"pjit": "jaxpr", "jit": "jaxpr", "closed_call": "call_jaxpr",
          "core_call": "call_jaxpr", "remat": "jaxpr",
          "checkpoint": "jaxpr", "custom_jvp_call": "call_jaxpr",
          "custom_vjp_call": "call_jaxpr", "shard_map": "jaxpr"}


def _open(jaxpr):
    return getattr(jaxpr, "jaxpr", jaxpr)


def walk(jaxpr, origin: dict, found: dict, depth: int = 0) -> None:
    """``origin`` maps a variable of ``jaxpr`` to the top-level input it
    IS (through nested calls, unchanged); anything computed is absent.
    Collects ragged_dot weight origins, and every scan as (how many
    scans it sits inside, its length, how many operands it slices)."""
    for eqn in _open(jaxpr).eqns:
        name = eqn.primitive.name
        if name.startswith("ragged_dot"):
            w = eqn.invars[1]
            found["ragged"].append(
                (origin.get(w) if isinstance(w, Var) else None,
                 tuple(w.aval.shape)))
        if name in _CALLS:
            inner = _open(eqn.params[_CALLS[name]])
            walk(inner, {i: origin[o] for i, o in
                         zip(inner.invars, eqn.invars)
                         if isinstance(o, Var) and o in origin},
                 found, depth)
        elif name == "scan":
            inner = _open(eqn.params["jaxpr"])
            nc = eqn.params["num_consts"]
            found["scans"].append(
                (depth, eqn.params["length"],
                 len(eqn.invars) - nc - eqn.params["num_carry"]))
            # Only the constants pass through a scan unchanged: the
            # carry is computed and the xs are slices of their stacks.
            walk(inner, {i: origin[o] for i, o in
                         zip(inner.invars[:nc], eqn.invars[:nc])
                         if isinstance(o, Var) and o in origin},
                 found, depth + 1)
        elif name == "cond":
            # Branch operands pass through unchanged (invars[0] is the
            # branch index).
            for branch in eqn.params["branches"]:
                inner = _open(branch)
                walk(inner, {i: origin[o] for i, o in
                             zip(inner.invars, eqn.invars[1:])
                             if isinstance(o, Var) and o in origin},
                     found, depth)
        elif name == "while":
            for key in ("body_jaxpr", "cond_jaxpr"):
                walk(eqn.params[key], {}, found, depth)


def trace(model, params) -> dict:
    closed = jax.make_jaxpr(lambda p: forward(model, p))(params)
    paths = [jax.tree_util.keystr(k) for k, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]]
    found = {"ragged": [], "scans": []}
    walk(closed, dict(zip(closed.jaxpr.invars, paths)), found)
    # The layer scan slices every stacked leaf of the layer tree and the
    # layer ids; attention's own scans (XLA path, over page indices)
    # slice one operand.
    found["layer_scans"] = [s for s in found["scans"] if s[2] > 1]
    return found


@pytest.mark.parametrize("family", ["olmoe", "mixtral"])
def test_every_ragged_dot_reads_a_program_argument(checkpoints, family):
    model, params = load(checkpoints(family)[0])
    L = model.cfg.num_layers
    found = trace(model, served(model, params))
    # Three grouped GEMMs a layer, each on its own layer's buffer.
    assert len(found["ragged"]) == 3 * L
    origins = [o for o, _ in found["ragged"]]
    assert all(o is not None for o in origins), found["ragged"]
    assert sorted(origins) == sorted(
        f"['layers']['{k}'][{i}]" for k in ("w_gate", "w_up", "w_down")
        for i in range(L))
    # One scan over the layers still, which slices the stacked leaves
    # and the layer ids and leaves the expert weights alone.
    stacked = len(params["layers"]) - 3
    assert found["layer_scans"] == [(0, L, stacked + 1)]
    # The walker has teeth: over the canonical tree the operand is a
    # slice of a scanned stack, not an argument.
    canonical = trace(model, params)
    assert canonical["layer_scans"] == [(0, L, stacked + 3 + 1)]
    assert len(canonical["ragged"]) == 3
    assert all(o is None for o, _ in canonical["ragged"])


@pytest.mark.parametrize("family", ["llama", "olmo2"])
def test_a_dense_model_still_scans(checkpoints, family):
    model, params = load(checkpoints(family)[0])
    layers = params["layers"]
    assert model.serving_layout(layers) is layers
    assert all(not isinstance(v, tuple) for v in layers.values())
    found = trace(model, params)
    assert found["ragged"] == []
    # Exactly the scans it had: one over the layers, which slices every
    # leaf of the layer tree and the layer ids, and attention's inside.
    L = model.cfg.num_layers
    assert found["layer_scans"] == [(0, L, len(layers) + 1)]
    others = [s for s in found["scans"] if s not in found["layer_scans"]]
    assert all(depth == 1 for depth, _, _ in others)


# ---------------------------------------------------------------------
# Equivalence, per family
# ---------------------------------------------------------------------
@pytest.mark.parametrize("family", MOE_FAMILIES)
def test_serving_layout_matches_canonical_and_hf(checkpoints, family,
                                                 monkeypatch):
    path, hf = checkpoints(family)
    model, params = load(path)
    canon = params["layers"]
    keys = model._per_layer_keys(canon)
    assert len(keys) >= 3
    depth = canon[keys[0]].shape[0]
    for k in keys:      # the loader's tree is layer-stacked
        assert not isinstance(canon[k], tuple), k
        assert canon[k].shape[0] == depth

    # One key at a time: when a stack is split, the one before it is
    # gone (nothing but the leaves holds its bytes).
    earlier = []
    real = mixtral._unstack

    def watched(stack):
        gc.collect()
        assert all(ref() is None for ref in earlier), \
            "an earlier key's stack is still alive"
        out = real(stack)
        earlier.append(weakref.ref(stack))
        return out

    monkeypatch.setattr(mixtral, "_unstack", watched)
    private = jax.tree.map(jnp.copy, canon)   # stacks nothing else holds
    serving = model.serving_layout(private)
    assert serving is private and len(earlier) == len(keys)
    monkeypatch.undo()

    for k in keys:
        leaves, stack = serving[k], canon[k]
        assert isinstance(leaves, tuple) and len(leaves) == depth
        for i, leaf in enumerate(leaves):
            assert leaf.shape == stack.shape[1:], k
            assert leaf.sharding.is_equivalent_to(
                jax.sharding.NamedSharding(
                    stack.sharding.mesh,
                    jax.sharding.PartitionSpec(*stack.sharding.spec[1:])),
                leaf.ndim), k
            np.testing.assert_array_equal(np.asarray(leaf),
                                          np.asarray(stack[i]))
    for k, v in serving.items():    # everything else stays stacked
        assert isinstance(v, tuple) == (k in keys), k

    got = logits(model, dict(params, layers=serving))
    want = logits(model, params)             # the scan / stacked walk
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    with torch.no_grad():
        ref = hf(torch.tensor([PROMPT])).logits[0].float().numpy()
    np.testing.assert_allclose(got, ref, rtol=5e-4, atol=5e-4)
    # And back: what a sharded-state checkpoint stores.
    back = model.canonical_layout(serving)
    for k in keys:
        np.testing.assert_array_equal(back[k], np.asarray(canon[k]))


# ---------------------------------------------------------------------
# What else meets the layout
# ---------------------------------------------------------------------
def test_eplb_install_and_live_rebalance_on_the_serving_layout(checkpoints):
    from vllm_distributed_tpu.parallel.eplb import rebalance_experts
    path, _hf = checkpoints("mixtral")
    plain_model, plain = load(path)
    want = logits(plain_model, plain)
    model, params = load(path, num_redundant_experts=2)
    L, Pn = model.cfg.num_layers, model.num_physical
    assert params["layers"]["w_gate"].shape[:2] == (L, Pn)   # installed
    serving = served(model, params)
    np.testing.assert_allclose(logits(model, serving), want, atol=1e-5)

    before = jax.tree.map(np.asarray, serving["layers"])
    first = rebalance_experts(np.asarray([[5.0, 1.0, 9.0, 2.0]] * L), Pn, 1)
    moved = model.apply_rebalance(serving, first)
    for k in ("w_gate", "w_up", "w_down"):
        assert isinstance(moved["layers"][k], tuple)
        assert len(moved["layers"][k]) == L
        assert moved["layers"][k][0].shape == before[k][0].shape
    assert not np.array_equal(np.asarray(moved["layers"]["expert_map"]),
                              before["expert_map"])
    np.testing.assert_allclose(logits(model, moved), want, atol=1e-5)
    # Round trip: the first placement again gives the first weights.
    again = rebalance_experts(np.ones((L, 4)), Pn, 1)
    back = model.apply_rebalance(moved, again)
    np.testing.assert_array_equal(np.asarray(back["layers"]["expert_map"]),
                                  before["expert_map"])
    for k in ("w_gate", "w_up", "w_down"):
        for l in range(L):
            np.testing.assert_array_equal(np.asarray(back["layers"][k][l]),
                                          before[k][l])


def test_a_pipeline_stage_slices_and_serves(checkpoints):
    model, params = load(checkpoints("olmoe")[0])
    tokens = jnp.asarray(PROMPT, jnp.int32)
    batch = one_request_batch(len(PROMPT))
    hidden = model.embed(params, tokens, batch.positions)
    want, _ = model.run_layers(
        params["layers"], model.make_kv_caches(NUM_PAGES, PAGE_SIZE),
        hidden, batch)
    h = hidden
    for s, e in ((0, 1), (1, 2)):
        # As worker/pp_runner.py: slice the canonical tree, then serve.
        stage = model.serving_layout(
            model.slice_layer_params(params["layers"], s, e))
        assert isinstance(stage["w_gate"], tuple)
        assert len(stage["w_gate"]) == e - s
        assert stage["wq"].shape[0] == e - s
        h, _ = model.run_layers(
            stage, model.make_kv_caches(NUM_PAGES, PAGE_SIZE,
                                        num_layers=e - s),
            h, batch, first_layer=s)
    np.testing.assert_allclose(np.asarray(h), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # A slice of a serving tree is a serving tree.
    whole = model.serving_layout(dict(params["layers"]))
    tail = model.slice_layer_params(whole, 1, 2)
    assert isinstance(tail["w_up"], tuple) and len(tail["w_up"]) == 1


def test_int8_expert_weights_on_the_serving_layout(checkpoints):
    model, params = load(checkpoints("mixtral")[0], quantization="int8")
    canon = params["layers"]
    assert canon["w_gate"].dtype == jnp.int8
    keys = model._per_layer_keys(canon)
    assert set(keys) == {k + s for k in ("w_gate", "w_up", "w_down")
                         for s in ("", "_scale")}
    serving = served(model, params)
    for k in keys:
        assert isinstance(serving["layers"][k], tuple), k
        assert serving["layers"][k][0].shape == canon[k].shape[1:]
    assert serving["layers"]["w_gate"][0].dtype == jnp.int8
    np.testing.assert_allclose(logits(model, serving),
                               logits(model, params), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("level", [1, 2])
def test_sleep_and_wake_keep_the_serving_layout(checkpoints, level):
    from vllm_distributed_tpu.engine.llm_engine import LLMEngine
    from vllm_distributed_tpu.sampling_params import SamplingParams
    path, _hf = checkpoints("mixtral")
    engine = LLMEngine(EngineArgs(
        model=path, dtype="float32", block_size=PAGE_SIZE,
        num_gpu_blocks_override=64, max_model_len=64,
        max_num_batched_tokens=64, max_num_seqs=4,
        skip_tokenizer_init=True).create_engine_config())
    runner = engine.engine_core.engine_core.executor.worker.model_runner

    def run(tag):
        engine.add_request(tag, PROMPT[:5], SamplingParams(
            temperature=0.0, max_tokens=6, ignore_eos=True))
        for _ in range(100):
            for out in engine.step():
                if out.finished:
                    return out.outputs[0].token_ids
        raise AssertionError("did not finish")

    def layout():
        return {k: (len(v), v[0].shape, v[0].sharding)
                if isinstance(v, tuple) else (v.shape, v.sharding)
                for k, v in runner.params["layers"].items()}

    before, was = run("a"), layout()
    assert isinstance(runner.params["layers"]["w_gate"], tuple)
    engine.sleep(level=level)
    engine.wake_up()
    assert layout() == was
    assert run("b") == before
