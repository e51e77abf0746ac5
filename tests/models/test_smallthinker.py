"""SmallThinker's block (``models/smallthinker.py``) at a toy width,
float32, on the CPU: the engine's steps (prefill in chunks, then decoding
through two cache groups, requests on both sides of an 8-token window in
one batch, 14 query heads in groups of SEVEN) against the plain
reference's one full forward (``benchmarks/references/smallthinker.py``),
logits not tokens; what each control takes away; the hook that carries
the early router's picks past attention, which adds nothing to another
family's program; the refusals.

Two engines in the whole file (``test_granite_hybrid.py``'s docstring
says what one costs): ``engine`` with ``make_engine``'s own arguments on
the CPU's default attention path, and the kernels' test's own, which
differs by the Pallas kernels in interpret mode and a budget of 16 tokens
a step."""

import json
import os

import numpy as np
import pytest

from benchmarks import run
from benchmarks.references import smallthinker as reference
from test_granite_hybrid import engine  # noqa: F401 (this module's own)
from test_granite_hybrid import (jit_logprobs, make_engine, prompt,
                                 runner_of, serve)

DATA = os.path.join(os.path.dirname(__file__), "..", "benchmarking", "data")
CELL = "tiny-smallthinker.tiny-mixed"
ARCH = "SmallThinkerForCausalLM"
WINDOW = 8
EVERY_CONTROL = ("router_after_attention", "silu_experts",
                 "rope_on_full_layers", "no_rope", "no_window")
# Float32 against float32 through eight layers: the same sums in another
# order.
ATOL = 2e-5


@pytest.fixture(scope="module")
def tiny():
    return run.load_cell(CELL, DATA)["config_file"]


@pytest.fixture(scope="module")
def model_dir(tiny, tmp_path_factory):
    work = run.WORK
    run.WORK = str(tmp_path_factory.mktemp("smallthinker"))
    try:
        return run.write_model_dir("tiny-smallthinker", tiny)
    finally:
        run.WORK = work


def reference_logprobs(engine, cfg, tokens, control=None):
    return jit_logprobs(reference, runner_of(engine).params, cfg, tokens,
                        control)


# Seventy tokens (nine windows), thirty-seven, six that cross the window
# while they decode, and three that never reach it: one running batch.
MIXED = [(70, 0), (37, 1), (6, 2), (3, 3)]


def test_both_sides_of_the_window_in_one_batch_match_the_reference(
        engine, tiny):
    """Prefill, then four decode steps through both cache groups: every
    logprob equals the reference's one full forward; the window group
    handed pages back for the long requests and the step's count of
    requests past the window saw both kinds."""
    before = engine.get_stats()
    got = serve(engine, [prompt(n, k) for n, k in MIXED], 4)
    cfg = run.hf_config(tiny)
    for tokens, lps in got:
        assert len(tokens) - 1 == len(lps)
        np.testing.assert_allclose(
            lps, reference_logprobs(engine, cfg, tokens), atol=ATOL)
    assert len(got[3][0]) == 7 < WINDOW < len(got[2][0]) == 10
    stats = engine.get_stats()
    assert stats["kv_window_pages_freed"] > \
        before.get("kv_window_pages_freed", 0)
    past = stats["step_requests_past_window"]
    assert past["count"] == stats["step_kv_window_tokens_read"]["count"]
    # Five steps of four requests: never all four past the window (one
    # stays inside it for all of its life), two of them from the start.
    steps = past["count"] - before.get(
        "step_requests_past_window", {"count": 0})["count"]
    total = past["sum"] - before.get(
        "step_requests_past_window", {"sum": 0})["sum"]
    assert steps == 5 and 2 * steps <= total <= 3 * steps


def test_chunks_and_the_group_of_seven_through_the_kernels(
        model_dir, monkeypatch, tiny):
    """The same batch under a budget of 16 tokens a step (the long
    prompts come in chunks that straddle pages and windows) through the
    Pallas kernels in interpret mode: 14 query heads on 2 K/V heads, each
    group of seven padded to eight rows in the launcher
    (``ops/pallas_attention.padded_q_heads``), window layers on the
    trimmed page loop, full layers without rope."""
    from vllm_distributed_tpu.ops.pallas_attention import padded_q_heads
    assert padded_q_heads(14, 2) == 16 and padded_q_heads(28, 4) == 32
    assert [padded_q_heads(*g) for g in ((16, 16), (32, 8), (128, 8),
                                         (8, 2))] == [16, 32, 128, 8]
    from vllm_distributed_tpu.ops import pallas_attention
    # The backend is resolved when a program is traced, at the first
    # step of its shape: the flag stands for the whole test.
    monkeypatch.setenv("VDT_ATTENTION_BACKEND", "pallas")
    engine_pallas = make_engine(model_dir, monkeypatch,
                                max_num_batched_tokens=16)
    launched = []
    real = pallas_attention._mega_call

    def spy(q, *args, **kwargs):
        launched.append((q.shape[1], kwargs["window"]))
        return real(q, *args, **kwargs)

    monkeypatch.setattr(pallas_attention, "_mega_call", spy)
    got = serve(engine_pallas, [prompt(n, k) for n, k in MIXED], 4)
    # Every traced layer went through the kernel's launcher with the 14
    # published heads: window layers trimmed, full layers not.
    assert {heads for heads, _ in launched} == {14}
    assert {w for _, w in launched} == {0, WINDOW}
    cfg = run.hf_config(tiny)
    for tokens, lps in got:
        np.testing.assert_allclose(
            lps, reference_logprobs(engine_pallas, cfg, tokens), atol=ATOL)


@pytest.fixture(scope="module")
def served_once(engine, tiny):
    """One served request of seven windows, its engine and the
    reference's logprobs."""
    (got, ) = serve(engine, [prompt(58, 4)], 6)
    return got, engine, reference_logprobs(engine, run.hf_config(tiny),
                                           got[0])


@pytest.mark.parametrize("control", EVERY_CONTROL)
def test_each_piece_changes_the_output_when_taken_away(served_once, tiny,
                                                       control):
    """The engine agrees with the reference; with the router moved
    behind attention, the gate's ReLU swapped for a SiLU, the full layers
    rotated, no layer rotated, or the window mask dropped, it does not."""
    (tokens, lps), engine, whole = served_once
    broken = reference_logprobs(engine, run.hf_config(tiny), tokens,
                                control)
    dev = np.abs(np.asarray(lps) - whole).max()
    assert dev < ATOL
    assert np.abs(np.asarray(lps) - broken).max() > 100 * dev + 1e-4
    assert set(reference.CONTROLS[ARCH]) <= set(EVERY_CONTROL) - {
        "no_window"}


def test_the_router_reads_the_attention_s_input(served_once, tiny):
    """``pre_attention`` on a layer's own weights is the reference's
    ``route`` of the same rows, and ``mlp_block`` computes the experts at
    THOSE picks on other rows; without them it refuses."""
    import jax
    import jax.numpy as jnp
    _, engine, _ = served_once
    runner = runner_of(engine)
    model, cfg = runner.model, run.hf_config(tiny)
    rng = np.random.default_rng(4)
    x, y = (jnp.asarray(rng.standard_normal((19, cfg["hidden_size"])),
                        jnp.float32) for _ in range(2))
    lay = runner.params["layers"]
    lp = {k: lay[k][5] for k in lay}
    with jax.default_matmul_precision("highest"):
        idx, vals = model.pre_attention(lp, x)
        weight = reference.route(x, lp["router"], cfg)
        got = model.mlp_block(lp, y, early=(idx, vals))
        want = reference.experts(y, weight, lp)
    assert idx.shape == vals.shape == (19, 3)
    np.testing.assert_allclose(vals.sum(-1), 1.0, atol=1e-6)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(weight), np.asarray(idx), 1), vals,
        atol=1e-6)
    np.testing.assert_allclose(got, want, atol=2e-6)
    with pytest.raises(ValueError, match="pre_attention"):
        model.mlp_block(lp, y)


def _build(cfg: dict, path, **engine_args):
    """(engine configuration, one-device mesh, model, seeded parameters)
    of a tiny ``config.json`` written to ``path``: no engine."""
    import jax

    from vllm_distributed_tpu.engine.arg_utils import EngineArgs
    from vllm_distributed_tpu.models.loader import get_model
    from vllm_distributed_tpu.parallel.mesh import build_mesh
    path.mkdir(exist_ok=True)
    (path / "config.json").write_text(json.dumps(cfg))
    args = dict(model=str(path), load_format="dummy", dtype="float32",
                max_model_len=128, skip_tokenizer_init=True)
    args.update(engine_args)
    config = EngineArgs(**args).create_engine_config()
    mesh = build_mesh(config.parallel_config, jax.devices()[:1])
    return (config, mesh, *get_model(config, mesh))


def _runner(cfg: dict, tmp_path, name: str):
    """A ``TPUModelRunner`` on a tiny configuration without an engine:
    enough to lower its forward program."""
    from vllm_distributed_tpu.worker.model_runner import TPUModelRunner
    config, mesh, model, params = _build(
        cfg, tmp_path / name, max_num_batched_tokens=64, max_num_seqs=4)
    params["layers"] = model.serving_layout(params["layers"])
    return TPUModelRunner(config, mesh, model=model, params=params)


def _lowered(runner, scopes: bool = False) -> str:
    """The forward program's text at the 16-token bucket; with
    ``scopes`` the operations' named scopes stand in it too."""
    import jax
    _, shapes, shardings = runner._cache_layout(16)
    caches = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        shapes, shardings)
    runner._build_step_fn()
    T, max_q, G = runner._batch_shape(16, 2)
    with runner.mesh:
        return runner._forward_fn.lower(
            runner.params, caches,
            runner._dummy_step_inputs(T, max_q, G)).as_text(
                debug_info=scopes)


@pytest.mark.parametrize("name", ["llama", "tiny-olmoe"])
def test_the_hook_adds_nothing_to_another_family_s_program(
        tiny, tmp_path, monkeypatch, name):
    """A Llama's and an OLMoE's forward program (the expert switch with
    one branch a layer) lowers to the same text whether ``layer_body``
    asks the family's ``pre_attention`` or not; a hook that does compute
    shows in the text, so the comparison can tell; and SmallThinker's
    own program has its early router ahead of the layer's attention."""
    from vllm_distributed_tpu.models.llama import LlamaForCausalLM
    if name == "llama":
        cfg = {k: v for k, v in run.hf_config(run.load_json(
            DATA, "configs", "tiny-olmo2.json")).items()
            if k not in ("architectures", "model_type")}
    else:
        cfg = run.hf_config(run.load_json(DATA, "configs", f"{name}.json"))
    runner = _runner(cfg, tmp_path, name)
    stock = _lowered(runner)
    assert "moe_early_router" not in _lowered(runner, scopes=True)
    calls = []

    def never_asked(self, lp, x):
        calls.append(x.shape)
        return None

    monkeypatch.setattr(LlamaForCausalLM, "pre_attention", never_asked)
    assert _lowered(_runner(cfg, tmp_path, name + "-again")) == stock
    assert calls      # the walk did ask, and got nothing

    def computes(self, lp, x):
        return (x * 2.0).sum()

    block = type(runner.model).mlp_block
    monkeypatch.setattr(LlamaForCausalLM, "pre_attention", computes)
    monkeypatch.setattr(
        type(runner.model), "mlp_block",
        lambda self, lp, x, lora_ctx=None, early=None: block(
            self, lp, x, lora_ctx) + early.astype(x.dtype))
    assert _lowered(_runner(cfg, tmp_path, name + "-hooked")) != stock


def test_the_early_router_stands_before_the_layer_s_attention(tiny,
                                                              tmp_path):
    """The named scope ``moe_early_router`` is in SmallThinker's own
    program, once a layer, and the first of them stands ahead of the
    first layer's expert products in program order (the layer's
    attention lies between the two: ``layer_body``)."""
    import re
    text = _lowered(_runner(run.hf_config(tiny), tmp_path, "own"),
                    scopes=True)
    # The scopes stand in the location table at the end; an operation
    # names its location by number, in program order.
    router = set(re.findall(r"(#loc\d+) = loc\(\"[^\"]*moe_early_router",
                            text))
    assert len(router) >= 8
    used = re.findall(r"loc\((#loc\d+)\)", text)
    first_router = next(i for i, loc in enumerate(used) if loc in router)
    gemm = set(re.findall(r"(#loc\d+) = loc\(\"[^\"]*moe_gate_gemm", text))
    first_gemm = next(i for i, loc in enumerate(used) if loc in gemm)
    assert first_router < first_gemm


def _load(tiny, tmp_path, **over):
    """(model, parameters) of the toy configuration with ``over`` laid
    over it; ``engine`` in ``over`` goes to the engine's arguments."""
    engine_over = over.pop("engine", {})
    return _build({**run.hf_config(tiny), **over}, tmp_path,
                  **engine_over)[2:]


@pytest.mark.parametrize("over,message", [
    ({"moe_primary_router_apply_softmax": False},
     "moe_primary_router_apply_softmax"),
    ({"rope_scaling": {"rope_type": "yarn", "factor": 4.0}},
     "rope_scaling"),
    ({"sliding_window_size": [8, 16]}, "unequal"),
    ({"sliding_window_layout": [0, 1, 1, 1]}, "sliding_window_layout"),
    ({"rope_layout": [0, 1, 2, 1, 0, 1, 1, 1]}, "rope_layout"),
    ({"sliding_window_size": None}, "sliding_window_size"),
    ({"engine": {"num_speculative_tokens": 2,
                 "speculative_method": "ngram"}}, "cache groups"),
    ({"engine": {"kv_cache_dtype": "fp8"}}, "cache groups"),
])
def test_what_is_not_wired_is_refused_at_load_by_name(tiny, tmp_path, over,
                                                      message):
    with pytest.raises(ValueError, match=message):
        _load(tiny, tmp_path, **over)


def test_a_checkpoint_s_tensor_names_are_refused(served_once):
    _, engine, _ = served_once
    with pytest.raises(NotImplementedError, match="offline"):
        runner_of(engine).model.params_from_hf_state_dict({})


def test_the_layouts_as_the_model_reads_them(served_once, tiny):
    """Two periods of (full without rope, window, window, window): the
    pattern, the NoPE mask, the ReLU, the second cache group (six window
    layers' pages in a pool of their own, two full layers' in the sized
    one), and the attention matrices a buffer a layer for the unrolled
    walk."""
    _, engine, _ = served_once
    runner = runner_of(engine)
    c = runner.model.cfg
    assert c.window_pattern == (0, WINDOW, WINDOW, WINDOW) * 2
    assert c.nope_layers == (True, False, False, False) * 2
    assert (c.hidden_act, c.window_group, c.num_experts,
            c.num_experts_per_tok, c.moe_intermediate_size) == (
                "relu", WINDOW, 8, 3, 32)
    assert runner.model._group_depths() == (2, 6)
    caches = runner.kv_caches
    assert caches["k"].shape[0] == 2 and caches["k_win"].shape[0] == 6
    assert runner.model.fixed_cache_bytes() == \
        2 * caches["k_win"].size * 4
    lay = runner.params["layers"]
    for key in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
        assert isinstance(lay[key], tuple) and len(lay[key]) == 8, key
    assert not isinstance(lay["router"], tuple)


@pytest.mark.parametrize("bit", [0, 1])
def test_one_kind_of_layer_keeps_one_group(tiny, tmp_path, bit):
    """Every layer windowed, or none: no second group, the walk is the
    scan, and only the experts are a buffer a layer."""
    from vllm_distributed_tpu.models.loader import (resolve_free_window,
                                                    resolve_window_group)
    model, _ = _load(tiny, tmp_path, sliding_window_layout=[bit] * 8)
    assert model.cfg.window_group is None
    assert model.cfg.window_pattern is None
    assert model.cfg.sliding_window == (WINDOW if bit else None)
    assert set(model.kv_cache_specs()) == {"k", "v"}
    assert model.fixed_cache_bytes() == 0
    assert model._PER_LAYER == model._EXPERT_WEIGHTS

    class MC:
        def maybe_load_hf_config(self):
            from transformers import PretrainedConfig
            with open(tmp_path / "config.json") as f:
                return PretrainedConfig(**json.load(f))

    assert resolve_free_window(MC()) == (WINDOW if bit else None)
    assert resolve_window_group(MC()) is None
