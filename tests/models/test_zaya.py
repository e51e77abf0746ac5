"""ZAYA1 (``models/zaya.py``) at a toy width, float32, on the CPU: the
engine's steps against the plain reference
(``benchmarks/references/zaya.py``); the carried rows across chunks of
every length, through preemption and a snapshot; what each control takes
away; the refusals."""

import json
import os

import numpy as np
import pytest

from benchmarks import run
from benchmarks.references import zaya as reference
# The engine, its steps and the prompts as the other carried-state
# model's tests make them (float32, 4 rows, a 64-token budget, seed 5),
# and one engine a module for the tests that want those arguments (that
# file's docstring says what an engine costs and what sharing one asks).
from test_granite_hybrid import engine  # noqa: F401 (this module's own)
from test_granite_hybrid import (drain, jit_logprobs, make_engine, prompt,
                                 runner_of, serve, served, two_turns)
from vllm_distributed_tpu import SamplingParams
from vllm_distributed_tpu.engine.arg_utils import EngineArgs
from vllm_distributed_tpu.engine.llm_engine import LLMEngine

DATA = os.path.join(os.path.dirname(__file__), "..", "benchmarking", "data")
CELL = "tiny-zaya.tiny-decode"
ARCH = "ZayaForCausalLM"


@pytest.fixture(scope="module")
def tiny():
    return run.load_cell(CELL, DATA)["config_file"]


@pytest.fixture(scope="module")
def model_dir(tiny, tmp_path_factory):
    work = run.WORK
    run.WORK = str(tmp_path_factory.mktemp("zaya"))
    try:
        return run.write_model_dir("tiny-zaya", tiny)
    finally:
        run.WORK = work


def reference_logprobs(engine, tiny, tokens, control=None):
    """The reference's logprobs of ``tokens[1:]`` on the engine's own
    seeded weights."""
    return jit_logprobs(reference, runner_of(engine).params,
                        run.hf_config(tiny), tokens, control)


def test_prefill_then_decode_matches_the_reference(engine, tiny):
    """Three requests of different lengths in one batch (37, 7 and 2
    tokens), prefill then six decode steps through the carried rows and
    the pages: every logprob equals the reference's one full forward."""
    got = serve(engine, [prompt(37), prompt(7, 1), prompt(2, 2)], 6)
    for tokens, lps in got:
        want = reference_logprobs(engine, tiny, tokens)
        np.testing.assert_allclose(lps, want, atol=2e-5)


def carried_rows(engine):
    """The two carried rows, in every layer, of the sequence the engine
    served last and alone (its row is still its own: a finished request
    leaves the batch at the next step)."""
    runner = runner_of(engine)
    (row, ) = runner.input_batch.req_id_to_index.values()
    return {name: np.asarray(runner.kv_caches[name])[:, row]
            for name in ("cca_u", "cca_cv")}


@pytest.fixture(scope="module")
def one_pass(engine, tiny):
    """A 40-token prompt in one pass under the budget of 64: its tokens
    and logprobs, held to the reference, and the rows it left."""
    (one, ) = serve(engine, [prompt(40)])
    np.testing.assert_allclose(
        one[1], reference_logprobs(engine, tiny, one[0]), atol=2e-5)
    return one, carried_rows(engine)


@pytest.mark.parametrize("budget", [16, 1])
def test_a_prompt_split_over_steps_leaves_the_same_rows_and_logprobs(
        model_dir, monkeypatch, one_pass, budget):
    """A 40-token prompt under a budget of 16 tokens a step (three
    chunks, each resuming the rows and the pages) and of one token a
    step (forty one-token segments, the decode form all the way)
    against one pass under a budget of 64: the same logprobs, and after
    the last decode step the same two rows in every layer. An engine of
    its own each: ``max_num_batched_tokens`` and ``max_num_seqs``."""
    one, rows = one_pass
    (cut, ), engine = served(model_dir, monkeypatch, [prompt(40)],
                             max_num_batched_tokens=budget,
                             max_num_seqs=min(4, budget))
    assert one[0] == cut[0]
    np.testing.assert_allclose(cut[1], one[1], atol=2e-5)
    for name, b in carried_rows(engine).items():
        assert np.abs(rows[name]).max() > 0.05
        np.testing.assert_allclose(b, rows[name], atol=2e-6)


def test_a_fresh_row_is_zeros_the_bias_and_zeros(model_dir, monkeypatch,
                                                 tiny):
    """A row that held another sequence is read as fresh by the next
    one at position 0: ``u = 0``, ``c0 = b0``, ``vb = 0``, whatever it
    holds. Two requests one after the other in the same row."""
    engine = make_engine(model_dir, monkeypatch, max_num_seqs=1)
    sp = SamplingParams(temperature=0.0, max_tokens=3, ignore_eos=True,
                        logprobs=1, prompt_logprobs=1)
    engine.add_request("a", prompt(19), sp)
    drain(engine)
    assert np.abs(np.asarray(runner_of(engine).kv_caches["cca_cv"])[:, 0]
                  ).max() > 0.05
    engine.add_request("b", prompt(11, 3), sp)
    tokens, lps = drain(engine)["b"]
    np.testing.assert_allclose(
        lps, reference_logprobs(engine, tiny, tokens), atol=2e-5)


def test_preemption_with_resume_gives_the_uninterrupted_logits(
        engine, model_dir, monkeypatch):
    """Four requests of 24 + 40 tokens against 15 pages of 16: one is
    preempted, parks its rows in the snapshot pool and resumes as a
    continuation beside its prefix's pages. The engine of its own
    differs by ``num_gpu_blocks_override=15``; the prompts are no other
    test's, so the calm pass finds no page of theirs."""
    prompts = [prompt(24, 10 + i) for i in range(4)]
    calm = serve(engine, prompts, 40, None)
    tight, small = served(model_dir, monkeypatch, prompts, 40, None,
                          num_gpu_blocks_override=15)
    stats = small.get_stats()
    assert int(stats["num_preemptions"]) > 0
    assert stats["ssm_state_cache_hits"] >= 1
    assert small.engine_core.engine_core.scheduler.kv_cache_manager \
        .get_num_free_blocks() == 15
    for a, b in zip(calm, tight):
        assert a[0] == b[0]
        np.testing.assert_allclose(b[1], a[1], atol=2e-5)


def test_snapshot_restore_gives_the_one_pass_logits(engine, model_dir,
                                                    monkeypatch):
    """A second turn that extends the first restores the rows from the
    chunk-boundary snapshot and reuses the prefix's pages: same logits
    as with the state cache off (an engine of its own: the flag is read
    at construction and, by the runner, after it); the pool's bytes are
    the rows'. The first turn's prompt is no other test's."""
    with monkeypatch.context() as off:    # the runner reads it later too
        plain, _, _ = two_turns(make_engine(model_dir, off, cache_on=False),
                                prompt(70, 20))
    hit, stats, added = two_turns(engine, prompt(70, 20))
    assert added["ssm_state_cache_hits"] >= 1
    assert added["ssm_resume_tokens_saved"] >= 64
    assert hit[0] == plain[0]
    np.testing.assert_allclose(hit[1], plain[1], atol=2e-5)
    runner = runner_of(engine)
    # 4 layers x (96 + 96 + 16) values x 4 B a sequence.
    assert runner.model.state_row_bytes() == 4 * 208 * 4 == \
        runner.state_pool_slot_bytes()
    assert stats["ssm_state_bytes_held"] % runner.state_pool_slot_bytes() == 0
    assert stats["ssm_state_bytes_held"] > 0


@pytest.fixture(scope="module")
def served_once(engine, tiny):
    """One served request, its engine and the reference's logprobs."""
    (got, ) = serve(engine, [prompt(37)], 4)
    return got, engine, reference_logprobs(engine, tiny, got[0])


def test_the_chip_s_controls_are_among_those_held_here():
    """Two of the five are asked of every chip run; all five are held
    here, in float32 (the reference's docstring says why)."""
    assert reference.EVERY_CONTROL == (
        "no_conv", "no_qk_mean", "no_value_shift", "no_router_state",
        "no_skip")
    assert set(reference.CONTROLS[ARCH]) < set(reference.EVERY_CONTROL)


@pytest.mark.parametrize("control", reference.EVERY_CONTROL)
def test_each_piece_changes_the_output_when_taken_away(
        served_once, tiny, control):
    """The engine agrees with the reference, and the reference with the
    convolutions, the q-k mean, the value shift, the router's state over
    depth or the skipped token's ``p x`` taken away does not."""
    (tokens, lps), engine, whole = served_once
    broken = reference_logprobs(engine, tiny, tokens, control)
    dev = np.abs(np.asarray(lps) - whole).max()
    assert dev < 2e-5
    assert np.abs(np.asarray(lps) - broken).max() > 100 * dev + 1e-3


def test_every_outcome_is_picked_and_the_skip_reaches_no_gemm(
        served_once, tiny):
    """Over 256 rows the seeded router of a layer picks every expert
    and the outcome that is none, with weights that are not flat;
    ``moe_dispatch`` gives a skipped token exactly nothing."""
    import jax
    import jax.numpy as jnp
    _, engine, _ = served_once
    runner = runner_of(engine)
    model, params = runner.model, runner.params
    cfg = run.hf_config(tiny)
    E = cfg["num_experts"]
    lp = {k: v[0] for k, v in params["layers"].items()}
    x = jnp.asarray(np.random.default_rng(0).normal(size=(256, 64)),
                    jnp.float32)
    weight, _ = reference.route(x, jnp.zeros((256, 8)), lp, cfg)
    picks = np.asarray(jnp.argmax(weight, -1))
    assert set(picks) == set(range(E + 1))
    top = np.asarray(weight.max(-1))
    assert 1.3 / (E + 1) < top.mean() < 0.9
    pick = jnp.asarray(picks[:, None], jnp.int32)
    out = np.asarray(model.moe_dispatch(lp, x, pick, weight.max(
        -1, keepdims=True)))
    assert np.abs(out[picks == E]).max() == 0.0
    assert np.abs(out[picks < E]).min(axis=0).max() > 0.0
    with jax.default_matmul_precision("highest"):
        want = reference.experts(x, weight, lp, cfg)
        got = model._experts(lp, x, pick, weight.max(-1, keepdims=True))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


@pytest.mark.parametrize("over,match", [
    (dict(tensor_parallel_size=2), "tensor parallelism over compressed"),
    (dict(tensor_parallel_size=2, enable_expert_parallel=True),
     "expert parallelism over stateful"),
    (dict(pipeline_parallel_size=2), "pipeline parallelism over stateful"),
])
def test_sharded_deployments_are_refused_at_load(model_dir, monkeypatch,
                                                 over, match):
    with pytest.raises(ValueError, match=match):
        make_engine(model_dir, monkeypatch, **over)


@pytest.mark.parametrize("key,value,match", [
    ("sliding_window", 4096, "sliding_window=4096"),
    ("layer_types", ["hybrid", "hybrid_sliding"] * 2, "hybrid_sliding"),
    ("cca_time0", 4, "cca_time0=4"),
    ("num_experts_per_tok", 2, "num_experts_per_tok=2"),
    ("zaya_use_mod", False, "zaya_use_mod=False"),
    ("tie_word_embeddings", False, "tie_word_embeddings=False"),
])
def test_unwired_sources_are_refused_by_name(tiny, tmp_path, key, value,
                                             match):
    cfg = dict(run.hf_config(tiny), **{key: value})
    (tmp_path / "config.json").write_text(json.dumps(cfg))
    with pytest.raises(ValueError, match=match):
        LLMEngine(EngineArgs(
            model=str(tmp_path), load_format="dummy", dtype="float32",
            max_model_len=64, skip_tokenizer_init=True
        ).create_engine_config())


def test_the_walk_is_one_scan_that_carries_the_router_s_state(engine):
    """The forward's jaxpr holds one scan over the four layers whose
    body runs the carried-row kernel twice (``u``; ``c0`` and ``vb``)
    and carries a ``[T, 8]`` float32 beside the stream; the expert
    weights are per-layer buffers behind a switch."""
    import jax
    runner = runner_of(engine)
    lay = runner.params["layers"]
    assert isinstance(lay["w_gate"], tuple) and len(lay["w_gate"]) == 4
    assert lay["w_gate"][0].shape == (4, 64, 48)
    assert lay["conv1_w"].shape == (4, 2, 6, 16, 16)
    assert runner.kv_caches["k"].shape == (4, 128, 2, 16, 16)
    assert runner.kv_caches["cca_u"].shape == (4, 5, 1, 12, 8)
    assert runner.kv_caches["cca_cv"].shape == (4, 5, 1, 14, 8)
    assert runner._forward_fn is not None   # built with the engine
    T, max_q, G = runner._batch_shape(16, 2)
    text = str(jax.make_jaxpr(
        lambda p, c, i: runner._forward_fn(p, c, i))(
            runner.params, runner.kv_caches,
            runner._dummy_step_inputs(T, max_q, G)))
    assert "length=4" in text
    assert text.count("name=conv_rows") == 2 * 2   # both arms of the cond
    assert f"f32[{T},8]" in text
