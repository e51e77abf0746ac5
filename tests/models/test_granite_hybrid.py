"""Granite 4.0-H (``models/granite_hybrid.py``) at a toy width, float32,
on the CPU: the checkpoint's names against transformers' own model, the
engine's steps against the plain reference
(``benchmarks/references/granite_hybrid.py``), state rows beside packed
K/V pages through preemption and a snapshot, what each multiplier, the
scale and the missing rotation do, and the refusals.

**What a case costs.** Not its steps: an ``LLMEngine`` built (the
weights seeded, the pool sized) and then its forward programs compiled,
two or three of them at 4-8 s each, because JAX caches a compiled program
by the function object and every engine closes over new ones. A test
that calls ``make_engine`` for itself pays 25-40 s before its first
assertion; a file of such tests compiled the same programs fifteen
times. So tests that want the same ``EngineArgs`` and flags take the
module's ``engine`` fixture (``make_engine``'s own arguments) or another
module-scoped one (``engine_16``), add their requests with ``serve``
and leave it drained; what one leaves behind (cached prefix pages,
snapshots, counters) the next must not read, so a test that serves
without prompt logprobs uses prompts of its own (``prompt(n, k)`` with a
``k`` no other test has) and counters are read as differences. A test
whose point is another budget, pool, flag or checkpoint keeps its own
engine and says which argument makes it differ. The float32 reference
goes through ``jit_logprobs``: one program a sequence length where
eager dispatch compiled some 280 one-operation programs. ``test_zaya.py``
and ``test_cohere_moe.py`` import this harness."""

import itertools
import json
import os

import numpy as np
import pytest
import torch

from benchmarks import run
from benchmarks.references import granite_hybrid as reference
from vllm_distributed_tpu import SamplingParams
from vllm_distributed_tpu.engine.arg_utils import EngineArgs
from vllm_distributed_tpu.engine.llm_engine import LLMEngine

DATA = os.path.join(os.path.dirname(__file__), "..", "benchmarking", "data")
CELL = "tiny-granite-h.tiny-decode"
SEED = 5


@pytest.fixture(scope="module")
def tiny():
    return run.load_cell(CELL, DATA)["config_file"]


@pytest.fixture(scope="module")
def model_dir(tiny, tmp_path_factory):
    work = run.WORK
    run.WORK = str(tmp_path_factory.mktemp("granite-h"))
    try:
        return run.write_model_dir("tiny-granite-h", tiny)
    finally:
        run.WORK = work


def make_engine(model_dir, monkeypatch, cache_on=True, interval=16, **over):
    # The scheduler and the runner read the flags at construction.
    monkeypatch.setenv("VDT_SSM_STATE_CACHE", "1" if cache_on else "0")
    monkeypatch.setenv("VDT_SSM_CKPT_INTERVAL", str(interval))
    args = dict(model=model_dir, load_format="dummy", seed=SEED,
                dtype="float32", block_size=16,
                num_gpu_blocks_override=128, max_model_len=128,
                max_num_batched_tokens=64, max_num_seqs=4,
                skip_tokenizer_init=True)
    args.update(over)
    return LLMEngine(EngineArgs(**args).create_engine_config())


@pytest.fixture(scope="module")
def engine(model_dir):
    """The one engine with ``make_engine``'s own arguments of the module
    that asks (``test_zaya.py`` and ``test_cohere_moe.py`` import it, and
    each gets its own from its own ``model_dir``)."""
    with pytest.MonkeyPatch.context() as mp:
        return make_engine(model_dir, mp)


def runner_of(engine):
    return engine.engine_core.engine_core.executor.worker.model_runner


def drain(engine, max_steps=600):
    """{request: (prompt + tokens, their logprobs from position 1; the
    generated tokens' alone where no prompt logprobs were asked for)}."""
    done = {}
    for _ in range(max_steps):
        for out in engine.step():
            if out.finished:
                c = out.outputs[0]
                lps = [d[t] for t, d in zip(out.prompt_token_ids[1:],
                                            (out.prompt_logprobs or [])[1:])]
                lps += [d[t] for t, d in zip(c.token_ids, c.logprobs)]
                done[out.request_id] = (
                    list(out.prompt_token_ids) + list(c.token_ids), lps)
        if not engine.has_unfinished_requests():
            break
    return done


_BATCH = itertools.count()


def serve(engine, prompts, max_tokens=6, prompt_lps=1):
    """The prompts as one batch through an engine that other tests use
    too (request ids are never reused), until it is drained."""
    # A request that wants its prompt's logprobs computes every prompt
    # position and so resumes from no snapshot.
    sp = SamplingParams(temperature=0.0, max_tokens=max_tokens,
                        ignore_eos=True, logprobs=1,
                        prompt_logprobs=prompt_lps)
    ids = [f"r{next(_BATCH)}-{i}" for i in range(len(prompts))]
    for rid, p in zip(ids, prompts):
        engine.add_request(rid, list(p), sp)
    done = drain(engine)
    return [done[rid] for rid in ids]


def served(model_dir, monkeypatch, prompts, max_tokens=6, prompt_lps=1,
           **over):
    """``serve`` through an engine of the test's own."""
    engine = make_engine(model_dir, monkeypatch, **over)
    return serve(engine, prompts, max_tokens, prompt_lps), engine


_JITTED = {}


def jit_logprobs(reference, params, cfg, tokens, control=None):
    """``reference.logprobs`` under one ``jax.jit`` a (reference,
    configuration, control), at the precision the references ask their
    callers for; the logprobs of ``tokens[1:]``."""
    import jax
    import jax.numpy as jnp
    key = (reference.__name__, json.dumps(cfg, sort_keys=True), control)
    if key not in _JITTED:
        _JITTED[key] = jax.jit(
            lambda params, ids: reference.logprobs(params, cfg, ids,
                                                   control))
    with jax.default_matmul_precision("highest"):
        table = _JITTED[key](params, jnp.asarray(tokens, jnp.int32))
    return np.asarray(table)[np.arange(len(tokens) - 1), tokens[1:]]


def reference_logprobs(engine, tiny, tokens, control=None, cfg=None):
    """The reference's logprobs of ``tokens[1:]`` on the engine's own
    seeded weights."""
    return jit_logprobs(reference, runner_of(engine).params,
                        cfg or run.hf_config(tiny), tokens, control)


def prompt(n, k=0):
    return [(j * 37 + k * 101 + 3) % 500 for j in range(n)]


def test_prefill_then_decode_matches_the_reference(engine, tiny):
    """Three requests of different lengths in one batch (a prompt that
    crosses several 8-token chunks, one under a chunk, one of two
    tokens), prefill then six decode steps through state rows and pages:
    every logprob equals the reference's one full forward."""
    got = serve(engine, [prompt(37), prompt(7, 1), prompt(2, 2)], 6)
    for tokens, lps in got:
        want = reference_logprobs(engine, tiny, tokens)
        np.testing.assert_allclose(lps, want, atol=2e-5)


@pytest.fixture(scope="module")
def engine_16(model_dir):
    """Differs by ``max_num_batched_tokens=16``: a prompt goes in over
    several steps."""
    with pytest.MonkeyPatch.context() as mp:
        return make_engine(model_dir, mp, max_num_batched_tokens=16)


def test_prefill_split_over_three_steps_is_one_pass(engine, engine_16,
                                                    tiny):
    """A 40-token prompt under a budget of 16 tokens a step: three
    steps, each resuming the row's state and the pages; a budget of 64
    takes it in one. Both equal the reference."""
    (one, ) = serve(engine, [prompt(40)])
    (three, ) = serve(engine_16, [prompt(40)])
    assert one[0] == three[0]
    np.testing.assert_allclose(three[1], one[1], atol=2e-5)
    np.testing.assert_allclose(
        one[1], reference_logprobs(engine, tiny, one[0]), atol=2e-5)


@pytest.mark.parametrize("left", [1, 2])
def test_a_split_one_or_two_tokens_into_a_chunk_reaches_back_into_the_row(
        engine_16, tiny, left):
    """A prompt of 32 tokens and ``left`` more under a budget of 16 a
    step: the third step brings fewer tokens than the convolution
    carries (three), so the row's new inputs are the chunk's and the
    last of the old ones, and the decode step after it reads both."""
    (got, ) = serve(engine_16, [prompt(32 + left)])
    np.testing.assert_allclose(
        got[1], reference_logprobs(engine_16, tiny, got[0]), atol=2e-5)


def test_preemption_with_resume_gives_the_uninterrupted_logits(
        engine, model_dir, monkeypatch):
    """Four requests of 24 + 40 tokens against 15 pages of 16: one is
    preempted, parks its 76 KB row in the snapshot pool and resumes as
    a continuation beside its prefix's pages. The engine of its own
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


def two_turns(engine, first_prompt):
    """A turn, then one that extends it by three tokens; the second's
    tokens and logprobs, and what the two added to the counters."""
    before = engine.get_stats()
    (first, ) = serve(engine, [first_prompt], 6, None)
    (second, ) = serve(engine, [first[0] + [17, 18, 19]], 6, None)
    after = engine.get_stats()
    return second, after, {
        k: after[k] - before[k]
        for k in ("ssm_state_cache_hits", "ssm_resume_tokens_saved")
        if k in after}


def test_snapshot_restore_gives_the_one_pass_logits(engine, model_dir,
                                                    monkeypatch):
    """A second turn that extends the first restores the row from the
    chunk-boundary snapshot and reuses the prefix's pages: same logits
    as with the state cache off (an engine of its own: the flag is read
    at construction and, by the runner, after it). The first turn's
    prompt is no other test's."""
    with monkeypatch.context() as off:    # the runner reads it later too
        plain, _, _ = two_turns(make_engine(model_dir, off, cache_on=False),
                                prompt(70, 20))
    hit, _, stats = two_turns(engine, prompt(70, 20))
    assert stats["ssm_state_cache_hits"] >= 1
    assert stats["ssm_resume_tokens_saved"] >= 64
    assert hit[0] == plain[0]
    np.testing.assert_allclose(hit[1], plain[1], atol=2e-5)


@pytest.fixture(scope="module")
def served_once(engine, tiny):
    """One served request, its engine and the reference's logprobs."""
    (got, ) = serve(engine, [prompt(37)], 4)
    return got, engine, reference_logprobs(engine, tiny, got[0])


@pytest.mark.parametrize("control", [
    "no_embed_mult", "no_resid_mult", "no_logit_scale", "rope_on",
    "scale_sqrt", "no_decay", "no_conv", "no_gate_norm"])
def test_each_piece_changes_the_output_when_taken_away(
        served_once, tiny, control):
    """The engine agrees with the reference, and the reference with one
    multiplier, the 1/32 scale, the missing rotation, the decay, the
    convolution or the gated norm taken away does not."""
    (tokens, lps), engine, whole = served_once
    broken = reference_logprobs(engine, tiny, tokens, control)
    dev = np.abs(np.asarray(lps) - whole).max()
    assert dev < 2e-5
    assert np.abs(np.asarray(lps) - broken).max() > 100 * dev + 1e-3


def hf_tiny(tiny, **over):
    from transformers import (GraniteMoeHybridConfig,
                              GraniteMoeHybridForCausalLM)
    keys = {k: v for k, v in run.hf_config(tiny).items()
            if k not in ("architectures", "model_type", "torch_dtype")}
    keys.update(vocab_size=128, eos_token_id=1, **over)
    torch.manual_seed(0)
    hf = GraniteMoeHybridForCausalLM(GraniteMoeHybridConfig(**keys))
    with torch.no_grad():
        for name, p in hf.named_parameters():
            if name.endswith(("conv1d.bias", "dt_bias")):
                p.normal_(std=0.2)
            elif name.endswith(("q_proj.weight", "k_proj.weight")):
                p.mul_(15.0)         # scores that are not all alike
            elif name.endswith("norm.weight"):
                p.uniform_(0.5, 1.5)
    return hf.eval()


def test_loader_maps_the_source_s_names(tiny, tmp_path, monkeypatch):
    """A checkpoint saved by transformers' own GraniteMoeHybridForCausalLM
    (``mamba.in_proj`` ... ``shared_mlp.input_linear`` fused,
    ``post_attention_layernorm``, ``model.norm``, the tied head): every
    tensor is taken, and the engine's logprobs of prompt and greedy
    tokens are those of that model's own forward."""
    hf = hf_tiny(tiny)
    names = set(hf.state_dict())
    assert {"model.layers.0.mamba.in_proj.weight",
            "model.layers.0.mamba.conv1d.bias",
            "model.layers.0.mamba.dt_bias", "model.layers.0.mamba.A_log",
            "model.layers.0.mamba.D", "model.layers.0.mamba.norm.weight",
            "model.layers.0.mamba.out_proj.weight",
            "model.layers.0.shared_mlp.input_linear.weight",
            "model.layers.0.shared_mlp.output_linear.weight",
            "model.layers.2.self_attn.q_proj.weight",
            "model.layers.2.post_attention_layernorm.weight",
            "model.norm.weight"} <= names
    assert hf.state_dict()[
        "model.layers.0.shared_mlp.input_linear.weight"].shape == (192, 64)
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
    lay = engine.engine_core.engine_core.executor.worker.model_runner \
        .params["layers"]
    fused = hf.state_dict()[
        "model.layers.1.shared_mlp.input_linear.weight"].numpy()
    np.testing.assert_array_equal(np.asarray(lay["m_gate"][1]), fused[:96].T)
    np.testing.assert_array_equal(np.asarray(lay["m_up"][1]), fused[96:].T)
    assert "lm_head" not in engine.engine_core.engine_core.executor.worker \
        .model_runner.params


@pytest.mark.parametrize("key,value,match", [
    ("num_local_experts", 4, "num_local_experts=4"),
    ("position_embedding_type", "rope", "position_embedding_type='rope'"),
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


def test_the_walk_is_a_scan_over_the_periods(engine):
    """Two periods of (mamba, mamba, attention, mamba): the forward's
    jaxpr holds one scan of length 2 whose body has three Mamba-2 mixers
    and one attention, whatever the depth; two K/V heads share a page
    row."""
    import jax
    runner = runner_of(engine)
    model = runner.model
    assert model._period() == 4 and model.cfg.kv_pack == 2
    assert runner.kv_caches["k"].shape[2:] == (1, 16, 32)
    assert runner.kv_caches["ssm"].shape == (6, 5, 16, 64)
    assert model.state_row_bytes() == 6 * (16 * 64 * 4 + 3 * (64 + 32) * 4)
    assert runner._forward_fn is not None   # built with the engine
    T, max_q, G = runner._batch_shape(16, 2)
    text = str(jax.make_jaxpr(
        lambda p, c, i: runner._forward_fn(p, c, i))(
            runner.params, runner.kv_caches,
            runner._dummy_step_inputs(T, max_q, G)))
    assert text.count("scan[") >= 1 and "length=2" in text
    assert text.count("name=ssd_recurrent") == 3
