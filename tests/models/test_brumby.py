"""Brumby (``models/brumby.py``, ``ops/power_retention.py``) at a toy
width, float32, on the CPU: the engine's steps against the plain
reference (``benchmarks/references/brumby.py``, the quadratic form, which
never forms a state); the two kernels in interpret mode against each
other and against the quadratic form; what each control takes away; a
snapshot parked and resumed; the gauges; the refusals."""

import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run
from benchmarks.references import brumby as reference
# The engine, its steps and the prompts as the other carried-state
# models' tests make them (float32, 4 rows, a 64-token budget, seed 5),
# and one engine a module for the tests that want those arguments (that
# file's docstring says what an engine costs and what sharing one asks).
from test_granite_hybrid import engine  # noqa: F401 (this module's own)
from test_granite_hybrid import (jit_logprobs, make_engine, prompt,
                                 runner_of, serve, two_turns)
from vllm_distributed_tpu.models.brumby import (GATE_DECAY_RANGE,
                                                BrumbyForCausalLM)
from vllm_distributed_tpu.models.llama import LlamaArchConfig
from vllm_distributed_tpu.ops import power_retention as pr
from vllm_distributed_tpu.ops.mamba import SegmentInfo

DATA = os.path.join(os.path.dirname(__file__), "..", "benchmarking", "data")
CELL = "tiny-brumby.tiny-decode"
ARCH = "BrumbyForCausalLM"


@pytest.fixture(scope="module")
def tiny():
    return run.load_cell(CELL, DATA)["config_file"]


@pytest.fixture(scope="module")
def model_dir(tiny, tmp_path_factory):
    work = run.WORK
    run.WORK = str(tmp_path_factory.mktemp("brumby"))
    try:
        return run.write_model_dir("tiny-brumby", tiny)
    finally:
        run.WORK = work


def reference_logprobs(engine, tiny, tokens, control=None):
    """The reference's logprobs of ``tokens[1:]`` on the engine's own
    seeded weights."""
    return jit_logprobs(reference, runner_of(engine).params,
                        run.hf_config(tiny), tokens, control)


# ---------------------------------------------------------------------
# The basis and the kernels (interpret mode) against the quadratic form
# ---------------------------------------------------------------------
S, HKV, G, D = 4, 2, 2, 16


def test_the_cyclic_basis_gives_the_squared_score_exactly():
    """``phi_q . phi_k = (q . k)^2`` with the factor two on the query side
    alone: D/2 + 1 offsets of D lanes, 8 320 entries at a head of 128
    where the symmetric square has 8 256."""
    rng = np.random.default_rng(0)
    a, b = (jnp.asarray(rng.integers(-8, 9, size=(5, D)), jnp.float32)
            for _ in range(2))
    got = jnp.sum(pr.phi(a, True) * pr.phi(b, False), axis=(-2, -1))
    np.testing.assert_array_equal(got, jnp.sum(a * b, axis=-1) ** 2)
    assert pr.phi(a, True).shape == (5, D // 2 + 1, D)
    assert pr.num_offsets(128) * 128 == 8320
    assert pr.state_shapes(6, 17, 8, 128) == (
        (6, 17, 8, 65, 128, 128), (6, 17, 8, 72, 128))


def segments(q_lens, starts, pos0, T):
    """The ``SegmentInfo`` of a step: row r brings ``q_lens[r]`` tokens at
    flat index ``starts[r]``, ``pos0[r]`` tokens into its sequence."""
    row = np.full(T, S, np.int32)
    valid, start, end, has_init = (np.zeros(T, bool) for _ in range(4))
    off = np.zeros(T, np.int32)
    by_row = [np.zeros(S + 1, np.int32), np.zeros(S + 1, np.int32),
              np.zeros(S + 1, bool)]
    for r, (n, at, before) in enumerate(zip(q_lens, starts, pos0)):
        if n == 0:
            continue
        row[at:at + n], valid[at:at + n] = r, True
        off[at:at + n] = np.arange(n)
        start[at], end[at + n - 1] = True, True
        has_init[at:at + n] = before > 0
        by_row[0][r], by_row[1][r], by_row[2][r] = n, at, before > 0
    return SegmentInfo(*(jnp.asarray(x) for x in (
        row, valid, off, start, end, has_init, *by_row)))


def retention_quadratic(q, k, v, log_g):
    """One sequence from its start, the first form of the op's docstring in
    plain ``jax.numpy`` float32: q ``[T, Hq, D]``, k, v ``[T, Hkv, D]``,
    ``log_g`` ``[T, Hkv]``; scores squared under the decay from a
    cumulative sum, the causal mask, the quotient."""
    T, Hq, _ = q.shape
    groups = Hq // k.shape[1]
    hi = jax.lax.Precision.HIGHEST
    reach = jnp.cumsum(log_g, axis=0)                            # [T, Hkv]
    decay = jnp.exp(jnp.minimum(reach[:, None] - reach[None, :], 0.0))
    score = jnp.einsum("thd,jhd->htj", q, jnp.repeat(k, groups, axis=1),
                       precision=hi)
    a = jnp.where(jnp.tril(jnp.ones((T, T), bool)), score * score
                  * jnp.repeat(decay, groups, axis=2).transpose(2, 0, 1), 0.0)
    top = jnp.einsum("htj,jhd->thd", a, jnp.repeat(v, groups, axis=1),
                     precision=hi)
    return top / jnp.sum(a, axis=2).T[:, :, None]


@pytest.fixture(scope="module")
def sequences():
    """Three sequences (q, k, v, log g) of 300, 140 and 9 tokens and the
    quadratic form's output for each: decays from half-lives of a few
    tokens to a few hundred."""
    rng = np.random.default_rng(1)
    out = []
    for n in (300, 140, 9):
        seq = (rng.normal(size=(n, HKV * G, D)), rng.normal(size=(n, HKV, D)),
               rng.normal(size=(n, HKV, D)),
               -np.exp(rng.uniform(-6, -1, size=(n, HKV))))
        seq = tuple(jnp.asarray(x, jnp.float32) for x in seq)
        out.append((seq, np.asarray(retention_quadratic(*seq))))
    return out


# Steps of (tokens of sequence 0, 1, 2), rows 2, 0 and 3 of a 4-row pool:
# a segment of three chunks beside a one-token segment at a sequence's
# start and a short one; two chunks resumed beside one resumed token;
# decode steps with a padded slot; a last mixed step.
PLAN = [(270, 1, 9), (29, 130, 0), (1, 1, 0), (0, 8, 0)]
ROWS = (2, 0, 3)


@pytest.mark.parametrize("form", ["chunk", "recurrent", "auto"])
def test_the_forms_agree_with_the_quadratic_form(sequences, form):
    """A batch that mixes chunked segments, one-token segments and padded
    slots, over four steps that hand the state from one to the next
    through the rows, in layer 1 of two: every token's output is the
    quadratic form's (float32, another order of summation); layer 0 and
    the dump row are left as they were."""
    T = 320
    ret, ret_z = pr.state_shapes(2, S + 1, HKV, D)
    state, zstate = jnp.zeros(ret, jnp.float32), jnp.zeros(ret_z, jnp.float32)
    layer = jnp.asarray([1], jnp.int32)
    done = [0, 0, 0]
    got = [np.zeros_like(want) for _, want in sequences]
    for step in PLAN:
        arrays = [np.zeros((T, ) + np.shape(x)[1:], np.float32)
                  for x in sequences[0][0]]
        q_lens, starts, pos0 = [0] * S, [0] * S, [0] * S
        at = 1                                   # a padding token first
        for i, n in enumerate(step):
            r = ROWS[i]
            q_lens[r], starts[r], pos0[r] = n, at, done[i]
            for dst, src in zip(arrays, sequences[i][0]):
                dst[at:at + n] = src[done[i]:done[i] + n]
            at += n + 1
        out, state, zstate = pr.retention_ragged(
            *(jnp.asarray(x) for x in arrays), state, zstate, layer,
            segments(q_lens, starts, pos0, T),
            decode_only=max(step) == 1, prefill_form=form)
        for i, n in enumerate(step):
            at = starts[ROWS[i]]
            got[i][done[i]:done[i] + n] = np.asarray(out)[at:at + n]
            done[i] += n
    for have, (_, want) in zip(got, sequences):
        np.testing.assert_allclose(have, want, atol=2e-5)
    assert float(jnp.abs(state[0]).max()) == 0.0
    assert float(jnp.abs(state[1, S]).max()) == 0.0
    assert float(jnp.abs(zstate[1, S]).max()) == 0.0
    assert float(jnp.abs(state[1, 1]).max()) == 0.0      # the unused row
    assert float(jnp.abs(state[1, 2]).max()) > 0.1


def test_a_sequence_s_first_token_is_its_value(sequences):
    """Alone in the state a token's output is its own value, bit for bit:
    not that value times a quotient of two sums that cancel."""
    (q, k, v, log_g), _ = sequences[2]
    ret, ret_z = pr.state_shapes(1, S + 1, HKV, D)
    T = 8
    pad = [jnp.zeros((T, ) + x.shape[1:], jnp.float32).at[3].set(x[0])
           for x in (q, k, v, log_g)]
    out, _, _ = pr.retention_ragged(
        *pad, jnp.zeros(ret, jnp.float32), jnp.zeros(ret_z, jnp.float32),
        jnp.asarray([0], jnp.int32), segments([0, 1, 0, 0], [0, 3, 0, 0],
                                              [0] * 4, T), decode_only=True)
    np.testing.assert_array_equal(
        np.asarray(out)[3], np.repeat(np.asarray(v[0]), G, axis=0))


@pytest.mark.parametrize("q_lens,tokens,want", [
    ([1] * 16, 16, 0),                       # a decode step
    ([2033] + [1] * 15, 2048, 2033),         # a chunk beside decodes
    ([100] * 16, 2048, 1600),                # 16 partial chunks of 20
    ([2] * 16, 32, 0),                       # 16 chunks where 5 fit
    ([129, 129, 2], 512, 260)])              # five chunks of eight
def test_the_counter_s_rule_is_the_program_s(q_lens, tokens, want):
    """``vdt:step_retention_chunk_tokens`` counts by ``retention_ragged``'s
    own rule: a step's multi-token segments take the chunked form when
    their chunks fit the bucket's own count and four more."""
    from vllm_distributed_tpu.ops import mamba
    assert mamba.num_chunks_for(2048, pr.CHUNK) == 20 and pr.CHUNK == 128
    assert mamba.chunk_form_tokens(np.asarray(q_lens), tokens,
                                   pr.CHUNK) == want


# ---------------------------------------------------------------------
# The served path against the reference's one full forward
# ---------------------------------------------------------------------
def test_prefill_then_decode_matches_the_reference(engine, tiny):
    """Three requests of different lengths in one batch (37, 7 and 2
    tokens: chunked segments in one step), then six decode steps through
    the state rows: every logprob equals the reference's one full
    forward, which never forms a state."""
    got = serve(engine, [prompt(37), prompt(7, 1), prompt(2, 2)], 6)
    for tokens, lps in got:
        want = reference_logprobs(engine, tiny, tokens)
        np.testing.assert_allclose(lps, want, atol=2e-5)


@pytest.fixture(scope="module")
def engine_long(model_dir):
    """Differs by ``max_model_len=512``, ``max_num_batched_tokens=256``:
    a prompt of 300 tokens goes in as two chunks of 128 in one step and
    44 tokens in the next."""
    with pytest.MonkeyPatch.context() as mp:
        return make_engine(model_dir, mp, max_model_len=512,
                           max_num_batched_tokens=256)


def test_a_prompt_over_chunks_and_steps_matches_the_reference(engine_long,
                                                              tiny):
    """300 tokens beside a short prompt: the state goes from chunk to
    chunk inside the first step (VMEM), from step to step through the
    row, and then through the recurrent kernel for eight tokens, while
    the short request decodes beside the second chunk step (a mixed
    batch). The chunk counter saw the prompts."""
    before = runner_of(engine_long).retention_chunk_tokens_hist.to_dict()
    got = serve(engine_long, [prompt(300, 4), prompt(5, 5)], 8)
    for tokens, lps in got:
        np.testing.assert_allclose(
            lps, reference_logprobs(engine_long, tiny, tokens), atol=5e-5)
    after = runner_of(engine_long).retention_chunk_tokens_hist.to_dict()
    assert after["sum"] - before["sum"] == 305


@pytest.fixture(scope="module")
def served_once(engine, tiny):
    (got, ) = serve(engine, [prompt(61, 6)], 4)
    return got, engine, reference_logprobs(engine, tiny, got[0])


@pytest.mark.parametrize("control", reference.ALL_CONTROLS)
def test_each_piece_changes_the_output_when_taken_away(served_once, tiny,
                                                       control):
    """The engine agrees with the reference, and the reference without the
    gate, at the fourth power, without the normaliser, the rotation or the
    per-head norms does not."""
    (tokens, lps), engine, whole = served_once
    broken = reference_logprobs(engine, tiny, tokens, control)
    dev = np.abs(np.asarray(lps) - whole).max()
    assert dev < 2e-5
    assert np.abs(np.asarray(lps) - broken).max() > 100 * dev + 1e-3
    assert control in reference.__doc__
    assert set(reference.CONTROLS[ARCH]) <= set(reference.ALL_CONTROLS)


def test_a_snapshot_parked_and_resumed_gives_the_one_pass_logits(
        engine, model_dir, monkeypatch):
    """A second turn that extends the first restores the rows from the
    chunk-boundary snapshot (no page goes with it: ``STATE_ONLY``): same
    logits as with the state cache off (an engine of its own: the flag is
    read at construction). The pool's slot is the row, the gauges read
    true, the families render."""
    from vllm_distributed_tpu.metrics.prometheus import render_metrics
    with monkeypatch.context() as off:
        plain, _, _ = two_turns(make_engine(model_dir, off, cache_on=False),
                                prompt(70, 20))
    hit, stats, added = two_turns(engine, prompt(70, 20))
    assert added["ssm_state_cache_hits"] >= 1
    assert added["ssm_resume_tokens_saved"] >= 64
    assert hit[0] == plain[0]
    np.testing.assert_allclose(hit[1], plain[1], atol=2e-5)
    runner = runner_of(engine)
    # 3 layers x 2 K/V heads x (9 x 16 x 16 + 16 x 16) float32.
    row = 3 * 2 * (9 * 16 * 16 + 16 * 16) * 4
    assert runner.model.state_row_bytes() == row == \
        runner.state_pool_slot_bytes()
    assert BrumbyForCausalLM.STATE_ONLY and BrumbyForCausalLM.STATEFUL
    assert runner.model.kv_cache_page_bytes(16) == 0
    assert runner.model.fixed_cache_bytes() == 5 * row      # 4 rows + dump
    entry = next(iter(stats["workers"].values()))
    assert entry["state_row_bytes"] == row
    assert stats["ssm_state_bytes_held"] > 0
    assert stats["ssm_state_bytes_held"] % row == 0
    text = render_metrics(stats)
    for family in ("vdt:state_row_bytes{", "vdt:ssm_state_bytes_held",
                   "vdt:step_retention_chunk_tokens_count"):
        assert family in text, family


def test_seeded_gates_decay_over_tens_to_thousands_of_tokens(engine):
    """The gate's bias is drawn so that ``-log g`` at a zero projection is
    log-uniform over 1e-4 to 1e-1; at a zero bias every state would halve
    each token."""
    bg = np.asarray(runner_of(engine).params["layers"]["bg"], np.float64)
    rate = np.log1p(np.exp(-bg))                  # -logsigmoid(bg)
    lo, hi = GATE_DECAY_RANGE
    assert bg.shape == (3, 2)
    assert lo * 0.99 <= rate.min() and rate.max() <= hi * 1.01
    assert rate.max() / rate.min() > 3


# ---------------------------------------------------------------------
# The refusals, by name
# ---------------------------------------------------------------------
@pytest.mark.parametrize("key,value,what", [
    ("rope_scaling", {"rope_type": "yarn", "factor": 4.0}, "rope_scaling"),
    ("use_sliding_window", True, "use_sliding_window"),
    ("attention_bias", True, "attention_bias"),
    ("head_dim", 15, "head_dim")])
def test_configure_arch_refuses_what_is_not_wired(tiny, key, value, what):
    from transformers import PretrainedConfig
    hf = PretrainedConfig(**{**run.hf_config(tiny), key: value})
    arch = LlamaArchConfig.from_hf_config(hf, dtype=jnp.float32)
    with pytest.raises(ValueError, match=f"brumby: {what}"):
        BrumbyForCausalLM.configure_arch(arch, hf)
    hf = PretrainedConfig(**run.hf_config(tiny))
    arch = LlamaArchConfig.from_hf_config(hf, dtype=jnp.float32)
    BrumbyForCausalLM.configure_arch(arch, hf)
    assert arch.qk_norm and arch.stateful and arch.power_retention
    assert arch.sliding_window is None and arch.window_pattern is None


@pytest.mark.parametrize("kwargs,what", [
    (dict(tensor_parallel_size=2), "tensor parallelism over power-ret"),
    (dict(pipeline_parallel_size=2), "pipeline parallelism"),
    (dict(token_parallel_size=2), "token parallelism")])
def test_loader_refuses_what_the_rows_cannot_be_combined_with(
        kwargs, what, model_dir, tiny):
    from vllm_distributed_tpu.engine.arg_utils import EngineArgs
    from vllm_distributed_tpu.models.loader import get_model
    from vllm_distributed_tpu.parallel.mesh import build_mesh
    config = EngineArgs(model=model_dir, load_format="dummy",
                        skip_tokenizer_init=True, **tiny["engine_args"],
                        **kwargs).create_engine_config()
    n = int(np.prod(list(kwargs.values())))
    mesh = build_mesh(config.parallel_config, jax.devices()[:n])
    with pytest.raises(ValueError, match=what):
        get_model(config, mesh)


def test_a_checkpoint_s_names_are_refused():
    model = BrumbyForCausalLM(types.SimpleNamespace())
    with pytest.raises(NotImplementedError, match="tensor names"):
        model.params_from_hf_state_dict({})
