"""Command A+'s block (``models/cohere_moe.py``) at a toy width, float32,
on the CPU: the engine's steps (prefill in chunks, then decoding through
two cache groups, the window group's pages freed and the kernel's loop
trimmed) against the plain reference's one full forward
(``benchmarks/references/cohere_moe.py``), logits not tokens; what each
control takes away; the eight shares of a layer; the refusals."""

import os

import numpy as np
import pytest

from benchmarks import run
from benchmarks.references import cohere_moe as reference
# The engine, its steps and the prompts as the other models' tests make
# them (float32, 4 rows, a 64-token budget, seed 5), and one engine a
# module for the tests that want those arguments (that file's docstring
# says what an engine costs and what sharing one asks).
from test_granite_hybrid import engine  # noqa: F401 (this module's own)
from test_granite_hybrid import (jit_logprobs, make_engine, prompt,
                                 runner_of, serve, served)
from vllm_distributed_tpu import SamplingParams

DATA = os.path.join(os.path.dirname(__file__), "..", "benchmarking", "data")
CELL = "tiny-cohere-moe.tiny-long"
ARCH = "Cohere2MoeForCausalLM"
WINDOW = 8


@pytest.fixture(scope="module")
def tiny():
    return run.load_cell(CELL, DATA)["config_file"]


@pytest.fixture(scope="module")
def model_dir(tiny, tmp_path_factory):
    work = run.WORK
    run.WORK = str(tmp_path_factory.mktemp("cohere_moe"))
    try:
        return run.write_model_dir("tiny-cohere-moe", tiny)
    finally:
        run.WORK = work


def manager_of(engine):
    return engine.engine_core.engine_core.scheduler.kv_cache_manager


def reference_logprobs(engine, cfg, tokens, control=None, params=None):
    """The reference's logprobs of ``tokens[1:]`` on the engine's own
    seeded weights."""
    return jit_logprobs(reference, params or runner_of(engine).params, cfg,
                        tokens, control)


@pytest.mark.parametrize("budget", [64, 16, 5])
def test_chunked_prefill_then_decode_matches_the_reference(
        model_dir, monkeypatch, tiny, budget):
    """Requests of 70, 37 and 5 tokens (nine, four and under one window
    long) in one batch, the prompt in chunks of ``budget`` tokens (one
    pass, multiples of the page, chunks that straddle pages and windows),
    then twelve decode steps: every logprob equals the reference's one
    full forward, and the window group handed pages back on the way.
    An engine of its own each, for its counters from zero (the budget of
    64 is ``make_engine``'s own, 16 and 5 are not)."""
    got, engine = served(model_dir, monkeypatch,
                         [prompt(70), prompt(37, 1), prompt(5, 2)], 12,
                         max_num_batched_tokens=budget,
                         max_num_seqs=min(4, budget))
    cfg = run.hf_config(tiny)
    for tokens, lps in got:
        np.testing.assert_allclose(
            lps, reference_logprobs(engine, cfg, tokens), atol=2e-5)
    stats = engine.get_stats()
    assert stats["kv_window_pages_freed"] >= 4 + 2
    assert stats["kv_full_pages_held"] == stats["kv_window_pages_held"] == 0
    assert stats["step_kv_window_tokens_read"]["count"] == \
        stats["step_kv_tokens_read"]["count"] > 12
    assert stats["step_kv_window_tokens_read"]["sum"] < \
        stats["step_kv_tokens_read"]["sum"]
    pairs = stats["step_attn_pairs"]
    assert 0 < pairs["window"]["sum"] < pairs["full"]["sum"]


@pytest.fixture(scope="module")
def served_once(engine, tiny):
    """One served request of seven windows, its engine and the
    reference's logprobs."""
    (got, ) = serve(engine, [prompt(58)], 6)
    return got, engine, reference_logprobs(engine, run.hf_config(tiny),
                                           got[0])


@pytest.mark.parametrize("control", [
    "no_window", "rope_on_full_layers", "shared_sum_not_mean",
    "softmax_router", "no_shared_experts"])
def test_each_piece_changes_the_output_when_taken_away(
        served_once, tiny, control):
    """The engine agrees with the reference, and with the window mask
    dropped, the full layers rotated, the shared experts summed or left
    out, or the router's sigmoid swapped for a softmax, it does not."""
    (tokens, lps), engine, whole = served_once
    broken = reference_logprobs(engine, run.hf_config(tiny), tokens,
                                control)
    dev = np.abs(np.asarray(lps) - whole).max()
    assert dev < 2e-5
    assert np.abs(np.asarray(lps) - broken).max() > 100 * dev + 1e-4
    assert set(reference.CONTROLS[ARCH]) < {
        "rope_on_full_layers", "no_shared_experts", "softmax_router",
        "shared_sum_not_mean"}
    assert "no_window" not in reference.CONTROLS[ARCH]


def test_the_eight_shares_add_up_to_the_uncut_layer(served_once, tiny):
    """One layer's feed-forward on the engine's weights: the routed parts
    of the eight shares (each holding two of the sixteen published
    experts, drawn apart from one uncut expert stack) plus the shared
    experts counted once are the uncut layer's output; attention does not
    depend on the share."""
    import jax
    import jax.numpy as jnp
    _, engine, _ = served_once
    cfg = run.hf_config(tiny)
    rng = np.random.default_rng(3)
    H, I, E = cfg["hidden_size"], cfg["intermediate_size"], 16
    x = jnp.asarray(rng.standard_normal((11, H)), jnp.float32)
    lay = runner_of(engine).params["layers"]
    lp = {k: lay[k][1] for k in ("router", "shared_gate", "shared_up",
                                 "shared_down")}
    stack = {k: jnp.asarray(0.2 * rng.standard_normal(shape), jnp.float32)
             for k, shape in (("w_gate", (E, H, I)), ("w_up", (E, H, I)),
                              ("w_down", (E, I, H)))}
    uncut = dict(cfg, num_experts=E)
    del uncut["expert_share"]
    with jax.default_matmul_precision("highest"):
        whole = reference.moe(x, {**lp, **stack}, uncut)
        shared = reference.moe(
            x, {**lp, **{k: v[:0] for k, v in stack.items()}},
            dict(uncut, num_experts=0))
        routed = 0
        for index in range(8):
            share = dict(cfg, expert_share={"published_experts": E,
                                            "chips": 8, "index": index})
            part = {k: v[2 * index:2 * index + 2] for k, v in stack.items()}
            routed = routed + reference.moe(
                x, {**lp, **part}, share, "no_shared_experts")
    assert float(jnp.abs(routed).max()) > 0.01
    np.testing.assert_allclose(routed + shared, whole, atol=1e-5)


def test_the_program_s_share_is_the_reference_s(served_once, tiny):
    """``mlp_block`` under the share (``_share_dispatch`` over the held
    experts, the shared experts folded into one product with the quarter
    in it) against the reference's definition, on picks that mostly miss
    the two held experts."""
    import jax
    import jax.numpy as jnp
    _, engine, _ = served_once
    runner = runner_of(engine)
    cfg = run.hf_config(tiny)
    x = jnp.asarray(np.random.default_rng(4).standard_normal(
        (19, cfg["hidden_size"])), jnp.float32)
    lay = runner.params["layers"]
    lp = {k: lay[k][2] for k in lay}
    with jax.default_matmul_precision("highest"):
        got = runner.model.mlp_block(lp, x)
        want = reference.moe(x, lp, cfg)
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert runner.model.cfg.experts_held == (0, 2)
    assert runner.model.cfg.router_experts == 16


def test_preemption_and_resume_give_the_uninterrupted_logits(
        engine, model_dir, monkeypatch, tiny):
    """Four requests of 40 + 30 tokens against a full group of 13 pages:
    one is preempted and prefills again from token 0 through both
    groups; same tokens and logprobs as with room for all. The engine of
    its own differs by ``num_gpu_blocks_override=13``; the prompts are
    no other test's."""
    prompts = [prompt(40, 10 + i) for i in range(4)]
    calm = serve(engine, prompts, 30, None)
    tight, small = served(model_dir, monkeypatch, prompts, 30, None,
                          num_gpu_blocks_override=13)
    assert int(small.get_stats()["num_preemptions"]) > 0
    mgr = manager_of(small)
    assert mgr.full_group.get_num_free_blocks() == 13
    assert mgr.window_group.get_num_free_blocks() == \
        mgr.window_group.block_pool.num_blocks
    for a, b in zip(calm, tight):
        assert a[0] == b[0]
        np.testing.assert_allclose(b[1], a[1], atol=2e-5)


def test_caches_and_tables_by_group(model_dir, monkeypatch, tiny):
    """The device arrays are a group's own (one full layer's pages from
    the sized pool, three window layers' from the window group's fixed
    pool); a request past its window holds every page in the full group
    and a window's worth in the other, and the table the kernel gets
    carries no freed page. An engine of its own: ``max_num_seqs=2``
    sizes the window group's pool."""
    from vllm_distributed_tpu.core.kv_cache_utils import \
        window_group_pool_blocks
    engine = make_engine(model_dir, monkeypatch, max_num_seqs=2)
    runner = runner_of(engine)
    caches = runner.kv_caches
    pool = window_group_pool_blocks(WINDOW, 16, 2, 64, 128 // 16)
    assert pool == 2 * (1 + 4) + 4
    assert caches["k"].shape[:2] == (1, 128)
    assert caches["k_win"].shape[:2] == (3, pool)
    assert runner.model.fixed_cache_bytes() == \
        2 * caches["k_win"].size * 4
    sp = SamplingParams(temperature=0.0, max_tokens=30, ignore_eos=True)
    engine.add_request("a", prompt(70), sp)
    seen = []
    real = runner._fill_window_group

    def spy(f, *args):
        real(f, *args)
        seen.append((int(runner.input_batch.num_computed[0]),
                     f["window_block_tables"][0].copy(),
                     runner.input_batch.block_table[0].copy()))

    monkeypatch.setattr(runner, "_fill_window_group", spy)
    mgr = manager_of(engine)
    for _ in range(24):
        engine.step()
        held = [b for b in mgr.window_group.req_to_blocks["a"]
                if b is not None]
        free = {b.block_id for b in
                mgr.window_group.block_pool.free_block_queue
                .get_all_free_blocks()}
        computed, window_table, full_table = seen[-1]
        # What the kernel got: live pages only, from the first slot a
        # query can still see; the full group's table is whole.
        dead = max(0, computed - WINDOW + 1) // 16
        assert (window_table[:dead] == 0).all()
        assert not free & set(window_table[dead:len(held) + dead].tolist())
        assert len(held) <= (WINDOW + 64) // 16 + 2
        assert (full_table[:-(-computed // 16)] > 0).sum() >= \
            -(-computed // 16) - 1
    assert computed > 70 + 16
    assert len(mgr.full_group.req_to_blocks["a"]) == -(-(computed + 1) // 16)
    assert len(held) <= 2 < len(mgr.full_group.req_to_blocks["a"])


def _load(tiny, tmp_path, **over):
    import json

    from vllm_distributed_tpu.engine.arg_utils import EngineArgs
    from vllm_distributed_tpu.models.loader import get_model
    from vllm_distributed_tpu.parallel.mesh import build_mesh
    import jax
    engine_over = over.pop("engine", {})
    with open(tmp_path / "config.json", "w") as f:
        json.dump({**run.hf_config(tiny), **over}, f)
    args = dict(model=str(tmp_path), load_format="dummy", dtype="float32",
                max_model_len=128, skip_tokenizer_init=True)
    args.update(engine_over)
    config = EngineArgs(**args).create_engine_config()
    mesh = build_mesh(config.parallel_config, jax.devices()[:1])
    return get_model(config, mesh)


@pytest.mark.parametrize("over,message", [
    ({"use_qk_norm": True}, "use_qk_norm"),
    ({"first_k_dense_replace": 1}, "first_k_dense_replace"),
    ({"expert_selection_fn": "softmax"}, "expert_selection_fn"),
    ({"shared_expert_combination_strategy": "sum"}, "combination"),
    ({"expert_share": {"published_experts": 16, "chips": 4, "index": 0}},
     "expert_share"),
    ({"engine": {"enable_expert_parallel": True}}, "expert_share"),
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


def test_one_kind_of_layer_keeps_one_group(tiny, tmp_path):
    """Every layer windowed alike: no second group, and the one table's
    pages are freed behind the window as for any such model."""
    from vllm_distributed_tpu.models.loader import (resolve_free_window,
                                                    resolve_window_group)
    model, _ = _load(tiny, tmp_path,
                     layer_types=["sliding_attention"] * 4)
    assert model.cfg.window_group is None
    assert set(model.kv_cache_specs()) == {"k", "v"}
    assert model.fixed_cache_bytes() == 0

    class MC:
        def __init__(self, path):
            self.path = path

        def maybe_load_hf_config(self):
            from transformers import PretrainedConfig
            import json
            with open(os.path.join(self.path, "config.json")) as f:
                return PretrainedConfig(**json.load(f))

    assert resolve_free_window(MC(str(tmp_path))) == WINDOW
    assert resolve_window_group(MC(str(tmp_path))) is None
