"""Ling-3.0-flash (``models/bailing.py``, ``ops/kda.py``) against the
plain reference (``benchmarks/references/bailing.py``) at a toy width on
the CPU, in float32: a tiny preset with all four kinds of layer (KDA
with the dense MLP, KDA with the expert layer, latent attention with
the expert layer, and the chip's share of the experts)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from transformers import PretrainedConfig

from benchmarks import run, traffic
from benchmarks.references import bailing as ref
from benchmarks.references import deepseek as dref
from vllm_distributed_tpu.models.bailing import BailingMoeV3ForCausalLM
from vllm_distributed_tpu.models.llama import LlamaArchConfig
from vllm_distributed_tpu.ops import kda
from vllm_distributed_tpu.ops.mamba import SegmentInfo
from vllm_distributed_tpu.utils import cdiv

DATA = os.path.join(os.path.dirname(__file__), "..", "benchmarking", "data")
CELL = "tiny-ling.tiny-decode"
TOL = 2e-4
CONTROLS = ("no_delta", "no_decay", "no_conv", "no_head_gate",
            "first_experts")


def tiny_cfg(**over) -> dict:
    cfg = run.hf_config(run.load_json(DATA, "configs", "tiny-ling.json"))
    cfg.update(over)
    return {k: v for k, v in cfg.items() if v is not None}


def build(cfg: dict) -> BailingMoeV3ForCausalLM:
    hf = PretrainedConfig(**cfg)
    arch = LlamaArchConfig.from_hf_config(hf, dtype=jnp.float32)
    BailingMoeV3ForCausalLM.configure_arch(arch, hf)
    arch.expert_parallel = False
    arch.quantization = None
    return BailingMoeV3ForCausalLM(arch)


def moe_layer(params: dict, j: int = 0) -> dict:
    return {k: params["layers"][k][j] for k in ref.MOE_KEYS}


# ---------------------------------------------------------------------
# The served path against the reference's one full forward
# ---------------------------------------------------------------------
def serve(tmp_path_factory, n_requests: int = 9):
    """Prompts of 56-217 tokens go in as chunks of 64 and a tail of
    another length (the state crosses each boundary through its row,
    the latent rows through their pages), then 8 tokens are decoded;
    returns, per sequence, its tokens and the engine's 20 best
    log-softmaxed logits at every position, and the seeded weights."""
    from vllm_distributed_tpu import LLM, SamplingParams
    from vllm_distributed_tpu.models.loader import get_model
    from vllm_distributed_tpu.parallel.mesh import build_mesh
    cell = run.load_cell(CELL, DATA)
    cf = cell["config_file"]
    work = run.WORK
    run.WORK = str(tmp_path_factory.mktemp("ling"))
    try:
        model_dir = run.write_model_dir(cell["config"], cf)
    finally:
        run.WORK = work
    args = run.engine_args(model_dir, cf, seed=11)
    llm = LLM(model=model_dir, load_format="dummy", seed=args.seed,
              skip_tokenizer_init=True, **cf["engine_args"])
    reqs = traffic.scoring_requests(9, cf["vocab_size"])[:n_requests]
    assert sorted(len(r["prompt"]) % 64 for r in reqs)[-1] > 0
    outs = llm.generate(
        [r["prompt"] for r in reqs],
        SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True,
                       logprobs=20, prompt_logprobs=20))
    stats = llm.llm_engine.get_stats()
    rows = []
    for r, o in zip(reqs, outs):
        c = o.outputs[0]
        rows.append((r["prompt"] + list(c.token_ids),
                     list(o.prompt_logprobs[1:]) + list(c.logprobs)))
    del llm
    config = args.create_engine_config()
    config.model_config.maybe_load_hf_config()
    mesh = build_mesh(config.parallel_config, jax.devices()[:1])
    return rows, get_model(config, mesh)[1], run.hf_config(cf), stats


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    return serve(tmp_path_factory)


@pytest.fixture(scope="module")
def served_on_kernels(tmp_path_factory):
    """The same through the latent kernels (interpret): a chunk step's
    latent layer attends its chunks in the multi-head form and its
    decoding rows, gathered, in the absorbed one, each head's output
    times the head-wise gate before ``wo``."""
    from vllm_distributed_tpu.ops import mla
    mixed, traced = mla._mixed_latent_attention, []
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("VDT_ATTENTION_BACKEND", "pallas")
        mp.setenv("VDT_PALLAS_INTERPRET", "1")
        mp.setattr(
            mla, "_mixed_latent_attention",
            lambda *a, **kw: traced.append(a[3].max_q) or mixed(*a, **kw))
        out = serve(tmp_path_factory, n_requests=4)
    assert traced and min(traced) > 1
    return out


def worst_deviation(served, control=None) -> float:
    rows, params, cfg, _ = served
    worst = 0.0
    for seq, got in rows:
        with jax.default_matmul_precision("highest"):
            table = np.asarray(ref.logprobs(
                params, cfg, jnp.asarray(seq, jnp.int32), control))
        assert len(got) == len(seq) - 1
        for pos, row in enumerate(got):
            assert len(row) >= 20
            worst = max(worst, max(abs(table[pos, t] - lp)
                                   for t, lp in row.items()))
    return worst


def ragged_batch(seed: int = 0, lens=(150, 70, 1), rows=(5, 2, 7),
                 inits=(False, True, True), S: int = 8, g_at=None):
    """Sequences in one flat batch of 256 slots, by default three: a
    fresh prompt of 150 tokens, a continued one of 70 (its row holds a
    state), one decode token; 4 heads of 16 x 16, ``S`` state rows.
    ``g_at`` sets every channel's log decay."""
    N, D = 4, 16
    T = 256
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    q = unit(rng.standard_normal((T, N, D))) * D ** -0.5
    k = unit(rng.standard_normal((T, N, D)) + 0.5)
    v = rng.standard_normal((T, N, D))
    g = -5.0 / (1.0 + np.exp(-(rng.standard_normal((T, N, D)) - 2.0)))
    if g_at is not None:
        g = np.full_like(g, g_at)
    beta = 1.0 / (1.0 + np.exp(-rng.standard_normal((T, N))))
    state = rng.standard_normal((2, S + 1, N, D, D)) * 0.3
    row = np.full(T, S, np.int32)
    off = np.zeros(T, np.int32)
    has_init = np.zeros(T, bool)
    q_len = np.zeros(S + 1, np.int32)
    q_start = np.zeros(S + 1, np.int32)
    init_row = np.zeros(S + 1, bool)
    t = 0
    for n, r, init in zip(lens, rows, inits):
        row[t:t + n], off[t:t + n], has_init[t:t + n] = r, np.arange(n), init
        q_len[r], q_start[r], init_row[r] = n, t, init
        t += n
    valid = np.arange(T) < t
    seg = SegmentInfo(
        row=jnp.asarray(row), valid=jnp.asarray(valid),
        off=jnp.asarray(off), start=jnp.asarray(valid & (off == 0)),
        end=jnp.asarray(valid & (off == q_len[row] - 1)),
        has_init=jnp.asarray(has_init), q_len_by_row=jnp.asarray(q_len),
        q_start_by_row=jnp.asarray(q_start),
        has_init_by_row=jnp.asarray(init_row))
    arrays = [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta, state)]
    return arrays, seg, list(zip(lens, rows, inits))


def reference_scan(arrays, segments, layer: int):
    """``ref.kda_scan``'s recurrence, sequence by sequence, from the
    row's state where the segment continues one."""
    q, k, v, g, beta, state = arrays
    want_o, want_state, t = [], np.array(state[layer]), 0
    for n, r, init in segments:
        s0 = state[layer, r] if init else jnp.zeros_like(state[layer, r])

        def step(s, x):
            q_t, k_t, v_t, a_t, b_t = x
            s = a_t[:, :, None] * s
            u = jnp.einsum("nk,nkv->nv", k_t, s)
            s = s + (b_t[:, None] * k_t)[:, :, None] * (v_t - u)[:, None, :]
            return s, jnp.einsum("nk,nkv->nv", q_t, s)

        sl = slice(t, t + n)
        s1, o = jax.lax.scan(step, s0, (q[sl], k[sl], v[sl],
                                        jnp.exp(g[sl]), beta[sl]))
        want_o.append(o)
        want_state[r] = s1
        t += n
    return jnp.concatenate(want_o), want_state


def check_forms_agree(_monkeypatch=None):
    """The chunked form, the recurrent form and what the program picks
    between them give the reference's outputs and final states on the
    ragged batch, in a layer of a stacked buffer whose other layer and
    idle rows stay as they were."""
    arrays, seg, segments = ragged_batch()
    layer = jnp.asarray([1], jnp.int32)
    with jax.default_matmul_precision("highest"):
        want_o, want_state = reference_scan(arrays, segments, 1)
        n = want_o.shape[0]
        for form in ("chunk", "recurrent", "auto"):
            o, state = kda.kda_ragged(*arrays, layer, seg,
                                      prefill_form=form)
            np.testing.assert_allclose(o[:n], want_o, atol=2e-5, err_msg=form)
            assert not np.asarray(o[n:]).any()
            np.testing.assert_allclose(state[1, :8], want_state[:8],
                                       atol=2e-5, err_msg=form)
            np.testing.assert_array_equal(state[0], arrays[5][0])
    # The same recurrence as the reference file's own scan from zero.
    q, k, v, g, beta, _ = arrays
    np.testing.assert_allclose(
        ref.kda_scan(q[:150], k[:150], v[:150], jnp.exp(g[:150]),
                     beta[:150]), want_o[:150], atol=2e-5)


def check_decode_padding_leaves_live_rows_alone(_monkeypatch=None):
    """A decode batch of three of eight rows in a bucket of 16 tokens:
    idle rows and the other layer are untouched, bit for bit."""
    arrays, _, _ = ragged_batch(1)
    N, D, S, T = 4, 16, 8, 16
    q, k, v, g, beta, state = (x[:T] if i < 5 else x
                               for i, x in enumerate(arrays))
    rows = np.array([6, 0, 3])
    row = np.full(T, S, np.int32)
    row[:3] = rows
    q_len = np.zeros(S + 1, np.int32)
    q_len[rows] = 1
    q_start = np.zeros(S + 1, np.int32)
    q_start[rows] = np.arange(3)
    valid = np.arange(T) < 3
    seg = SegmentInfo(
        row=jnp.asarray(row), valid=jnp.asarray(valid),
        off=jnp.zeros(T, jnp.int32), start=jnp.asarray(valid),
        end=jnp.asarray(valid), has_init=jnp.asarray(valid),
        q_len_by_row=jnp.asarray(q_len), q_start_by_row=jnp.asarray(q_start),
        has_init_by_row=jnp.asarray(q_len > 0))
    o, new = kda.kda_ragged(q, k, v, g, beta, state,
                            jnp.asarray([0], jnp.int32), seg,
                            decode_only=True)
    idle = [r for r in range(S) if r not in rows]
    np.testing.assert_array_equal(new[0, idle], state[0, idle])
    np.testing.assert_array_equal(new[1], state[1])
    assert np.abs(np.asarray(new[0, rows] - state[0, rows])).max() > 1e-3
    assert not np.asarray(o[3:]).any() and np.isfinite(np.asarray(new)).all()


def check_shares_sum_to_the_uncut_layer(_monkeypatch=None):
    """Over the four shares of a 32-expert deployment: the routed parts
    each share computes, summed, with the shared expert counted once,
    are the uncut reference's layer output, for this class's own router
    and dispatch."""
    chips, held = 4, 8
    uncut_cfg = tiny_cfg(num_experts=chips * held, expert_share=None)
    full = moe_layer(build(uncut_cfg).init_params(jax.random.PRNGKey(3)))
    moe_cfg = dict(uncut_cfg, n_routed_experts=chips * held)
    x = jax.random.normal(jax.random.PRNGKey(4), (37, 128), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = dref.moe(x, full, moe_cfg)
        shared = want - dref.moe(x, full, moe_cfg,
                                 control="no_shared_expert")
        routed, picks = [], 0
        for index in range(chips):
            model = build(tiny_cfg(expert_share={
                "published_experts": chips * held, "chips": chips,
                "index": index}))
            lo = index * held
            assert model.cfg.experts_held == (lo, held)
            assert model.cfg.router_experts == chips * held
            lp = dict(full, **{k: full[k][lo:lo + held]
                               for k in ("w_gate", "w_up", "w_down")})
            top_idx, top_vals = model._route(lp, x)
            picks += int(((top_idx >= lo) & (top_idx < lo + held)).sum())
            routed.append(model.moe_dispatch(lp, x, top_idx, top_vals))
            np.testing.assert_allclose(model.mlp_block(lp, x),
                                       routed[-1] + shared, atol=2e-6)
    assert picks == x.shape[0] * uncut_cfg["num_experts_per_tok"]
    np.testing.assert_allclose(sum(routed) + shared, want, atol=2e-6)
    assert float(jnp.abs(routed[1] + shared - want).max()) > 1e-3


def check_rows_past_the_groups_may_hold_anything(monkeypatch):
    """``ragged_dot`` leaves the rows past its groups uninitialised on
    the TPU (PR 28): this class's expert layer gives the same result
    when they hold NaN."""
    model = build(tiny_cfg())
    lp = moe_layer(model.init_params(jax.random.PRNGKey(5)))
    x = jax.random.normal(jax.random.PRNGKey(6), (21, 128), jnp.float32)
    want = model.mlp_block(lp, x)
    plain = jax.lax.ragged_dot

    def poisoned(lhs, rhs, group_sizes, **kw):
        out = plain(lhs, rhs, group_sizes, **kw)
        past = jnp.arange(lhs.shape[0]) >= group_sizes.sum()
        return jnp.where(past[:, None], jnp.nan, out)

    monkeypatch.setattr(jax.lax, "ragged_dot", poisoned)
    got = model.mlp_block(lp, x)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, atol=1e-6)


def check_layer_kinds_and_caches(_monkeypatch=None):
    """The layer pattern at the published sizes and at the toy's, and
    the two kinds of cache side by side."""
    m = build(tiny_cfg(num_hidden_layers=42, layer_group_size=6,
                       first_k_dense_replace=2))
    assert m._attn_layers == [5, 11, 17, 23, 29, 35, 41]
    assert len(m._kda_layers) == 35 and m._n_dense == 2
    m = build(tiny_cfg())
    assert (m._kda_layers, m._attn_layers, m._n_dense) == ([0, 1], [2], 1)
    m.cfg.state_slots = 8
    caches = jax.eval_shape(lambda: m.make_kv_caches(32, 16))
    assert caches["c"].shape == (1, 32, 16, 48)
    assert caches["kda"].shape == (2, 9, 4, 16, 16)
    assert caches["kda"].dtype == jnp.float32
    # Three carried inputs of q, k and v, in ops/conv1d.py's tiles.
    assert caches["conv"].shape == (2, 9, 3, 24, 8)
    # 2 layers x (4 heads x 16 x 16 x 4 B + 3 taps x 192 x 4 B).
    assert m.state_row_bytes() == 2 * (4096 + 2304)
    assert m.fixed_cache_bytes() == 9 * m.state_row_bytes()
    assert m.kv_cache_page_bytes(16) == 16 * 48 * 4
    assert set(m.state_shapes()) == {"kda", "conv"}
    with pytest.raises(NotImplementedError, match="tensor names"):
        m.params_from_hf_state_dict({})
    for bad in (dict(kda_safe_gate=False), dict(kda_lower_bound=-6),
                dict(use_kda_lora=True), dict(score_function="softmax"),
                dict(expert_swiglu_limit_list=[0, 4, 0])):
        with pytest.raises(ValueError):
            build(tiny_cfg(**bad))


HASH_SCRIPT = """
import hashlib, sys
sys.path.insert(0, {here!r})
import jax, jax.numpy as jnp
import test_bailing as tb
from vllm_distributed_tpu.models.common import AttentionBatch
m = tb.build(tb.tiny_cfg())
m.cfg.state_slots = 8
i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
def fwd(params, caches, tok, req, pos, slot, tables, lens, info, n):
    return m.forward(params, caches, tok, AttentionBatch(
        req_idx=req, positions=pos, slot_mapping=slot, block_tables=tables,
        seq_lens=lens, seq_info=info, num_seqs=n, max_q=16))
text = jax.jit(fwd).lower(
    jax.eval_shape(m.init_params, jax.random.PRNGKey(0)),
    jax.eval_shape(lambda: m.make_kv_caches(32, 16)), i32(32), i32(32),
    i32(32), i32(32), i32(8, 16), i32(8), i32(8, 4), i32(1)).as_text()
print(hashlib.sha256(text.encode()).hexdigest())
"""


def check_the_program_is_the_same_in_every_process(_monkeypatch=None):
    """The forward lowers to the same text whatever the process's string
    hashing: a layer walk that emits its slices in a set's order makes a
    program of another cache key in every process, and every start of a
    server then compiles its forwards again (40 chip-minutes of PR 33)."""
    import subprocess
    import sys
    here = os.path.dirname(os.path.abspath(__file__))
    # Both at once: each is a start of Python and JAX and one lowering,
    # about 20 s alone (300 s bounds a hang).
    procs = [subprocess.Popen(
        [sys.executable, "-c", HASH_SCRIPT.format(here=here)],
        env=dict(os.environ, PYTHONHASHSEED=seed, JAX_PLATFORMS="cpu",
                 VDT_PALLAS_INTERPRET="1"),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for seed in ("1", "2")]
    digests = set()
    try:
        for p in procs:
            out, err = p.communicate(timeout=300)
            assert p.returncode == 0, err[-2000:]
            digests.add(out.split()[-1])
    finally:
        for p in procs:
            p.kill()
    assert len(digests) == 1


# lens, rows, inits of a 256-slot bucket over 12 state rows: 4 chunks of
# its own and MAX_CHUNK_SEGMENTS more.
CHUNK_CASES = {
    # Its second and third chunks find the row's stored state, decayed
    # and corrected by the first, in the output block (off > 0).
    "continues_a_stored_state_mid_segment":
        dict(lens=(140, 1, 90), rows=(3, 6, 9), inits=(True, True, True)),
    "last_chunk_shorter_than_a_sub_chunk":
        dict(lens=(131, 67), rows=(1, 4), inits=(False, True)),
    "trailing_chunks_all_padding":
        dict(lens=(5, 1, 3), rows=(0, 11, 2), inits=(True, True, False)),
    "decay_at_the_lower_bound_for_whole_chunks":
        dict(lens=(150, 70), rows=(5, 2), inits=(False, True), g_at=-5.0),
    "five_segments_over_the_buckets_chunks_go_token_by_token":
        dict(lens=(2, ) * 9, rows=tuple(range(9)),
             inits=(True, False) * 4 + (True, )),
}


@pytest.mark.parametrize("case", list(CHUNK_CASES))
def test_chunked_form_case(case):
    """The chunk kernel at its edges, against the token-by-token scan:
    outputs and final states within ``check_forms_agree``'s tolerance,
    padding slots zero, the dump row finite, idle rows and the other
    layer bit for bit as they were."""
    S = 12
    spec = CHUNK_CASES[case]
    arrays, seg, segments = ragged_batch(3, S=S, **spec)
    fits = (sum(cdiv(n, kda.CHUNK) for n in spec["lens"] if n > 1)
            <= cdiv(arrays[0].shape[0], kda.CHUNK) + kda.MAX_CHUNK_SEGMENTS)
    layer = jnp.asarray([1], jnp.int32)
    idle = sorted(set(range(S)) - set(spec["rows"]))
    got = {}
    with jax.default_matmul_precision("highest"):
        want_o, want_state = reference_scan(arrays, segments, 1)
        n = want_o.shape[0]
        for form in ("chunk", "recurrent", "auto"):
            o, state = kda.kda_ragged(*arrays, layer, seg, prefill_form=form)
            got[form] = np.asarray(o)
            assert np.isfinite(np.asarray(state)).all(), form
            np.testing.assert_array_equal(state[0], arrays[5][0])
            np.testing.assert_array_equal(state[1, idle], arrays[5][1, idle])
            assert not np.asarray(o[n:]).any()
            if form == "chunk" and not fits:
                continue        # pinned past its chunk count: not served
            np.testing.assert_allclose(o[:n], want_o, atol=2e-5, err_msg=form)
            np.testing.assert_allclose(
                state[1, list(spec["rows"])],
                want_state[list(spec["rows"])], atol=2e-5, err_msg=form)
    # What the program picked: the chunked form where the segments fit
    # its static chunk count, else the recurrent kernel.
    np.testing.assert_array_equal(got["auto"],
                                  got["chunk" if fits else "recurrent"])
    assert fits == (case != list(CHUNK_CASES)[-1])
    if not fits:
        assert np.abs(got["chunk"][:n] - np.asarray(want_o)).max() > 1e-3


PROPERTIES = {
    "chunked_recurrent_and_reference_agree_on_a_ragged_batch":
        check_forms_agree,
    "decode_padding_leaves_live_rows_alone":
        check_decode_padding_leaves_live_rows_alone,
    "shares_sum_to_the_uncut_layer": check_shares_sum_to_the_uncut_layer,
    "rows_past_the_groups_may_hold_anything":
        check_rows_past_the_groups_may_hold_anything,
    "layer_kinds_and_caches": check_layer_kinds_and_caches,
    "the_program_is_the_same_in_every_process":
        check_the_program_is_the_same_in_every_process,
}


@pytest.mark.parametrize("name", list(PROPERTIES))
def test_property(name, monkeypatch):
    PROPERTIES[name](monkeypatch)


@pytest.mark.parametrize("case,passes", [
    ("the_routers_picks", 1), ("every_pick_held", 3),
    ("held_one_past_capacity", 2), ("no_pick_held", 0)])
def test_share_dispatch_takes_the_passes_its_held_picks_need(
        case, passes, monkeypatch):
    """This class inherits the share's dispatch (models/deepseek.py): a
    pass works on `share_capacity` rows (896 for 512 tokens of 4 picks,
    a quarter held) and there are as many passes as the held picks need;
    the result is the reference's expert sum for the same picks,
    whatever they are."""
    cfg = tiny_cfg()
    model = build(cfg)
    lo, n = model.cfg.experts_held
    T, k = 512, cfg["num_experts_per_tok"]
    C = model.share_capacity(T, k)
    assert C == 896
    lp = moe_layer(model.init_params(jax.random.PRNGKey(7)))
    x = jax.random.normal(jax.random.PRNGKey(8), (T, 128), jnp.float32)
    top_idx, top_vals = model._route(lp, x)
    flat = np.full(T * k, lo + n, np.int32)      # absent
    if case == "every_pick_held":
        flat[:] = lo + np.arange(T * k) % n
    elif case == "held_one_past_capacity":
        where = np.random.default_rng(8).permutation(T * k)[:C + 1]
        flat[where] = lo + np.arange(C + 1) * 5 % n
    if case != "the_routers_picks":
        top_idx = jnp.asarray(flat.reshape(T, k))
    held = int(((top_idx >= lo) & (top_idx < lo + n)).sum())
    assert cdiv(held, C) == passes, (held, C)
    dense = jnp.zeros((T, model.cfg.router_experts), jnp.float32).at[
        jnp.arange(T)[:, None], top_idx].add(top_vals)
    monkeypatch.setattr(dref, "route", lambda *a, **kw: dense)
    with jax.default_matmul_precision("highest"):
        want = dref.moe(x, lp, dict(cfg, n_routed_experts=n),
                        control="no_shared_expert")
        got = jax.jit(model.moe_dispatch)(lp, x, top_idx, top_vals)
    assert (held == 0) == (float(jnp.abs(want).max()) == 0.0)
    np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.parametrize("control", [None, *CONTROLS])
def test_served_logits_against_the_reference(served, control):
    """Without a control: at every position the engine's 20 best
    log-softmaxed logits are the reference's to 2e-4 (both float32; the
    chunked form, the absorbed form and the pages sum in another
    order). With one: the reference with that path broken lies at
    least a hundred tolerances away, so the comparison sees the path."""
    worst = worst_deviation(served, control)
    if control is None:
        assert worst < TOL, worst
    else:
        assert worst > 100 * TOL, (control, worst)


@pytest.mark.parametrize("control", [None, "no_head_gate"])
def test_chunk_steps_on_the_kernels_against_the_reference(
        served_on_kernels, control):
    """Prefill in chunks, then decode through the cache, on the Pallas
    backend: the multi-head form's output meets the head-wise gate as
    the absorbed form's does, to the tolerance the XLA path is held to;
    the reference without the gate lies a hundred tolerances away."""
    worst = worst_deviation(served_on_kernels, control)
    if control is None:
        assert worst < TOL, worst
    else:
        assert worst > 100 * TOL, (control, worst)


def test_gauges_and_state_rows_flow_to_metrics(served):
    from vllm_distributed_tpu.metrics.prometheus import render_metrics
    stats = served[3]
    entry = next(iter(stats["workers"].values()))
    assert entry["state_row_bytes"] == 2 * (4096 + 2304)
    assert entry["moe_experts_held"] == 8
    assert entry["moe_experts_published"] == 32
    # ONE latent layer x (32 + 16) lanes x 4 bytes a token.
    assert entry["mla_latent_token_bytes"] == 48 * 4
    text = render_metrics(stats)
    for family in ("vdt:state_row_bytes{", "vdt:ssm_state_bytes_held",
                   "vdt:step_kv_tokens_read_sum",
                   "vdt:mla_latent_token_bytes{"):
        assert family in text, family


@pytest.mark.parametrize("kwargs,what", [
    (dict(enable_expert_parallel=True), "expert parallelism"),
    (dict(pipeline_parallel_size=2), "pipeline parallelism"),
    (dict(tensor_parallel_size=2), "tensor parallelism over gated"),
    (dict(token_parallel_size=2), "token parallelism")])
def test_loader_refuses_what_the_block_cannot_be_combined_with(
        kwargs, what, tmp_path, monkeypatch):
    """The stateful refusals hold for this class, a share on a stateful
    hybrid loads (the fixture above), and tensor parallelism is refused
    by name."""
    from vllm_distributed_tpu.engine.arg_utils import EngineArgs
    from vllm_distributed_tpu.models.loader import get_model
    from vllm_distributed_tpu.parallel.mesh import build_mesh
    monkeypatch.setattr(run, "WORK", str(tmp_path))
    cell = run.load_cell(CELL, DATA)
    cf = cell["config_file"]
    model_dir = run.write_model_dir(cell["config"], cf)
    config = EngineArgs(model=model_dir, load_format="dummy",
                        skip_tokenizer_init=True, **cf["engine_args"],
                        **kwargs).create_engine_config()
    n = (kwargs.get("tensor_parallel_size", 1)
         * kwargs.get("pipeline_parallel_size", 1)
         * kwargs.get("token_parallel_size", 1))
    mesh = build_mesh(config.parallel_config, jax.devices()[:n])
    with pytest.raises(ValueError, match=what):
        get_model(config, mesh)
