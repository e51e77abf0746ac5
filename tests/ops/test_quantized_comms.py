"""Quantized communication plane: parity gates per in-graph path.

Every quantized collective must stay within one block-scaled int8
round-trip of its exact counterpart (bounded divergence), and the
engine-level greedy decode must be TOKEN-IDENTICAL on the CPU smoke
configs with a fine scale block (VDT_QCOMM_BLOCK=16 — at toy-model
scale the random-weight logit gaps sit near the coarse-block noise
floor; real checkpoints tolerate the default 256, which is what the
EQuARX quality results are about). VDT_QCOMM=0 must revert every path
byte-identically.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch
from jax.sharding import PartitionSpec as P
from transformers import LlamaConfig
from transformers import LlamaForCausalLM as HFLlama

from vllm_distributed_tpu.config import ParallelConfig
from vllm_distributed_tpu.engine.arg_utils import EngineArgs
from vllm_distributed_tpu.engine.llm_engine import LLMEngine
from vllm_distributed_tpu.parallel import collectives
from vllm_distributed_tpu.parallel.mesh import (build_mesh, global_mesh,
                                                shard_map)
from vllm_distributed_tpu.sampling_params import SamplingParams


@pytest.fixture()
def qcomm_on(monkeypatch):
    monkeypatch.setenv("VDT_QCOMM", "1")
    collectives.refresh()
    yield
    collectives.refresh()


@pytest.fixture(autouse=True)
def _refresh_after(monkeypatch):
    # Every test leaves the cached env gating the way it found it.
    yield
    collectives.refresh()


def _mesh(k: int):
    return build_mesh(ParallelConfig(tensor_parallel_size=k),
                      devices=jax.devices("cpu")[:k])


# ---------------------------------------------------------------------------
# Dispatcher gating
# ---------------------------------------------------------------------------

def test_disabled_by_default(monkeypatch):
    monkeypatch.delenv("VDT_QCOMM", raising=False)
    collectives.refresh()
    for path in ("tknp", "ep", "tp", "dcn_pull"):
        assert not collectives.enabled(path)


def test_path_override(monkeypatch):
    monkeypatch.setenv("VDT_QCOMM", "1")
    monkeypatch.setenv("VDT_QCOMM_PATHS", "tknp,kv")
    collectives.refresh()
    assert collectives.enabled("tknp")
    assert not collectives.enabled("ep")
    assert not collectives.enabled("tp")
    assert not collectives.enabled("tknp_kv")
    monkeypatch.setenv("VDT_QCOMM_PATHS", "tknp_kv")
    collectives.refresh()
    assert collectives.enabled("tknp_kv")
    assert not collectives.enabled("tknp")
    monkeypatch.setenv("VDT_QCOMM_PATHS", "tknp,kv")
    collectives.refresh()
    # "kv" is the group token for every connector payload path.
    assert collectives.enabled("dcn_pull")
    assert collectives.enabled("p2p")
    assert collectives.enabled("shared_storage")
    monkeypatch.setenv("VDT_QCOMM_PATHS", "")
    collectives.refresh()
    assert all(collectives.enabled(p)
               for p in ("tknp", "ep", "tp", "shared_storage"))


def test_psum_off_is_exact_lax_psum(monkeypatch):
    monkeypatch.setenv("VDT_QCOMM", "0")
    collectives.refresh()
    mesh = _mesh(2)
    x = np.arange(2 * 24, dtype=np.float32).reshape(2, 24)
    with global_mesh(mesh), mesh:
        got = shard_map(
            lambda x_: collectives.psum(x_[0], "model", path="tknp"),
            mesh=mesh, in_specs=(P("model", None), ), out_specs=P(),
            check_vma=False)(jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(got), x.sum(axis=0))


def test_divisor_block():
    assert collectives.divisor_block(64, 256) == 64
    assert collectives.divisor_block(256, 256) == 256
    assert collectives.divisor_block(96, 64) == 48
    assert collectives.divisor_block(7, 4) == 1


# ---------------------------------------------------------------------------
# Quantized psum (the TKNP decode merge + EP combine + TP reduce form)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [2, 4])
def test_quantized_psum_bounded_divergence(qcomm_on, k):
    mesh = _mesh(k)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(k, 37, 64)).astype(np.float32)
    with global_mesh(mesh), mesh:
        got = shard_map(
            lambda x_: collectives.psum(x_[0], "model", path="ep"),
            mesh=mesh, in_specs=(P("model", None, None), ),
            out_specs=P(), check_vma=False)(jnp.asarray(x))
    want = x.sum(axis=0)
    # One int8 block round-trip per leg: 2 * amax/127 per contributing
    # rank, summed — loose analytic bound.
    bound = 2.0 * (k + 1) * np.max(np.abs(x)) / 127.0
    assert np.max(np.abs(np.asarray(got) - want)) < bound


def test_quantized_psum_disjoint_rows_like_tknp(qcomm_on):
    """The TKNP merge shape: each rank owns disjoint rows, foreign rows
    are zero. All-zero blocks must contribute exactly zero."""
    mesh = _mesh(2)
    rng = np.random.default_rng(1)
    full = rng.normal(size=(8, 4, 16)).astype(np.float32)
    per_rank = np.zeros((2, ) + full.shape, np.float32)
    per_rank[0, :4] = full[:4]
    per_rank[1, 4:] = full[4:]
    with global_mesh(mesh), mesh:
        got = shard_map(
            lambda x_: collectives.psum(x_[0], "model", path="tknp"),
            mesh=mesh, in_specs=(P("model", None, None, None), ),
            out_specs=P(), check_vma=False)(jnp.asarray(per_rank))
    bound = 2.0 * 3 * np.max(np.abs(full)) / 127.0
    assert np.max(np.abs(np.asarray(got) - full)) < bound


def test_quantized_psum_zeros_exact(qcomm_on):
    mesh = _mesh(2)
    z = np.zeros((2, 5, 33), np.float32)
    with global_mesh(mesh), mesh:
        got = shard_map(
            lambda x_: collectives.psum(x_[0], "model", path="ep"),
            mesh=mesh, in_specs=(P("model", None, None), ),
            out_specs=P(), check_vma=False)(jnp.asarray(z))
    np.testing.assert_array_equal(np.asarray(got), z[0])


def test_psum_integer_operand_falls_back_exact(qcomm_on):
    """Lossy rounding of integer sums is silently wrong — the drop-in
    must take the exact psum (counted as a fallback) for non-floats."""
    collectives.reset_counters()
    mesh = _mesh(2)
    x = np.arange(2 * 1000, dtype=np.int32).reshape(2, 1000) * 1000
    with global_mesh(mesh), mesh:
        got = shard_map(
            lambda x_: collectives.psum(x_[0], "model", path="ep"),
            mesh=mesh, in_specs=(P("model", None), ), out_specs=P(),
            check_vma=False)(jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(got), x.sum(axis=0))
    assert collectives.traced_snapshot()["fallbacks"].get("ep") == 1


def test_all_to_all_no_win_falls_back_exact(qcomm_on):
    """A bf16 payload with a 2-wide feature dim would ship MORE bytes
    quantized (scales outweigh the dtype shrink) — must stay raw."""
    import ml_dtypes
    collectives.reset_counters()
    k = 2
    mesh = _mesh(k)
    y = (np.arange(k * k * 4 * 2).reshape(k, k, 4, 2)
         .astype(ml_dtypes.bfloat16))
    specs = (P("model", None, None, None), )
    out_spec = P("model", None, None)  # the per-rank result is rank 3
    with global_mesh(mesh), mesh:
        got = shard_map(
            lambda y_: collectives.all_to_all(y_[0], "model", 0, 0,
                                              path="ep"),
            mesh=mesh, in_specs=specs, out_specs=out_spec,
            check_vma=False)(jnp.asarray(y))
        want = shard_map(
            lambda y_: jax.lax.all_to_all(y_[0], "model", 0, 0),
            mesh=mesh, in_specs=specs, out_specs=out_spec,
            check_vma=False)(jnp.asarray(y))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert collectives.traced_snapshot()["fallbacks"].get("ep") == 1


def test_trace_counters_record_savings(qcomm_on):
    collectives.reset_counters()
    mesh = _mesh(2)
    x = np.ones((2, 16, 64), np.float32)
    with global_mesh(mesh), mesh:
        shard_map(lambda x_: collectives.psum(x_[0], "model", path="ep"),
                  mesh=mesh, in_specs=(P("model", None, None), ),
                  out_specs=P(), check_vma=False)(jnp.asarray(x))
    snap = collectives.traced_snapshot()
    assert snap["bytes_saved"].get("ep", 0) > 0


# ---------------------------------------------------------------------------
# Quantized all_to_all (the MoE-EP dispatch/combine shuffle)
# ---------------------------------------------------------------------------

def test_quantized_all_to_all_bounded_divergence(qcomm_on):
    k = 4
    mesh = _mesh(k)
    rng = np.random.default_rng(2)
    y = rng.normal(size=(k, k, 8, 64)).astype(np.float32)
    specs = (P("model", None, None, None), )
    out_spec = P("model", None, None)  # the per-rank result is rank 3
    with global_mesh(mesh), mesh:
        got = shard_map(
            lambda y_: collectives.all_to_all(y_[0], "model", 0, 0,
                                              path="ep"),
            mesh=mesh, in_specs=specs, out_specs=out_spec,
            check_vma=False)(jnp.asarray(y))
        want = shard_map(
            lambda y_: jax.lax.all_to_all(y_[0], "model", 0, 0),
            mesh=mesh, in_specs=specs, out_specs=out_spec,
            check_vma=False)(jnp.asarray(y))
    bound = np.max(np.abs(y)) / 127.0 + 1e-6
    assert np.max(np.abs(np.asarray(got) - np.asarray(want))) < bound


# ---------------------------------------------------------------------------
# Quantized all_gather (the MoE-EP re-replicate step; path "ep" —
# and the TPLA "tpla" path's gating rides the same dispatcher)
# ---------------------------------------------------------------------------

def test_quantized_all_gather_bounded_divergence(qcomm_on):
    k = 4
    mesh = _mesh(k)
    rng = np.random.default_rng(3)
    x = rng.normal(size=(k * 8, 64)).astype(np.float32)
    in_specs = (P("model", None), )
    with global_mesh(mesh), mesh:
        got = shard_map(
            lambda x_: collectives.all_gather(x_, "model", tiled=True,
                                              path="ep"),
            mesh=mesh, in_specs=in_specs, out_specs=P(),
            check_vma=False)(jnp.asarray(x))
        want = shard_map(
            lambda x_: jax.lax.all_gather(x_, "model", tiled=True),
            mesh=mesh, in_specs=in_specs, out_specs=P(),
            check_vma=False)(jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(want), x)
    bound = np.max(np.abs(x)) / 127.0 + 1e-6
    assert np.max(np.abs(np.asarray(got) - x)) < bound
    assert collectives.traced_snapshot()["bytes_saved"].get("ep", 0) > 0


def test_all_gather_off_is_exact_lax(monkeypatch):
    monkeypatch.delenv("VDT_QCOMM", raising=False)
    collectives.refresh()
    k = 2
    mesh = _mesh(k)
    x = np.arange(k * 4 * 8, dtype=np.float32).reshape(k * 4, 8)
    with global_mesh(mesh), mesh:
        got = shard_map(
            lambda x_: collectives.all_gather(x_, "model", tiled=True,
                                              path="ep"),
            mesh=mesh, in_specs=(P("model", None), ), out_specs=P(),
            check_vma=False)(jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(got), x)


def test_all_gather_integer_operand_falls_back_exact(qcomm_on):
    collectives.reset_counters()
    k = 2
    mesh = _mesh(k)
    x = np.arange(k * 4 * 8, dtype=np.int32).reshape(k * 4, 8)
    with global_mesh(mesh), mesh:
        got = shard_map(
            lambda x_: collectives.all_gather(x_, "model", tiled=True,
                                              path="ep"),
            mesh=mesh, in_specs=(P("model", None), ), out_specs=P(),
            check_vma=False)(jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(got), x)
    assert collectives.traced_snapshot()["fallbacks"].get("ep") == 1


# ---------------------------------------------------------------------------
# TKNP KV-write shuffle (path "tknp_kv") — the last raw collective of
# ROADMAP item 5: the step's new K/V rows crossing the token-axis
# shard_map boundary ship block-scaled int8.
# ---------------------------------------------------------------------------


def test_kv_shuffle_quantize_bounded_divergence(monkeypatch):
    monkeypatch.setenv("VDT_QCOMM", "1")
    monkeypatch.setenv("VDT_QCOMM_PATHS", "tknp_kv")
    monkeypatch.setenv("VDT_QCOMM_BLOCK", "16")
    collectives.refresh()
    collectives.reset_counters()
    rng = np.random.default_rng(5)
    k_new = rng.normal(size=(12, 4, 32)).astype(np.float32)
    v_new = rng.normal(size=(12, 4, 32)).astype(np.float32)
    pack = collectives.kv_shuffle_quantize(jnp.asarray(k_new),
                                           jnp.asarray(v_new), 2)
    assert pack is not None
    k_d, v_d = collectives.kv_shuffle_dequantize(*pack, jnp.float32)
    bound = np.max(np.abs(np.stack([k_new, v_new]))) / 127.0 + 1e-6
    assert np.max(np.abs(np.asarray(k_d) - k_new)) < bound
    assert np.max(np.abs(np.asarray(v_d) - v_new)) < bound
    assert collectives.traced_snapshot()["bytes_saved"]["tknp_kv"] > 0


def test_kv_shuffle_no_win_falls_back(monkeypatch):
    """Axis size 1 (no shuffle) and integer payloads must keep the raw
    path, counted as fallbacks."""
    monkeypatch.setenv("VDT_QCOMM", "1")
    monkeypatch.setenv("VDT_QCOMM_PATHS", "tknp_kv")
    collectives.refresh()
    collectives.reset_counters()
    x = jnp.ones((4, 2, 16), jnp.float32)
    assert collectives.kv_shuffle_quantize(x, x, 1) is None
    xi = jnp.ones((4, 2, 16), jnp.int32)
    assert collectives.kv_shuffle_quantize(xi, xi, 2) is None
    assert collectives.traced_snapshot()["fallbacks"]["tknp_kv"] == 2


def test_kv_shuffle_off_is_inert(monkeypatch):
    monkeypatch.delenv("VDT_QCOMM", raising=False)
    collectives.refresh()
    x = jnp.ones((4, 2, 16), jnp.float32)
    assert collectives.kv_shuffle_quantize(x, x, 2) is None


def test_tknp_kv_write_parity_through_ops(monkeypatch):
    """The full _write_kv_cache_tknp path on a 2-rank token mesh:
    quantized writes stay within one int8 block round-trip of the raw
    writes, untouched pages stay byte-identical."""
    from vllm_distributed_tpu.models.common import (AttentionBatch,
                                                    TknpAttentionBatch)
    from vllm_distributed_tpu.ops.attention import write_kv_cache
    K, L, Nl, KVH, PS, D = 2, 1, 8, 2, 4, 16
    N = K * Nl
    rng = np.random.default_rng(9)
    k_all = jnp.zeros((L, N, KVH, PS, D), jnp.float32)
    v_all = jnp.zeros((L, N, KVH, PS, D), jnp.float32)
    T = 4
    k_new = jnp.asarray(rng.normal(size=(T, KVH, D)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(T, KVH, D)), jnp.float32)
    # Two tokens per rank: rank 0 owns pages [0, Nl), rank 1 the rest.
    slots = np.full((K, T), -1, np.int32)
    kv_runs = np.zeros((K, 4, 4), np.int32)
    n_runs = np.zeros((K, 1), np.int32)
    for t in range(T):
        owner = t % K
        local_page, off = t, 1
        slots[owner, t] = local_page * PS + off
        g = n_runs[owner, 0]
        kv_runs[owner, g] = (local_page, off, t - off + PS, 1)
        n_runs[owner, 0] = g + 1
    tk = TknpAttentionBatch(
        slot_mapping=jnp.asarray(slots),
        block_tables=jnp.zeros((K, 4, 4), jnp.int32),
        seq_info=jnp.zeros((K, 4, 4), jnp.int32),
        num_seqs=jnp.zeros((K, 1), jnp.int32),
        kv_runs=jnp.asarray(kv_runs),
        num_kv_runs=jnp.asarray(n_runs),
    )
    batch = AttentionBatch(
        req_idx=jnp.zeros((T, ), jnp.int32),
        positions=jnp.zeros((T, ), jnp.int32),
        slot_mapping=jnp.zeros((T, ), jnp.int32),
        block_tables=jnp.zeros((4, 4), jnp.int32),
        seq_lens=jnp.zeros((4, ), jnp.int32),
        tknp=tk,
    )
    from vllm_distributed_tpu.config import ParallelConfig
    mesh = build_mesh(
        ParallelConfig(token_parallel_size=K),
        devices=jax.devices("cpu")[:K])
    layer = jnp.zeros((1, ), jnp.int32)
    with global_mesh(mesh), mesh:
        monkeypatch.delenv("VDT_QCOMM", raising=False)
        collectives.refresh()
        k_raw, v_raw = write_kv_cache(k_all, v_all, k_new, v_new,
                                      batch, layer)
        monkeypatch.setenv("VDT_QCOMM", "1")
        monkeypatch.setenv("VDT_QCOMM_PATHS", "tknp_kv")
        monkeypatch.setenv("VDT_QCOMM_BLOCK", "16")
        collectives.refresh()
        collectives.reset_counters()
        k_q, v_q = write_kv_cache(k_all, v_all, k_new, v_new, batch,
                                  layer)
    bound = np.max(np.abs(np.asarray(k_new))) / 127.0 + 1e-6
    assert np.max(np.abs(np.asarray(k_q) - np.asarray(k_raw))) < bound
    bound_v = np.max(np.abs(np.asarray(v_new))) / 127.0 + 1e-6
    assert np.max(np.abs(np.asarray(v_q) - np.asarray(v_raw))) < bound_v
    # The raw leg actually wrote the rows it claims to have written.
    assert np.max(np.abs(np.asarray(k_raw))) > 0
    assert collectives.traced_snapshot()["bytes_saved"]["tknp_kv"] > 0


def test_tknp_kv_engine_greedy_parity(checkpoint, baseline,
                                      monkeypatch):
    """Engine-level: the quantized KV-write shuffle keeps greedy decode
    token-identical at the fine scale block (like the other paths)."""
    monkeypatch.setenv("VDT_QCOMM", "1")
    monkeypatch.setenv("VDT_QCOMM_PATHS", "tknp_kv")
    monkeypatch.setenv("VDT_QCOMM_BLOCK", "16")
    collectives.refresh()
    got = _run(_make_engine(checkpoint, token_parallel_size=2), PROMPTS,
               "qtknpkv")
    assert got == baseline


# ---------------------------------------------------------------------------
# EP MoE block: quantized dispatch/combine vs exact, both EP modes
# ---------------------------------------------------------------------------

@pytest.fixture()
def ep_setup():
    from vllm_distributed_tpu.models.llama import LlamaArchConfig
    from vllm_distributed_tpu.models.mixtral import MixtralForCausalLM
    ep = 4
    T, H, I, E = 8, 32, 16, 4
    mesh = _mesh(ep)
    cfg = LlamaArchConfig(
        vocab_size=64, hidden_size=H, intermediate_size=I,
        num_layers=1, num_q_heads=4, num_kv_heads=4, head_dim=8,
        num_experts=E, num_experts_per_tok=2, norm_topk_prob=True,
        expert_parallel=True, expert_parallel_ranks=ep,
        dtype=jnp.float32)
    model = MixtralForCausalLM(cfg)
    rng = np.random.default_rng(0)
    lp = {
        "router": jnp.asarray(rng.normal(size=(H, E)), jnp.float32),
        "w_gate": jnp.asarray(rng.normal(size=(E, H, I)) * 0.1,
                              jnp.float32),
        "w_up": jnp.asarray(rng.normal(size=(E, H, I)) * 0.1,
                            jnp.float32),
        "w_down": jnp.asarray(rng.normal(size=(E, I, H)) * 0.1,
                              jnp.float32),
    }
    x = jnp.asarray(rng.normal(size=(T, H)), jnp.float32)
    return mesh, model, lp, x


@pytest.mark.parametrize("mode", ["a2a", "replicate"])
def test_moe_ep_quantized_bounded_divergence(ep_setup, monkeypatch,
                                             mode):
    mesh, model, lp, x = ep_setup
    monkeypatch.setenv("VDT_MOE_EP_MODE", mode)
    def block():
        # Under one jit a reading (the flags are read when it is
        # traced): eager dispatch compiles every operation of the
        # block, across four devices, on its own.
        return np.asarray(jax.jit(lambda x_: model.mlp_block(lp, x_))(x))

    with global_mesh(mesh), mesh:
        monkeypatch.setenv("VDT_QCOMM", "1")
        collectives.refresh()
        got = block()
        monkeypatch.setenv("VDT_QCOMM", "0")
        collectives.refresh()
        want = block()
    assert np.max(np.abs(got - want)) < 0.05 * (np.max(np.abs(want))
                                                + 1.0)


# ---------------------------------------------------------------------------
# Engine-level greedy parity (CPU smoke config, fine scale block)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def checkpoint(tmp_path_factory):
    torch.manual_seed(0)
    cfg = LlamaConfig(vocab_size=128, hidden_size=64,
                      intermediate_size=128, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=64, eos_token_id=1)
    hf = HFLlama(cfg).eval()
    path = tmp_path_factory.mktemp("tiny_llama_qcomm")
    hf.save_pretrained(path, safe_serialization=True)
    return str(path)


PROMPTS = [
    [3, 17, 92, 45, 8],
    [5, 9, 33, 71],
    [11, 12, 13, 14, 15, 16],
    [7, 44, 101, 13, 2, 64, 99],
]


def _make_engine(path, **overrides) -> LLMEngine:
    args = dict(model=path, dtype="float32", block_size=4,
                num_gpu_blocks_override=128, max_model_len=64,
                max_num_batched_tokens=64, max_num_seqs=8,
                skip_tokenizer_init=True)
    args.update(overrides)
    return LLMEngine(EngineArgs(**args).create_engine_config())


def _run(engine, prompts, tag, max_tokens=8, logprobs=None):
    sp = SamplingParams(temperature=0.0, max_tokens=max_tokens,
                        ignore_eos=True, logprobs=logprobs)
    for i, p in enumerate(prompts):
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
    if logprobs:
        return [(done[k].outputs[0].token_ids, done[k].outputs[0].logprobs)
                for k in order]
    return [done[k].outputs[0].token_ids for k in order]


@pytest.fixture(scope="module")
def baseline(checkpoint):
    return _run(_make_engine(checkpoint), PROMPTS, "base")


@pytest.fixture()
def qcomm_fine_block(monkeypatch):
    monkeypatch.setenv("VDT_QCOMM", "1")
    monkeypatch.setenv("VDT_QCOMM_BLOCK", "16")
    collectives.refresh()
    yield
    collectives.refresh()


# Largest logprob gap between two candidates that still counts as a tie
# int8 block rounding may flip (measured flip on this checkpoint: 35 vs 26
# at -4.4727 / -4.4732, gap 5e-4).
_TIE_BOUND = 5e-3


def test_tknp_engine_greedy_parity(checkpoint, qcomm_fine_block,
                                   monkeypatch):
    """The exact TKNP engine (VDT_QCOMM=0) is token-identical to the
    unsharded one; the int8 wire may flip a greedy choice only at a
    near tie of the exact engine's own logprobs."""
    got = _run(_make_engine(checkpoint, token_parallel_size=2), PROMPTS,
               "qtknp")
    monkeypatch.setenv("VDT_QCOMM", "0")
    collectives.refresh()
    exact = _run(_make_engine(checkpoint, token_parallel_size=2), PROMPTS,
                 "xtknp", logprobs=2)
    base = _run(_make_engine(checkpoint), PROMPTS, "base-lp")
    assert [ids for ids, _ in exact] == base
    for (ids, lps), q_ids in zip(exact, got):
        for i, (tok, q_tok) in enumerate(zip(ids, q_ids)):
            if tok != q_tok:
                assert q_tok in lps[i], (i, tok, q_tok, lps[i])
                assert lps[i][tok] - lps[i][q_tok] < _TIE_BOUND, (
                    i, tok, q_tok, lps[i])
                break  # past a flip the sequences differ by construction


def test_tp_engine_greedy_parity(checkpoint, baseline,
                                 qcomm_fine_block):
    got = _run(_make_engine(checkpoint, tensor_parallel_size=2), PROMPTS,
               "qtp")
    assert got == baseline


def test_tp_engine_qcomm_off_reverts(checkpoint, baseline, monkeypatch):
    """VDT_QCOMM=1 with the tp path excluded keeps the GSPMD reduce:
    byte-identical greedy to the stock engine."""
    monkeypatch.setenv("VDT_QCOMM", "1")
    monkeypatch.setenv("VDT_QCOMM_PATHS", "ep")
    collectives.refresh()
    got = _run(_make_engine(checkpoint, tensor_parallel_size=2), PROMPTS,
               "qtpoff")
    assert got == baseline
