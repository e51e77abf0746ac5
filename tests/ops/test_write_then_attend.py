import functools
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from vllm_distributed_tpu.config import ParallelConfig
from vllm_distributed_tpu.models.common import AttentionBatch
from vllm_distributed_tpu.parallel.mesh import build_mesh, global_mesh
from vllm_distributed_tpu.ops.attention import (write_kv_cache,
                                                paged_attention,
                                                naive_ragged_attention)


def make(ps=4, n=5, L=2, N=16, KVH=2, QH=4, D=16, max_q=8, T=24,
         max_reqs=8, ppr=16):
    rng = np.random.default_rng(0)
    k_all = jnp.zeros((L, N, KVH, ps, D), jnp.float32)
    v_all = jnp.zeros((L, N, KVH, ps, D), jnp.float32)
    k_new = jnp.asarray(rng.standard_normal((T, KVH, D)), jnp.float32)
    v_new = jnp.asarray(rng.standard_normal((T, KVH, D)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((T, QH, D)), jnp.float32)
    bt = np.zeros((max_reqs, ppr), np.int32)
    bt[0, 0] = 1; bt[0, 1] = 2
    slot = np.full((T,), -1, np.int32)
    slot[:n] = bt[0, np.arange(n) // ps] * ps + np.arange(n) % ps
    seq_info = np.zeros((max_reqs, 4), np.int32)
    seq_info[0] = (0, n, n, 0)
    kv_runs = []
    consumed = 0
    while consumed < n:
        off = consumed % ps
        run_len = min(ps - off, n - consumed)
        kv_runs.append((int(bt[0, consumed // ps]), off,
                        consumed - off + ps, run_len))
        consumed += run_len
    kvr = np.zeros((8, 4), np.int32)
    kvr[:len(kv_runs)] = kv_runs
    positions = np.zeros((T,), np.int32); positions[:n] = np.arange(n)
    batch = AttentionBatch(
        req_idx=jnp.zeros((T,), jnp.int32),
        positions=jnp.asarray(positions),
        slot_mapping=jnp.asarray(slot), block_tables=jnp.asarray(bt),
        seq_lens=jnp.zeros((max_reqs,), jnp.int32),
        seq_info=jnp.asarray(seq_info),
        num_seqs=jnp.asarray([1], jnp.int32),
        kv_runs=jnp.asarray(kvr),
        num_kv_runs=jnp.asarray([len(kv_runs)], jnp.int32),
        max_q=max_q)
    return k_all, v_all, k_new, v_new, q, batch, n


@pytest.fixture()
def one_device_mesh():
    """The ops read the process-global mesh at trace time, and a worker
    leaves its own there (init_device): whatever engine test ran earlier
    in this pytest worker would decide whether these direct calls get
    wrapped in a shard_map over ITS model axis. Pin a one-device mesh."""
    with global_mesh(build_mesh(ParallelConfig(), jax.devices()[:1])):
        yield


def test_combo_jit(monkeypatch, one_device_mesh):
    monkeypatch.setenv("VDT_ATTENTION_BACKEND", "pallas")
    k_all, v_all, k_new, v_new, q, batch, n = make()
    layer = jnp.asarray([1], jnp.int32)

    def f(k_all, v_all, k_new, v_new, q):
        k_all, v_all = write_kv_cache(k_all, v_all, k_new, v_new, batch,
                                      layer)
        out = paged_attention(q, k_all, v_all, batch, sm_scale=0.125,
                              layer=layer)
        return out, k_all, v_all

    out, k2, v2 = jax.jit(f)(k_all, v_all, k_new, v_new, q)
    ref = naive_ragged_attention(
        q, k2[1], v2[1], batch.block_tables, batch.req_idx,
        batch.positions, sm_scale=0.125)
    got = np.asarray(out)[:n]
    want = np.asarray(ref)[:n]
    print("combo max diff:", np.abs(got - want).max())
    print("got row0:", got[0, 0, :4])
    print("want row0:", want[0, 0, :4])
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_combo_scan(monkeypatch, one_device_mesh):
    monkeypatch.setenv("VDT_ATTENTION_BACKEND", "pallas")
    k_all, v_all, k_new, v_new, q, batch, n = make()

    def layer_fn(carry, xs):
        k_all, v_all = carry
        layer = xs
        k_all, v_all = write_kv_cache(k_all, v_all, k_new, v_new, batch,
                                      layer)
        out = paged_attention(q, k_all, v_all, batch, sm_scale=0.125,
                              layer=layer)
        return (k_all, v_all), out

    def f(k_all, v_all):
        layer_ids = jnp.arange(2, dtype=jnp.int32)[:, None]
        (k2, v2), outs = jax.lax.scan(layer_fn, (k_all, v_all), layer_ids)
        return outs, k2, v2

    outs, k2, v2 = jax.jit(f)(k_all, v_all)
    for l in range(2):
        ref = naive_ragged_attention(
            q, k2[l], v2[l], batch.block_tables, batch.req_idx,
            batch.positions, sm_scale=0.125)
        got = np.asarray(outs[l])[:n]
        want = np.asarray(ref)[:n]
        print(f"layer {l} max diff:", np.abs(got - want).max(),
              "finite:", np.isfinite(got).all())
