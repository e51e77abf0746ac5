import jax.numpy as jnp
import numpy as np
import pytest
import torch
from tests.engine.test_llm_engine import checkpoint, make_engine
from vllm_distributed_tpu.models.common import AttentionBatch


def get_runner(engine):
    ex = engine.engine_core.model_executor if hasattr(
        engine.engine_core, "model_executor") else None
    if ex is None:
        for a in dir(engine.engine_core):
            o = getattr(engine.engine_core, a)
            if hasattr(o, "driver_worker") or "Executor" in type(o).__name__:
                ex = o
                break
    w = getattr(ex, "driver_worker", None) or getattr(ex, "worker", None)
    return w.model_runner


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_forward_matches_hf(checkpoint, monkeypatch, backend):
    """One prompt through ``model.forward`` on a hand-built batch, under
    each attention backend, against HF's logits for the same prompt."""
    path, hf = checkpoint
    prompt = [3, 17, 92, 45, 8]
    n = len(prompt)
    with torch.no_grad():
        want = hf(torch.tensor([prompt])).logits[0].numpy()
    monkeypatch.setenv("VDT_ATTENTION_BACKEND", backend)
    engine = make_engine(path, max_num_batched_tokens=16)
    r = get_runner(engine)
    ps = r.page_size
    T = 24  # same as runner: bucket16 + qtile 8
    max_q = 8
    token_ids = np.zeros((T,), np.int32); token_ids[:n] = prompt
    positions = np.zeros((T,), np.int32); positions[:n] = np.arange(n)
    req_idx = np.zeros((T,), np.int32)
    slot = np.full((T,), -1, np.int32)
    # pages 1..2 allocated to request row 0 (avoid page 0 to catch garbage)
    bt = np.zeros((r.max_num_reqs, r.max_pages_per_req), np.int32)
    bt[0, 0] = 1; bt[0, 1] = 2
    slot[:n] = bt[0, np.arange(n) // ps] * ps + np.arange(n) % ps
    seq_info = np.zeros((r.max_num_reqs, 4), np.int32)
    seq_info[0] = (0, n, n, 0)
    kv_runs = []
    consumed = 0
    while consumed < n:
        p = consumed
        off = p % ps
        run_len = min(ps - off, n - consumed)
        kv_runs.append((int(bt[0, p // ps]), off, consumed - off + ps, run_len))
        consumed += run_len
    G = 8
    kvr = np.zeros((G, 4), np.int32)
    kvr[:len(kv_runs)] = kv_runs
    batch = AttentionBatch(
        req_idx=jnp.asarray(req_idx), positions=jnp.asarray(positions),
        slot_mapping=jnp.asarray(slot), block_tables=jnp.asarray(bt),
        seq_lens=jnp.asarray(np.zeros((r.max_num_reqs,), np.int32)),
        seq_info=jnp.asarray(seq_info),
        num_seqs=jnp.asarray([1], np.int32),
        kv_runs=jnp.asarray(kvr),
        num_kv_runs=jnp.asarray([len(kv_runs)], np.int32),
        max_q=max_q)
    with r.mesh:
        hidden, _ = r.model.forward(r.params, r.kv_caches,
                                    jnp.asarray(token_ids), batch)
        got = np.asarray(r.model.compute_logits(r.params, hidden[:n]))
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)
