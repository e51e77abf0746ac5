"""Pallas MLA (latent MQA) kernel vs the XLA reference path
(ops/pallas_mla.py vs ops/mla.ragged_latent_attention), interpret mode."""

import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

from vllm_distributed_tpu.ops.mla import ragged_latent_attention
from vllm_distributed_tpu.ops.pallas_mla import \
    ragged_latent_attention_pallas
from vllm_distributed_tpu.parallel import mesh as mesh_state


@pytest.mark.parametrize("max_q", [1, 8])
def test_kernel_matches_xla_reference(max_q):
    rng = np.random.default_rng(0)
    N, Lkv, R_dim, PS = 4, 32, 8, 8
    num_pages, PPR = 16, 4
    L = 2
    layer = 1
    kdim = Lkv + R_dim

    # Two sequences: a decode row and (for max_q=8) a prefill chunk.
    if max_q == 1:
        runs = [(0, 1, 13, 0), (1, 1, 7, 1)]   # (q_start, q_len, kv, row)
        T = 2
    else:
        runs = [(0, 6, 14, 0), (6, 1, 9, 1)]
        T = 7
    T_pad = T + max_q

    c_pages = jnp.asarray(
        rng.standard_normal((L, num_pages, PS, kdim)).astype(np.float32))
    bt = np.zeros((4, PPR), np.int32)
    bt[0, :PPR] = [3, 5, 7, 9]
    bt[1, :PPR] = [2, 4, 6, 8]
    ql = rng.standard_normal((T_pad, N, Lkv)).astype(np.float32)
    qpe = rng.standard_normal((T_pad, N, R_dim)).astype(np.float32)

    req_idx, q_pos = [], []
    for (qs, qlen, kv, row) in runs:
        for j in range(qlen):
            req_idx.append(row)
            q_pos.append(kv - qlen + j)
    want = ragged_latent_attention(
        jnp.asarray(ql[:T]), jnp.asarray(qpe[:T]), c_pages[layer],
        jnp.asarray(bt), jnp.asarray(req_idx, jnp.int32),
        jnp.asarray(q_pos, jnp.int32), sm_scale=0.25,
        kv_lora_rank=Lkv, rope_dim=R_dim)

    seq_info = np.zeros((4, 4), np.int32)
    for i, r in enumerate(runs):
        seq_info[i] = r
    qc = jnp.concatenate([jnp.asarray(ql), jnp.asarray(qpe)], axis=-1)
    got = ragged_latent_attention_pallas(
        qc, c_pages, jnp.asarray(seq_info),
        jnp.asarray([len(runs)], jnp.int32), jnp.asarray(bt),
        jnp.asarray([layer], jnp.int32), sm_scale=0.25, max_q=max_q,
        kv_lora_rank=Lkv, rope_dim=R_dim, interpret=True)

    np.testing.assert_allclose(np.asarray(got[:T, :, :Lkv]),
                               np.asarray(want), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------
# A chunk-bearing step: the multi-head form for the rows of a chunk, the
# absorbed form over the decoding rows (ops/mla.latent_attention_values)
# ---------------------------------------------------------------------
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PS = 8


def published_dims(name):
    """(heads, kv_lora_rank, no-rope, rope, V) of a benchmark
    configuration, from its file."""
    with open(os.path.join(REPO, "benchmarks", "configs",
                           f"{name}.json")) as f:
        cf = json.load(f)
    return (cf["num_attention_heads"], cf["kv_lora_rank"],
            cf["qk_nope_head_dim"], cf["qk_rope_head_dim"],
            cf["v_head_dim"])


DIMS = {
    "toy": (4, 32, 16, 8, 24),
    "gigachat3-702b-ep16": published_dims("gigachat3-702b-ep16"),
    "ling3-flash-ep4": published_dims("ling3-flash-ep4"),
}
# (q_len, kv_len) of each sequence, in batch order.
STEPS = {
    # Decoding rows, a fresh prompt, a later chunk of a request whose
    # earlier chunks are in the pages; padding rows follow.
    "mixed": [(1, 13), (11, 11), (1, 30), (9, 27), (1, 1)],
    "chunk_ends_on_a_page_boundary": [(1, 5), (10, 3 * PS)],
    "chunk_ends_inside_a_page": [(13, 3 * PS + 3), (1, 9)],
    "two_query_rows": [(2, 19), (1, 19), (2, 2)],
    "no_decoding_row": [(7, 7), (5, 21)],
}


def chunk_step(dims, runs, max_q, rng, rows=8, layers=2):
    """Random queries, weights and pages of a step of ``runs``, and the
    batch the runner would hand the model for it."""
    from vllm_distributed_tpu.models.common import AttentionBatch
    N, Lkv, Pn, R, V = dims
    ppr = 4
    T = sum(q for q, _ in runs)
    # One bucket of token rows for every step of ``STEPS``: what differs
    # between them is then data, and a configuration's steps run the
    # programs its first one compiled.
    T_pad = 48
    assert T + min(max_q, 128) <= T_pad
    pages = rng.permutation(rows * ppr) + 1
    bt = np.zeros((rows, ppr), np.int32)
    seq_info = np.zeros((rows, 4), np.int32)
    req_idx = np.zeros(T_pad, np.int32)
    pos = np.zeros(T_pad, np.int32)
    slot = np.full(T_pad, -1, np.int32)
    t = 0
    for i, (q_len, kv_len) in enumerate(runs):
        row = rows - 1 - i  # batch rows need not follow batch order
        bt[row] = pages[i * ppr:(i + 1) * ppr]
        seq_info[i] = (t, q_len, kv_len, row)
        p = np.arange(kv_len - q_len, kv_len)
        req_idx[t:t + q_len], pos[t:t + q_len] = row, p
        slot[t:t + q_len] = bt[row, p // PS] * PS + p % PS
        t += q_len
    f32 = lambda *s: jnp.asarray(  # noqa: E731
        rng.standard_normal(s).astype(np.float32))
    batch = AttentionBatch(
        req_idx=jnp.asarray(req_idx), positions=jnp.asarray(pos),
        slot_mapping=jnp.asarray(slot), block_tables=jnp.asarray(bt),
        seq_lens=jnp.zeros((rows, ), jnp.int32),
        seq_info=jnp.asarray(seq_info),
        num_seqs=jnp.asarray([len(runs)], jnp.int32), max_q=max_q)
    return dict(q_nope=f32(T_pad, N, Pn), q_pe=f32(T_pad, N, R),
                c_all=f32(layers, rows * ppr + 1, PS, Lkv + R),
                w_uk=f32(Lkv, N, Pn) / Lkv**0.5,
                w_uv=f32(Lkv, N, V) / Lkv**0.5, batch=batch, T=T)


def values(step, layer, **dims):
    from vllm_distributed_tpu.ops.mla import latent_attention_values
    return latent_attention_values(
        step["q_nope"], step["q_pe"], step["c_all"], step["batch"],
        step["w_uk"], step["w_uv"], sm_scale=0.2,
        layer=jnp.asarray([layer], jnp.int32), **dims)


@pytest.mark.parametrize("dims", list(DIMS))
@pytest.mark.parametrize("runs", list(STEPS))
def test_chunk_step_matches_the_absorbed_reference(dims, runs,
                                                   monkeypatch):
    """The mixed program on the kernels (interpret) against the XLA scan
    in the absorbed form, both float32: every live row to rounding, every
    padding row zero."""
    N, Lkv, Pn, R, V = DIMS[dims]
    step = chunk_step(DIMS[dims], STEPS[runs], 16,
                      np.random.default_rng(3))
    kw = dict(kv_lora_rank=Lkv, rope_dim=R)
    # One chip: no mesh of an engine that another test file started.
    monkeypatch.setattr(mesh_state, "_GLOBAL_MESH", None)
    monkeypatch.setenv("VDT_ATTENTION_BACKEND", "xla")
    want = values(step, 1, **kw)
    monkeypatch.setenv("VDT_ATTENTION_BACKEND", "pallas")
    got = values(step, 1, **kw)
    T = step["T"]
    assert got.shape == (step["q_nope"].shape[0], N, V)
    np.testing.assert_allclose(got[:T], want[:T], rtol=2e-4, atol=2e-5)
    assert not np.asarray(got[T:]).any()
    # The other layer's pages hold other rows: the layer index is read.
    assert float(jnp.abs(values(step, 0, **kw)[:T] - want[:T]).max()) > 1e-2


@pytest.mark.parametrize("max_q,ctx,q_start,q_len,kv_len", [
    (16, 100, 0, 5, 37), (48, 300, 21, 33, 290), (600, 700, 7, 600, 600),
    (1040, 1100, 530, 520, 1000)])
def test_prefill_kernel_matches_dense_softmax(max_q, ctx, q_start, q_len,
                                              kv_len):
    """``mla_latent_prefill`` alone, on a sequence that starts anywhere
    among the step's rows: tiles past the chunk's end and blocks past a
    tile's last position are skipped, the diagonal's blocks masked, over
    one tile and over several; the rows around the sequence's keep what
    they held."""
    from vllm_distributed_tpu.ops.pallas_mla import (
        latent_prefill_attention_pallas, prefill_blocks)
    rng = np.random.default_rng(0)
    N, Pn, R, V = 3, 16, 8, 24
    bq, _, S = prefill_blocks(max_q, ctx)
    T = q_start + q_len + 9 + bq
    qn, qp = rng.standard_normal((T, N, Pn)), rng.standard_normal((T, N, R))
    kn, kp = rng.standard_normal((S, N, Pn)), rng.standard_normal((S, R))
    v, held = rng.standard_normal((S, N, V)), rng.standard_normal((T, N, V))
    got = latent_prefill_attention_pallas(
        *(jnp.asarray(a, jnp.float32).reshape(a.shape[0], -1)
          for a in (qn, qp, kn, kp, v, held)),
        jnp.asarray([q_start, q_len, kv_len], jnp.int32), num_heads=N,
        max_q=max_q, sm_scale=0.2, interpret=True).reshape(T, N, V)
    mine = slice(q_start, q_start + q_len)
    s = 0.2 * (np.einsum("qnp,snp->nqs", qn[mine], kn) +
               np.einsum("qnr,sr->nqs", qp[mine], kp))
    seen = (np.arange(S)[None, :] <=
            (kv_len - q_len + np.arange(q_len))[:, None])
    s = np.where(seen[None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = held.copy()
    want[mine] = np.einsum("nqs,snv->qnv", p / p.sum(-1, keepdims=True), v)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
