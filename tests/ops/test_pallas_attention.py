"""Pallas ragged paged attention kernel vs the dense reference.

Runs the kernel in interpret mode on CPU (reference test strategy: CPU/
interpret-mode Pallas path for kernel tests, SURVEY.md §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vllm_distributed_tpu.ops.attention import naive_ragged_attention
from vllm_distributed_tpu.ops.pallas_attention import (
    ragged_paged_attention_pallas)


def build_case(rng, *, seqs, page_size, pages_per_req, num_q_heads,
               num_kv_heads, head_dim, max_q, dtype=jnp.float32,
               t_bucket=None, max_reqs=None):
    """seqs: list of (q_len, kv_len) with kv_len >= q_len. ``t_bucket``
    (token rows) and ``max_reqs`` (table rows) pad the arrays as the
    runner's buckets do: cases that pass the same pair, geometry and
    ``max_q`` run one compiled program, and the interpreted kernel's
    lowering is all a case costs (tests/ops/test_unified_attention.py's
    docstring). Left out, they fit the case."""
    R = len(seqs)
    max_reqs = max_reqs or R + 1  # at least one inactive padding row
    assert max_reqs > R
    num_pages = max_reqs * pages_per_req
    T = sum(q for q, _ in seqs)
    bq = min(max_q, 128)
    T_pad = (t_bucket or T) + bq
    assert T_pad >= T + bq

    k_pages = jnp.asarray(rng.standard_normal(
        (num_pages, num_kv_heads, page_size, head_dim)), dtype)
    v_pages = jnp.asarray(rng.standard_normal(
        (num_pages, num_kv_heads, page_size, head_dim)), dtype)
    q = jnp.asarray(rng.standard_normal((T_pad, num_q_heads, head_dim)),
                    dtype)

    # Page tables: request r owns pages [r*P, (r+1)*P).
    bt = np.zeros((max_reqs, pages_per_req), np.int32)
    for r in range(max_reqs):
        bt[r] = np.arange(r * pages_per_req, (r + 1) * pages_per_req)

    seq_info = np.zeros((max_reqs, 4), np.int32)
    req_idx = np.zeros((T_pad, ), np.int32)
    q_pos = np.zeros((T_pad, ), np.int32)
    t = 0
    for r, (q_len, kv_len) in enumerate(seqs):
        seq_info[r] = (t, q_len, kv_len, r)
        req_idx[t:t + q_len] = r
        q_pos[t:t + q_len] = np.arange(kv_len - q_len, kv_len)
        t += q_len

    return dict(
        q=q, k_pages=k_pages, v_pages=v_pages,
        seq_info=jnp.asarray(seq_info),
        num_seqs=jnp.asarray([R], jnp.int32),
        block_tables=jnp.asarray(bt),
        req_idx=jnp.asarray(req_idx), q_pos=jnp.asarray(q_pos),
        T=T, max_q=max_q,
    )


def run_both(case, sm_scale=0.125):
    out_pallas = ragged_paged_attention_pallas(
        case["q"], case["k_pages"], case["v_pages"], case["seq_info"],
        case["num_seqs"], case["block_tables"], sm_scale=sm_scale,
        max_q=case["max_q"], interpret=True)
    out_ref = naive_ragged_attention(
        case["q"], case["k_pages"], case["v_pages"], case["block_tables"],
        case["req_idx"], case["q_pos"], sm_scale=sm_scale)
    T = case["T"]
    return np.asarray(out_pallas)[:T], np.asarray(out_ref)[:T]


def float32_reference(case):
    """The dense reference on float32 copies of the case's inputs."""
    want = naive_ragged_attention(
        *(case[k].astype(jnp.float32) for k in ("q", "k_pages", "v_pages")),
        case["block_tables"], case["req_idx"], case["q_pos"], sm_scale=0.125)
    return np.asarray(want)[:case["T"]]


# One geometry and one bucket (32 token rows, 12 table rows) for the
# cases that differ only in their lengths: one program a ``max_q``.
BUCKET = dict(page_size=8, pages_per_req=4, num_q_heads=8, num_kv_heads=4,
              head_dim=128, t_bucket=32, max_reqs=12)


@pytest.mark.parametrize("seqs,max_q", [
    # Pure decode: one token per sequence, varying kv lens.
    ([(1, 1), (1, 5), (1, 17), (1, 32)], 1),
    # Pure prefill from scratch.
    ([(7, 7), (16, 16), (3, 3)], 16),
    # Chunked prefill: later chunk attends earlier kv.
    ([(8, 24), (4, 9)], 8),
    # Mixed prefill + decode.
    ([(1, 13), (12, 12), (1, 30), (5, 21)], 16),
])
def test_matches_reference(seqs, max_q):
    rng = np.random.default_rng(0)
    case = build_case(rng, seqs=seqs, max_q=max_q, **BUCKET)
    got, want = run_both(case)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("kvh", [1, 2, 8])
def test_gqa_group_and_mha(kvh):
    rng = np.random.default_rng(1)
    case = build_case(rng, seqs=[(3, 11), (1, 4)], page_size=8,
                      pages_per_req=4, num_q_heads=8, num_kv_heads=kvh,
                      head_dim=128, max_q=8)
    got, want = run_both(case)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_multi_q_tile_long_prefill():
    """q_len spanning several q tiles (bq < max_q would need max_q > 128;
    here exercise several kv blocks + full tile boundary instead)."""
    rng = np.random.default_rng(2)
    case = build_case(rng, seqs=[(32, 32), (32, 48)], page_size=8,
                      pages_per_req=8, num_q_heads=4, num_kv_heads=4,
                      head_dim=128, max_q=32)
    got, want = run_both(case)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_batched_decode_many_seqs():
    """The SB-batched decode kernel: enough sequences for several grid
    programs, ragged kv lens, an sb that does not divide num_seqs."""
    rng = np.random.default_rng(7)
    seqs = [(1, k) for k in (1, 5, 17, 32, 9, 25, 13, 2, 31, 8, 20)]
    case = build_case(rng, seqs=seqs, max_q=1, **BUCKET)
    got, want = run_both(case)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_batched_decode_scattered_q_start():
    """Decode rows addressed through q_start, not the run index — the
    layout token parallelism's per-rank compacted seq lists produce."""
    rng = np.random.default_rng(8)
    case = build_case(rng, seqs=[(1, 7), (1, 19), (1, 3)], max_q=1,
                      **BUCKET)
    # Scatter the three queries to rows 3, 0, 2 of the token array.
    si = np.asarray(case["seq_info"]).copy()
    perm = [3, 0, 2]
    q_old = np.asarray(case["q"])
    q_new = np.zeros_like(q_old)
    req_idx = np.full((q_old.shape[0], ), len(perm), np.int32)
    q_pos = np.zeros((q_old.shape[0], ), np.int32)
    for r, row in enumerate(perm):
        q_new[row] = q_old[si[r, 0]]
        si[r, 0] = row
        req_idx[row] = r
        q_pos[row] = si[r, 2] - 1
    out = ragged_paged_attention_pallas(
        jnp.asarray(q_new), case["k_pages"], case["v_pages"],
        jnp.asarray(si), case["num_seqs"], case["block_tables"],
        sm_scale=0.125, max_q=1, interpret=True)
    want = naive_ragged_attention(
        jnp.asarray(q_new), case["k_pages"], case["v_pages"],
        case["block_tables"], jnp.asarray(req_idx), jnp.asarray(q_pos),
        sm_scale=0.125)
    got = np.asarray(out)
    want = np.asarray(want)
    for row in perm:
        np.testing.assert_allclose(got[row], want[row], rtol=2e-3,
                                   atol=2e-3)


def test_inactive_rows_and_bf16():
    rng = np.random.default_rng(3)
    case = build_case(rng, seqs=[(1, 9), (1, 3)], page_size=8,
                      pages_per_req=2, num_q_heads=4, num_kv_heads=2,
                      head_dim=128, max_q=1, dtype=jnp.bfloat16)
    got, _ = run_both(case)
    # Against float32 math on the same bf16 inputs: the kernel's own
    # rounding, not the reference's.
    np.testing.assert_allclose(np.float32(got), float32_reference(case),
                               rtol=3e-2, atol=3e-2)
