"""Unit tests for the ragged segmented-scan Mamba ops.

Strategy (SURVEY.md §4 kernel tests): build a ragged batch of chunks —
fresh prefills, resumed chunks with carried state, single-token decodes,
padding — and check the flat segmented ops against a per-request
sequential numpy recurrence.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vllm_distributed_tpu.ops.mamba import (SegmentInfo,
                                            build_segment_info,
                                            causal_conv1d_ragged,
                                            segmented_linear_scan,
                                            selective_scan_ragged)


def _make_seg(chunks, T, S):
    """chunks: list of (row, chunk_start_pos, q_len). Flat tokens are
    laid out contiguously in order; the tail up to T is padding."""
    row = np.full((T, ), S, np.int32)
    valid = np.zeros((T, ), bool)
    off = np.zeros((T, ), np.int32)
    start = np.zeros((T, ), bool)
    end = np.zeros((T, ), bool)
    has_init = np.zeros((T, ), bool)
    q_len_by_row = np.zeros((S + 1, ), np.int32)
    q_start_by_row = np.zeros((S + 1, ), np.int32)
    has_init_by_row = np.zeros((S + 1, ), bool)
    t = 0
    for r, pos0, n in chunks:
        row[t:t + n] = r
        valid[t:t + n] = True
        off[t:t + n] = np.arange(n)
        start[t] = True
        end[t + n - 1] = True
        has_init[t:t + n] = pos0 > 0
        q_len_by_row[r] = n
        q_start_by_row[r] = t
        has_init_by_row[r] = pos0 > 0
        t += n
    return SegmentInfo(
        row=jnp.asarray(row), valid=jnp.asarray(valid),
        off=jnp.asarray(off), start=jnp.asarray(start),
        end=jnp.asarray(end), has_init=jnp.asarray(has_init),
        q_len_by_row=jnp.asarray(q_len_by_row),
        q_start_by_row=jnp.asarray(q_start_by_row),
        has_init_by_row=jnp.asarray(has_init_by_row))


CHUNKS = [(2, 0, 5), (0, 7, 3), (4, 1, 1), (1, 0, 1)]  # mixed batch
T, S = 16, 6


def test_segmented_linear_scan_matches_loop():
    rng = np.random.default_rng(0)
    a = rng.uniform(0.5, 1.0, (T, 3)).astype(np.float32)
    b = rng.normal(size=(T, 3)).astype(np.float32)
    reset = np.zeros((T, ), bool)
    reset[[0, 5, 9]] = True
    h = segmented_linear_scan(jnp.asarray(a), jnp.asarray(b),
                              jnp.asarray(reset))
    expect = np.zeros_like(b)
    carry = np.zeros((3, ), np.float32)
    for t in range(T):
        if reset[t]:
            carry = np.zeros((3, ), np.float32)
        carry = a[t] * carry + b[t]
        expect[t] = carry
    np.testing.assert_allclose(np.asarray(h), expect, rtol=1e-5,
                               atol=1e-5)


def test_causal_conv1d_ragged_matches_sequential():
    rng = np.random.default_rng(1)
    Di, K = 4, 4
    seg = _make_seg(CHUNKS, T, S)
    x = rng.normal(size=(T, Di)).astype(np.float32)
    w = rng.normal(size=(K, Di)).astype(np.float32)
    bias = rng.normal(size=(Di, )).astype(np.float32)
    conv_state = rng.normal(size=(S + 1, K - 1, Di)).astype(np.float32)

    y, new_state = causal_conv1d_ragged(
        jnp.asarray(x), jnp.asarray(w), jnp.asarray(bias),
        jnp.asarray(conv_state), seg)
    y, new_state = np.asarray(y), np.asarray(new_state)

    t = 0
    for r, pos0, n in CHUNKS:
        # Sequential reference: full input history for the chunk is
        # [carried (or zeros), chunk tokens].
        hist = (conv_state[r] if pos0 > 0 else
                np.zeros((K - 1, Di), np.float32))
        buf = np.concatenate([hist, x[t:t + n]], axis=0)
        for i in range(n):
            want = bias + sum(w[k] * buf[i + k] for k in range(K))
            np.testing.assert_allclose(y[t + i], want, rtol=1e-5,
                                       atol=1e-5)
        np.testing.assert_allclose(new_state[r], buf[n:n + K - 1],
                                   rtol=1e-6, atol=1e-6)
        t += n
    # Inactive rows keep their carried state.
    np.testing.assert_allclose(new_state[3], conv_state[3])


def test_selective_scan_ragged_matches_sequential():
    rng = np.random.default_rng(2)
    Di, N = 6, 4
    seg = _make_seg(CHUNKS, T, S)
    x = rng.normal(size=(T, Di)).astype(np.float32)
    dt = rng.uniform(0.01, 0.5, (T, Di)).astype(np.float32)
    A = -rng.uniform(0.1, 1.0, (Di, N)).astype(np.float32)
    B = rng.normal(size=(T, N)).astype(np.float32)
    C = rng.normal(size=(T, N)).astype(np.float32)
    D = rng.normal(size=(Di, )).astype(np.float32)
    ssm_state = rng.normal(size=(S + 1, Di, N)).astype(np.float32)

    y, new_state = selective_scan_ragged(
        jnp.asarray(x), jnp.asarray(dt), jnp.asarray(A), jnp.asarray(B),
        jnp.asarray(C), jnp.asarray(D), jnp.asarray(ssm_state), seg)
    y, new_state = np.asarray(y), np.asarray(new_state)

    t = 0
    for r, pos0, n in CHUNKS:
        h = (ssm_state[r].copy() if pos0 > 0 else
             np.zeros((Di, N), np.float32))
        for i in range(n):
            a = np.exp(dt[t + i][:, None] * A)
            h = a * h + (dt[t + i] * x[t + i])[:, None] * B[t + i][None]
            want = h @ C[t + i] + D * x[t + i]
            np.testing.assert_allclose(y[t + i], want, rtol=1e-4,
                                       atol=1e-4)
        np.testing.assert_allclose(new_state[r], h, rtol=1e-4, atol=1e-4)
        t += n
    np.testing.assert_allclose(new_state[3], ssm_state[3])


def test_build_segment_info_from_attention_batch():
    from vllm_distributed_tpu.models.common import AttentionBatch
    # Two chunks: row 1 resumed at pos 4 (3 tokens), row 0 fresh decode
    # at pos 0 (1 token); 2 padding tokens.
    max_reqs = 4
    req_idx = jnp.asarray([1, 1, 1, 0, 0, 0], jnp.int32)
    positions = jnp.asarray([4, 5, 6, 0, 0, 0], jnp.int32)
    slot = jnp.asarray([8, 9, 10, 0, -1, -1], jnp.int32)
    seq_info = jnp.zeros((max_reqs, 4), jnp.int32)
    seq_info = seq_info.at[0].set(jnp.asarray([0, 3, 7, 1]))
    seq_info = seq_info.at[1].set(jnp.asarray([3, 1, 1, 0]))
    batch = AttentionBatch(
        req_idx=req_idx, positions=positions, slot_mapping=slot,
        block_tables=jnp.zeros((max_reqs, 2), jnp.int32),
        seq_lens=jnp.zeros((max_reqs, ), jnp.int32),
        seq_info=seq_info, num_seqs=jnp.asarray([2], jnp.int32))
    seg = build_segment_info(batch, max_reqs)
    np.testing.assert_array_equal(np.asarray(seg.row),
                                  [1, 1, 1, 0, 4, 4])
    np.testing.assert_array_equal(np.asarray(seg.valid),
                                  [True, True, True, True, False, False])
    np.testing.assert_array_equal(np.asarray(seg.off)[:4], [0, 1, 2, 0])
    np.testing.assert_array_equal(np.asarray(seg.start)[:4],
                                  [True, False, False, True])
    np.testing.assert_array_equal(np.asarray(seg.end)[:4],
                                  [False, False, True, True])
    np.testing.assert_array_equal(np.asarray(seg.has_init)[:4],
                                  [True, True, True, False])
    assert int(seg.q_len_by_row[1]) == 3
    assert int(seg.q_len_by_row[0]) == 1
