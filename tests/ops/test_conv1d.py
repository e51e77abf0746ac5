"""``ops/conv1d.py`` against its oracle, ``ops/mamba.causal_conv1d_ragged``
on the layer's rows, in interpret mode at the widths that serve (the
kernel's compile for the chip is ``tests/ops/test_tpu_compile.py``'s)."""

import jax.numpy as jnp
import numpy as np
import pytest

from test_mamba_ops import _make_seg
from vllm_distributed_tpu.ops.conv1d import (causal_conv1d_rows, rows_shape,
                                             step_entries)
from vllm_distributed_tpu.ops.mamba import causal_conv1d_ragged

K = 4
# Granite's x and its B and C (a bias each), Ling's q, k and v (none).
WIDTHS = [(4096, True), (256, True), (12288, False)]
# (row, position of the chunk's first token, tokens)
DECODES = [(5, 9, 1), (0, 1, 1), (3, 700, 1), (6, 2, 1)]
# Decoding rows beside a fresh prompt and resumed chunks of 1, 2, 3 and
# 300 tokens: shorter than, equal to and longer than K - 1.
MIXED = [(5, 9, 1), (2, 0, 7), (0, 4, 2), (7, 11, 3), (3, 700, 1),
         (1, 64, 300), (6, 0, 2)]
S = 9                       # rows 4 and 8 never hold a segment


def run(chunks, T, width, bias, decode_only=False, layer=1,
        dtype=jnp.bfloat16):
    rng = np.random.default_rng(width + len(chunks))
    f32 = jnp.float32
    seg = _make_seg(chunks, T, S)
    x = jnp.asarray(rng.normal(size=(T, width)), dtype)
    w = jnp.asarray(rng.normal(size=(K, width)), dtype)
    b = jnp.asarray(rng.normal(size=(width, )), dtype) if bias else None
    flat = jnp.asarray(rng.normal(size=(2, S + 1, K - 1, width)), dtype)
    want_y, want_rows = causal_conv1d_ragged(
        x.astype(f32), w.astype(f32), None if b is None else b.astype(f32),
        flat[layer], seg)
    y, stack = causal_conv1d_rows(
        x, w, b, flat.reshape(rows_shape(2, S + 1, K, width)),
        jnp.full((1, ), layer, jnp.int32), seg,
        step_entries(seg, T, K, decode_only))
    n = sum(c[2] for c in chunks)
    np.testing.assert_allclose(np.asarray(y)[:n], np.asarray(want_y)[:n],
                               rtol=1e-5, atol=1e-5)
    assert stack.shape == rows_shape(2, S + 1, K, width)
    got = np.asarray(stack.reshape(flat.shape).astype(f32))
    before = np.asarray(flat.astype(f32))
    live = [r for r, _, _ in chunks]
    # A row is its chunk's last K - 1 inputs, bit for bit what the
    # oracle stores.
    np.testing.assert_array_equal(
        got[layer][live], np.asarray(want_rows.astype(dtype).astype(f32))[live])
    # Rows without a segment and the other layer are not touched.
    idle = [r for r in range(S) if r not in live]
    np.testing.assert_array_equal(got[layer][idle], before[layer][idle])
    np.testing.assert_array_equal(got[1 - layer], before[1 - layer])


@pytest.mark.parametrize("width,bias", WIDTHS)
@pytest.mark.parametrize("decode_only", [True, False])
def test_decode_batch_with_padding_slots(width, bias, decode_only):
    """One token a row, four of nine rows live, four padding tokens: as a
    decode program (no longer entries in it) and as the program that
    picks at run time."""
    run(DECODES, 8, width, bias, decode_only)


@pytest.mark.parametrize("width,bias", WIDTHS)
def test_mixed_step_reaches_back_into_the_old_row(width, bias):
    run(MIXED, 320, width, bias)


@pytest.mark.parametrize("layer", [0, 1])
def test_float32_rows_and_either_layer(layer):
    """A float32 model (the CPU tests' dtype) at a toy width that is no
    multiple of 128, on either layer of the stack."""
    run(MIXED, 320, 24, True, layer=layer, dtype=jnp.float32)


def test_rows_shape_keeps_a_row_s_inputs_in_whole_tiles():
    assert rows_shape(36, 57, 4, 4096) == (36, 57, 3, 32, 128)
    assert rows_shape(36, 57, 4, 256) == (36, 57, 3, 2, 128)
    assert rows_shape(6, 97, 4, 12288) == (6, 97, 3, 96, 128)
    # A toy width still divides over a mesh of 2, 4 or 8.
    assert rows_shape(2, 9, 4, 64) == (2, 9, 3, 8, 8)
    assert rows_shape(2, 9, 4, 20) == (2, 9, 3, 5, 4)


def test_entries_hold_every_head_token_once():
    """The longer list: a segment's first min(q_len, K - 1) tokens, live
    entries first; the short list: a row each."""
    T = 320
    entries = step_entries(_make_seg(MIXED, T, S), T, K)
    assert bool(entries["any_longer"])
    row, tok, _, code = (np.asarray(a) for a in entries["longer"])
    live = (code & 1) == 1
    starts, t = {}, 0
    for r, _, n in MIXED:
        starts[r] = (t, n)
        t += n
    want = sorted((r, t0 + h) for r, (t0, n) in starts.items()
                  for h in range(min(n, K - 1)))
    assert sorted(zip(row[live].tolist(), tok[live].tolist())) == want
    assert not live[np.argmin(live):].any()          # live ones first
    assert (row[~live] == S).all() and (tok[~live] == T).all()
    row, tok, _, code = (np.asarray(a) for a in entries["one"])
    live = (code & 1) == 1
    assert sorted(row[live].tolist()) == sorted(starts)
    assert not bool(step_entries(_make_seg(DECODES, 8, S), 8, K)["any_longer"])
    assert set(step_entries(_make_seg(DECODES, 8, S), 8, K, True)) == {"one"}
