"""The carried rows of compressed convolutional attention
(``models/zaya.carried_latents``: ``ops/conv1d.py``'s kernel twice, two
taps) against the whole-sequence convolutions: sequences walked over
several steps in chunks of every length, beside decoding rows and
padding, each step starting from the rows the last one left."""

import jax.numpy as jnp
import numpy as np
import pytest

from test_mamba_ops import _make_seg
from vllm_distributed_tpu.models.zaya import carried_latents
from vllm_distributed_tpu.ops.conv1d import (rows_shape, step_entries,
                                             whole_tiles)

S = 6                                  # state rows; row 4 never used
# Row -> the lengths of its chunks, step by step (0: not scheduled).
WALKS = {
    0: [7, 1, 1, 1, 2],                # a prompt, decode, a 2-token chunk
    1: [0, 1, 1, 5, 1],                # starts later, token by token
    2: [1, 1, 1, 1, 1],                # one token a step from the start
    3: [3, 0, 9, 0, 1],                # idle steps between chunks
    5: [0, 0, 12, 0, 0],               # one whole chunk in the middle
}


def whole_sequence(u, vb, w0, b0):
    """c0, c0 at the token before and vb at the token before, float32,
    of one sequence ``[n, C]`` with two zeros up front on ``u``."""
    past = np.concatenate([np.zeros((2, u.shape[1]), np.float32), u])
    c0 = w0[0] * past[:-1] + w0[1] * past[1:] + b0     # positions -1..n-1
    vb_prev = np.concatenate([np.zeros_like(vb[:1]), vb[:-1]])
    return c0[1:], c0[:-1], vb_prev


@pytest.mark.parametrize("C,half,dtype", [
    (1280, 128, jnp.bfloat16),         # the published widths
    (96, 16, jnp.float32),             # the CPU tests' toy
])
@pytest.mark.parametrize("decode_program", [False, True])
def test_rows_carry_what_the_whole_sequence_convolutions_read(
        C, half, dtype, decode_program):
    """Every step's c0_t, c0_{t-1} and vb_{t-1} equal the whole
    sequence's; a sequence's first token reads (0, b0, 0) whatever its
    row held; after its last chunk the rows hold its last token's
    ``u``, ``c0`` and ``vb``. As the program that picks the form at run
    time and, one token a row, as the decode program."""
    rng = np.random.default_rng(C)
    f32 = np.float32
    walks = {r: ([1] * 4 if decode_program else w)
             for r, w in WALKS.items()}
    total = {r: sum(w) for r, w in walks.items()}

    def held(a):                                       # as the dtype holds
        return np.asarray(jnp.asarray(a, dtype).astype(jnp.float32))

    u = {r: held(rng.normal(size=(n, C))) for r, n in total.items()}
    vb = {r: held(rng.normal(size=(n, half))) for r, n in total.items()}
    w0, b0 = held(rng.normal(size=(2, C))), held(rng.normal(size=(C, )))
    want = {r: whole_sequence(u[r], vb[r], w0, b0) for r in total}
    # Rows that held other sequences: garbage, not zeros.
    rows_u = jnp.asarray(rng.normal(size=rows_shape(2, S + 1, 2, C)), dtype)
    wide = whole_tiles(C + half)
    rows_cv = jnp.asarray(
        rng.normal(size=rows_shape(2, S + 1, 2, wide)), dtype)
    other = (np.asarray(rows_u[0].astype(jnp.float32)),
             np.asarray(rows_cv[0].astype(jnp.float32)))
    layer = jnp.full((1, ), 1, jnp.int32)
    done = {r: 0 for r in total}
    # c0 is held as the row holds it: one rounding to the dtype.
    tol = (dict(rtol=2 ** -8, atol=1e-3) if dtype == jnp.bfloat16
           else dict(rtol=0, atol=1e-5))
    for step in range(len(next(iter(walks.values())))):
        chunks = [(r, done[r], w[step]) for r, w in walks.items()
                  if w[step]]
        n = sum(c[2] for c in chunks)
        # One bucket for every step (at least three padding tokens): a
        # walk's steps then run one program, not one each.
        T = 27
        assert T >= n + 3
        seg = _make_seg(chunks, T, S)
        flat_u = np.zeros((T, C), f32)
        flat_vb = np.zeros((T, half), f32)
        t = 0
        for r, pos, q in chunks:
            flat_u[t:t + q] = u[r][pos:pos + q]
            flat_vb[t:t + q] = vb[r][pos:pos + q]
            t += q
        c0, c0_prev, vb_prev, rows_u, rows_cv = carried_latents(
            jnp.asarray(flat_u, dtype), jnp.asarray(flat_vb, dtype),
            jnp.asarray(w0, dtype), jnp.asarray(b0, dtype), rows_u,
            rows_cv, layer, seg,
            step_entries(seg, T, 2, decode_only=decode_program))
        got = [np.asarray(a.astype(jnp.float32))
               for a in (c0, c0_prev, vb_prev)]
        t = 0
        for r, pos, q in chunks:
            for g, w in zip(got, want[r]):
                np.testing.assert_allclose(g[t:t + q], w[pos:pos + q],
                                           **tol)
            if pos == 0:                               # a fresh row
                np.testing.assert_array_equal(got[1][t], b0)
                np.testing.assert_array_equal(got[2][t], 0.0)
            t += q
            done[r] = pos + q
        ru = np.asarray(rows_u[1].astype(jnp.float32)).reshape(S + 1, C)
        rcv = np.asarray(rows_cv[1].astype(jnp.float32)).reshape(
            S + 1, wide)
        for r, pos, q in chunks:
            last = pos + q - 1
            np.testing.assert_array_equal(ru[r], u[r][last])
            np.testing.assert_allclose(rcv[r, :C], want[r][0][last], **tol)
            np.testing.assert_array_equal(rcv[r, C:C + half], vb[r][last])
            np.testing.assert_array_equal(rcv[r, C + half:], 0.0)
    assert done == total
    # The other layer's rows were never touched.
    np.testing.assert_array_equal(
        np.asarray(rows_u[0].astype(jnp.float32)), other[0])
    np.testing.assert_array_equal(
        np.asarray(rows_cv[0].astype(jnp.float32)), other[1])


def test_the_rows_keep_the_layout_they_are_written_in():
    """1 280 values of ``u`` and 1 280 + 128 of ``c0`` and ``vb`` in the
    model's dtype a layer a sequence. Ten tiles of 128 lanes would come
    in with the layers on the sublanes (``tests/ops/test_tpu_compile.py``
    compiles the program), so ``u`` is two tiles of 640 and the second
    row, eleven tiles, is widened to twelve: four of 384."""
    assert rows_shape(16, 65, 2, 1280) == (16, 65, 1, 2, 640)
    assert whole_tiles(1408) == 1536 and whole_tiles(1280) == 1280
    assert rows_shape(16, 65, 2, 1536) == (16, 65, 1, 4, 384)
    assert (1280 + 1536) * 2 == 5632
    # What was served before keeps its shape.
    assert rows_shape(36, 57, 4, 4096) == (36, 57, 3, 32, 128)
    assert rows_shape(36, 57, 4, 256) == (36, 57, 3, 2, 128)
    assert rows_shape(6, 97, 4, 12288) == (6, 97, 3, 96, 128)
    assert whole_tiles(112) == 112 and whole_tiles(128) == 128
