"""``ops/ssd.py``: the chunked form, the recurrent form and a plain
token-by-token recurrence agree on ragged mixes of segments, at a tiny
size, float32, in interpret mode (the kernels' compile for the chip is
``tests/ops/test_tpu_compile.py``'s)."""

import jax.numpy as jnp
import numpy as np
import pytest

from test_mamba_ops import _make_seg
from vllm_distributed_tpu.ops import ssd

Q = 8                      # the tiny preset's mamba_chunk_size
HM, P, N = 4, 16, 16


def sequential(chunks, x, dt, A, B, C, D, state):
    """Per request, token by token: (y [T, Hm, P], the rows' new state
    ``[S + 1, N, Hm * P]``)."""
    Hm, Pd = x.shape[1:]
    rep = Hm // B.shape[1]
    y = np.zeros(x.shape, np.float64)
    new = state.astype(np.float64).copy()
    t = 0
    for r, pos0, n in chunks:
        h = (new[r].reshape(-1, Hm, Pd).transpose(1, 2, 0).copy() if pos0
             else np.zeros((Hm, Pd, B.shape[2])))
        for i in range(t, t + n):
            for hd in range(Hm):
                g = hd // rep
                h[hd] = (np.exp(dt[i, hd] * A[hd]) * h[hd]
                         + dt[i, hd] * x[i, hd][:, None] * B[i, g][None])
                y[i, hd] = h[hd] @ C[i, g] + D[hd] * x[i, hd]
        new[r] = h.transpose(2, 0, 1).reshape(new[r].shape)
        t += n
    return y, new


def inputs(T, S, G, seed, layers=2):
    rng = np.random.default_rng(seed)
    f = np.float32
    return dict(
        x=rng.normal(size=(T, HM, P)).astype(f),
        dt=rng.uniform(0.01, 0.5, (T, HM)).astype(f),
        A=-rng.uniform(0.1, 1.0, (HM, )).astype(f),
        B=rng.normal(size=(T, G, N)).astype(f),
        C=rng.normal(size=(T, G, N)).astype(f),
        D=rng.normal(size=(HM, )).astype(f),
        state=rng.normal(size=(layers, S + 1, N, HM * P)).astype(f))


def run(chunks, T, S, G=1, seed=0, layer=1, **kw):
    v = inputs(T, S, G, seed)
    seg = _make_seg(chunks, T, S)
    kw.setdefault("chunk_size", Q)
    y, state = ssd.ssd_ragged(
        *(jnp.asarray(v[k]) for k in ("x", "dt", "A", "B", "C", "D",
                                      "state")),
        jnp.full((1, ), layer, jnp.int32), seg, **kw)
    want_y, want_rows = sequential(chunks, v["x"], v["dt"], v["A"], v["B"],
                                   v["C"], v["D"], v["state"][layer])
    n = sum(c[2] for c in chunks)
    return (np.asarray(y)[:n], want_y[:n], np.asarray(state), want_rows,
            v["state"])


def check(chunks, T, S, **kw):
    y, want_y, state, want_rows, before = run(chunks, T, S, **kw)
    layer = kw.get("layer", 1)
    np.testing.assert_allclose(y, want_y, rtol=2e-4, atol=2e-4)
    live = [r for r, _, _ in chunks]
    np.testing.assert_allclose(state[layer][live], want_rows[live],
                               rtol=2e-4, atol=2e-4)
    # Every other row of the layer and every other layer are untouched
    # (the dump row S aside).
    idle = [r for r in range(S) if r not in live]
    np.testing.assert_array_equal(state[layer][idle], before[layer][idle])
    np.testing.assert_array_equal(state[1 - layer], before[1 - layer])


# (row, position of the chunk's first token, tokens): lengths 1, Q - 1,
# Q, Q + 1 and 3 Q + 5, a resumed segment among them, decodes between.
MIX = [(2, 0, Q - 1), (0, 7, 1), (5, 0, Q), (1, 3, Q + 1), (4, 9, 1),
       (3, 0, 3 * Q + 5)]
MIX_T = 64                 # 56 tokens and 8 of padding


@pytest.mark.parametrize("form", ["chunk", "recurrent", "auto"])
def test_forms_agree_with_the_recurrence_on_a_ragged_mix(form):
    check(MIX, MIX_T, 6, prefill_form=form)


@pytest.mark.parametrize("groups", [1, 2, 4])
def test_groups_share_b_and_c(groups):
    """What ``ops/mamba.ssd_scan_ragged``'s test held before this op
    replaced it: B and C shared by the heads of a group, a mixed batch
    with a resumed chunk and two decodes."""
    check([(2, 0, 5), (0, 7, 3), (4, 1, 1), (1, 0, 1)], 16, 6, G=groups,
          seed=3)


def test_a_resumed_segment_continues_the_row_s_state():
    """One request in two steps equals it in one."""
    v = inputs(2 * Q + 3, 2, 1, seed=5)
    args = [jnp.asarray(v[k]) for k in ("x", "dt", "A", "B", "C", "D")]
    layer = jnp.zeros((1, ), jnp.int32)
    n = 2 * Q + 3
    once_y, once = ssd.ssd_ragged(*args, jnp.asarray(v["state"]), layer,
                                  _make_seg([(1, 0, n)], n, 2),
                                  chunk_size=Q)
    cut = Q + 2

    def part(lo, hi, state):
        a = [t[lo:hi] if t.shape[0] == n else t for t in args]
        return ssd.ssd_ragged(*a, state, layer,
                              _make_seg([(1, lo, hi - lo)], hi - lo, 2),
                              chunk_size=Q)

    y1, state = part(0, cut, jnp.asarray(v["state"]))
    y2, state = part(cut, n, state)
    np.testing.assert_allclose(np.concatenate([y1, y2]), once_y,
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(state[0, 1], once[0, 1], rtol=2e-4,
                               atol=2e-4)


def test_more_segments_than_the_chunk_count_take_the_fallback():
    """Ten two-token segments in a 24-token program: 10 chunks against a
    static count of 3 + 4. The op answers token by token, exactly, and
    the runner's counter reads 0."""
    chunks = [(r, 0 if r % 2 else 4, 2) for r in range(10)]
    assert ssd.num_chunks_for(24, Q) == 3 + ssd.MAX_CHUNK_SEGMENTS < 10
    check(chunks, 24, 10)
    lens = np.array([n for _, _, n in chunks])
    assert ssd.chunk_form_tokens(lens, 24, Q) == 0
    assert ssd.chunk_form_tokens(lens[:7], 24, Q) == 14
    assert ssd.chunk_form_tokens(np.array([1, 1, 1]), 24, Q) == 0


def test_decode_only_program_has_no_chunk_kernel():
    import jax
    chunks = [(r, 5, 1) for r in (3, 0, 2)]
    check(chunks, 8, 4, decode_only=True)
    v = inputs(8, 4, 1, 0)
    seg = _make_seg(chunks, 8, 4)
    text = str(jax.make_jaxpr(
        lambda *a: ssd.ssd_ragged(*a, seg, chunk_size=Q, decode_only=True))(
            *(jnp.asarray(v[k]) for k in ("x", "dt", "A", "B", "C", "D",
                                          "state")),
            jnp.zeros((1, ), jnp.int32)))
    assert ssd.KERNEL_RECURRENT in text and ssd.KERNEL_CHUNK not in text


def test_a_bucket_under_a_chunk_is_one_chunk():
    assert ssd.chunk_size_for(24, 256) == 24
    assert ssd.num_chunks_for(24, 256) == 1 + ssd.MAX_CHUNK_SEGMENTS
    assert ssd.num_chunks_for(2056, 256) == 9 + ssd.MAX_CHUNK_SEGMENTS
    check([(0, 0, 5), (1, 2, 1), (2, 6, 7)], 16, 3, seed=9, chunk_size=256)
