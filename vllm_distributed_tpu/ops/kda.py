"""Gated delta-rule linear attention (Kimi Delta Attention, KDA;
arXiv:2510.26692) over the flat ragged token batch.

Per head, with ``S`` a ``[Dk, Dv]`` float32 state (zero at a sequence's
start), ``k`` of unit 2-norm, ``a = exp(g)`` a decay per KEY channel
and ``b`` one scalar:

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

The state lives in the runner's state rows (one row a request, row S
the dump row of padding; ``ops/mamba.py`` has the contract) and is read
and written IN PLACE by two Pallas kernels, both handed the whole
layer-stacked buffer and a layer index, so no layer's slab is ever
sliced out or copied back:

* ``kda_recurrent``: one grid step a token, all heads, the row's
  ``[N, Dk, Dv]`` block in VMEM. A run of tokens of one row keeps the
  state in the output block (the block index does not change, so
  nothing goes to HBM between them); a decode batch is one token a row:
  the state read once and written once, which is the whole cost. It is
  exact for any mix of segments, so it is also the fallback of the
  chunked form.
* ``kda_chunk``: the chunked WY form for prefill, a chunk of ``CHUNK``
  tokens a grid step. The kernel reads the chunk's q, k, v and log-decay
  rows where they lie in the flat batch (a ``CHUNK``-row window from the
  chunk's first token on; rows of the window that are not the chunk's
  are masked inert) and forms in VMEM what does not depend on the
  incoming state: the cumulative log decay, the decayed key-key and
  query-key matrices, and ``T = (I + Diag(b) A)^-1``, the unit-triangular
  solve that turns values into pseudo-values. Ratios of cumulative
  decays are formed inside sub-chunks of ``SUB`` tokens only, about the
  sub-chunk's middle token, where ``kda_lower_bound`` keeps both factors
  well inside float32 (8 tokens x -5 = e^-40 and e^40); across
  sub-chunks every factor is a decay <= 1. Then, with the state in VMEM
  from chunk to chunk: ``W = T b (V - K+ S)``, ``o = Qg S + Aq W``,
  ``S <- Diag(d) S + Kd^T W``. Two heads go through the chunk's
  matrices at once (block-diagonal, 128 rows: the MXU's tile). Float32
  throughout, every product at ``Precision.HIGHEST``. No state per
  token and nothing a chunk computes is ever an array in HBM.

A step's segments are split by kind: one-token segments (decodes) run
the recurrent kernel over the state rows; longer ones are cut into
chunks (each starts a chunk of its own; a last chunk may be short) and
run the chunked form when they fit its static chunk count
(``MAX_CHUNK_SEGMENTS`` segments more than the bucket's own chunks),
else the recurrent kernel token by token.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from vllm_distributed_tpu import envs
from vllm_distributed_tpu.ops import mamba
from vllm_distributed_tpu.ops.mamba import MAX_CHUNK_SEGMENTS  # noqa: F401
from vllm_distributed_tpu.ops.mamba import (SLOT_CONT, SLOT_HAS_INIT,
                                            SLOT_VALID, SegmentInfo)

KERNEL_RECURRENT = "kda_recurrent"
KERNEL_CHUNK = "kda_chunk"
CHUNK = 64
SUB = 16
_HEAD_BLOCK = 8
# Heads whose chunk matrices share one product: 2 x CHUNK fills a tile.
_HEADS_AT_ONCE = 2
_HI = jax.lax.Precision.HIGHEST
_VMEM_LIMIT = 48 * 1024 * 1024

def _enter_row(flags, s_ref, so_ref):
    """Both kernels' first lines: a continued run finds its state in the
    output block (same block index as the step before: the buffer
    stays); a new run brings the row's stored state, or zero at a
    sequence's start. Returns whether the slot is live."""
    valid = (flags & SLOT_VALID) != 0
    has_init = (flags & SLOT_HAS_INIT) != 0
    fresh = valid & ((flags & SLOT_CONT) == 0)

    @pl.when(fresh & has_init)
    def _():
        so_ref[...] = s_ref[...]

    @pl.when(fresh & jnp.logical_not(has_init))
    def _():
        so_ref[...] = jnp.zeros_like(so_ref)

    return valid


# ---------------------------------------------------------------------
# kda_recurrent: one token a grid step
# ---------------------------------------------------------------------
def _recurrent_kernel(layer_ref, row_ref, flag_ref, a_ref, k_ref, bk_ref,
                      q_ref, v_ref, s_ref, o_ref, so_ref):
    del layer_ref, row_ref
    valid = _enter_row(flag_ref[pl.program_id(0)], s_ref, so_ref)
    heads = v_ref.shape[1]

    @pl.when(valid)
    def _():
        for h in range(heads):
            s = a_ref[0, :, h:h + 1] * so_ref[0, 0, h]          # [Dk, Dv]
            u = jnp.sum(k_ref[0, :, h:h + 1] * s, axis=0, keepdims=True)
            s = s + bk_ref[0, :, h:h + 1] * (v_ref[0, h:h + 1, :] - u)
            o_ref[0, h:h + 1, :] = jnp.sum(q_ref[0, :, h:h + 1] * s,
                                           axis=0, keepdims=True)
            so_ref[0, 0, h] = s

    @pl.when(jnp.logical_not(valid))
    def _():
        # Padding: the dump row, kept finite; nothing of a live row.
        o_ref[...] = jnp.zeros_like(o_ref)
        so_ref[...] = jnp.zeros_like(so_ref)


def _recurrent_call(state, layer, rows, flags, q, k, v, a, beta,
                    interpret: bool):
    """``M`` slots in order: slot m is a token of state row ``rows[m]``
    (q, k, a ``[M, N, Dk]``, v ``[M, N, Dv]``, beta ``[M, N]``, float32)
    with ``flags[m]``. Returns (o ``[M, N, Dv]``, state)."""
    M, N, Dk = q.shape
    Dv = v.shape[-1]

    def col(x):          # [M, N, Dk] -> [M, Dk, N]: a head is a lane
        return jnp.swapaxes(x, 1, 2)

    cols = pl.BlockSpec((1, Dk, N), lambda m, *_: (m, 0, 0))
    tok = pl.BlockSpec((1, N, Dv), lambda m, *_: (m, 0, 0))
    row = pl.BlockSpec((1, 1, N, Dk, Dv),
                       lambda m, layer, rows, flags:
                       (layer[0], rows[m], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(M, ),
        in_specs=[cols, cols, cols, cols, tok, row],
        out_specs=(tok, row))
    o, state = pl.pallas_call(
        _recurrent_kernel,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((M, N, Dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        # 3 scalar-prefetch operands, then a, k, bk, q, v, state.
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", ),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=KERNEL_RECURRENT,
    )(layer, rows, flags, col(a), col(k), col(k * beta[..., None]), col(q),
      v, state)
    return o, state


# ---------------------------------------------------------------------
# kda_chunk: the chunked form
# ---------------------------------------------------------------------
def _dot(x, y):
    return jnp.dot(x, y, preferred_element_type=jnp.float32, precision=_HI)


def _dot_nt(x, y):
    """``x y^T``: both contract their lanes."""
    return jax.lax.dot_general(x, y, (((1, ), (1, )), ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=_HI)


def _chunk_heads(q, k, v, g, b, states):
    """One chunk of ``len(states)`` heads at once, their ``CHUNK`` tokens
    stacked head after head (the matrices over a chunk's tokens are then
    block-diagonal over the heads and fill the MXU's tile): q, k, g
    ``[R, Dk]``, v ``[R, Dv]``, b ``[R, 1]``, each head's incoming state
    ``[Dk, Dv]``, all float32. Returns the outputs ``[R, Dv]`` and each
    head's state after the chunk."""
    R, Dk = k.shape
    C = CHUNK
    n_sub, per_head = R // SUB, C // SUB

    def rows(i, n=SUB):
        return slice(i * n, (i + 1) * n)

    def stack(parts):
        return jnp.concatenate(parts, axis=0)

    def spread(per_sub):             # n_sub rows [1, Dk] -> [R, Dk]
        return stack([jnp.broadcast_to(r, (SUB, Dk)) for r in per_sub])

    # The cumulative log decay inside each sub-chunk, by doubling.
    at = jax.lax.broadcasted_iota(jnp.int32, (R, Dk), 0) % SUB
    gl, step = g, 1
    while step < SUB:
        gl = gl + jnp.where(at >= step, pltpu.roll(gl, step, 0), 0.0)
        step *= 2
    z = [gl[(i + 1) * SUB - 1:(i + 1) * SUB] for i in range(n_sub)]
    before = []                              # at the sub-chunk's start
    for i in range(n_sub):
        before.append(before[-1] + z[i - 1] if i % per_head
                      else jnp.zeros_like(z[0]))
    total = [before[i] + z[i]                # a head's whole chunk
             for i in range(per_head - 1, n_sub, per_head)]
    # Ratios inside a sub-chunk are taken about its middle token: both
    # factors then stay within e^(+-SUB / 2 x the lower bound) and a
    # product that matters never comes from a denormal.
    centre = [gl[i * SUB + SUB // 2 - 1:i * SUB + SUB // 2]
              for i in range(n_sub)]
    grow = jnp.exp(gl - spread(centre))
    kp, qp = k * grow, q * grow
    km = k * jnp.exp(spread(centre) - gl)
    ke = k * jnp.exp(spread(z) - gl)         # to the sub-chunk's end
    g_all = gl + spread(before)
    reach = jnp.exp(g_all)
    k_plus, qg = k * reach, q * reach
    kd = k * jnp.exp(spread([t for t in total for _ in range(per_head)])
                     - g_all)

    # Sub-chunk i of every head against the head's keys in one product:
    # inside the sub-chunk a ratio of its own cumulative decays, against
    # an earlier sub-chunk j the decay from j's end to i's middle token
    # (z[j + 1] + ... + z[i - 1] + centre[i], a sum of log decays) times
    # ke; later sub-chunks hold zero, and so does, once masked, whatever
    # a head's rows make of another head's keys.
    a_rows, aq_rows = [[] for _ in states], [[] for _ in states]
    for i in range(per_head):
        lhs, rhs = [], []
        for h in range(len(states)):
            at_i = h * per_head + i
            lhs += [kp[rows(at_i)], qp[rows(at_i)]]
            for j in range(h * per_head, at_i):
                mid = centre[at_i]
                for m in range(j + 1, at_i):
                    mid = mid + z[m]
                rhs.append(ke[rows(j)] * jnp.exp(mid))
            rhs.append(km[rows(at_i)])
            if i + 1 < per_head:
                rhs.append(jnp.zeros(((per_head - 1 - i) * SUB, Dk),
                                     jnp.float32))
        both = _dot_nt(stack(lhs), stack(rhs))          # [heads x 2 SUB, R]
        for h in range(len(states)):
            a_rows[h].append(both[rows(2 * h)])
            aq_rows[h].append(both[rows(2 * h + 1)])
    r_idx = jax.lax.broadcasted_iota(jnp.int32, (R, R), 0)
    c_idx = jax.lax.broadcasted_iota(jnp.int32, (R, R), 1)
    own = r_idx // C == c_idx // C
    n_mat = b * jnp.where(own & (r_idx > c_idx),
                          stack(sum(a_rows, [])), 0.0)
    aq = jnp.where(own & (r_idx >= c_idx), stack(sum(aq_rows, [])), 0.0)

    # T = (I + N)^-1, N strictly lower. The MXU's time goes by the rows
    # it is fed, so the blocks of a block-diagonal matrix go through
    # side by side, [width, R], against blocks laid out in the square.
    def block_of_column(width):
        return jax.lax.broadcasted_iota(jnp.int32, (width, R), 1) // width

    def side_by_side(m, width, down):
        """Under block column c, block (c + down, c) of the square m."""
        column = block_of_column(width)
        out = jnp.zeros((width, R), jnp.float32)
        for c in range(R // width - down):
            out = jnp.where(column == c, m[rows(c + down, width)], out)
        return out

    def squared(side, width, down=0):
        """``side_by_side``'s inverse, zero elsewhere."""
        return jnp.where(r_idx // width == c_idx // width + down,
                         stack([side] * (R // width)), 0.0)

    # The diagonal SUB-blocks are nilpotent:
    # (I - N)(I + N^2)(I + N^4)(I + N^8).
    x = side_by_side(-n_mat, SUB, 0)
    t = x + (jax.lax.broadcasted_iota(jnp.int32, (SUB, R), 0)
             == jax.lax.broadcasted_iota(jnp.int32, (SUB, R), 1) % SUB
             ).astype(jnp.float32)
    x = _dot(x, squared(x, SUB))
    for _ in range(SUB.bit_length() - 3):
        both = _dot(stack([x, t]), squared(x, SUB))
        x, t = both[:SUB], t + both[SUB:]
    t = t + _dot(t, squared(x, SUB))
    # Then pairs of blocks, doubling: [[A, 0], [B, D]]^-1 =
    # [[A^-1, 0], [-D^-1 B A^-1, D^-1]].
    width = SUB
    while width < C:
        first = block_of_column(width) % 2 == 0          # A's columns
        b_a = _dot(jnp.where(first, side_by_side(n_mat, width, 1), 0.0),
                   squared(t, width))
        d_b_a = _dot(jnp.where(first, 0.0, t), squared(b_a, width, down=1))
        t = stack([jnp.where(first, t, 0.0), jnp.where(first, -d_b_a, t)])
        width *= 2
    t = squared(t, C)

    # What the incoming states add.
    ks = [_dot(stack([k_plus[rows(h, C)], qg[rows(h, C)]]), s)
          for h, s in enumerate(states)]                      # [2C, Dv]
    w = _dot(t, b * (v - stack([x[:C] for x in ks])))         # pseudo-values
    o = stack([x[C:] for x in ks]) + _dot(aq, w)
    after = []
    for h, s in enumerate(states):
        # kd^T and the chunk's whole decay as a column, by one transpose.
        turned = stack([kd[rows(h, C)],
                        jnp.broadcast_to(jnp.exp(total[h]), (C, Dk))]).T
        w_h = w[rows(h, C)]
        after.append(turned[:, C:C + 1] * s
                     + _dot(turned, stack([w_h, jnp.zeros_like(w_h)])))
    return o, after


def _chunk_kernel(layer_ref, row_ref, flag_ref, win_ref, q_ref, k_ref, v_ref,
                  g_ref, b_ref, s_ref, o_ref, so_ref):
    del layer_ref, row_ref
    n = pl.program_id(1)
    valid = _enter_row(flag_ref[n], s_ref, so_ref)
    C, hb, Dk = k_ref.shape
    # The chunk's tokens are rows [lo, hi) of the window; whatever the
    # flat batch holds around them is inert (zero, b = 0, g = 0).
    at = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    live = (at >= win_ref[1, n]) & (at < win_ref[2, n])

    group = _HEADS_AT_ONCE if hb % _HEADS_AT_ONCE == 0 else 1
    lane = jax.lax.broadcasted_iota(jnp.int32, (C, hb), 1)

    def rows(ref, heads):
        return jnp.concatenate(
            [jnp.where(live, ref[:, h, :], 0.0) for h in heads], axis=0)

    def some_heads(i, carry):
        # One body for every group of the block: it is traced and
        # lowered once, and the program's text holds it once.
        heads = [group * i + j for j in range(group)]
        beta = b_ref[0]
        o, after = _chunk_heads(
            rows(q_ref, heads), rows(k_ref, heads), rows(v_ref, heads),
            rows(g_ref, heads),
            jnp.concatenate([jnp.sum(jnp.where(lane == h, beta, 0.0), axis=1,
                                     keepdims=True) for h in heads], axis=0),
            [so_ref[0, 0, h] for h in heads])
        for j, h in enumerate(heads):
            o_ref[:, h, :] = o[j * C:(j + 1) * C]
            so_ref[0, 0, h] = after[j]
        return carry

    @pl.when(valid)
    def _():
        jax.lax.fori_loop(0, hb // group, some_heads, 0)

    @pl.when(jnp.logical_not(valid))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)
        so_ref[...] = jnp.zeros_like(so_ref)


def _chunk_call(state, layer, rows, flags, windows, q, k, v, g, beta,
                interpret: bool):
    """``NC`` chunks in order, chunk n of state row ``rows[n]``: its
    tokens are rows ``lo ... hi`` of the ``CHUNK`` rows of the flat q, k,
    g ``[T, N, Dk]`` and v ``[T, N, Dv]`` from ``start`` on, ``windows``
    ``[3, NC]`` holding start, lo and hi; ``beta`` ``[NC * CHUNK, N]``
    comes window by window, zero outside the tokens. Returns (o ``[NC *
    CHUNK, N, Dv]`` window by window, state)."""
    _, _, N, Dk, Dv = state.shape
    P = beta.shape[0]
    hb = min(_HEAD_BLOCK, N)
    assert N % hb == 0 and P % CHUNK == 0
    # A head block's beta: [N // hb, P, hb], a token is a sublane.
    beta = jnp.swapaxes(beta.reshape(P, N // hb, hb), 0, 1)

    def window(width):
        # Rows from any token on: the offsets are elements, not blocks.
        return pl.BlockSpec(
            (pl.Element(CHUNK), pl.Element(hb), pl.Element(width)),
            lambda j, n, layer, rows, flags, win: (win[0, n], j * hb, 0))

    row = pl.BlockSpec((1, 1, hb, Dk, Dv),
                       lambda j, n, layer, rows, flags, win:
                       (layer[0], rows[n], j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(N // hb, P // CHUNK),
        in_specs=[window(Dk), window(Dk), window(Dv), window(Dk),
                  pl.BlockSpec((1, CHUNK, hb), lambda j, n, *_: (j, n, 0)),
                  row],
        out_specs=(pl.BlockSpec((CHUNK, hb, Dv), lambda j, n, *_: (n, j, 0)),
                   row))
    o, state = pl.pallas_call(
        _chunk_kernel,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((P, N, Dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        # 4 scalar-prefetch operands, then q, k, v, g, beta, state.
        input_output_aliases={9: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=KERNEL_CHUNK,
    )(layer, rows, flags, windows, q, k, v, g, beta, state)
    return o, state


# ---------------------------------------------------------------------
# The step's segments, by kind
# ---------------------------------------------------------------------
def _decode_part(state, layer, q, k, v, a, beta, seg: SegmentInfo,
                 interpret: bool):
    """One-token segments, one slot a state row, live rows first."""
    rows, flags, tok, live = mamba.decode_slots(seg)
    o, state = _recurrent_call(
        state, layer, rows, flags,
        q[tok], k[tok], v[tok], a[tok], beta[tok], interpret)
    return mamba.scatter_slots(o, tok, live, q.shape[0]), state


def _token_part(state, layer, q, k, v, a, beta, seg: SegmentInfo, multi,
                interpret: bool):
    """Multi-token segments token by token: the recurrent form."""
    return _recurrent_call(
        state, layer, *mamba.token_slots(seg, multi),
        q, k, v, a, beta, interpret)


def _chunk_part(state, layer, q, k, v, g, beta, seg: SegmentInfo, multi,
                chunk_base, chunks, num_chunks: int, interpret: bool):
    """Multi-token segments chunk by chunk: a row's segment is chunks
    ``chunk_base ... chunk_base + chunks`` of the step, and the kernel
    reads each chunk's tokens where they lie in the flat batch."""
    T = q.shape[0]
    if T < CHUNK:                # a window is CHUNK rows of the batch
        q, k, v, g, beta = (jnp.pad(x, ((0, CHUNK - T), ) + ((0, 0), )
                                    * (x.ndim - 1))
                            for x in (q, k, v, g, beta))
    used, row, done, first, count = mamba.chunk_windows(
        seg, chunk_base, chunks, num_chunks, CHUNK)
    start = jnp.clip(first, 0, q.shape[0] - CHUNK)
    lo = jnp.where(used, first - start, 0)
    at = jnp.arange(CHUNK, dtype=jnp.int32)[None, :]
    beta = jnp.where(((at >= lo[:, None]) & (at < (lo + count)[:, None])
                      )[:, :, None], beta[start[:, None] + at], 0.0)
    o, state = _chunk_call(
        state, layer, row,
        mamba.slot_flags(used, seg.has_init_by_row[row], done > 0),
        jnp.stack([start, lo, lo + count]), q, k, v, g,
        beta.reshape(num_chunks * CHUNK, -1), interpret)
    # Token t's output row: its chunk's window, lo rows in.
    chunk = mamba.chunk_of_token(seg, multi, chunk_base, CHUNK)
    return jnp.where(multi[:, None, None],
                     o[chunk * CHUNK + lo[chunk] + seg.off % CHUNK],
                     0.0), state


@functools.partial(jax.jit, static_argnames=("decode_only", "prefill_form",
                                             "interpret"))
def kda_ragged(q, k, v, g, beta, state, layer, seg: SegmentInfo, *,
               decode_only: bool = False, prefill_form: str = "auto",
               interpret: bool | None = None):
    """KDA over the step's flat batch. ``q`` (scaled), ``k`` (both
    2-normed) ``[T, N, Dk]``, ``v`` ``[T, N, Dv]``, ``g`` ``[T, N, Dk]``
    the log decay, ``beta`` ``[T, N]``; ``state`` the layer-stacked
    ``[L, S + 1, N, Dk, Dv]`` float32 rows, ``layer`` ``[1]`` int32.
    Returns (o ``[T, N, Dv]`` float32, state). ``decode_only`` (static:
    the batch's ``max_q`` is 1) leaves the prefill forms out of the
    program; ``prefill_form`` ``"chunk"`` / ``"recurrent"`` pins the
    form of multi-token segments (tests)."""
    if interpret is None:
        interpret = envs.VDT_PALLAS_INTERPRET
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    # Padding tokens are inert wherever they land.
    live = seg.valid
    g = jnp.where(live[:, None, None], g, 0.0)
    beta = jnp.where(live[:, None], beta, 0.0)
    a = jnp.exp(g)
    o, state = _decode_part(state, layer, q, k, v, a, beta, seg, interpret)
    if decode_only:
        return o, state

    multi, chunks, chunk_base = mamba.multi_token_segments(seg, CHUNK)
    num_chunks = mamba.num_chunks_for(q.shape[0], CHUNK)

    def none(state):
        return jnp.zeros_like(o), state

    def chunked(state):
        return _chunk_part(state, layer, q, k, v, g, beta, seg, multi,
                           chunk_base, chunks, num_chunks, interpret)

    def by_token(state):
        return _token_part(state, layer, q, k, v, a, beta, seg, multi,
                           interpret)

    o_multi, state = mamba.by_form(prefill_form, chunks, num_chunks, none,
                                   chunked, by_token, state)
    return o + o_multi, state
