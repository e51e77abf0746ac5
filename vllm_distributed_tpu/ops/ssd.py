"""Mamba-2 (state-space duality, SSD; arXiv:2405.21060) over the flat
ragged token batch, without a state per token.

Per head ``h`` of ``P`` channels, with ``S`` a ``[P, N]`` float32 state
(zero at a sequence's start), ``A_h < 0`` one scalar a head, ``B_t`` and
``C_t`` of ``N`` channels shared by the heads of a group:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t + D x_t

The state lives in the runner's state rows (one row a request, row S the
dump row of padding; ``ops/mamba.py`` has the contract), layer-stacked
and STATE-MAJOR: ``[L, S + 1, N, Hm * P]``, the ``N`` state channels on
the sublanes and every head's channels side by side on the lanes (the
same bytes as ``[Hm, P, N]``). So a token's ``a``, ``dt x`` and ``y`` are
rows in the layout the projections already have, ``B`` and ``C`` are
columns, ``y`` is a sum over sublanes, and the chunked form's products
with the state are ``[Q, N] x [N, Hm * P]`` for all heads of a group at
once. Two Pallas kernels read and write it IN PLACE, both handed the
whole buffer and a layer index, so no layer's slab is ever sliced out
or copied back (``ops/kda.py`` set the pattern):

* ``ssd_recurrent``: one grid step a token, all heads, the row's
  ``[N, Hm * P]`` block in VMEM. A decode batch is one token a row: the
  state read once and written once, which is the whole cost. Exact for
  any mix of segments, so also the fallback of the chunked form.
* ``ssd_chunk``: the chunked form for prefill. Per chunk of ``Q``
  tokens, with ``L_i`` the running sum of ``dt A`` inside the chunk,

      Y = ((C B^T) o M) (dt x) + exp(L) (C S_in),   M_ij = exp(L_i - L_j)
      S_out = exp(L_Q) S_in + sum_j exp(L_Q - L_j) B_j (dt_j x_j)^T

  (``i >= j``; every factor is a decay <= 1, so float32 needs no bound).
  What does not depend on the incoming state (``C B^T``, the mask, the
  product with ``dt x``, the chunk's own ``B^T (decayed dt x)``) is plain
  XLA batched over chunks, operands in the activations' dtype with
  float32 accumulation, decays and ``L`` in float32; the kernel walks a
  segment's chunks in order with the state in VMEM: ``exp(L) (C S)``
  out, ``S <- exp(L_Q) S + dS``.

A step's segments are split by kind as in ``ops/kda.py``: one-token
segments (decodes) run the recurrent kernel over the state rows; longer
ones are laid out chunk-aligned (zero padding is inert: ``dt = 0``) and
run the chunked form when they fit its static chunk count, else the
recurrent kernel token by token.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from vllm_distributed_tpu import envs
from vllm_distributed_tpu.ops import mamba
from vllm_distributed_tpu.ops.kda import _enter_row
from vllm_distributed_tpu.ops.mamba import MAX_CHUNK_SEGMENTS  # noqa: F401
from vllm_distributed_tpu.ops.mamba import SegmentInfo, slot_flags

KERNEL_RECURRENT = "ssd_recurrent"
KERNEL_CHUNK = "ssd_chunk"
# Lanes of the state a grid step of the chunk kernel holds, and lanes a
# pass of the recurrent kernel's body works on.
_CHUNK_LANES = 1024
_BODY_LANES = 256
_HI = jax.lax.Precision.HIGHEST
_VMEM_LIMIT = 48 * 1024 * 1024


def chunk_size_for(num_tokens: int, chunk_size: int) -> int:
    """The chunk a ``num_tokens`` program runs: the configuration's, or
    the whole (smaller) bucket."""
    return min(chunk_size, num_tokens)


def num_chunks_for(num_tokens: int, chunk_size: int) -> int:
    """The static chunk count of a ``num_tokens`` program."""
    return mamba.num_chunks_for(num_tokens,
                                chunk_size_for(num_tokens, chunk_size))


def chunk_form_tokens(q_lens: np.ndarray, num_tokens: int,
                      chunk_size: int) -> int:
    """Tokens of a step (``q_lens`` its segments' lengths, in a program
    of ``num_tokens``) that take the chunked form: ``ssd_ragged``'s rule,
    for the runner's counter."""
    return mamba.chunk_form_tokens(q_lens, num_tokens,
                                   chunk_size_for(num_tokens, chunk_size))


def _lane_block(lanes: int, want: int) -> int:
    block = min(want, lanes)
    assert lanes % block == 0, (lanes, block)
    return block


# ---------------------------------------------------------------------
# ssd_recurrent: one token a grid step
# ---------------------------------------------------------------------
def _recurrent_kernel(layer_ref, row_ref, flag_ref, a_ref, dtx_ref, bc_ref,
                      s_ref, y_ref, so_ref):
    del layer_ref, row_ref
    valid = _enter_row(flag_ref[pl.program_id(0)], s_ref, so_ref)
    groups = bc_ref.shape[2] // 2
    lanes = a_ref.shape[2]
    per_group = lanes // groups
    block = _lane_block(per_group, _BODY_LANES)

    @pl.when(valid)
    def _():
        for g in range(groups):
            b_col = bc_ref[0, :, g:g + 1]                       # [N, 1]
            c_col = bc_ref[0, :, groups + g:groups + g + 1]
            for lo in range(g * per_group, (g + 1) * per_group, block):
                ln = slice(lo, lo + block)
                s = (a_ref[0, :, ln] * so_ref[0, 0, :, ln]
                     + b_col * dtx_ref[0, :, ln])               # [N, block]
                so_ref[0, 0, :, ln] = s
                y_ref[0, :, ln] = jnp.sum(s * c_col, axis=0, keepdims=True)

    @pl.when(jnp.logical_not(valid))
    def _():
        # Padding: the dump row, kept finite; nothing of a live row.
        y_ref[...] = jnp.zeros_like(y_ref)
        so_ref[...] = jnp.zeros_like(so_ref)


def _recurrent_call(state, layer, rows, flags, a, dtx, b, c,
                    interpret: bool):
    """``M`` slots in order: slot m is a token of state row ``rows[m]``
    with ``flags[m]``; ``a`` (the decay, a head's scalar over its
    channels) and ``dtx`` ``[M, Di]``, ``b`` and ``c`` ``[M, G, N]``,
    float32. Returns (y ``[M, Di]``, state)."""
    M, Di = dtx.shape
    G, N = b.shape[1:]
    # B then C as columns: [M, N, 2 G], a group is a lane.
    bc = jnp.swapaxes(jnp.concatenate([b, c], axis=1), 1, 2)
    tok = pl.BlockSpec((1, 1, Di), lambda m, *_: (m, 0, 0))
    row = pl.BlockSpec((1, 1, N, Di),
                       lambda m, layer, rows, flags:
                       (layer[0], rows[m], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(M, ),
        in_specs=[tok, tok,
                  pl.BlockSpec((1, N, 2 * G), lambda m, *_: (m, 0, 0)),
                  row],
        out_specs=(tok, row))
    y, state = pl.pallas_call(
        _recurrent_kernel,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((M, 1, Di), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        # 3 scalar-prefetch operands, then a, dtx, bc, state.
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", ),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=KERNEL_RECURRENT,
    )(layer, rows, flags, a[:, None], dtx[:, None], bc, state)
    return y[:, 0], state


# ---------------------------------------------------------------------
# ssd_chunk: the chunked form
# ---------------------------------------------------------------------
def chunk_local(x, dt_a, dt, b, c, operand_dtype):
    """What a chunk can compute without its incoming state. ``x``
    ``[NC, Q, Hm, P]``, ``dt_a`` (``dt A``, <= 0) and ``dt``
    ``[NC, Q, Hm]`` float32, ``b`` and ``c`` ``[NC, Q, G, N]``. Returns
    ``y`` ``[NC, Q, Hm * P]`` the chunk's own tokens' answers, ``el``
    ``[NC, Q, Hm * P]`` the decay from the chunk's start to each token,
    ``ds`` ``[NC, N, Hm * P]`` and ``dec`` ``[NC, 1, Hm * P]`` with
    ``S_out = dec S_in + ds``, and ``c`` as ``[NC, G, Q, N]``: the
    incoming state adds ``el (c S_in)`` to ``y``."""
    NC, Q, Hm, P = x.shape
    G, N = b.shape[2:]
    f32 = jnp.float32
    od = operand_dtype
    prec = _HI if od == f32 else None
    L = jnp.cumsum(dt_a, axis=1)                                # [NC, Q, Hm]
    total = L[:, -1]                                            # [NC, Hm]
    idx = jnp.arange(Q)
    causal = idx[:, None] >= idx[None, :]
    Lh = jnp.swapaxes(L, 1, 2)                                  # [NC, Hm, Q]
    mask = jnp.exp(jnp.where(causal, Lh[..., :, None] - Lh[..., None, :],
                             -jnp.inf))                         # [NC,Hm,Q,Q]
    scores = jnp.einsum("cign,cjgn->cgij", c.astype(od), b.astype(od),
                        preferred_element_type=f32, precision=prec)
    w = (mask.reshape(NC, G, Hm // G, Q, Q) * scores[:, :, None]).reshape(
        NC, Hm, Q, Q)
    dtx = dt[..., None] * x.astype(f32)                         # [NC,Q,Hm,P]
    y = jnp.einsum("chij,cjhp->cihp", w.astype(od), dtx.astype(od),
                   preferred_element_type=f32, precision=prec)
    to_end = jnp.exp(total[:, None] - L)                        # [NC, Q, Hm]
    xdec = (to_end[..., None] * dtx).astype(od).reshape(
        NC, Q, G, (Hm // G) * P)
    ds = jnp.einsum("cqgn,cqgl->cngl", b.astype(od), xdec,
                    preferred_element_type=f32, precision=prec)

    def lanes(v):                      # [NC, ..., Hm] -> [NC, ..., Hm * P]
        return jnp.repeat(v, P, axis=-1)

    return dict(y=y.reshape(NC, Q, Hm * P), el=lanes(jnp.exp(L)),
                ds=ds.reshape(NC, N, Hm * P),
                dec=lanes(jnp.exp(total))[:, None],
                c=jnp.swapaxes(c, 1, 2).astype(od))


def _chunk_kernel(layer_ref, row_ref, flag_ref, c_ref, el_ref, ds_ref,
                  dec_ref, s_ref, y_ref, so_ref):
    del layer_ref, row_ref
    valid = _enter_row(flag_ref[pl.program_id(1)], s_ref, so_ref)

    @pl.when(valid)
    def _():
        s = so_ref[0, 0]                                        # [N, lanes]
        c = c_ref[0, 0]                                         # [Q, N]
        y_ref[0] = el_ref[0] * jnp.dot(
            c, s.astype(c.dtype), preferred_element_type=jnp.float32,
            precision=_HI if c.dtype == jnp.float32 else None)
        so_ref[0, 0] = dec_ref[0] * s + ds_ref[0]

    @pl.when(jnp.logical_not(valid))
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)
        so_ref[...] = jnp.zeros_like(so_ref)


def _chunk_call(state, layer, rows, flags, local, interpret: bool):
    """``NC`` chunks in order, chunk n of state row ``rows[n]``. Returns
    (what the incoming states add to ``y``, ``[NC, Q, Di]``, state)."""
    NC, G, Q, N = local["c"].shape
    Di = local["el"].shape[-1]
    per_group = Di // G
    lanes = _lane_block(per_group, _CHUNK_LANES)
    blocks_a_group = per_group // lanes

    def per_chunk(rows_):
        return pl.BlockSpec((1, rows_, lanes), lambda j, n, *_: (n, 0, j))

    row = pl.BlockSpec((1, 1, N, lanes),
                       lambda j, n, layer, rows, flags:
                       (layer[0], rows[n], 0, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(Di // lanes, NC),
        in_specs=[pl.BlockSpec((1, 1, Q, N),
                               lambda j, n, *_: (n, j // blocks_a_group,
                                                 0, 0)),
                  per_chunk(Q), per_chunk(N), per_chunk(1), row],
        out_specs=(per_chunk(Q), row))
    y, state = pl.pallas_call(
        _chunk_kernel,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((NC, Q, Di), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        # 3 scalar-prefetch operands, then c, el, ds, dec, state.
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=KERNEL_CHUNK,
    )(layer, rows, flags, local["c"], local["el"], local["ds"],
      local["dec"], state)
    return y, state


# ---------------------------------------------------------------------
# The step's segments, by kind
# ---------------------------------------------------------------------
def _token_rows(x, dt_a, dt, P: int):
    """A token's decay and drive as rows over every head's channels."""
    a = jnp.repeat(jnp.exp(dt_a), P, axis=-1)                   # [M, Di]
    dtx = (dt[..., None] * x).reshape(x.shape[0], -1)
    return a, dtx


def _decode_part(state, layer, x, dt_a, dt, b, c, seg: SegmentInfo,
                 interpret: bool):
    """One-token segments, one slot a state row, live rows first."""
    T, _, P = x.shape
    S = seg.q_len_by_row.shape[0] - 1
    live = seg.q_len_by_row[:S] == 1
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    live = live[order]
    tok = jnp.where(live, seg.q_start_by_row[order], 0)
    a, dtx = _token_rows(x[tok], dt_a[tok], dt[tok], P)
    y, state = _recurrent_call(
        state, layer, jnp.where(live, order, S),
        slot_flags(live, seg.has_init_by_row[order], jnp.zeros_like(live)),
        a, dtx, b[tok], c[tok], interpret)
    return mamba.scatter_slots(y, tok, live, T), state


def _token_part(state, layer, x, dt_a, dt, b, c, seg: SegmentInfo, multi,
                interpret: bool):
    """Multi-token segments token by token: the recurrent form."""
    a, dtx = _token_rows(x, dt_a, dt, x.shape[2])
    return _recurrent_call(
        state, layer, *mamba.token_slots(seg, multi),
        a, dtx, b, c, interpret)


def _chunk_part(state, layer, x, dt_a, dt, b, c, seg: SegmentInfo, multi,
                chunk_base, Q: int, num_chunks: int, operand_dtype,
                interpret: bool):
    """Multi-token segments in the chunk-aligned layout."""
    T = x.shape[0]
    S = seg.q_len_by_row.shape[0] - 1
    Pn = num_chunks * Q
    pos = jnp.where(multi, chunk_base[seg.row] * Q + seg.off, Pn)

    def lay(v):
        out = jnp.zeros((Pn, ) + v.shape[1:], v.dtype)
        return out.at[pos].set(v, mode="drop").reshape(
            (num_chunks, Q) + v.shape[1:])

    local = chunk_local(lay(x), lay(dt_a), lay(dt), lay(b), lay(c),
                        operand_dtype)
    # A chunk's first slot is a token whenever the chunk holds any.
    head = jnp.full((Pn, ), -1, jnp.int32).at[pos].set(
        jnp.arange(T, dtype=jnp.int32), mode="drop")[::Q]
    used = head >= 0
    first_tok = jnp.maximum(head, 0)
    y_state, state = _chunk_call(
        state, layer, jnp.where(used, seg.row[first_tok], S),
        slot_flags(used, seg.has_init[first_tok], seg.off[first_tok] > 0),
        local, interpret)
    y = (local["y"] + y_state).reshape(Pn, -1)
    return jnp.where(multi[:, None], y[jnp.minimum(pos, Pn - 1)], 0.0), state


@functools.partial(jax.jit, static_argnames=(
    "chunk_size", "decode_only", "prefill_form", "interpret"))
def ssd_ragged(x, dt, A, B, C, D, state, layer, seg: SegmentInfo, *,
               chunk_size: int = 256, decode_only: bool = False,
               prefill_form: str = "auto", interpret: bool | None = None):
    """Mamba-2 over the step's flat batch. ``x`` ``[T, Hm, P]`` (the
    activated convolution's output; its dtype is the chunked form's
    operand dtype), ``dt`` ``[T, Hm]`` the softplus'd step, ``A``
    ``[Hm]`` negative, ``B`` and ``C`` ``[T, G, N]``, ``D`` ``[Hm]``;
    ``state`` the layer-stacked ``[L, S + 1, N, Hm * P]`` float32 rows,
    ``layer`` ``[1]`` int32. Returns (y ``[T, Hm, P]`` float32, state).
    ``decode_only`` (static: the batch's ``max_q`` is 1) leaves the
    prefill forms out of the program; ``prefill_form`` ``"chunk"`` /
    ``"recurrent"`` pins the form of multi-token segments (tests)."""
    if interpret is None:
        interpret = envs.VDT_PALLAS_INTERPRET
    f32 = jnp.float32
    operand_dtype = x.dtype
    T, Hm, P = x.shape
    x32, b32, c32 = x.astype(f32), B.astype(f32), C.astype(f32)
    # Padding tokens are inert wherever they land.
    dt = jnp.where(seg.valid[:, None], dt.astype(f32), 0.0)
    dt_a = dt * A.astype(f32)[None, :]
    y, state = _decode_part(state, layer, x32, dt_a, dt, b32, c32, seg,
                            interpret)
    if not decode_only:
        Q = chunk_size_for(T, chunk_size)
        num_chunks = num_chunks_for(T, chunk_size)
        multi, chunks, chunk_base = mamba.multi_token_segments(seg, Q)

        def none(state):
            return jnp.zeros_like(y), state

        def chunked(state):
            return _chunk_part(state, layer, x32, dt_a, dt, b32, c32, seg,
                               multi, chunk_base, Q, num_chunks,
                               operand_dtype, interpret)

        def by_token(state):
            return _token_part(state, layer, x32, dt_a, dt, b32, c32, seg,
                               multi, interpret)

        y_multi, state = mamba.by_form(prefill_form, chunks, num_chunks,
                                       none, chunked, by_token, state)
        y = y + y_multi
    y = y.reshape(T, Hm, P) + D.astype(f32)[None, :, None] * x32
    return y, state
