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
* ``kda_chunk``: the chunked WY form for prefill. Per chunk of ``CHUNK``
  tokens the products that do not depend on the incoming state (decayed
  key-key and query-key matrices, the unit-triangular solve that turns
  values into pseudo-values) are plain XLA, batched over chunks; ratios
  of cumulative decays are formed inside sub-chunks of ``SUB`` tokens
  only, where ``kda_lower_bound`` keeps them inside float32 (16 tokens
  x -5 = e^-80), and across sub-chunks every factor is a decay <= 1.
  The kernel then walks the chunks in order with the state in VMEM:
  ``W = U - Wk S``, ``o = Qg S + Aq W``, ``S <- Diag(d) S + Kd^T W``.
  No state per token is ever materialised.

A step's segments are split by kind: one-token segments (decodes) run
the recurrent kernel over the state rows; longer ones are laid out
chunk-aligned (each starts on a chunk boundary, zero padding is inert:
``b = 0, g = 0``) and run the chunked form when they fit its static
chunk count (``MAX_CHUNK_SEGMENTS`` segments more than the bucket's own
chunks), else the recurrent kernel token by token.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from vllm_distributed_tpu import envs
from vllm_distributed_tpu.ops.mamba import SegmentInfo

KERNEL_RECURRENT = "kda_recurrent"
KERNEL_CHUNK = "kda_chunk"
CHUNK = 64
SUB = 16
# Multi-token segments a step may bring beyond its bucket's own chunks
# and still run the chunked form (each may add one partial chunk).
MAX_CHUNK_SEGMENTS = 4
_HEAD_BLOCK = 8
_HI = jax.lax.Precision.HIGHEST
_VMEM_LIMIT = 48 * 1024 * 1024

_VALID, _HAS_INIT, _CONT = 1, 2, 4


def _flags(valid, has_init, cont):
    return (valid.astype(jnp.int32) * _VALID
            + has_init.astype(jnp.int32) * _HAS_INIT
            + cont.astype(jnp.int32) * _CONT)


def _enter_row(flags, s_ref, so_ref):
    """Both kernels' first lines: a continued run finds its state in the
    output block (same block index as the step before: the buffer
    stays); a new run brings the row's stored state, or zero at a
    sequence's start. Returns whether the slot is live."""
    valid = (flags & _VALID) != 0
    has_init = (flags & _HAS_INIT) != 0
    fresh = valid & ((flags & _CONT) == 0)

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
def _sub_inverse(n_diag):
    """(I + N)^-1 of strictly lower ``[..., SUB, SUB]`` blocks, N
    nilpotent: (I - N)(I + N^2)(I + N^4)(I + N^8)."""
    eye = jnp.eye(SUB, dtype=n_diag.dtype)
    x = -n_diag
    t = eye + x
    for _ in range(SUB.bit_length() - 2):
        x = jnp.einsum("...ij,...jk->...ik", x, x, precision=_HI)
        t = t + jnp.einsum("...ij,...jk->...ik", t, x, precision=_HI)
    return t


def chunk_local(q, k, v, g, beta):
    """What a chunk can compute without its incoming state. Inputs
    ``[NC, CHUNK, N, D]`` float32 (beta ``[NC, CHUNK, N]``), g the log
    decay (<= 0). Returns head-major ``[NC, N, ...]`` arrays:
    ``u`` ``[C, Dv]`` and ``wk`` ``[C, Dk]`` with pseudo-values
    ``W = u - wk S0``; ``qg`` ``[C, Dk]`` and ``aq`` ``[C, C]`` with
    ``o = qg S0 + aq W``; ``kd_t`` ``[Dk, C]`` and ``dec`` ``[Dk]`` with
    ``S1 = Diag(dec) S0 + kd_t W``."""
    NC, C, N, Dk = k.shape
    n_sub = C // SUB

    def sub(x):
        return x.reshape(NC, n_sub, SUB, *x.shape[2:])

    q, k, g = sub(q), sub(k), sub(g)
    gl = jnp.cumsum(g, axis=2)                     # inside the sub-chunk
    z = gl[:, :, -1]                               # [NC, n_sub, N, Dk]
    before = jnp.cumsum(z, axis=1) - z             # at the sub-chunk's start
    total = before[:, -1] + z[:, -1]               # [NC, N, Dk]
    kp, km = k * jnp.exp(gl), k * jnp.exp(-gl)
    qp = q * jnp.exp(gl)
    ke = k * jnp.exp(z[:, :, None] - gl)           # to the sub-chunk's end

    def pairs(x, y):                               # [.., s, N, D] x [.., r, N, D]
        return jnp.einsum("...snd,...rnd->...nsr", x, y, precision=_HI)

    zero = jnp.zeros((NC, N, SUB, SUB), jnp.float32)
    a_rows, aq_rows = [], []
    for i in range(n_sub):
        a_row, aq_row = [], []
        for j in range(n_sub):
            if j > i:
                a_row.append(zero)
                aq_row.append(zero)
            elif j == i:
                a_row.append(pairs(kp[:, i], km[:, i]))
                aq_row.append(pairs(qp[:, i], km[:, i]))
            else:
                # From the end of sub-chunk j to the start of i: a decay.
                mid = jnp.exp(before[:, i] - before[:, j] - z[:, j])
                a_row.append(pairs(kp[:, i] * mid[:, None], ke[:, j]))
                aq_row.append(pairs(qp[:, i] * mid[:, None], ke[:, j]))
        a_rows.append(jnp.concatenate(a_row, axis=-1))
        aq_rows.append(jnp.concatenate(aq_row, axis=-1))
    s_idx = jnp.arange(C)
    a = jnp.where(s_idx[:, None] > s_idx[None, :],
                  jnp.concatenate(a_rows, axis=-2), 0.0)     # [NC, N, C, C]
    aq = jnp.where(s_idx[:, None] >= s_idx[None, :],
                   jnp.concatenate(aq_rows, axis=-2), 0.0)

    b = jnp.swapaxes(beta, 1, 2)                    # [NC, N, C]
    n_mat = a * b[..., None]
    g_all = (gl + before[:, :, None]).reshape(NC, C, N, Dk)
    k_all, q_all = k.reshape(NC, C, N, Dk), q.reshape(NC, C, N, Dk)
    k_plus = k_all * jnp.exp(g_all)
    rhs = jnp.concatenate([v, k_plus], axis=-1)     # [NC, C, N, Dv + Dk]
    rhs = jnp.swapaxes(rhs, 1, 2) * b[..., None]    # [NC, N, C, Dv + Dk]
    # (I + N) X = rhs by block forward substitution over sub-chunks.
    blocks = n_mat.reshape(NC, N, n_sub, SUB, n_sub, SUB)
    solved = []
    for i in range(n_sub):
        r = rhs[:, :, i * SUB:(i + 1) * SUB]
        for j in range(i):
            r = r - jnp.einsum("...sr,...rd->...sd", blocks[:, :, i, :, j],
                               solved[j], precision=_HI)
        inv = _sub_inverse(blocks[:, :, i, :, i])
        solved.append(jnp.einsum("...sr,...rd->...sd", inv, r,
                                 precision=_HI))
    x = jnp.concatenate(solved, axis=2)
    Dv = v.shape[-1]
    kd = k_all * jnp.exp(total[:, None] - g_all)
    return dict(
        u=x[..., :Dv], wk=x[..., Dv:],
        qg=jnp.swapaxes(q_all * jnp.exp(g_all), 1, 2), aq=aq,
        kd_t=jnp.transpose(kd, (0, 2, 3, 1)), dec=jnp.exp(total))


def _chunk_kernel(layer_ref, row_ref, flag_ref, u_ref, wk_ref, qg_ref,
                  aq_ref, kd_ref, dec_ref, s_ref, o_ref, so_ref):
    del layer_ref, row_ref
    valid = _enter_row(flag_ref[pl.program_id(1)], s_ref, so_ref)
    hb = u_ref.shape[1]

    def dot(x, y):
        return jnp.dot(x, y, preferred_element_type=jnp.float32,
                       precision=_HI)

    @pl.when(valid)
    def _():
        for h in range(hb):
            s = so_ref[0, 0, h]                                 # [Dk, Dv]
            w = u_ref[0, h] - dot(wk_ref[0, h], s)
            o_ref[0, h] = dot(qg_ref[0, h], s) + dot(aq_ref[0, h], w)
            so_ref[0, 0, h] = (dec_ref[0, 0, :, h:h + 1] * s
                               + dot(kd_ref[0, h], w))

    @pl.when(jnp.logical_not(valid))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)
        so_ref[...] = jnp.zeros_like(so_ref)


def _chunk_call(state, layer, rows, flags, local, interpret: bool):
    """``NC`` chunks in order, chunk n of state row ``rows[n]``."""
    NC, N, C, Dv = local["u"].shape
    Dk = local["wk"].shape[-1]
    hb = min(_HEAD_BLOCK, N)
    assert N % hb == 0
    # Decays as columns: [NC, N // hb, Dk, hb], a head is a lane.
    dec = jnp.transpose(local["dec"].reshape(NC, N // hb, hb, Dk),
                        (0, 1, 3, 2))

    def per_chunk(*tail):
        return pl.BlockSpec((1, hb) + tail,
                            lambda j, n, *_: (n, j) + (0, ) * len(tail))

    row = pl.BlockSpec((1, 1, hb, Dk, Dv),
                       lambda j, n, layer, rows, flags:
                       (layer[0], rows[n], j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(N // hb, NC),
        in_specs=[per_chunk(C, Dv), per_chunk(C, Dk), per_chunk(C, Dk),
                  per_chunk(C, C), per_chunk(Dk, C),
                  pl.BlockSpec((1, 1, Dk, hb),
                               lambda j, n, *_: (n, j, 0, 0)),
                  row],
        out_specs=(per_chunk(C, Dv), row))
    o, state = pl.pallas_call(
        _chunk_kernel,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((NC, N, C, Dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        # 3 scalar-prefetch operands, then u, wk, qg, aq, kd_t, dec, state.
        input_output_aliases={9: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=KERNEL_CHUNK,
    )(layer, rows, flags, local["u"], local["wk"], local["qg"],
      local["aq"], local["kd_t"], dec, state)
    return o, state


# ---------------------------------------------------------------------
# The step's segments, by kind
# ---------------------------------------------------------------------
def _decode_part(state, layer, q, k, v, a, beta, seg: SegmentInfo,
                 interpret: bool):
    """One-token segments, one slot a state row, live rows first."""
    T = q.shape[0]
    S = seg.q_len_by_row.shape[0] - 1
    live = seg.q_len_by_row[:S] == 1
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    live = live[order]
    tok = jnp.where(live, seg.q_start_by_row[order], 0)
    o, state = _recurrent_call(
        state, layer, jnp.where(live, order, S),
        _flags(live, seg.has_init_by_row[order], jnp.zeros_like(live)),
        q[tok], k[tok], v[tok], a[tok], beta[tok], interpret)
    out = jnp.zeros((T, ) + o.shape[1:], o.dtype)
    return out.at[jnp.where(live, tok, T)].set(o, mode="drop"), state


def _token_part(state, layer, q, k, v, a, beta, seg: SegmentInfo, multi,
                interpret: bool):
    """Multi-token segments token by token: the recurrent form."""
    S = seg.q_len_by_row.shape[0] - 1
    return _recurrent_call(
        state, layer, jnp.where(multi, seg.row, S),
        _flags(multi, seg.has_init, jnp.logical_not(seg.start)),
        q, k, v, a, beta, interpret)


def _chunk_part(state, layer, q, k, v, g, beta, seg: SegmentInfo, multi,
                chunk_base, num_chunks: int, interpret: bool):
    """Multi-token segments in the chunk-aligned layout."""
    T = q.shape[0]
    S = seg.q_len_by_row.shape[0] - 1
    P = num_chunks * CHUNK
    pos = jnp.where(multi, chunk_base[seg.row] * CHUNK + seg.off, P)

    def lay(x):
        out = jnp.zeros((P, ) + x.shape[1:], x.dtype)
        return out.at[pos].set(x, mode="drop").reshape(
            (num_chunks, CHUNK) + x.shape[1:])

    local = chunk_local(lay(q), lay(k), lay(v), lay(g), lay(beta))
    # A chunk's first slot is a token whenever the chunk holds any.
    head = jnp.full((P, ), -1, jnp.int32).at[pos].set(
        jnp.arange(T, dtype=jnp.int32), mode="drop")[::CHUNK]
    used = head >= 0
    first_tok = jnp.maximum(head, 0)
    o, state = _chunk_call(
        state, layer, jnp.where(used, seg.row[first_tok], S),
        _flags(used, seg.has_init[first_tok], seg.off[first_tok] > 0),
        local, interpret)
    o = jnp.swapaxes(o, 1, 2).reshape(P, o.shape[1], o.shape[-1])
    return jnp.where(multi[:, None, None],
                     o[jnp.minimum(pos, P - 1)], 0.0), state


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

    T = q.shape[0]
    S = seg.q_len_by_row.shape[0] - 1
    q_len = seg.q_len_by_row.at[S].set(0)
    row_multi = q_len > 1
    multi = live & row_multi[seg.row]
    chunks = jnp.where(row_multi, -(-q_len // CHUNK), 0)
    # Chunks of the segments that start earlier in the flat batch.
    earlier = seg.q_start_by_row[None, :] < seg.q_start_by_row[:, None]
    chunk_base = jnp.sum(jnp.where(earlier, chunks[None, :], 0), axis=1)
    num_chunks = -(-T // CHUNK) + MAX_CHUNK_SEGMENTS

    def none(state):
        return jnp.zeros_like(o), state

    def chunked(state):
        return _chunk_part(state, layer, q, k, v, g, beta, seg, multi,
                           chunk_base, num_chunks, interpret)

    def by_token(state):
        return _token_part(state, layer, q, k, v, a, beta, seg, multi,
                           interpret)

    if prefill_form == "chunk":
        o_multi, state = chunked(state)
    elif prefill_form == "recurrent":
        o_multi, state = by_token(state)
    else:
        total = chunks.sum()
        which = jnp.where(total == 0, 0,
                          jnp.where(total <= num_chunks, 1, 2))
        o_multi, state = jax.lax.switch(which, (none, chunked, by_token),
                                        state)
    return o + o_multi, state
